"""dcr-store: device-sharded top-k query engine over an embedding store.

The compute half of ROADMAP item 5. The brute-force path
(``search/search.py``) streams every dump through a single-device matmul
and merges top-k tables on the HOST per chunk — fine for one LAION chunk,
hopeless for the corpus sizes the CVPR'23 paper searched. Here the corpus
is laid out across the device mesh and the whole per-segment query runs as
ONE program:

- store shards regroup into fixed **segments** of ``segment_rows`` rows
  (padded, pad rows masked to ``-inf``), so every query of a given store
  hits exactly one compiled shape regardless of how ingestion sharded it;
- segment rows shard across the mesh via the existing
  :mod:`dcr_tpu.parallel.mesh` machinery (rows over ``data``+``fsdp``,
  queries replicated), so GSPMD runs the matmul as per-device partial
  products — the pjit-sharded equivalent of the reference's chunk loop;
- the ``search/topk`` program does matmul + pad-mask + ``lax.top_k`` — the
  global merge across mesh shards happens ON DEVICE inside the program. The
  host's exchanges with the runtime overlap the scan instead of following
  it: the answer's two host copies are queued at dispatch
  (``copy_to_host_async``), so the runtime starts both when the program
  ends instead of one after the other when the caller asks, and the fetch
  pays the tail of one transfer, not two round trips; and inside ONE
  placed segment the loop dispatches chunk n+1 before it collects chunk n
  (two executions in flight, never more, never across segments) where
  ``build()`` saw room for a second execution's temporaries — the compiled
  program's ``memory_analysis()`` against the mesh devices' free memory
  (``memory_stats()``) — and stays serial where it did not or where either
  figure cannot be read (gauge ``search/dispatch_ahead``);
- across segments (a store bigger than resident memory) the [B, K] tables
  merge on host — K rows per segment, not N: host traffic shrinks from the
  brute force's [B, N] similarity slabs to the answer itself.

Queries run at a fixed padded batch (``query_batch``, pad rows discarded),
and the program resolves through :mod:`dcr_tpu.core.warmcache` — a warm
restart answers its first query with ZERO XLA compiles.

Exactness: with ``normalize_queries=False`` and a store built without
ingest normalization, every score is the same float32 dot product the
brute force computes (the contraction axis is never split, and the matmul
asks for ``precision=HIGHEST``), so store-backed results equal
``search_folders`` on the same dump: keys exactly, scores to a few ulps (XLA
picks a reduction order per batch and shard shape) — pinned by
tests/test_store.py.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import numpy as np

from dcr_tpu.core import tracing
from dcr_tpu.core import warmcache
from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core.config import MeshConfig
from dcr_tpu.search.store import (EmbeddingStoreReader, StoreError,
                                  normalize_rows)

log = logging.getLogger("dcr_tpu")

#: default rows per device segment (one compiled program scans this many
#: rows per call); stores smaller than this compile to their padded size
DEFAULT_SEGMENT_ROWS = 65536
#: segments whose total rows fit under this stay device-resident between
#: queries; bigger stores keep host segments and ship per query
DEFAULT_MAX_RESIDENT_ROWS = 1 << 20


@compile_surface("search/topk")
def make_topk(top_k: int, normalize_queries: bool = False):
    """Jitted ``(feats [R, D], valid [R], q [B, D]) -> (scores [B, K],
    idx [B, K])`` — the sharded search kernel.

    ``feats`` rides as an ARGUMENT laid out across the mesh (rows sharded,
    D contiguous), so one executable serves every segment of a store and
    survives index reloads of the same shape; ``valid`` masks the segment's
    pad rows to ``-inf`` before the on-device ``lax.top_k`` merge.
    ``normalize_queries`` bakes the copy-risk cosine convention into the
    program (the store-backed risk index); the search path leaves it off so
    scores stay equal to the brute force. The matmul asks for
    ``precision=HIGHEST``: scores are promised as float32 dot products, and
    a TPU's default precision multiplies in bf16 passes (2e-3 off)."""
    import jax
    import jax.numpy as jnp

    def topk(feats, valid, q):
        if normalize_queries:
            q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True),
                                1e-12)
        sims = jnp.matmul(q, feats.T, precision=jax.lax.Precision.HIGHEST)
        sims = jnp.where(valid[None, :], sims, -jnp.inf)
        return jax.lax.top_k(sims, top_k)

    return jax.jit(topk)


def merge_topk(scores: np.ndarray, keys: np.ndarray, new_scores: np.ndarray,
               new_keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side cross-segment merge of two [N, K] top-k tables (desc) —
    the same merge the brute force applies across folders
    (``search.topk_merge`` delegates here, one implementation)."""
    all_scores = np.concatenate([scores, new_scores], axis=1)
    all_keys = np.concatenate([keys, new_keys], axis=1)
    order = np.argsort(-all_scores, axis=1, kind="stable")[:, : scores.shape[1]]
    return (np.take_along_axis(all_scores, order, axis=1),
            np.take_along_axis(all_keys, order, axis=1))


def room_for_two(execution_bytes: Optional[int],
                 free_bytes: Optional[int]) -> bool:
    """Whether two ``search/topk`` executions fit the device at once: what
    decides if ``_scan_segment`` dispatches a chunk ahead. ``execution_bytes``
    is what ONE execution allocates (the compiled program's temporaries and
    outputs: the [query_batch, segment_rows] scores are most of it),
    ``free_bytes`` what the device had left before any ran. An unknown
    figure (XLA:CPU reports neither) is no room."""
    if execution_bytes is None or free_bytes is None:
        return False
    return free_bytes >= 2 * execution_bytes


class ShardedTopK:
    """Compiled mesh-sharded top-k over an :class:`EmbeddingStoreReader`.

    ``query`` is thread-safe after :meth:`build` (serve /check handler
    threads share one engine); ``build`` is idempotent and eager — a built
    engine means compiled-and-resident, not hoped-for.
    """

    def __init__(self, reader: EmbeddingStoreReader, *, mesh=None,
                 top_k: int = 1, query_batch: int = 64,
                 segment_rows: int = 0,
                 max_resident_rows: int = DEFAULT_MAX_RESIDENT_ROWS,
                 normalize_queries: bool = False,
                 normalize_rows: bool = False, warm_dir: str = ""):
        import jax

        from dcr_tpu.parallel import mesh as pmesh

        self.reader = reader
        self.mesh = mesh if mesh is not None else pmesh.make_mesh(
            MeshConfig(data=1), devices=jax.devices()[:1])
        self.top_k = max(1, int(top_k))
        self.query_batch = max(1, int(query_batch))
        self.normalize_queries = bool(normalize_queries)
        self.warm_dir = warm_dir
        row_shards = pmesh.data_parallel_size(self.mesh)
        total = max(1, reader.total)
        want = int(segment_rows) if segment_rows > 0 else min(
            total, DEFAULT_SEGMENT_ROWS)
        # pad the segment to the row-sharding multiple so GSPMD splits rows
        # evenly; K can never exceed the segment
        want = max(want, self.top_k)
        self.segment_rows = -(-want // row_shards) * row_shards
        self.resident = (reader.total <= max(max_resident_rows,
                                             self.segment_rows))
        # host segments: (features [segment_rows, D] zero-padded,
        # valid [segment_rows] bool, keys [segment_rows] object — ""-padded,
        # n_rows)
        self._segments: list[tuple] = []
        self._dev_segments: list[tuple] = []
        self.num_segments = 0
        self._row_sharding = None
        self._q_sharding = None
        self._fn = None
        # build()'s decision (room_for_two): whether a chunk is dispatched
        # before the previous one is collected, or the loop is serial
        self._ahead = False
        self._normalize_rows = bool(normalize_rows)
        self._built = False

    @property
    def total(self) -> int:
        return self.reader.total

    def __len__(self) -> int:
        return self.reader.total

    # -- construction --------------------------------------------------------

    def _host_segments(self):
        """Regroup verified store shards into fixed padded segments."""
        dim = self.reader.embed_dim
        rows: list[np.ndarray] = []
        keys: list[np.ndarray] = []
        pending = 0
        for feats, ks in self.reader.iter_shards():
            if self._normalize_rows:
                feats = normalize_rows(feats)
            rows.append(feats)
            keys.append(np.asarray(ks, dtype=object))
            pending += feats.shape[0]
            while pending >= self.segment_rows:
                feats_all = np.concatenate(rows)
                keys_all = np.concatenate(keys)
                yield self._pad_segment(feats_all[:self.segment_rows],
                                        keys_all[:self.segment_rows], dim)
                rows = [feats_all[self.segment_rows:]]
                keys = [keys_all[self.segment_rows:]]
                pending = rows[0].shape[0]
        if pending:
            yield self._pad_segment(np.concatenate(rows),
                                    np.concatenate(keys), dim)

    def _pad_segment(self, feats: np.ndarray, keys: np.ndarray, dim: int):
        n = feats.shape[0]
        valid = np.zeros((self.segment_rows,), bool)
        valid[:n] = True
        if n < self.segment_rows:
            feats = np.concatenate(
                [feats, np.zeros((self.segment_rows - n, dim), np.float32)])
            keys = np.concatenate(
                [keys, np.full((self.segment_rows - n,), "", dtype=object)])
        return feats, valid, keys, n

    def build(self) -> "ShardedTopK":
        """Load segments, place them (device-resident when they fit), and
        compile (or warm-load) the ``search/topk`` program."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dcr_tpu.parallel import mesh as pmesh
        from dcr_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS

        if self._built:
            return self
        self._segments = list(self._host_segments())
        if not self._segments:
            raise StoreError(f"store {self.reader.dir} holds no rows")
        self.num_segments = len(self._segments)
        self._row_sharding = NamedSharding(self.mesh,
                                           P((DATA_AXIS, FSDP_AXIS)))
        self._q_sharding = NamedSharding(self.mesh, P())
        dim = self.reader.embed_dim
        k = min(self.top_k, self.segment_rows)
        jit_fn = make_topk(k, self.normalize_queries)
        feats_aval = jax.ShapeDtypeStruct((self.segment_rows, dim),
                                          jnp.float32,
                                          sharding=self._row_sharding)
        valid_aval = jax.ShapeDtypeStruct((self.segment_rows,), jnp.bool_,
                                          sharding=self._row_sharding)
        q_aval = jax.ShapeDtypeStruct((self.query_batch, dim), jnp.float32,
                                      sharding=self._q_sharding)
        cache = warmcache.WarmCache(self.warm_dir) if self.warm_dir else None
        res = warmcache.aot_compile(
            "search/topk", jit_fn, (feats_aval, valid_aval, q_aval),
            static_config={
                "top_k": k, "segment_rows": self.segment_rows,
                "query_batch": self.query_batch, "embed_dim": dim,
                "normalize_queries": self.normalize_queries,
                # same helper as the __init__ segment padding, so the
                # warm-cache key and the padding rule can never diverge
                "row_shards": int(pmesh.data_parallel_size(self.mesh)),
            }, cache=cache)
        self._fn = warmcache.guarded(res.fn, jit_fn, "search/topk")
        if self.resident:
            self._dev_segments = [self._put_segment(seg)
                                  for seg in self._segments]
            # the host feats/valid copies are dead weight once resident on
            # device (keys + row counts ride the device tuples) — dropping
            # them halves the engine's host-RAM footprint
            self._segments = []
            # placed, not on their way: the free figure below is read after
            jax.block_until_ready([seg[:2] for seg in self._dev_segments])
        mem = res.memory or {}
        execution = (mem["temp_bytes"] + mem.get("output_bytes", 0)
                     if "temp_bytes" in mem else None)
        free = self._free_bytes()
        self._ahead = room_for_two(execution, free)
        self._built = True
        reg = tracing.registry()
        reg.gauge("search/index_rows").set(self.reader.total)
        reg.gauge("search/index_segments").set(self.num_segments)
        reg.gauge("search/dispatch_ahead").set(int(self._ahead))
        log.info("shardindex: ready — %d rows in %d segment(s) of %d "
                 "(top_k=%d, batch=%d, %s, program %s; an execution takes "
                 "%s bytes of %s free: %s)", self.reader.total,
                 self.num_segments, self.segment_rows, k, self.query_batch,
                 "device-resident" if self.resident else "host-streamed",
                 res.source, execution, free,
                 "a chunk ahead" if self._ahead else "serial")
        return self

    def _free_bytes(self) -> Optional[int]:
        """The least free memory over this process's devices of the mesh
        once the resident segments are placed, less ONE more segment's
        share: the one a host-streamed scan or a ``query_rows`` tail places
        beside them. None where a device reports no figures (XLA:CPU). A
        device of THIS mesh against the program's bytes on a device:
        ``memwatch.remaining_device_bytes`` sums over the whole host."""
        free = []
        for dev in self.mesh.local_devices:
            stats = dev.memory_stats() or {}
            if "bytes_limit" not in stats or "bytes_in_use" not in stats:
                return None
            free.append(int(stats["bytes_limit"]) - int(stats["bytes_in_use"]))
        segment = self.segment_rows * (4 * self.reader.embed_dim + 1)
        return min(free) - segment // len(self.mesh.devices.flat)

    def _put_segment(self, seg):
        import jax

        feats, valid, keys, n = seg
        return (jax.device_put(feats, self._row_sharding),
                jax.device_put(valid, self._row_sharding), keys, n)

    # -- query ---------------------------------------------------------------

    def query(self, q: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Top-k of every query row against the whole store.

        ``q`` is float32 [n, D] (any n: chunks of ``query_batch`` run at the
        fixed compiled shape, pad rows discarded). Returns
        ``(scores [n, top_k] desc, keys [n, top_k] object)`` padded with
        ``-inf``/"" when the store holds fewer than ``top_k`` rows — the
        same table contract as the brute force."""
        if not self._built:
            self.build()
        import jax

        q = np.asarray(q, np.float32)
        if q.ndim != 2 or q.shape[1] != self.reader.embed_dim:
            raise ValueError(
                f"queries must be [n, {self.reader.embed_dim}], got "
                f"{q.shape}")
        n = q.shape[0]
        out_scores = np.full((n, self.top_k), -np.inf, np.float32)
        out_keys = np.full((n, self.top_k), "", dtype=object)
        if n == 0:
            return out_scores, out_keys
        reg = tracing.registry()
        reg.counter("search/query_total").inc()
        reg.counter("search/query_rows_total").inc(n)
        # the root of one call; its children (search/put, search/topk with
        # dispatch / device_wait / fetch inside it, search/merge) say what
        # the call was made of
        with tracing.span("search/query", rows=n,
                          chunks=-(-n // self.query_batch),
                          segments=self.num_segments):
            chunks = self._chunked_queries(q)
            segments = (self._dev_segments if self.resident
                        else map(self._put_segment, self._segments))
            for si, seg in enumerate(segments):
                self._scan_segment(si, seg, chunks, out_scores, out_keys)
        return out_scores, out_keys

    def _chunked_queries(self, q: np.ndarray) -> list[tuple[int, int, object]]:
        """All query chunks padded + device-put upfront (each is B x D,
        tiny), so segments can stream OUTERMOST: a host-streamed corpus is
        uploaded once per query, not once per chunk."""
        import jax

        chunks: list[tuple[int, int, object]] = []
        with tracing.span("search/put"):
            for start in range(0, q.shape[0], self.query_batch):
                chunk = q[start:start + self.query_batch]
                m = chunk.shape[0]
                if m < self.query_batch:
                    chunk = np.concatenate(
                        [chunk, np.repeat(chunk[-1:], self.query_batch - m,
                                          axis=0)])
                chunks.append((start, m,
                               jax.device_put(chunk, self._q_sharding)))
        return chunks

    def _dispatch(self, feats, valid, chunk_dev):
        """Start one chunk's scan of one placed segment; returns the two
        results (on the device, their host copies on the way)."""
        with tracing.span("search/dispatch"):
            scores, idx = self._fn(feats, valid, chunk_dev)
            # the answer is wanted on the host: said now, the runtime starts
            # both copies when the program ends instead of when the caller
            # asks, one after the other, once it has woken up
            scores.copy_to_host_async()
            idx.copy_to_host_async()
        tracing.registry().counter("search/host_copy_queued_total").inc(2)
        return scores, idx

    def _scan_segment(self, si: int, seg, chunks, out_scores: np.ndarray,
                      out_keys: np.ndarray) -> None:
        """Run every query chunk against one placed segment and fold the
        [B, K] tables into the running answer in place, in the chunks'
        order. Where ``build()`` saw room, chunk n+1 is dispatched before
        chunk n is collected: two executions in flight at most, both local
        to this call and collected before it returns (nothing runs ahead
        across segments)."""
        reg = tracing.registry()
        ran_ahead = reg.counter("search/dispatch_ahead_total")
        scanned = reg.counter("search/segments_scanned_total")
        feats, valid, keys, n_rows = seg
        ahead = None                    # the next chunk's results, if started
        for n, (start, m, chunk_dev) in enumerate(chunks):
            with tracing.span("search/topk", segment=si,
                              rows=int(n_rows), batch=m,
                              index_size=self.reader.total):
                scores, idx = ahead or self._dispatch(feats, valid, chunk_dev)
                ahead = None
                if self._ahead and n + 1 < len(chunks):
                    ahead = self._dispatch(feats, valid, chunks[n + 1][2])
                    ran_ahead.inc()
                # one host call that returns when the program has: the
                # device's share of the call, apart from the copies below
                # (both results are one program's: one is ready, both are;
                # waiting on the pair costs 0.05 ms a call more on a v5e)
                with tracing.span("search/device_wait"):
                    scores.block_until_ready()
                # what the copies queued at dispatch still cost the caller
                with tracing.span("search/fetch"):
                    scores = np.asarray(scores)[:m]
                    idx = np.asarray(idx)[:m]
            scanned.inc()
            with tracing.span("search/merge"):
                # pad hits (score -inf) keep key "" — invisible post-merge
                seg_keys = np.where(np.isneginf(scores), "", keys[idx])
                sl = slice(start, start + m)
                out_scores[sl], out_keys[sl] = merge_topk(
                    out_scores[sl], out_keys[sl], scores, seg_keys)

    def query_rows(self, q: np.ndarray, feats: np.ndarray,
                   keys: Sequence[str]) -> tuple[np.ndarray, np.ndarray]:
        """Top-k of ``q`` against AD-HOC rows (the live WAL tail) through
        the SAME compiled ``search/topk`` program the committed segments
        run, so a row scores bit-identically whether it is still in the
        tail or already compacted into a shard — the live tier's
        crash-equivalence pin rests on exactly this. Rows follow the
        engine's store conventions (normalization, ``segment_rows``
        padding); callers merge the result with :meth:`query` via
        :func:`merge_topk`."""
        if not self._built:
            self.build()
        q = np.asarray(q, np.float32)
        if q.ndim != 2 or q.shape[1] != self.reader.embed_dim:
            raise ValueError(
                f"queries must be [n, {self.reader.embed_dim}], got "
                f"{q.shape}")
        feats = np.asarray(feats, np.float32)
        keys_arr = np.asarray(keys, dtype=object)
        if feats.ndim != 2 or feats.shape[1] != self.reader.embed_dim:
            raise ValueError(
                f"tail rows must be [n, {self.reader.embed_dim}], got "
                f"{feats.shape}")
        if len(keys_arr) != feats.shape[0]:
            raise ValueError(f"{feats.shape[0]} tail rows but "
                             f"{len(keys_arr)} keys")
        n = q.shape[0]
        out_scores = np.full((n, self.top_k), -np.inf, np.float32)
        out_keys = np.full((n, self.top_k), "", dtype=object)
        if n == 0 or feats.shape[0] == 0:
            return out_scores, out_keys
        if self._normalize_rows:
            feats = normalize_rows(feats)
        chunks = self._chunked_queries(q)
        dim = self.reader.embed_dim
        for start in range(0, feats.shape[0], self.segment_rows):
            seg = self._put_segment(self._pad_segment(
                feats[start:start + self.segment_rows],
                keys_arr[start:start + self.segment_rows], dim))
            self._scan_segment(self.num_segments + start // self.segment_rows,
                               seg, chunks, out_scores, out_keys)
        return out_scores, out_keys


def open_engine(store_dir, *, mesh=None, top_k: int = 1,
                query_batch: int = 64, segment_rows: int = 0,
                normalize_queries: bool = False,
                normalize_rows: bool = False, warm_dir: str = "",
                build: bool = True) -> ShardedTopK:
    """Reader + engine in one call (the CLI/serve convenience)."""
    engine = ShardedTopK(
        EmbeddingStoreReader(store_dir), mesh=mesh, top_k=top_k,
        query_batch=query_batch, segment_rows=segment_rows,
        normalize_queries=normalize_queries, normalize_rows=normalize_rows,
        warm_dir=warm_dir)
    return engine.build() if build else engine
