"""Stage 3: chunked brute-force max-inner-product search of generations
against every LAION chunk's embedding dump.

Capability-equivalent of embedding_search/similarity_search.py (22-91): the
generation embeddings are split into chunks, each LAION folder's embeddings are
streamed through device matmuls, and a running top-k (reference: top-1)
score/key table is merged across chunks. The reference's crashes — the
mis-named args.laion_embeddings_folders flag (line 34 vs 16) and the swapped
open/pickle.dump arguments (90-91) — have no equivalent here; results land in
a .npz with named fields.
"""

from __future__ import annotations

import logging
import time
from pathlib import Path
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing
from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core.config import SearchConfig
from dcr_tpu.search.embed import find_embedding_file, load_embeddings

log = logging.getLogger("dcr_tpu")


@compile_surface("search/matmul")
def make_search_matmul():
    """Jitted ``(gen_chunk [M, D], laion_feats [N, D]) -> sims [M, N]`` —
    the chunked brute-force similarity kernel. Registered so DCR010 and the
    compile-surface manifest cover the search workload's one device
    program (it was a bare ``jax.jit(lambda ...)`` before dcr-watch).

    ``precision=HIGHEST``: the scores are promised as float32 dot products,
    and a TPU's default precision multiplies in bf16 passes (2e-3 off a
    float32 reference on a v5e, 1e-7 at HIGHEST)."""
    return jax.jit(
        lambda a, b: jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST))


def topk_merge(scores: np.ndarray, keys: np.ndarray, new_scores: np.ndarray,
               new_keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merge two [N, K] top-k tables (scores desc) into one. Delegates to
    the store engine's merge so the brute-force and store-backed paths can
    never drift on merge semantics."""
    from dcr_tpu.search.shardindex import merge_topk

    return merge_topk(scores, keys, new_scores, new_keys)


def load_folder_embeddings(emb_file: Path, *, quarantine: bool = True):
    """Load one folder's dump under the copyrisk/latent-cache
    verify-before-load contract, or None when the folder can't serve.

    An UNREADABLE dump (truncated zip, bit-flipped pickle, sha-sidecar
    mismatch) is genuinely corrupt: quarantine-renamed so no later search
    retries known-bad bytes, counted (``search/folder_corrupt``), and
    logged. A READABLE dump that merely fails validation (features/keys
    row-count mismatch, non-2D features) stays IN PLACE — it may be a valid
    artifact of the wrong kind that a rerun will replace — counted as
    ``search/folder_invalid``. Nothing is ever swallowed silently."""
    from dcr_tpu.core.warmcache import quarantine_rename

    reg = tracing.registry()
    try:
        feats, keys = load_embeddings(emb_file)
    except OSError as e:
        # transient read failure (NFS timeout, EINTR) that survived the
        # retry tier is NOT evidence of corruption: skip this search, keep
        # the dump — quarantining would permanently shrink the corpus over
        # a flaky mount
        R.log_event("search_folder_read_error", path=str(emb_file),
                    error=repr(e))
        reg.counter("search/folder_read_error").inc()
        log.warning("unreadable (I/O) embedding dump %s (%r); left in "
                    "place, skipping", emb_file, e)
        return None
    except Exception as e:  # unreadable/corrupt damage (reference 51-56)
        from dcr_tpu.search.embed import quarantine_sidecar

        dest = quarantine_rename(emb_file) if quarantine else None
        if quarantine:
            quarantine_sidecar(emb_file)
        R.log_event("search_folder_corrupt", path=str(emb_file),
                    error=repr(e),
                    quarantined_to=str(dest) if dest else None)
        reg.counter("search/folder_corrupt").inc()
        log.warning("corrupt embedding dump %s (%r); quarantined -> %s",
                    emb_file, e, dest.name if dest else "<rename failed>")
        return None
    feats = np.asarray(feats)
    if feats.ndim != 2 or feats.shape[0] != len(keys):
        R.log_event("search_folder_invalid", path=str(emb_file),
                    shape=list(feats.shape), keys=len(keys))
        reg.counter("search/folder_invalid").inc()
        log.warning("invalid embedding dump %s (features %s, %d keys); "
                    "left in place, skipping", emb_file, feats.shape,
                    len(keys))
        return None
    return np.asarray(feats, np.float32), keys


def search_folders(gen_features: np.ndarray, gen_keys: Sequence[str],
                   laion_folders: Sequence[str | Path], *, top_k: int = 1,
                   num_chunks: int = 20) -> dict:
    """Running top-k of every generation against all LAION chunks.

    Returns {"scores": [N,K], "keys": [N,K] laion ids, "gen_images": [N]}.
    """
    n = len(gen_features)
    if n == 0:
        return {"scores": np.zeros((0, top_k), np.float32),
                "keys": np.zeros((0, top_k), dtype=object),
                "gen_images": np.asarray([], dtype=object)}
    num_chunks = max(1, min(num_chunks, n))
    chunk_size = -(-n // num_chunks)
    best_scores = np.full((n, top_k), -np.inf, np.float32)
    best_keys = np.full((n, top_k), "", dtype=object)

    matmul = make_search_matmul()

    folders_done = tracing.registry().counter("search/folders_done")
    for folder in laion_folders:
        emb_file = find_embedding_file(folder)
        if emb_file is None:
            log.warning("no embedding dump under %s; skipping", folder)
            continue
        loaded = load_folder_embeddings(emb_file)
        if loaded is None:
            continue
        feats, keys = loaded
        if not len(feats):
            continue
        t0 = time.time()
        keys_arr = np.asarray(keys, dtype=object)
        feats_j = jnp.asarray(feats)
        for start in range(0, n, chunk_size):
            gen_chunk = jnp.asarray(gen_features[start:start + chunk_size])
            # one span per device matmul + host top-k merge: the search
            # stage's time breakdown in trace_report comes from here (it
            # previously had only a per-folder log line + time.time())
            with tracing.span("search/chunk", folder=str(folder),
                              start=start, rows=int(gen_chunk.shape[0]),
                              index_size=int(feats_j.shape[0])):
                sims = np.asarray(jax.device_get(matmul(gen_chunk, feats_j)))
                k = min(top_k, sims.shape[1])
                top_idx = np.argpartition(-sims, k - 1, axis=1)[:, :k]
                top_scores = np.take_along_axis(sims, top_idx, axis=1)
                order = np.argsort(-top_scores, axis=1)
                top_idx = np.take_along_axis(top_idx, order, axis=1)
                top_scores = np.take_along_axis(top_scores, order, axis=1)
                if k < top_k:  # pad tiny chunks
                    pad = top_k - k
                    top_scores = np.pad(top_scores, ((0, 0), (0, pad)),
                                        constant_values=-np.inf)
                    top_idx = np.pad(top_idx, ((0, 0), (0, pad)))
                sl = slice(start, start + len(top_scores))
                best_scores[sl], best_keys[sl] = topk_merge(
                    best_scores[sl], best_keys[sl],
                    top_scores, keys_arr[top_idx])
        folders_done.inc()
        log.info("searched %s (%d embeddings) in %.1fs", folder, len(feats),
                 time.time() - t0)
    return {"scores": best_scores, "keys": best_keys,
            "gen_images": np.asarray(list(gen_keys), dtype=object)}


def search_store(gen_features: np.ndarray, gen_keys: Sequence[str],
                 store_dir: str | Path, *, top_k: int = 1,
                 mesh=None, query_batch: int = 64, segment_rows: int = 0,
                 warm_dir: str = "") -> dict:
    """The store-backed path of :func:`search_folders`: one device-sharded
    top-k over a built embedding store (dcr-store) instead of the
    per-folder host-merged chunk loop. Same result contract —
    ``{"scores": [N,K], "keys": [N,K], "gen_images": [N]}`` — and on the
    same embedding dump the keys equal the brute force's and the scores
    agree to a few float32 ulps (pinned by tests/test_store.py)."""
    from dcr_tpu.search.shardindex import open_engine

    n = len(gen_features)
    if n == 0:
        return {"scores": np.zeros((0, top_k), np.float32),
                "keys": np.zeros((0, top_k), dtype=object),
                "gen_images": np.asarray([], dtype=object)}
    engine = open_engine(store_dir, mesh=mesh, top_k=top_k,
                         query_batch=query_batch, segment_rows=segment_rows,
                         warm_dir=warm_dir)
    t0 = time.time()
    scores, keys = engine.query(np.asarray(gen_features, np.float32))
    log.info("store search: %d queries x %d rows in %.1fs", n, engine.total,
             time.time() - t0)
    return {"scores": scores, "keys": keys,
            "gen_images": np.asarray(list(gen_keys), dtype=object)}


def search_store_ann(gen_features: np.ndarray, gen_keys: Sequence[str],
                     store_dir: str | Path, *, top_k: int = 1, mesh=None,
                     nprobe: int = 0, shortlist_k: int = 0,
                     query_batch: int = 64, segment_rows: int = 0,
                     live: bool = False, warm_dir: str = "") -> dict:
    """The dcr-ann path of :func:`search_store`: nprobe-bounded IVF scan
    over int8 inverted lists with exact f32 re-ranking
    (:mod:`dcr_tpu.search.annindex`) — sublinear in corpus size, gated on
    recall against the exact oracle by tools/bench_ann.py. ``live`` also
    scans the WAL tail exactly (tail rows are in no inverted list) and
    merges. Same result contract as every other path."""
    from dcr_tpu.search.annindex import (DEFAULT_NPROBE, DEFAULT_SHORTLIST_K,
                                         open_ann_engine)
    from dcr_tpu.search.shardindex import merge_topk

    n = len(gen_features)
    if n == 0:
        return {"scores": np.zeros((0, top_k), np.float32),
                "keys": np.zeros((0, top_k), dtype=object),
                "gen_images": np.asarray([], dtype=object)}
    engine = open_ann_engine(
        store_dir, mesh=mesh, top_k=top_k,
        nprobe=int(nprobe) or DEFAULT_NPROBE,
        shortlist_k=int(shortlist_k) or DEFAULT_SHORTLIST_K,
        query_batch=query_batch, segment_rows=segment_rows,
        warm_dir=warm_dir)
    q = np.asarray(gen_features, np.float32)
    t0 = time.time()
    scores, keys = engine.query(q)
    if live:
        from dcr_tpu.search.livestore import load_wal_tail

        tail_feats, tail_keys, _stats = load_wal_tail(
            store_dir, after_seq=engine.reader.wal_through,
            embed_dim=engine.reader.embed_dim)
        if len(tail_feats):
            t_scores, t_keys = engine.query_rows(q, tail_feats, tail_keys)
            scores, keys = merge_topk(scores, keys, t_scores, t_keys)
    log.info("ann search: %d queries x %d rows (nprobe=%d) in %.1fs", n,
             engine.total, engine.nprobe, time.time() - t0)
    return {"scores": scores, "keys": keys,
            "gen_images": np.asarray(list(gen_keys), dtype=object)}


def run_search(cfg: SearchConfig, *,
               laion_folders: Sequence[str | Path] = (),
               top_k: int = 1) -> Path:
    """Full stage: load gen embeddings, search (ann tier when ``cfg.ann``,
    store-backed when ``cfg.store_dir`` names a built store, else the
    per-folder brute force), dump results."""
    gen_emb = find_embedding_file(cfg.gen_folder)
    if gen_emb is None:
        raise FileNotFoundError(
            f"no embedding dump under {cfg.gen_folder}; run search.embed first")
    gen_features, gen_keys = load_embeddings(gen_emb)
    top_k = max(top_k, cfg.top_k)
    if cfg.ann:
        if not cfg.store_dir:
            raise ValueError("--search.ann needs --search.store_dir (the "
                             "IVF tier indexes a built store)")
        from dcr_tpu.parallel import mesh as pmesh

        result = search_store_ann(
            gen_features, gen_keys, cfg.store_dir, top_k=top_k,
            mesh=pmesh.make_mesh(cfg.mesh), nprobe=cfg.nprobe,
            shortlist_k=cfg.shortlist_k, query_batch=cfg.query_batch,
            segment_rows=cfg.segment_rows, live=cfg.live,
            warm_dir=cfg.warm_dir)
    elif cfg.store_dir and cfg.live:
        # dcr-live: committed snapshot + WAL tail, merged (livestore.py)
        from dcr_tpu.parallel import mesh as pmesh
        from dcr_tpu.search.livestore import query_live

        scores, keys = query_live(
            cfg.store_dir, np.asarray(gen_features, np.float32),
            top_k=top_k, mesh=pmesh.make_mesh(cfg.mesh),
            query_batch=cfg.query_batch, segment_rows=cfg.segment_rows,
            warm_dir=cfg.warm_dir)
        result = {"scores": scores, "keys": keys,
                  "gen_images": np.asarray(list(gen_keys), dtype=object)}
    elif cfg.store_dir:
        from dcr_tpu.parallel import mesh as pmesh

        result = search_store(gen_features, gen_keys, cfg.store_dir,
                              top_k=top_k, query_batch=cfg.query_batch,
                              segment_rows=cfg.segment_rows,
                              mesh=pmesh.make_mesh(cfg.mesh),
                              warm_dir=cfg.warm_dir)
    else:
        result = search_folders(gen_features, gen_keys, laion_folders,
                                top_k=top_k, num_chunks=cfg.num_chunks)
    out = Path(cfg.out_path)
    out.parent.mkdir(parents=True, exist_ok=True)
    np.savez(out, scores=result["scores"],
             keys=result["keys"].astype(str),
             gen_images=result["gen_images"].astype(str))
    log.info("search results -> %s", out)
    return out
