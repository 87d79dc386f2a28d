"""Driver `store_search`: one caller, `engine.query(q)` back to back, over a
device-resident embedding store (dcr_tpu/search/shardindex.py).

Traffic parameters (the workload's file): `query_batch`, `top_k`,
`pool_batches` (distinct query batches drawn from `--seed`,
cycled), `near_copy_share` and `near_copy_noise` (that share of the queries
are corpus rows plus noise of that norm, the rest are random directions),
`store_shard_rows`, `reference` block sizes, `limits`.
Configuration (the config's file): `rows`, `embed_dim`, `corpus_seed`.
"""
from __future__ import annotations

import gc
import shutil
import time

import numpy as np

from benchmark.lib import harness, rng
from benchmark.reference import topk as reference


def build_store(store_dir, rows: int, dim: int, corpus_seed: int,
                shard_rows: int) -> None:
    """A real EmbeddingStoreWriter store of the seeded corpus, keys the row
    numbers as strings; built beside its final place and renamed, so a killed
    build leaves nothing that looks like a store."""
    from dcr_tpu.search.store import EmbeddingStoreWriter

    tmp = store_dir.with_name(store_dir.name + ".building")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.parent.mkdir(parents=True, exist_ok=True)
    writer = EmbeddingStoreWriter.create(tmp, embed_dim=dim,
                                         shard_rows=shard_rows)
    for base in range(0, rows, shard_rows):
        ids = np.arange(base, min(base + shard_rows, rows))
        writer.add(np.asarray(rng.unit_rows(corpus_seed, ids, dim)),
                   ids.astype(str))
    writer.finalize()
    tmp.rename(store_dir)


def query_pool(seed: int, corpus_seed: int, rows: int, dim: int, batches: int,
               batch: int, near_share: float, near_noise: float):
    """[batches, batch, dim] float32 unit-norm queries from the seed. A
    `near_share` of them are near copies: a corpus row plus Gaussian noise of
    norm `near_noise`, normalised, as a generated image that copies a
    training image embeds; the rest are random directions."""
    gen = np.random.default_rng([int(seed), 7])
    n = batches * batch
    q = gen.standard_normal((n, dim), dtype=np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    near = gen.random(n) < near_share
    # a source row for every query, used or not: one shape whatever the seed
    src = np.asarray(rng.unit_rows(corpus_seed, gen.integers(0, rows, n), dim))
    q = np.where(near[:, None], src + near_noise * q, q)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    return q.reshape(batches, batch, dim).astype(np.float32)


class Driver:
    def __init__(self, bench):
        self.bench = bench
        self.cfg = bench.cell.config
        self.traffic = bench.cell.traffic
        self.engine = None
        self.answers: list = []         # (pool index, scores [B], keys [B])
        self.failed = 0
        self._i = 0

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        from dcr_tpu.search.shardindex import open_engine

        b, c, t = self.bench, self.cfg, self.traffic
        rows, dim = int(c["rows"]), int(c["embed_dim"])
        store = b.cache / "stores" / (
            f"{b.cell.config_name}-r{rows}-d{dim}-s{c['corpus_seed']}")
        if not (store / "store_manifest.json").exists():
            t0 = time.perf_counter()
            build_store(store, rows, dim, int(c["corpus_seed"]),
                        int(t["store_shard_rows"]))
            b.log("store_built", seconds=round(time.perf_counter() - t0, 2),
                  rows=rows, path=str(store.relative_to(b.cell.root)))
        self.pool = query_pool(b.seed, int(c["corpus_seed"]), rows, dim,
                               int(t["pool_batches"]), int(t["query_batch"]),
                               float(t["near_copy_share"]),
                               float(t["near_copy_noise"]))
        t0 = time.perf_counter()
        self.engine = open_engine(store, top_k=int(t["top_k"]),
                                  query_batch=int(t["query_batch"]),
                                  segment_rows=rows)
        if not self.engine.resident:
            raise harness.BenchFailure("the store is not device-resident")
        b.log("engine_open", seconds=round(time.perf_counter() - t0, 2),
              rows=self.engine.total, segments=self.engine.num_segments,
              resident=self.engine.resident)
        self.engine.query(self.pool[-1])        # warm the one shape

    # -- the window -----------------------------------------------------
    def unit(self) -> None:
        i = self._i % len(self.pool)
        self._i += 1
        with self.bench.span("query"):
            scores, keys = self.engine.query(self.pool[i])
        self.answers.append((i, scores[:, 0], keys[:, 0]))

    def drain(self) -> None:
        pass                     # query() returns with the answer on the host

    def end_to_end(self, window) -> dict:
        ms = sorted(1e3 * d for _, d in window.unit_times)
        p95 = ms[min(len(ms) - 1, int(np.ceil(0.95 * len(ms))) - 1)]
        return {"search_queries_per_s":
                    window.units * self.pool.shape[1] / window.seconds,
                "search_batch_ms_p95": p95}

    def counters(self, window) -> dict:
        n, d = int(self.cfg["rows"]), int(self.cfg["embed_dim"])
        b = self.pool.shape[1]
        times = [1e3 * t for _, t in window.unit_times]
        ms = sorted(times)
        slowest = sorted(range(len(times)), key=times.__getitem__)[-3:]
        self.bench.log("latency_ms", calls=len(ms), p50=ms[len(ms) // 2],
                       p99=ms[int(0.99 * (len(ms) - 1))], max=ms[-1],
                       slowest_calls=[[i, times[i]] for i in slowest])
        return {"flops_per_unit": 2.0 * b * n * d,
                "bytes_per_unit": 4.0 * n * d,
                "rows": n, "dim": d, "batch": b,
                "module_pattern": r"^jit_topk\("}

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        self.engine = None
        gc.collect()

    def verify(self, window) -> list:
        import jax.numpy as jnp

        c, t = self.cfg, self.traffic
        rows, dim = int(c["rows"]), int(c["embed_dim"])
        seed = int(c["corpus_seed"])
        limits = t["limits"]
        block = int(t["reference"]["row_block"])

        def rows_of(ids):
            """Corpus rows by number, made in blocks of one size (the last
            padded), so one program serves whatever the window held."""
            ids = np.asarray(ids)
            out = []
            for a in range(0, len(ids), block):
                part = ids[a:a + block]
                pad = np.resize(part, block) if len(part) < block else part
                out.append(rng.unit_rows(seed, pad, dim)[:len(part)])
            return out[0] if len(out) == 1 else jnp.concatenate(out)

        used = sorted({i for i, _, _ in self.answers})
        flat = self.pool[used].reshape(-1, dim)
        ref_best, _ = reference.best_rows(
            flat, rows_of, rows, row_block=block,
            query_block=int(t["reference"]["query_block"]))
        self.rows_of, self.ref_best_flat, self.used = rows_of, ref_best, used
        ref_best = ref_best.reshape(len(used), -1)
        where = {i: n for n, i in enumerate(used)}
        idx = np.zeros((len(self.answers), self.pool.shape[1]), np.int64)
        for n, (_, _, keys) in enumerate(self.answers):
            for j, key in enumerate(keys):
                try:
                    idx[n, j] = int(key)
                except (TypeError, ValueError):
                    idx[n, j] = -1
        bad = int(((idx < 0) | (idx >= rows)).sum())
        idx = np.clip(idx, 0, rows - 1)
        # the reference's own score of the row each answer names
        ref_rows = rows_of(idx.reshape(-1))
        q_all = np.stack([self.pool[i] for i, _, _ in self.answers])
        ref_score = reference.scores_of(q_all.reshape(-1, dim),
                                        ref_rows).reshape(idx.shape)
        got = np.stack([s for _, s, _ in self.answers]).astype(np.float32)
        best = np.stack([ref_best[where[i]] for i, _, _ in self.answers])
        gap = float(np.max(best - ref_score))
        err = float(np.max(np.abs(got - ref_score)))
        self.bench.log("verified", answers=int(idx.size),
                       distinct_batches=len(used))
        return [harness.check("bad_keys", bad, limits["bad_keys"]),
                harness.check("best_score_gap", gap, limits["best_score_gap"]),
                harness.check("score_error", err, limits["score_error"])]

    def close(self) -> None:
        self.engine = None
