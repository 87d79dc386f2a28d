"""Plain exact top-1 search: float32 inner products of every query with every
row, the largest kept. Imports nothing of the program; the rows come from the
benchmark's seeded generator, block by block, so that it fits beside nothing
and needs no store."""
from __future__ import annotations

import functools

import numpy as np


@functools.cache
def _block_best(precision: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block_best(q, rows, base, best, arg):
        sims = jnp.matmul(q, rows.T, precision=precision)
        idx = jnp.argmax(sims, axis=1)
        val = jnp.take_along_axis(sims, idx[:, None], axis=1)[:, 0]
        better = val > best
        return (jnp.where(better, val, best),
                jnp.where(better, idx.astype(jnp.int32) + base, arg))

    return block_best


def best_rows(queries: np.ndarray, rows_of, n_rows: int, *, row_block: int,
              query_block: int, precision: str = "highest"):
    """(best score [n], its row [n]) of each query over rows 0..n_rows-1.
    `rows_of(ids)` gives the corpus rows with those numbers. `precision` is
    'highest' for the reference; the control asks for 'high' (three bf16
    passes), the step below what the configuration states."""
    import jax.numpy as jnp

    fn = _block_best(precision)
    n = queries.shape[0]
    chunks = [jnp.asarray(queries[a:a + query_block])
              for a in range(0, n, query_block)]
    best = [jnp.full((c.shape[0],), -jnp.inf, jnp.float32) for c in chunks]
    arg = [jnp.zeros((c.shape[0],), jnp.int32) for c in chunks]
    for base in range(0, n_rows, row_block):
        rows = rows_of(np.arange(base, min(base + row_block, n_rows)))
        for i, c in enumerate(chunks):
            best[i], arg[i] = fn(c, rows, jnp.int32(base), best[i], arg[i])
    return (np.concatenate([np.asarray(b) for b in best]),
            np.concatenate([np.asarray(a) for a in arg]))


def scores_of(queries: np.ndarray, rows) -> np.ndarray:
    """Row-wise float32 dot products, query i with row i (elementwise product
    and sum in float32: no matrix unit, no passes)."""
    import jax.numpy as jnp

    return np.asarray(jnp.sum(jnp.asarray(queries, jnp.float32)
                              * jnp.asarray(rows, jnp.float32), axis=-1))
