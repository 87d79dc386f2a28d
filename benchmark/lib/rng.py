"""Seeded data for the benchmark: keys, corpus rows, queries.

`--seed` may be a little over 2**31; `jax.random.key` takes 32 signed bits, so
the seed is folded in two halves."""
from __future__ import annotations

import functools


def key_of(seed: int, stream: int = 0):
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.cache
def _rows_fn(dim: int):
    import jax
    import jax.numpy as jnp

    def one(key, i):
        x = jax.random.normal(jax.random.fold_in(key, i), (dim,), jnp.float32)
        return x / jnp.linalg.norm(x)

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def unit_rows(seed: int, ids, dim: int):
    """Row `i` of the seeded corpus, for each `i` in `ids`: a unit-norm
    float32 vector that depends on (seed, i) alone, so any block or any
    single row can be made again without the rest."""
    import jax.numpy as jnp

    return _rows_fn(dim)(key_of(seed, 1), jnp.asarray(ids, jnp.uint32))
