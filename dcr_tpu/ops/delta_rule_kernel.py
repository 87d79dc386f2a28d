"""Pallas TPU kernel for the chunked gated delta rule, forward only.

The same mathematics as `ops/delta_rule.py`'s XLA form and its docstring:
channel-wise decay, beta in (0, 2), S_0 = 0, padding positions that neither
decay nor write, and no exponent above 0 formed anywhere. What changes is
where the intermediates live. A grid program owns whole heads of one
sequence (`HEADS` side by side, whose chains are independent, so that the
scheduler interleaves them); each head's [Dk, Dv] float32 state stays in a
VMEM scratch buffer across all of the sequence's chunks, and the cumulative
log decays, the within-chunk matrices A and P and the UT transform of a chunk
are values of the loop body that never go to HBM.

Layout contract: q, k and log alpha as `[B, T, H*Dk]`, v as `[B, T, H*Dv]`,
the projections' own layout, each program reading its heads' lane slabs
(`BlockSpec` (1, T, heads * D)); beta as `[B, H, 1, T]`, a row a head (1 MB
at the Solar cell's shape, where a `[T, 1]` column a head would be padded to
128 lanes in HBM), turned into a column in VMEM once a program; o as
`[B, T, H*Dv]` float32. Dk and Dv are multiples of 128, T a multiple of the
chunk (`delta_rule_fwd` pads), the chunk a multiple of 8.

A chunk, in order:

- g: the cumulative log decays down the chunk's rows (a prefix sum by
  doubling, float32 adds);
- every sub-chunk block of `SUB` rows: the decay of each pair inside it,
  e^{g_r - g_i} masked to i <= r BEFORE the exponential, formed once a key
  position i and read by both A (strictly below the diagonal) and P (on and
  below it), each summed over the key channels in float32; the pairs with
  earlier sub-chunks through the position before the block, as the XLA form
  takes them (operands in q's dtype, float32 accumulation);
- the UT transform: (I + Diag(beta) A) X = beta [V | e^g K] solved block
  after block by forward substitution in float32 (one row's column of
  Diag(beta) A a step, exact elementwise products), the rows already solved
  entering later blocks through a product at HIGHEST, as in the XLA form;
- the state's step: u = U~ - W S, o = (e^g Q) S + P u,
  S <- Diag(e^{g_C}) S + (e^{g_C - g} K)^T u, with operands in q's dtype and
  float32 accumulation, as the XLA form's `lax.scan` does; W S and (e^g Q) S
  are one product, and (e^{g_C - g} K)^T is turned before the step, so that
  the chain from one chunk's state to the next holds two products and no
  transpose.

interpret=True runs the same kernel through the Pallas interpreter (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

#: positions a chunk: the state is read and written once a chunk
CHUNK = 64
#: positions a sub-chunk: pairs inside one take their decay elementwise
SUB = 16
#: heads of one row a program takes side by side (fewer where they do not
#: divide the heads or do not fit VMEM)
HEADS = 4

#: what the kernel may take of the v5e's 128 MiB of VMEM (as the flash kernels)
VMEM_LIMIT_BYTES = 32 * 2**20
#: the blocks a program holds, double-buffered by the pipeline, may take this
#: much of it; the rest is the chunk body's values
RESIDENT_MAX_BYTES = 16 * 2**20

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
TILE = 8         # a float32 vreg's sublanes


def resident_bytes(t: int, dk: int, dv: int, itemsize: int, heads: int = 1) -> int:
    """VMEM a program's blocks take at a padded length t: q, k and v in their
    dtype, log alpha and o in float32 and beta's row (8 sublanes), twice each
    for the pipeline's buffers; a state and beta's column (lane-padded to 128)
    once; all of it for each of `heads` heads."""
    per_row = (2 * dk + dv) * itemsize + 4 * (dk + dv) + 4 * 8
    return heads * (2 * t * per_row + 4 * t * LANES + 4 * dk * dv)


def padded_length(t: int, chunk: int) -> int:
    return -(-t // chunk) * chunk


def heads_per_program(h: int, t: int, dk: int, dv: int, itemsize: int) -> int:
    """The most heads, up to `HEADS`, that divide h and fit VMEM together."""
    return max(n for n in range(1, min(HEADS, h) + 1) if h % n == 0 and (
        n == 1 or resident_bytes(padded_length(t, CHUNK), dk, dv, itemsize, n)
        <= RESIDENT_MAX_BYTES))


def supported(q, k, v, log_alpha, beta) -> bool:
    """Kernel-capable operands: [B, T, H, D] q, k and log alpha alike, v with
    the same B, T, H, beta [B, T, H]; Dk and Dv multiples of 128 (a head is
    whole lane slabs); q, k and v of one dtype, float32 or bfloat16; and what
    a program keeps resident fits VMEM. Shapes and dtypes only, so
    `jax.ShapeDtypeStruct`s will do."""
    if q.ndim != 4 or v.ndim != 4:
        return False
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    return (k.shape == q.shape and log_alpha.shape == q.shape
            and v.shape[:3] == (b, t, h) and tuple(beta.shape) == (b, t, h)
            and dk % LANES == 0 and dv % LANES == 0
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and k.dtype == q.dtype and v.dtype == q.dtype
            and resident_bytes(padded_length(t, CHUNK), dk, dv, q.dtype.itemsize)
            <= RESIDENT_MAX_BYTES)


def _decay(x: jax.Array) -> jax.Array:
    """e^x for an exponent that is at most 0 by construction; the clamp
    keeps rounding in the cumulative sums from making it a hair above."""
    return jnp.exp(jnp.minimum(x, 0.0))


def _dot(a, b, contract: tuple[int, int], precision=None) -> jax.Array:
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                               preferred_element_type=F32, precision=precision)


def _prefix_sums(x: jax.Array) -> jax.Array:
    """Inclusive prefix sums down the rows of x [C, L], by doubling: after
    the step of shift m every row holds the sum of the 2m rows ending at it."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


@functools.partial(jax.jit, static_argnames=("sub", "mm"))
def _chunk_terms(g, q32, k32, v32, beta, *, sub: int, mm):
    """What a chunk hands the state's step, none of it reading the state:
    (U~, [W; e^g Q], P, (e^{g_C - g} K)^T, e^{g_C} as a column); g [C, Dk]
    its cumulative log decays, q32, k32 [C, Dk] and v32 [C, Dv] in float32,
    beta [C, 1]. A jit of its own, so that a program's heads share one trace
    (Mosaic inlines it: the kernel is the same)."""
    chunk, dk = k32.shape
    dv = v32.shape[1]
    # the rows from each tile's start on (an iota of its own a tile: a slice
    # of one iota is not lowered by Mosaic once a head spans two lane slabs)
    rows_from = {t0: t0 + jax.lax.broadcasted_iota(jnp.int32, (sub - t0, dk), 0)
                 for t0 in range(0, sub, TILE)}
    row_of_col = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    lane_of_p = jax.lax.broadcasted_iota(jnp.int32, (sub, chunk), 1)
    row_of_chunk = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk), 0)
    e_g = _decay(g)
    # the right-hand side of the UT transform: beta [V | e^g K]
    rhs = beta * jnp.concatenate([v32, k32 * e_g], axis=1)      # [C, Dv+Dk]
    solved, p_rows = [], []
    for a in range(chunk // sub):
        lo, hi = a * sub, (a + 1) * sub
        gs, ks, qs, bs = g[lo:hi], k32[lo:hi], q32[lo:hi], beta[lo:hi]
        r = rhs[lo:hi]
        if a:
            # pairs in earlier sub-chunks, through m = lo - 1: both factors'
            # exponents are at most 0, and the rows from lo on are masked
            # before the exponential (they read 0)
            gm = g[lo - 1:lo]
            near = _decay(gs - gm)
            left = jnp.concatenate([ks * near, qs * near], axis=0).astype(mm)
            right = (k32 * _decay(jnp.where(row_of_chunk < lo, gm - g, -jnp.inf))
                     ).astype(mm)
            off = _dot(left, right, (1, 1))                      # [2s, C]
            before = jnp.concatenate(solved, axis=0)             # [lo, Dv+Dk]
            r = r - _dot(bs * off[:sub, :lo], before, (1, 0), HIGHEST)
            p_row = off[sub:]
        else:
            p_row = jnp.zeros((sub, chunk), F32)
        # pairs inside the block: key position j's decays to the rows at and
        # after it, formed once, read by A's column and P's. Rows go in tiles
        # of 8 (a float32 vreg's sublanes): the rows at and after j lie in
        # the tiles from j's own on, and only those are computed.
        columns = []
        for j in range(sub):
            t0 = j // TILE * TILE
            x = _decay(jnp.where(rows_from[t0] >= j, gs[t0:] - gs[j:j + 1],
                                 -jnp.inf)) * ks[j:j + 1]
            a_col, p_col = (jnp.sum(x * y[t0:], axis=1, keepdims=True) for y in (ks, qs))
            if t0:
                a_col, p_col = (jnp.concatenate([jnp.zeros((t0, 1), F32), c], axis=0)
                                for c in (a_col, p_col))
            columns.append(bs * jnp.where(row_of_col > j, a_col, 0.0))  # (beta A)[:, j]
            p_row = jnp.where(lane_of_p == lo + j, p_col, p_row)
        # (I + Diag(beta) A) X = r by forward substitution: row j is final
        # when its column is taken off the rows below it, which lie in the
        # tiles from j's own on
        tiles = [r[t:t + TILE] for t in range(0, sub, TILE)]
        for j in range(sub - 1):
            first = j // TILE
            row = tiles[first][j % TILE:j % TILE + 1]
            for t in range(first, len(tiles)):
                tiles[t] = tiles[t] - columns[j][t * TILE:(t + 1) * TILE] * row
        solved.append(jnp.concatenate(tiles, axis=0))
        p_rows.append(p_row)
    x = jnp.concatenate(solved, axis=0)
    g_end = g[chunk - 1:]
    # the state's decay over the chunk, a key channel a row: the last rows
    # of g turned (a tile of 8 rows, the transpose's unit)
    keep = _decay(jnp.transpose(g[chunk - 8:])[:, 7:])          # [Dk, 1]
    return (x[:, :dv],                                           # U = U~ - W S
            jnp.concatenate([x[:, dv:], q32 * e_g], axis=0).astype(mm),  # [W; e^g Q]
            jnp.concatenate(p_rows, axis=0).astype(mm),          # P [C, C]
            jnp.transpose(k32 * _decay(g_end - g)).astype(mm),   # (e^{g_C - g} K)^T
            keep)


def _kernel(q_ref, k_ref, v_ref, la_ref, beta_ref, o_ref, s_ref, b_ref, *,
            chunk: int, sub: int, heads: int):
    """Grid (b, head group): the whole sequences of `heads` heads of one row,
    chunk after chunk. The heads' chains are independent and sit side by side
    in one loop body, so the scheduler interleaves them."""
    mm = q_ref.dtype
    t = q_ref.shape[1]
    dk, dv = q_ref.shape[2] // heads, v_ref.shape[2] // heads
    n = t // chunk
    s_ref[...] = jnp.zeros_like(s_ref)                           # S [heads, Dk, Dv]
    for hh in range(heads):
        b_ref[hh] = jnp.transpose(beta_ref[0, hh])               # beta [T, 1]

    def body(i, carry):
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        la, q, k, v = (ref[0, rows, :] for ref in (la_ref, q_ref, k_ref, v_ref))
        terms = [_chunk_terms(
            _prefix_sums(la[:, hh * dk:(hh + 1) * dk].astype(F32)),
            q[:, hh * dk:(hh + 1) * dk].astype(F32),
            k[:, hh * dk:(hh + 1) * dk].astype(F32),
            v[:, hh * dv:(hh + 1) * dv].astype(F32), b_ref[hh, rows, :],
            sub=sub, mm=mm) for hh in range(heads)]
        for hh, (u_free, wq, p, k_dec_t, keep) in enumerate(terms):
            s = s_ref[hh]
            read = _dot(wq, s.astype(mm), (1, 0))                # [W S; e^g Q S]
            u = (u_free - read[:chunk]).astype(mm)
            o_ref[0, rows, hh * dv:(hh + 1) * dv] = read[chunk:] + _dot(p, u, (1, 0))
            s_ref[hh] = keep * s + _dot(k_dec_t, u, (1, 0))
        return carry

    # rolled: unrolled, the lowering repeats the body once a chunk, and every
    # process pays the lowering at start-up, compile cache or not
    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "chunk", "sub", "heads"))
def delta_rule_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                   log_alpha: jax.Array, beta: jax.Array, *,
                   interpret: bool = False, chunk: int | None = None,
                   sub: int | None = None, heads: int | None = None) -> jax.Array:
    """q, k [B, T, H, Dk]; v [B, T, H, Dv]; log_alpha [B, T, H, Dk]; beta
    [B, T, H]. -> o [B, T, H, Dv] float32, as `ops/delta_rule`'s XLA form.
    `chunk` and `sub` default to `CHUNK` and `SUB` (`sub` divides `chunk`),
    `heads` a program to `heads_per_program`'s."""
    chunk, sub = chunk or CHUNK, sub or SUB
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    heads = heads or heads_per_program(h, t, dk, dv, q.dtype.itemsize)
    tp = padded_length(t, chunk)
    flat = lambda x: x.reshape(b, t, -1)                         # noqa: E731
    q3, k3, v3, la3 = flat(q), flat(k), flat(v), flat(log_alpha.astype(F32))
    beta4 = jnp.swapaxes(beta.astype(F32), 1, 2)[:, :, None]     # [B, H, 1, T]
    if tp != t:
        # padding positions neither decay (log alpha 0) nor write (beta 0)
        q3, k3, v3, la3 = (jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))
                           for x in (q3, k3, v3, la3))
        beta4 = jnp.pad(beta4, ((0, 0), (0, 0), (0, 0), (0, tp - t)))
    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}
    keys = pl.BlockSpec((1, tp, heads * dk), lambda b, h: (b, 0, h), **mem)
    values = pl.BlockSpec((1, tp, heads * dv), lambda b, h: (b, 0, h), **mem)
    o = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, sub=sub, heads=heads),
        grid=(b, h // heads),
        in_specs=[keys, keys, values, keys,
                  pl.BlockSpec((1, heads, 1, tp), lambda b, h: (b, h, 0, 0), **mem)],
        out_specs=values,
        out_shape=jax.ShapeDtypeStruct((b, tp, h * dv), F32),
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32),
                        pltpu.VMEM((heads, tp, 1), F32)],
        interpret=interpret,
        name="delta_rule_fwd",
        **params,
    )(q3, k3, v3, la3, beta4)
    return o[:, :t].reshape(b, t, h, dv)
