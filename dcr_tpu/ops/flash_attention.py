"""Pallas TPU flash attention — fused forward AND backward kernels.

Replaces the role xformers' CUDA memory-efficient attention plays in the
reference (diff_train.py:578): O(S) memory attention for the UNet's spatial
self-attention from 1,024 latent tokens up (256 px in bulk, 512 px+). Classic
FlashAttention (Dao et al. 2022):

- forward: online softmax over key blocks, f32 logits/statistics/accumulator on
  the MXU while operands stay bf16; emits the per-row logsumexp as ONE
  [B, Sq, 128] f32 array whose lane h holds head h's value.
- backward: recompute-based fused kernels that never materialize the S×S
  matrix. dQ: the forward's grid, key fori-loop inside. dK/dV: grid
  (b, slab, k block, q block) accumulating into f32 VMEM scratch across the
  sequential q dimension. delta (= rowsum do∘o, a masked lane sum a head) is
  recomputed per block in-kernel instead of being passed as an operand.
- the forward and dQ kernels keep one slab's whole K and V resident in VMEM
  (the key loop runs inside the kernel), and the forward one batch row's lse:
  that bounds the lengths the kernel can be built for within VMEM_LIMIT_BYTES.
  supported() refuses what does not fit (RESIDENT_MAX_BYTES), and those shapes
  take XLA attention.

Block sizes are tunable per call; the defaults (_resolve_blocks) and the
dispatch policy (should_use) are set from device-trace readings on a TPU v5e
(tools/sweep_flash.py, PR 27 and PR 31; the table is in PERF.md section 5).

Layout contract: [B, S, H, D] at the dispatcher, reshaped to [B, S, H*D] here:
the layout to_q/to_k/to_v write and to_out reads, so the reshape is the
inverse of the model's own and XLA cancels the pair. The kernels read and
write 128-lane slabs of that last dimension, 128 // D heads to a slab (two at
D = 64); a slab's heads are told apart by lane selects, never by a shift.
Where 128 does not divide H*D the last slab's lanes past H*D are padding that
may hold anything, NaN included: it is selected away, never multiplied.
interpret=True runs the same kernels through the Pallas interpreter (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128      # TPU lane count: the width of a slab, and of the lse array

# Dispatch policy, from device times on a v5e (PERF.md section 5; PR 27's
# sweep, read again by PR 31 with the kernels on the projections' own layout:
# the kernel against XLA's fused attention, same [B, S, H*D] operands).
# XLA is the faster path for as long as it keeps the f32 [B*H, Sq, Sk] logits
# on the chip (128 MiB of VMEM). At 1,024 keys in float32, forward: 15 heads
# (60 MiB) cost it 0.051 ms against the kernel's 0.057; 20 heads (80 MiB)
# 0.068 against 0.074 as 4 rows of 5 heads; 25 heads 0.093 against 0.092;
# and 30 heads' 120 MiB 0.563 against 0.109: from there the logits cross HBM
# three times a call and the kernel wins 5x (100 heads: 1.873 against 0.354)
# forward and 3.3x forward and backward (80 heads in bfloat16: 3.724 against
# 1.116; at 20 heads 0.291 against 0.286). Standing alone, 2 rows of 10 heads
# read 0.109 against 0.073, but inside the 512 px sampler XLA's five such
# sites cost 0.04 ms a call and the kernel there lost 1% of the image: the
# floor stays where XLA's cliff is. Under 1,024 keys the kernel is never a
# tenth ahead (256 keys, 200 heads: 0.120 ms against 0.131 forward; 0.278
# against 0.138 forward and backward), so the key length keeps its own floor.
# Since PR 33 the logits floor serves TWO decisions, both about the one fact
# that XLA's attention is fast for as long as a call's f32 logits stay on the
# chip: kernel or XLA (should_use, below), and, for a site that stays on XLA
# (a mask, a v narrower than q/k, under 1,024 keys), whole or in row groups
# that are each at or under the floor (ops/attention._xla_attention).
FLASH_MIN_SEQ = 1024
FLASH_MIN_LOGITS_BYTES = 112 * 2**20

# What a kernel may take of the v5e's 128 MiB of VMEM. Mosaic's own default,
# 16 MiB, is 0.08 MiB short at 4,096 keys in float32 and 0.6 MiB at 9,216 in
# bfloat16: a 128-lane K or V slab is twice a 64-wide head, and the forward
# keeps a batch row's lse beside them. Every accepted shape compiles at 24.
VMEM_LIMIT_BYTES = 32 * 2**20

# Resident whole, each double-buffered by the pipeline: one slab of K and of V
# (forward and dQ kernels: 4 * sk * 128 * itemsize bytes) and one batch row's
# lse (forward: 2 * sq * 128 * 4). The bound is what S=9216 in bf16 takes
# (SD-2.1 at 768 px): beside it the loop body's [block_q, block_k] f32 logits
# still fit VMEM_LIMIT_BYTES; tests/test_chip_compile.py walks every accepted
# shape through the chip's compiler.
RESIDENT_MAX_BYTES = 4 * 9216 * LANES * 2 + 2 * 9216 * LANES * 4


def _resolve_blocks(sq: int, sk: int, block_q: int | None,
                    block_k: int | None, itemsize: int) -> tuple[int, int]:
    """Pick (block_q, block_k): explicit args win, else the measured default
    clamped so blocks divide the sequence lengths. On a v5e (PERF.md section 5,
    PR 27) block_k 1024 won at every shape, and block_q 1024 with it at 1,024
    keys in both dtypes. At 4,096 keys in float32 block_q 512 is 3% faster."""
    bq = block_q or min(512 if itemsize == 4 and sk > 1024 else 1024, sq)
    bk = block_k or min(1024, sk)
    while sq % bq:
        bq //= 2
    while sk % bk:
        bk //= 2
    return max(bq, 8), max(bk, 8)


def supported(q: jax.Array, k: jax.Array, v: jax.Array) -> bool:
    """Kernel-capable shapes: 128 divides both sequence lengths, whole heads
    fill a 128-lane slab (D 64 or 128), at most 128 heads (a lane of lse
    each), and what a kernel keeps resident fits VMEM (RESIDENT_MAX_BYTES).
    Anything else takes XLA attention (correct, still fused). Capability
    only — the dispatch *policy* is should_use()."""
    if q.ndim != 4:
        return False
    _, sq, h, d = q.shape
    sk = k.shape[1]
    return (
        sq % 128 == 0
        and sk % 128 == 0
        and d in (64, 128)
        and h <= LANES
        and q.dtype in (jnp.float32, jnp.bfloat16)
        and (4 * sk * LANES * q.dtype.itemsize + 2 * sq * LANES * 4
             <= RESIDENT_MAX_BYTES)
    )


def should_use(q: jax.Array, k: jax.Array, v: jax.Array) -> bool:
    """Dispatch policy: the Pallas kernel handles this attention only where it
    beats XLA's fused attention on the chip: at least FLASH_MIN_SEQ keys, and
    f32 logits larger than XLA keeps on the chip (FLASH_MIN_LOGITS_BYTES).
    The shapes are one device's (ops.attention divides a sharded batch)."""
    if not supported(q, k, v):
        return False
    b, sq, h, _ = q.shape
    sk = k.shape[1]
    return sk >= FLASH_MIN_SEQ and 4 * b * h * sq * sk > FLASH_MIN_LOGITS_BYTES


def _mem(interpret: bool) -> dict:
    return {} if interpret else {"memory_space": pltpu.VMEM}


def _compiler_params(interpret: bool, semantics: tuple[str, ...]):
    """Tell Mosaic which grid dims are embarrassingly parallel; sequential
    (accumulating) dims must be 'arbitrary'."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES)}


# ---------------------------------------------------------------------------
# slabs: 128 lanes of [B, S, H*D], 128 // D heads side by side
# ---------------------------------------------------------------------------

def _slabs(hd: int) -> int:
    return pl.cdiv(hd, LANES)


def _lane(rows: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)


def _keep(mask: jax.Array, x: jax.Array) -> jax.Array:
    """x where mask, 0 elsewhere: a select, so what is dropped may be NaN."""
    return jnp.where(mask, x, jnp.zeros_like(x))


def _valid(x: jax.Array, slab, hd: int) -> jax.Array:
    """A key-side block with the last slab's padding lanes zeroed (a product
    over all 128 lanes would carry them); nothing to do where 128 divides H*D."""
    if hd % LANES == 0:
        return x
    return _keep(_lane(x.shape[0]) < hd - slab * LANES, x)


def _heads_here(slab, d: int, hd: int, run) -> None:
    """run(n), n the heads this slab holds: 128 // D, fewer in a half-filled
    last slab (H odd at D = 64), where the missing head's work is not done."""
    per, last = LANES // d, (hd % LANES) // d
    if not last:
        run(per)
    else:
        pl.when(slab < _slabs(hd) - 1)(lambda: run(per))
        pl.when(slab == _slabs(hd) - 1)(lambda: run(last))


def _lanes_of(j: int, d: int, rows: int) -> jax.Array:
    """[rows, 128] mask of the lanes of the slab's j-th head."""
    lane = _lane(rows)
    return (lane >= j * d) & (lane < (j + 1) * d)


def _own(j: int, d: int, x: jax.Array) -> jax.Array:
    """A [rows, 128] block with the lanes of the slab's other heads zeroed: a
    product over all 128 lanes is then the j-th head's alone, exactly."""
    return x if d == LANES else _keep(_lanes_of(j, d, x.shape[0]), x)


def _by_head(parts, d: int) -> jax.Array:
    """One [rows, 128] slab from a full-width result a head: each head's own
    lanes (the others hold the products with its neighbour's V or K)."""
    out = parts[0]
    for j, part in enumerate(parts[1:], 1):
        out = jnp.where(_lanes_of(j, d, out.shape[0]), part, out)
    return out


def _row_stat(tile: jax.Array, head) -> jax.Array:
    """[rows, 1]: lane `head` of a [rows, 128] lse tile."""
    return jnp.sum(_keep(_lane(tile.shape[0]) == head, tile), axis=-1,
                   keepdims=True)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                block_k: int, d: int, hd: int):
    """Grid (b, slab, q block). The lse block is the batch row's whole
    [Sq, 128], resident across both inner dims: every head adds its lane."""
    # operands stay in their native dtype (bf16 hits the MXU at full rate);
    # logits, softmax statistics, and the accumulator are f32
    slab, qi = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    sk = k_ref.shape[1]
    in_dtype = q_ref.dtype
    rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)

    def run(n):
        qs = [_own(j, d, q_ref[0]) for j in range(n)]

        def body(kb, carry):
            k_blk = _valid(k_ref[0, pl.ds(kb * block_k, block_k), :], slab, hd)
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
            new = []
            for q, (m, l, acc) in zip(qs, carry):
                s = jax.lax.dot_general(
                    q, k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                # PV over the whole slab: the head's own lanes are kept below
                new.append((m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                            acc * corr + jax.lax.dot_general(
                                p.astype(in_dtype), v_blk,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)))
            return tuple(new)

        start = (jnp.full((bq, 1), NEG_INF, jnp.float32),
                 jnp.zeros((bq, 1), jnp.float32),
                 jnp.zeros((bq, LANES), jnp.float32))
        done = jax.lax.fori_loop(0, sk // block_k, body, (start,) * n)
        o_ref[0] = _by_head([acc / l for _, l, acc in done], d
                            ).astype(o_ref.dtype)
        # lanes under this slab's first head were written by the slabs before
        # it (for these rows); lanes past its last head read 0
        lane, first = _lane(bq), slab * (LANES // d)
        tile = _keep(lane < first, lse_ref[0, rows, :])
        for j, (m, l, _) in enumerate(done):
            tile = jnp.where(lane == first + j, m + jnp.log(l), tile)
        lse_ref[0, rows, :] = tile

    _heads_here(slab, d, hd, run)


def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, d: int, *,
               interpret: bool, block_q: int | None = None,
               block_k: int | None = None) -> tuple[jax.Array, jax.Array]:
    """q/k/v: [B, S, H*D] -> (out [B, Sq, H*D], lse [B, Sq, 128] f32, head h's
    logsumexp in lane h, 0 from lane H on)."""
    b, sq, hd = q.shape
    sk = k.shape[1]
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k, q.dtype.itemsize)
    kernel = functools.partial(_fwd_kernel, scale=1.0 / (d ** 0.5), block_k=bk,
                               d=d, hd=hd)
    mem = _mem(interpret)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, _slabs(hd), sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, LANES), lambda b, s, i: (b, i, s), **mem),
            pl.BlockSpec((1, sk, LANES), lambda b, s, i: (b, 0, s), **mem),
            pl.BlockSpec((1, sk, LANES), lambda b, s, i: (b, 0, s), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, LANES), lambda b, s, i: (b, i, s), **mem),
            pl.BlockSpec((1, sq, LANES), lambda b, s, i: (b, 0, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((b, sq, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **_compiler_params(interpret, ("parallel", "arbitrary", "arbitrary")),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward (recompute; FlashAttention eq. dS = P ∘ (dP − D), D = rowsum do∘o)
# ---------------------------------------------------------------------------

def _head_rows(j: int, head, d: int, q_ref, o_ref, do_ref, lse_ref):
    """The slab's j-th head: its q and do (the neighbour's lanes zeroed), its
    lse and its delta, each [bq, .]."""
    do = do_ref[0]
    delta = jnp.sum(_own(j, d, do.astype(jnp.float32)
                         * o_ref[0].astype(jnp.float32)),
                    axis=-1, keepdims=True)                # [bq, 1]
    return (_own(j, d, q_ref[0]), _own(j, d, do),
            _row_stat(lse_ref[0], head), delta)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, *,
                   scale: float, block_k: int, d: int, hd: int):
    slab = pl.program_id(1)
    bq = q_ref.shape[1]
    sk = k_ref.shape[1]
    in_dtype = q_ref.dtype

    def run(n):
        heads = [_head_rows(j, slab * (LANES // d) + j, d, q_ref, o_ref,
                            do_ref, lse_ref) for j in range(n)]

        def body(kb, dqs):
            k_blk = _valid(k_ref[0, pl.ds(kb * block_k, block_k), :], slab, hd)
            v_blk = _valid(v_ref[0, pl.ds(kb * block_k, block_k), :], slab, hd)
            new = []
            for (q, do, lse, delta), dq in zip(heads, dqs):
                s = jax.lax.dot_general(
                    q, k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                p = jnp.exp(s - lse)                       # [bq, bk]
                dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = p * (dp - delta)                      # [bq, bk] f32
                new.append(dq + jax.lax.dot_general(
                    ds.astype(in_dtype), k_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            return tuple(new)

        dqs = jax.lax.fori_loop(0, sk // block_k, body,
                                (jnp.zeros((bq, LANES), jnp.float32),) * n)
        dq_ref[0] = (_by_head(dqs, d) * scale).astype(dq_ref.dtype)

    _heads_here(slab, d, hd, run)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    q_steps: int, d: int, hd: int):
    """Grid (b, slab, k block, q block); the q dim is sequential — dK/dV
    accumulate in f32 scratch across it and flush to the outputs on the last
    q step. With q and do zeroed outside a head's lanes each head's products
    land in its own lanes of the slab's accumulators."""
    slab, qi = pl.program_id(1), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k_blk = _valid(k_ref[0], slab, hd)                     # [bk, 128]
    v_blk = _valid(v_ref[0], slab, hd)
    in_dtype = k_blk.dtype

    def run(n):
        for j in range(n):
            q, do, lse, delta = _head_rows(j, slab * (LANES // d) + j, d,
                                           q_ref, o_ref, do_ref, lse_ref)
            s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - lse)                           # [bq, bk]
            dv_acc[...] += jax.lax.dot_general(
                p.astype(in_dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # p^T @ do -> [bk, 128]
            dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(in_dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # ds^T @ q -> [bk, 128]

    _heads_here(slab, d, hd, run)

    @pl.when(qi == q_steps - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, d: int, *, interpret: bool,
               block_q: int | None = None, block_k: int | None = None):
    b, sq, hd = q.shape
    sk = k.shape[1]
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k, q.dtype.itemsize)
    scale = 1.0 / (d ** 0.5)
    mem = _mem(interpret)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_k=bk, d=d, hd=hd),
        grid=(b, _slabs(hd), sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, LANES), lambda b, s, i: (b, i, s), **mem),
            pl.BlockSpec((1, sk, LANES), lambda b, s, i: (b, 0, s), **mem),
            pl.BlockSpec((1, sk, LANES), lambda b, s, i: (b, 0, s), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, s, i: (b, i, s), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, s, i: (b, i, s), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, s, i: (b, i, 0), **mem),
        ],
        out_specs=pl.BlockSpec((1, bq, LANES), lambda b, s, i: (b, i, s), **mem),
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        **_compiler_params(interpret, ("parallel", "parallel", "parallel")),
    )(q, k, v, o, do, lse)

    scratch = [pltpu.VMEM((bk, LANES), jnp.float32),
               pltpu.VMEM((bk, LANES), jnp.float32)]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, q_steps=sq // bq,
                          d=d, hd=hd),
        grid=(b, _slabs(hd), sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, LANES), lambda b, s, j, i: (b, i, s), **mem),
            pl.BlockSpec((1, bk, LANES), lambda b, s, j, i: (b, j, s), **mem),
            pl.BlockSpec((1, bk, LANES), lambda b, s, j, i: (b, j, s), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, s, j, i: (b, i, s), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, s, j, i: (b, i, s), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, s, j, i: (b, i, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, LANES), lambda b, s, j, i: (b, j, s), **mem),
            pl.BlockSpec((1, bk, LANES), lambda b, s, j, i: (b, j, s), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, sk, hd), v.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bwd_dkv",
        **_compiler_params(interpret,
                           ("parallel", "parallel", "parallel", "arbitrary")),
    )(q, k, v, o, do, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, d, interpret, block_q, block_k):
    """The differentiable op on [B, S, H*D] operands, D wide heads."""
    return _fwd_rule(q, k, v, d, interpret, block_q, block_k)[0]


def _fwd_rule(q, k, v, d, interpret, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, d, interpret=interpret, block_q=block_q,
                          block_k=block_k)
    # the residuals are in the layout autodiff already keeps for the linears;
    # the backward reads lse as saved
    return out, (q, k, v, out, lse)


def _bwd_rule(d, interpret, block_q, block_k, residuals, g):
    return _flash_bwd(*residuals, g, d, interpret=interpret, block_q=block_q,
                      block_k=block_k)


_flash.defvjp(_fwd_rule, _bwd_rule)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    interpret: bool = False, block_q: int | None = None,
                    block_k: int | None = None) -> jax.Array:
    """Flash attention over [B, S, H, D] tensors."""
    b, sq, h, d = q.shape
    flat = lambda x: x.reshape(*x.shape[:2], h * d)    # noqa: E731
    out = _flash(flat(q), flat(k), flat(v), d, interpret, block_q, block_k)
    return out.reshape(b, sq, h, d)
