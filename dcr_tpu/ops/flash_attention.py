"""Pallas TPU flash attention — fused forward AND backward kernels.

Replaces the role xformers' CUDA memory-efficient attention plays in the
reference (diff_train.py:578): O(S) memory attention for the UNet's spatial
self-attention from 1,024 latent tokens up (256 px in bulk, 512 px+). Classic
FlashAttention (Dao et al. 2022):

- forward: online softmax over key blocks, f32 logits/statistics/accumulator on
  the MXU while operands stay bf16; emits the per-row logsumexp lane-broadcast
  to [BH, S, 128] (TPU tiling requires >=128 lanes on the last dim — same
  trick as jax.experimental.pallas.ops.tpu.flash_attention's MIN_BLOCK_SIZE).
- backward: recompute-based fused kernels that never materialize the S×S
  matrix. dQ: grid over q blocks, key fori-loop inside. dK/dV: 3-D grid
  (bh, k block, q block) accumulating into f32 VMEM scratch across the
  sequential q dimension. delta (= rowsum do∘o) is recomputed per block
  in-kernel instead of being passed as a full-sequence operand.
- the forward and dQ kernels keep one head's whole K and V resident in VMEM
  (the key loop runs inside the kernel), which bounds the key length the
  kernel can be built for within the ~16 MB/core budget: supported() refuses
  what does not fit (RESIDENT_KV_MAX_BYTES), and those shapes take XLA
  attention.

Block sizes are tunable per call; the defaults (_resolve_blocks) and the
dispatch policy (should_use) are set from device-trace readings on a TPU v5e
(tools/sweep_flash.py, PR 27; the table is in PERF.md section 5).

Layout contract: [B, S, H, D] at the dispatcher, reshaped to [B*H, S, D] here.
interpret=True runs the same kernels through the Pallas interpreter (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128      # TPU lane count: min last-dim tile for f32 outputs

# Dispatch policy, from device times on a v5e (PERF.md section 5, PR 27;
# kernel WITH its relayouts against XLA's fused attention, same operands).
# XLA is the faster path for as long as it keeps the f32 [B*H, Sq, Sk] logits
# on the chip (128 MiB of VMEM): at 1,024 keys 25 heads' 100 MiB cost it
# 0.09 ms against the kernel's 0.15, and 30 heads' 120 MiB 0.56 to 0.58 ms
# against 0.17 to 0.19; from there the logits cross HBM three times a call
# and the kernel wins 2.2x (100 heads in float32, forward) to 2.5x (80 heads
# in bfloat16, forward and backward; the backward's cliff is the same one).
# Under 1,024 keys the relayouts round the kernel alone cost what XLA's whole
# call does (256 keys, 200 heads: 0.13 ms against 0.24), whatever the logits'
# size, so the key length keeps its own floor.
FLASH_MIN_SEQ = 1024
FLASH_MIN_LOGITS_BYTES = 112 * 2**20

# The forward and dQ kernels hold K and V of one head whole, each
# double-buffered by the pipeline: 4 * sk * d * itemsize bytes of the ~16 MB
# of VMEM a v5e core gives a kernel, most of which the [block_q, block_k] f32
# logits of the loop body take. 4.5 MiB is the largest resident K/V that
# compiles for v5e, forward and backward (S=9216, D=64, bf16: SD-2.1 at
# 768 px); tests/test_chip_compile.py walks every accepted shape through the
# chip's compiler.
RESIDENT_KV_MAX_BYTES = 4 * 9216 * 64 * 2


def _resolve_blocks(sq: int, sk: int, block_q: int | None,
                    block_k: int | None, itemsize: int) -> tuple[int, int]:
    """Pick (block_q, block_k): explicit args win, else the measured default
    clamped so blocks divide the sequence lengths. On a v5e (PERF.md section 5,
    PR 27) block_k 1024 won at every shape, and block_q 1024 with it at 1,024
    keys in both dtypes. At 4,096 keys in float32 block_q 512 is 3% faster and
    is what fits: with (1024, 1024) the f32 [block_q, block_k] logits beside
    4 MiB of resident K/V pass the 16 MiB of scoped VMEM from 4 rows on."""
    bq = block_q or min(512 if itemsize == 4 and sk > 1024 else 1024, sq)
    bk = block_k or min(1024, sk)
    while sq % bq:
        bq //= 2
    while sk % bk:
        bk //= 2
    return max(bq, 8), max(bk, 8)


def supported(q: jax.Array, k: jax.Array, v: jax.Array) -> bool:
    """Kernel-capable shapes: 128 divides both sequence lengths, D fits the MXU
    lane layout, and one head's resident K/V fits VMEM
    (RESIDENT_KV_MAX_BYTES). Anything else takes XLA attention (correct,
    still fused). Capability only — the dispatch *policy* is should_use()."""
    if q.ndim != 4:
        return False
    _, sq, _, d = q.shape
    sk = k.shape[1]
    return (
        sq % 128 == 0
        and sk % 128 == 0
        and d in (64, 128, 256)
        and q.dtype in (jnp.float32, jnp.bfloat16)
        and 4 * sk * d * q.dtype.itemsize <= RESIDENT_KV_MAX_BYTES
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
        dimension_semantics=semantics)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                block_k: int):
    # operands stay in their native dtype (bf16 hits the MXU at full rate);
    # logits, softmax statistics, and the accumulator are f32
    q = q_ref[0]                                      # [bq, D]
    sk = k_ref.shape[1]
    bq, d = q.shape
    in_dtype = q.dtype

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(in_dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, sk // block_k, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # lane-broadcast so the f32 output block meets the (8, 128) tile minimum
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (bq, LANES))


def _flash_fwd(q3: jax.Array, k3: jax.Array, v3: jax.Array, *,
               interpret: bool, block_q: int | None = None,
               block_k: int | None = None) -> tuple[jax.Array, jax.Array]:
    """q3/k3/v3: [BH, S, D] -> (out [BH,S,D], lse [BH,S,LANES] lane-broadcast)."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k, q3.dtype.itemsize)
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_fwd_kernel, scale=scale, block_k=bk)
    mem = _mem(interpret)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **mem),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0), **mem),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, i: (b, i, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **_compiler_params(interpret, ("parallel", "parallel")),
    )(q3, k3, v3)
    return out, lse


# ---------------------------------------------------------------------------
# backward (recompute; FlashAttention eq. dS = P ∘ (dP − D), D = rowsum do∘o)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, *,
                   scale: float, block_k: int):
    q = q_ref[0]                                       # [bq, D]
    do = do_ref[0]
    lse = lse_ref[0, :, 0:1]                           # [bq, 1]
    delta = jnp.sum(do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)            # [bq, 1]
    sk = k_ref.shape[1]
    bq, d = q.shape
    in_dtype = q.dtype

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                           # [bq, bk]
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                          # [bq, bk] f32
        return dq + jax.lax.dot_general(
            ds.astype(in_dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, sk // block_k, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    q_steps: int):
    """Grid (bh, k block, q block); the q dim is sequential — dK/dV accumulate
    in f32 scratch across it and flush to the outputs on the last q step."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k_blk = k_ref[0]                                   # [bk, D]
    v_blk = v_ref[0]
    in_dtype = k_blk.dtype
    q = q_ref[0]                                       # [bq, D]
    do = do_ref[0]
    lse = lse_ref[0, :, 0:1]                           # [bq, 1]
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)            # [bq, 1]

    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse)                               # [bq, bk]
    dv_acc[...] += jax.lax.dot_general(
        p.astype(in_dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # p^T @ do -> [bk, D]
    dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_acc[...] += jax.lax.dot_general(
        ds.astype(in_dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # ds^T @ q -> [bk, D]

    @pl.when(qi == q_steps - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, o3, lse, do3, *, interpret: bool,
               block_q: int | None = None, block_k: int | None = None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k, q3.dtype.itemsize)
    scale = 1.0 / (d ** 0.5)
    mem = _mem(interpret)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_k=bk),
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **mem),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0), **mem),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0), **mem),
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **mem),
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, i: (b, i, 0), **mem),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        **_compiler_params(interpret, ("parallel", "parallel")),
    )(q3, k3, v3, o3, do3, lse)

    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, d), jnp.float32)]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, q_steps=sq // bq),
        grid=(bh, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0), **mem),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0), **mem),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0), **mem),
            pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v3.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bwd_dkv",
        **_compiler_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(q3, k3, v3, o3, do3, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def _to3(x: jax.Array) -> jax.Array:
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from3(x3: jax.Array, b: int, h: int) -> jax.Array:
    bh, s, d = x3.shape
    return x3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    interpret: bool = False, block_q: int | None = None,
                    block_k: int | None = None) -> jax.Array:
    """Flash attention over [B, S, H, D] tensors."""
    out, _ = _flash_fwd(_to3(q), _to3(k), _to3(v), interpret=interpret,
                        block_q=block_q, block_k=block_k)
    return _from3(out, q.shape[0], q.shape[2])


def _fwd_rule(q, k, v, interpret, block_q, block_k):
    q3, k3, v3 = _to3(q), _to3(k), _to3(v)
    o3, lse = _flash_fwd(q3, k3, v3, interpret=interpret,
                         block_q=block_q, block_k=block_k)
    b, h = q.shape[0], q.shape[2]
    # store the residual compact [BH, S] — the lane-broadcast [BH, S, 128]
    # would pin 128x the memory from forward to backward
    return _from3(o3, b, h), (q3, k3, v3, o3, lse[:, :, 0], b, h)


def _bwd_rule(interpret, block_q, block_k, residuals, g):
    q3, k3, v3, o3, lse2, b, h = residuals
    lse = jnp.broadcast_to(lse2[:, :, None], (*lse2.shape, LANES))
    dq3, dk3, dv3 = _flash_bwd(q3, k3, v3, o3, lse, _to3(g),
                               interpret=interpret,
                               block_q=block_q, block_k=block_k)
    return _from3(dq3, b, h), _from3(dk3, b, h), _from3(dv3, b, h)


flash_attention.defvjp(_fwd_rule, _bwd_rule)
