"""Attention dispatcher.

The single kernel-level capability the reference gets from native code is
xformers' memory-efficient attention (diff_train.py:578, env.yaml:359). Here the
role is played by a Pallas flash-attention kernel on TPU (dcr_tpu.ops.flash_attention)
with XLA's fused attention as the portable fallback — both behind one function so
models never care.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dcr_tpu.core import tracing
from dcr_tpu.parallel import mesh as pmesh


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # a backend that fails to come up raises here: it must not silently
    # become XLA attention on some other device
    return jax.devices()[0].platform == "tpu"


def _shards(mesh) -> tuple[int, int, Optional[P]]:
    """How `mesh` splits a [B, S, H, D] operand: (ways over the rows, ways
    over the heads, the partition spec that does it). Rows go over the batch
    axes, heads over the tensor axis (the Megatron rules shard
    to_q/to_k/to_v by head). (1, 1, None) on one device."""
    if mesh is None or mesh.size == 1:
        return 1, 1, None
    sizes = dict(mesh.shape)
    batch_axes = tuple(a for a in (pmesh.DATA_AXIS, pmesh.FSDP_AXIS) if a in sizes)
    head_axis = pmesh.TENSOR_AXIS if pmesh.TENSOR_AXIS in sizes else None
    return (math.prod(sizes[a] for a in batch_axes), sizes.get(head_axis, 1),
            P(batch_axes or None, None, head_axis, None))


def path_for(q, k, v, *, mask=None, use_flash: bool = True, mesh=None) -> str:
    """"flash" or "xla": which implementation a site of these shapes takes.
    Reads shapes and dtypes only, so `jax.ShapeDtypeStruct`s will do. The
    policy is asked about ONE device's share; rows or heads that the mesh
    does not divide cannot be sharded for the kernel, so XLA."""
    if not (use_flash and _on_tpu() and mask is None and q.ndim == 4):
        return "xla"
    if v.shape[-1] != q.shape[-1]:
        return "xla"        # latent attention: v narrower than q/k
    from dcr_tpu.ops import flash_attention as fa

    rows, heads, _ = _shards(mesh)
    if q.shape[0] % rows or q.shape[2] % heads:
        return "xla"
    local = [jax.ShapeDtypeStruct(
        (x.shape[0] // rows, x.shape[1], x.shape[2] // heads, x.shape[3]),
        x.dtype) for x in (q, k, v)]
    return "flash" if fa.should_use(*local) else "xla"


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          mask: Optional[jax.Array] = None,
                          use_flash: bool = True,
                          mesh: Optional[Mesh] = None) -> jax.Array:
    """Multi-head attention over [B, S, H, D] tensors (BSHD layout).

    q: [B, Sq, H, D]; k: [B, Sk, H, D]; v: [B, Sk, H, Dv] with Dv <= D (latent
    attention's values are narrower than its keys). Returns [B, Sq, H, Dv].
    Dispatches to the Pallas TPU flash kernel where it beats XLA on the chip
    (flash_attention.should_use) and we're on TPU, otherwise XLA (which fuses
    the softmax chain on its own). Every site counts itself once a trace in
    `attention/sites_total/<path>`: what a lowered step or sampler holds.

    `mesh` is the mesh the enclosing jit spans (pmesh axes), or None on one
    device or inside a shard_map. A Mosaic kernel is never partitioned
    automatically, so over more than one device the kernel runs under
    shard_map, each device on its own rows and heads.
    """
    path = path_for(q, k, v, mask=mask, use_flash=use_flash, mesh=mesh)
    tracing.registry().counter(f"attention/sites_total/{path}").inc()
    if path == "xla":
        return _xla_attention(q, k, v, mask)
    from dcr_tpu.ops import flash_attention as fa

    spec = _shards(mesh)[2]
    if spec is None:
        return fa.flash_attention(q, k, v)
    return jax.shard_map(fa.flash_attention, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def _xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: Optional[jax.Array]) -> jax.Array:
    # jax.nn.dot_product_attention takes the same BSHD layout and scaling and
    # lets XLA pick its fused implementation. The scope tells this path from
    # the Pallas kernels (named flash_*) in a device trace.
    with jax.named_scope("attention_xla"):
        dv = v.shape[-1]
        if dv < q.shape[-1]:
            # jax.nn's call wants v as wide as q/k: zero columns of v give
            # zero columns of the output, which are cut off again (exact)
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
        return jax.nn.dot_product_attention(q, k, v, mask=mask)[..., :dv]
