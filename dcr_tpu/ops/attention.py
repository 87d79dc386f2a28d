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
    if k.shape[2] != q.shape[2]:
        return "xla"        # grouped key/value heads
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

    q: [B, Sq, H, D]; k: [B, Sk, Hkv, D]; v: [B, Sk, Hkv, Dv] with Dv <= D
    (latent attention's values are narrower than its keys) and Hkv dividing H
    (grouped-query attention: query head h reads key/value head
    h // (H / Hkv); on the XLA path only). Returns [B, Sq, H, Dv].
    Dispatches to the Pallas TPU flash kernel where it beats XLA on the chip
    (flash_attention.should_use) and we're on TPU, otherwise XLA (which fuses
    the softmax chain on its own). The floor that decides it,
    flash_attention.FLASH_MIN_LOGITS_BYTES (the f32 logits XLA keeps on the
    chip), decides a second thing on the XLA path: a site over it that the
    kernel cannot take runs, on the TPU, as row groups that are each under it,
    one after the other (_xla_attention). Every site counts itself once a
    trace in `attention/sites_total/<path>`, whole or cut: what a lowered step
    or sampler holds; a cut site adds its groups to
    `attention/xla_row_groups_total`.

    `mesh` is the mesh the enclosing jit spans (pmesh axes), or None on one
    device or inside a shard_map. A Mosaic kernel is never partitioned
    automatically, so over more than one device the kernel runs under
    shard_map, each device on its own rows and heads; the XLA path's groups
    are sized from, and cut out of, each device's own rows and heads too.
    """
    path = path_for(q, k, v, mask=mask, use_flash=use_flash, mesh=mesh)
    tracing.registry().counter(f"attention/sites_total/{path}").inc()
    rows, heads, spec = _shards(mesh)
    if path == "xla":
        # the floor is a fact about the TPU's VMEM: off the chip a site is
        # left whole, whatever its size
        return _xla_attention(q, k, v, mask, shards=(rows, heads),
                              floor=None if _on_tpu() else math.inf)
    from dcr_tpu.ops import flash_attention as fa

    if spec is None:
        return fa.flash_attention(q, k, v)
    return jax.shard_map(fa.flash_attention, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def _group_of(b: int, h: int, sq: int, sk: int, floor: int,
              kv_heads: int | None = None) -> tuple[int, int]:
    """(rows, heads) of one group of a site of b rows and h query heads: the
    whole site where its float32 logits are at or under `floor` bytes, else
    the largest divisor of the rows whose group is, else (one row is over the
    floor) one row and the largest such divisor of its heads. With
    `kv_heads` key/value heads a group of heads is a whole number of the
    query heads that share one (the groups cut k and v in step)."""
    kv_heads = h if kv_heads is None else kv_heads

    def largest_divisor(n, bytes_each, least=1):
        return max(g for g in range(least, n + 1, least)
                   if n % g == 0 and (g == least or g * bytes_each <= floor))

    head = 4 * sq * sk
    if h * head <= floor:
        return largest_divisor(b, h * head), h
    return 1, largest_divisor(h, head, h // kv_heads)


def _one_after_the_other(fn, n: int, ways: int, operands, axes):
    """`fn(*operands)` over `n` equal groups, one after the other, the results
    joined along axes[0]. Operand i is cut along axes[i], or handed to every
    group whole where that is None. A mesh that splits the axis `ways` ways
    holds contiguous shares of it, so the axis is read as [ways, n * group]
    and every group takes its part of each device's share.

    The loop slices the operands as they are handed in (`fori_loop` and
    `dynamic_slice`, which a static trip count keeps differentiable): inside a
    tower XLA then has the projections write q, k and v in the layout the
    loop's body reads, where `lax.map` over a [n, group, ...] view cost a
    relayout copy of each (PERF.md section 6, PR 33)."""
    if n == 1:
        return fn(*operands)

    def split(x, axis):         # [..., ways * n * size, ...] -> [..., ways, n * size, ...]
        return x.reshape(x.shape[:axis] + (ways, -1) + x.shape[axis + 1:])

    def merged(x, axis):
        return x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])

    def group(i):
        def of(x, axis):
            size = x.shape[axis] // (ways * n)
            return merged(jax.lax.dynamic_slice_in_dim(
                split(x, axis), i * size, size, axis + 1), axis)

        return [x if a is None else of(x, a) for x, a in zip(operands, axes)]

    axis = axes[0]
    one = jax.eval_shape(lambda: fn(*group(0)))
    size = one.shape[axis] // ways
    out = jnp.zeros(one.shape[:axis] + (ways, n * size) + one.shape[axis + 1:],
                    one.dtype)
    out = jax.lax.fori_loop(0, n, lambda i, out: jax.lax.dynamic_update_slice_in_dim(
        out, split(fn(*group(i)), axis), i * size, axis + 1), out)
    return merged(out, axis)


def _xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: Optional[jax.Array], *, shards: tuple[int, int] = (1, 1),
                   floor: Optional[int] = None) -> jax.Array:
    """XLA's fused attention: whole where one device's float32 logits stay on
    the chip (`floor`, by default flash_attention.FLASH_MIN_LOGITS_BYTES: the
    fact the kernel's policy stands on too), and where they would not, in
    groups of rows that each do. Past the floor XLA writes a site's
    [B, H, Sq, Sk] exponentials to HBM between its two products; a group's
    stay in VMEM. The groups go one after the other through the same call on
    the same values, so a cut site computes what the whole one did, row for
    row; one row over the floor is cut by heads the same way. `shards` is how
    the enclosing mesh splits (rows, heads): a group is cut from every
    device's own share."""
    if floor is None:
        from dcr_tpu.ops.flash_attention import FLASH_MIN_LOGITS_BYTES as floor
    b, sq, h, _ = q.shape
    h_kv, dv = k.shape[2], v.shape[-1]
    ways_b, ways_h = shards if (b % shards[0] == 0 and h % shards[1] == 0
                                and h_kv % shards[1] == 0) else (1, 1)
    g_b, g_h = _group_of(b // ways_b, h // ways_h, sq, k.shape[1], floor,
                         h_kv // ways_h)
    n_b, n_h = b // (ways_b * g_b), h // (ways_h * g_h)
    if n_b * n_h > 1:
        tracing.registry().counter("attention/xla_row_groups_total").inc(n_b * n_h)

    def attend(q, k, v, mask):
        # jax.nn.dot_product_attention takes the same BSHD layout and scaling
        # (and k, v with fewer heads than q) and lets XLA pick its fused
        # implementation
        if dv < q.shape[-1]:
            # jax.nn's call wants v as wide as q/k: zero columns of v give
            # zero columns of the output, which are cut off again (exact)
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
        return jax.nn.dot_product_attention(q, k, v, mask=mask)[..., :dv]

    def by_heads(q, k, v, mask):
        # k and v are cut by their own heads, in step with q's groups
        cut_mask = mask is not None and mask.shape[1] == h
        return _one_after_the_other(attend, n_h, ways_h, (q, k, v, mask),
                                    (2, 2, 2, 1 if cut_mask else None))

    # The scope tells this path from the Pallas kernels (named flash_*) in a
    # device trace.
    with jax.named_scope("attention_xla"):
        cut_mask = mask is not None and mask.shape[0] == b
        return _one_after_the_other(by_heads, n_b, ways_b, (q, k, v, mask),
                                    (0, 0, 0, 0 if cut_mask else None))
