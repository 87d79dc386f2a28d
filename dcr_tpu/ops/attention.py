"""Attention dispatcher.

The single kernel-level capability the reference gets from native code is
xformers' memory-efficient attention (diff_train.py:578, env.yaml:359). Here the
role is played by a Pallas flash-attention kernel on TPU (dcr_tpu.ops.flash_attention)
with XLA's fused attention as the portable fallback — both behind one function so
models never care.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # a backend that fails to come up raises here: it must not silently
    # become XLA attention on some other device
    return jax.devices()[0].platform == "tpu"


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          mask: Optional[jax.Array] = None,
                          use_flash: bool = True) -> jax.Array:
    """Multi-head attention over [B, S, H, D] tensors (BSHD layout).

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]. Returns [B, Sq, H, D].
    Dispatches to the Pallas TPU flash kernel when shapes are kernel-friendly and
    we're on TPU, otherwise XLA (which fuses the softmax chain on its own).
    """
    if use_flash and _on_tpu() and mask is None:
        from dcr_tpu.ops import flash_attention as fa

        if fa.should_use(q, k, v):
            return fa.flash_attention(q, k, v)
    return _xla_attention(q, k, v, mask)


def _xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: Optional[jax.Array]) -> jax.Array:
    # jax.nn.dot_product_attention takes the same BSHD layout and scaling and
    # lets XLA pick its fused implementation. The scope tells this path from
    # the Pallas kernels (named flash_*) in a device trace.
    with jax.named_scope("attention_xla"):
        return jax.nn.dot_product_attention(q, k, v, mask=mask)
