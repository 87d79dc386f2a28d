"""Mixed-precision policy: params in fp32, compute in bf16.

TPU-native replacement for the reference's Accelerate fp16/bf16 handling
(diff_train.py:216-225, 522-533): no GradScaler (bf16 needs no loss scaling —
the NativeScalerWithGradNormCount machinery at utils_ret.py:834-860 has no
equivalent here by design), just dtype casts at the jit boundary.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from dcr_tpu.core.config import TEXT_TOWERS


@dataclass(frozen=True)
class Policy:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    output_dtype: jnp.dtype = jnp.float32

    def cast_to_compute(self, tree):
        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            tree,
        )

    def cast_to_param(self, tree):
        return jax.tree.map(
            lambda x: x.astype(self.param_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            tree,
        )

    def cast_to_output(self, tree):
        return jax.tree.map(
            lambda x: x.astype(self.output_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            tree,
        )


def text_param_dtype(text_tower: str) -> jnp.dtype:
    """What a frozen text tower's leaves are HELD in: its row of
    core/config.TEXT_TOWERS says."""
    return jnp.dtype(TEXT_TOWERS[text_tower].held_dtype)


def policy_from_string(mixed_precision: str) -> Policy:
    if mixed_precision in ("no", "fp32", "float32"):
        return Policy(compute_dtype=jnp.float32)
    if mixed_precision in ("bf16", "bfloat16"):
        return Policy(compute_dtype=jnp.bfloat16)
    raise ValueError(f"unsupported mixed_precision {mixed_precision!r} (use 'no' or 'bf16')")
