"""The text tower: one factory for every program that conditions on text.

`ModelConfig.text_tower` names the architecture, a row of
`core/config.TEXT_TOWERS`: "clip" (models/clip_text.py, the tower of
SD-1.x/2.x) or a frozen language model whose final states are projected to
the UNet's cross-attention width ("longcat_flash", models/longcat_flash.py;
"openpangu_ultra_moe", models/openpangu_ultra_moe.py). Each takes `[B, L]`
token ids and returns an object with `.last_hidden_state` of `[B, L,
cross_attention_dim]` float32, so the train step, the encode stage, the
sampler and the server apply them alike; a language-model tower's output also
carries `.moe_stats`, its routing counts.
"""

from __future__ import annotations

import importlib

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import TEXT_TOWERS, ModelConfig, validate_text_tower
from dcr_tpu.core.precision import text_param_dtype


def build_text_tower(cfg: ModelConfig, compute_dtype=jnp.float32,
                     mesh=None) -> nn.Module:
    """The tower's module (no parameters are made). CLIP computes in float32
    on leaves the caller casts; a tower held in bfloat16 computes in
    `compute_dtype` on those leaves and hands `mesh`, the mesh the enclosing
    jit spans, to its attention sites (ops/attention sizes a cut site's row
    groups from one device's share)."""
    validate_text_tower(cfg)
    tower = TEXT_TOWERS[cfg.text_tower]
    module, _, name = tower.module.partition(":")
    cls = getattr(importlib.import_module(module), name)
    if tower.held_dtype == "float32":
        return cls(cfg, dtype=jnp.float32)
    return cls(cfg, dtype=compute_dtype,
               param_dtype=text_param_dtype(cfg.text_tower), mesh=mesh)


def init_text_tower(cfg: ModelConfig, key: jax.Array, model: nn.Module):
    """Random parameters for `model` (jitted, as every flax init here is)."""
    ids = jnp.zeros((1, cfg.text_max_length), jnp.int32)
    return jax.jit(model.init)(key, ids)["params"]
