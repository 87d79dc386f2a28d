"""CLIP text encoder (OpenCLIP ViT-H text tower shape for SD-2.1), Flax.

Capability-equivalent of the frozen transformers CLIPTextModel the reference
conditions on (diff_train.py:376-381, 636). Pre-LN transformer with causal mask;
returns the full hidden-state stack so callers can pick the final or penultimate
layer (SD-2.x conditions on the penultimate).
"""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import ModelConfig


class CLIPTextOutput(NamedTuple):
    last_hidden_state: jax.Array        # [B, S, D] after final LN
    penultimate_hidden_state: jax.Array  # [B, S, D] layer -2, final-LN applied
    pooled: jax.Array                    # [B, D] EOT-token embedding


class CLIPLayer(nn.Module):
    heads: int
    dtype: jnp.dtype = jnp.float32
    # "gelu" (SD-2.x OpenCLIP ViT-H tower) or "quick_gelu" (OpenAI CLIP-B/L)
    act: str = "gelu"

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array) -> jax.Array:
        d = x.shape[-1]
        h = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="ln1")(x)
        h = nn.MultiHeadDotProductAttention(num_heads=self.heads, dtype=self.dtype,
                                            deterministic=True, name="attn")(h, mask=mask)
        x = x + h
        h = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="ln2")(x)
        h = nn.Dense(4 * d, dtype=self.dtype, name="fc1")(h)
        if self.act == "quick_gelu":
            h = h * nn.sigmoid(1.702 * h)
        else:
            h = nn.gelu(h, approximate=False)
        h = nn.Dense(d, dtype=self.dtype, name="fc2")(h)
        return x + h


class CLIPTextModel(nn.Module):
    config: ModelConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> CLIPTextOutput:
        cfg = self.config
        b, s = input_ids.shape
        tok = nn.Embed(cfg.text_vocab_size, cfg.text_hidden_size,
                       dtype=self.dtype, name="token_embedding")(input_ids)
        pos = self.param("position_embedding", nn.initializers.normal(0.01),
                         (cfg.text_max_length, cfg.text_hidden_size))
        x = tok + pos[None, :s, :].astype(self.dtype)
        causal = nn.make_causal_mask(input_ids)  # [B, 1, S, S]
        hidden = x
        penultimate = x
        for i in range(cfg.text_layers):
            if i == cfg.text_layers - 1:
                penultimate = hidden
            hidden = CLIPLayer(cfg.text_heads, dtype=self.dtype,
                               act=getattr(cfg, "text_act", "gelu"),
                               name=f"layers_{i}")(hidden, causal)
        ln_final = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="final_layer_norm")
        last = ln_final(hidden)
        penultimate = ln_final(penultimate)
        # pooled = embedding at the EOT token (highest token id = argmax trick,
        # matching CLIP: eot has the largest id in the vocab)
        eot_idx = jnp.argmax(input_ids, axis=-1)
        pooled = jnp.take_along_axis(
            last, eot_idx[:, None, None].astype(jnp.int32), axis=1
        ).squeeze(1)
        return CLIPTextOutput(last.astype(jnp.float32),
                              penultimate.astype(jnp.float32),
                              pooled.astype(jnp.float32))


def init_clip_text(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32,
                   model: "CLIPTextModel | None" = None):
    model = model if model is not None else CLIPTextModel(cfg, dtype=dtype)
    ids = jnp.zeros((1, cfg.text_max_length), jnp.int32)
    # jitted for the reason init_unet gives
    params = jax.jit(model.init)(key, ids)["params"]
    return model, params
