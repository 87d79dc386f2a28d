"""UNet2DCondition — the flagship denoiser (SD-2.1 architecture), TPU-native Flax.

Capability-equivalent of the diffusers UNet2DConditionModel the reference
finetunes (diff_train.py:386-408: loaded from checkpoint or built from a
unet_config.json for --unet_from_scratch). NHWC, bf16-compute friendly, with
every attention going through dcr_tpu.ops (Pallas flash on TPU).

Structure (SD-2.x): conv_in → [CrossAttnDown ×(n-1), Down] → mid(Res, T2D, Res)
→ [Up, CrossAttnUp ×(n-1)] with skip concats → GN → silu → conv_out.
Timesteps enter through a sinusoidal embedding + MLP added in every ResnetBlock.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import ModelConfig
from dcr_tpu.models import layers as L


def attn_dims(cfg: ModelConfig, ch: int) -> tuple[int, int]:
    """(num_heads, head_dim) for a block of width ch. SD-2.x fixes head_dim
    (64) and varies the count; SD-1.x fixes the count (8) and varies the dim."""
    if cfg.attention_num_heads:
        return cfg.attention_num_heads, ch // cfg.attention_num_heads
    return ch // cfg.attention_head_dim, cfg.attention_head_dim


def self_attention_shapes(cfg: ModelConfig, rows: int,
                          latent: int) -> list[tuple[int, int, int, int]]:
    """[B, S, H, D] of q, k and v at every spatial self-attention site of one
    UNet call over `rows` latents of latent x latent, in call order: what the
    attention dispatcher is asked, site by site."""
    n = len(cfg.block_out_channels)

    def level(i: int, sites: int) -> list[tuple[int, int, int, int]]:
        heads, head_dim = attn_dims(cfg, cfg.block_out_channels[i])
        tokens = (latent // 2 ** i) ** 2
        return [(rows, tokens, heads, head_dim)] * (sites * cfg.transformer_layers)

    down = [s for i in range(n - 1) for s in level(i, cfg.layers_per_block)]
    up = [s for i in reversed(range(n - 1))
          for s in level(i, cfg.layers_per_block + 1)]
    return down + level(n - 1, 1) + up


class UNet2DCondition(nn.Module):
    config: ModelConfig
    dtype: jnp.dtype = jnp.float32
    # the mesh the enclosing jit spans, when it has more than one device: the
    # flash kernel then runs per device under shard_map (ops/attention.py),
    # and a seq axis >1 turns on ring-attention sequence parallelism in the
    # spatial self-attentions (config.seq_parallel_min_seq)
    mesh: Optional[jax.sharding.Mesh] = None

    @nn.compact
    def __call__(self, sample: jax.Array, timesteps: jax.Array,
                 encoder_hidden_states: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        """sample: [B, H, W, C_latent]; timesteps: [B] int; context: [B, S, D_txt]."""
        cfg = self.config
        dtype = self.dtype
        block_out = cfg.block_out_channels
        n_blocks = len(block_out)
        groups = cfg.norm_num_groups

        def transformer(ch: int, name: str) -> L.Transformer2D:
            heads, head_dim = attn_dims(cfg, ch)
            return L.Transformer2D(
                heads, head_dim, num_layers=cfg.transformer_layers,
                num_groups=groups, use_flash=cfg.flash_attention,
                use_linear_projection=cfg.use_linear_projection, dtype=dtype,
                mesh=self.mesh,
                seq_parallel_min_seq=cfg.seq_parallel_min_seq,
                seq_parallel_mode=cfg.seq_parallel_mode, name=name)

        # --- time embedding
        t_emb = L.timestep_embedding(timesteps, block_out[0])
        temb = L.TimestepEmbedding(block_out[0] * 4, dtype=dtype,
                                   name="time_embedding")(t_emb.astype(dtype))

        context = encoder_hidden_states.astype(dtype)
        sample = sample.astype(dtype)

        # --- down path
        h = nn.Conv(block_out[0], (3, 3), padding=((1, 1), (1, 1)), dtype=dtype,
                    name="conv_in")(sample)
        skips = [h]
        for i, ch in enumerate(block_out):
            is_final = i == n_blocks - 1
            for j in range(cfg.layers_per_block):
                h = L.ResnetBlock2D(ch, num_groups=groups, dtype=dtype,
                                    name=f"down_{i}_res_{j}")(h, temb, deterministic)
                if not is_final:  # cross-attn blocks everywhere but the bottom
                    h = transformer(ch, f"down_{i}_attn_{j}")(h, context)
                skips.append(h)
            if not is_final:
                h = L.Downsample2D(ch, dtype=dtype, name=f"down_{i}_downsample")(h)
                skips.append(h)

        # --- mid
        mid_ch = block_out[-1]
        h = L.ResnetBlock2D(mid_ch, num_groups=groups, dtype=dtype,
                            name="mid_res_0")(h, temb, deterministic)
        h = transformer(mid_ch, "mid_attn")(h, context)
        h = L.ResnetBlock2D(mid_ch, num_groups=groups, dtype=dtype,
                            name="mid_res_1")(h, temb, deterministic)

        # --- up path (mirror, consuming skips)
        for i, ch in enumerate(reversed(block_out)):
            block_idx = n_blocks - 1 - i
            is_first = i == 0  # bottom of the U: no cross-attn (mirrors DownBlock2D)
            for j in range(cfg.layers_per_block + 1):
                skip = skips.pop()
                h = jnp.concatenate([h, skip], axis=-1)
                h = L.ResnetBlock2D(ch, num_groups=groups, dtype=dtype,
                                    name=f"up_{block_idx}_res_{j}")(h, temb, deterministic)
                if not is_first:
                    h = transformer(ch, f"up_{block_idx}_attn_{j}")(h, context)
            if block_idx > 0:
                h = L.Upsample2D(ch, dtype=dtype, name=f"up_{block_idx}_upsample")(h)

        # --- out
        h = L.GroupNorm(groups, name="conv_norm_out")(h)
        h = nn.silu(h)
        h = nn.Conv(cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=dtype, name="conv_out")(h)
        return h.astype(jnp.float32)


def init_unet(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32, mesh=None,
              model: "UNet2DCondition | None" = None):
    """Initialize params with tiny dummy shapes (shape-polymorphic in H/W).
    `mesh` (seq axis >1) turns on ring-attention sequence parallelism; init
    itself always runs the single-chip path (batch-1 dummy shapes never pass
    the divisibility gate). Pass `model` to init a prebuilt module
    (trainer.build_modules) instead of constructing a second one."""
    model = model if model is not None else UNet2DCondition(cfg, dtype=dtype, mesh=mesh)
    sample = jnp.zeros((1, cfg.sample_size, cfg.sample_size, cfg.in_channels))
    t = jnp.zeros((1,), jnp.int32)
    ctx = jnp.zeros((1, cfg.text_max_length, cfg.cross_attention_dim))
    # jitted: flax's init runs the forward pass to find the shapes, and
    # un-jitted every distinct op of it is a compile of its own on a TPU
    # (minutes at SD-2.1 widths); under jit the forward is dead code
    params = jax.jit(model.init)(key, sample, t, ctx)["params"]
    return model, params


def unet_param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
