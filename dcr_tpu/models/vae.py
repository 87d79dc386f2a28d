"""AutoencoderKL — latent-space VAE (SD architecture), TPU-native Flax, NHWC.

Capability-equivalent of the frozen diffusers AutoencoderKL the reference uses to
map pixels↔latents (diff_train.py:383,620-621 encode ×0.18215; decode inside the
sampling pipeline). Encoder outputs a diagonal Gaussian (mean, logvar); training
samples it with an explicit rng key (the reference relies on torch global rng).
"""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import ModelConfig
from dcr_tpu.models import layers as L


class DiagonalGaussian(NamedTuple):
    mean: jax.Array
    logvar: jax.Array

    def sample(self, key: jax.Array) -> jax.Array:
        std = jnp.exp(0.5 * jnp.clip(self.logvar, -30.0, 20.0))
        return self.mean + std * jax.random.normal(key, self.mean.shape, self.mean.dtype)

    def mode(self) -> jax.Array:
        return self.mean


class Encoder(nn.Module):
    config: ModelConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        block_out = cfg.vae_block_out_channels
        groups = min(cfg.norm_num_groups, block_out[0])
        h = nn.Conv(block_out[0], (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
                    name="conv_in")(x.astype(self.dtype))
        for i, ch in enumerate(block_out):
            for j in range(cfg.vae_layers_per_block):
                h = L.ResnetBlock2D(ch, num_groups=groups, epsilon=1e-6, dtype=self.dtype,
                                    name=f"down_{i}_res_{j}")(h)
            if i < len(block_out) - 1:
                # asymmetric (0,1,0,1) pad + VALID conv, matching diffusers'
                # AutoencoderKL encoder (Downsample2D with padding=0).
                h = L.Downsample2D(ch, asymmetric_pad=True, dtype=self.dtype,
                                   name=f"down_{i}_downsample")(h)
        ch = block_out[-1]
        h = L.ResnetBlock2D(ch, num_groups=groups, epsilon=1e-6, dtype=self.dtype, name="mid_res_0")(h)
        h = L.AttentionBlock2D(num_groups=groups, dtype=self.dtype, name="mid_attn")(h)
        h = L.ResnetBlock2D(ch, num_groups=groups, epsilon=1e-6, dtype=self.dtype, name="mid_res_1")(h)
        h = L.GroupNorm(groups, epsilon=1e-6, name="conv_norm_out")(h)
        h = nn.silu(h)
        h = nn.Conv(2 * cfg.vae_latent_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv_out")(h)
        # diffusers applies an extra 1x1 "quant_conv"
        h = nn.Conv(2 * cfg.vae_latent_channels, (1, 1), dtype=self.dtype,
                    name="quant_conv")(h)
        return h.astype(jnp.float32)


class Decoder(nn.Module):
    config: ModelConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, z: jax.Array) -> jax.Array:
        cfg = self.config
        block_out = cfg.vae_block_out_channels
        groups = min(cfg.norm_num_groups, block_out[0])
        z = nn.Conv(cfg.vae_latent_channels, (1, 1), dtype=self.dtype,
                    name="post_quant_conv")(z.astype(self.dtype))
        ch = block_out[-1]
        h = nn.Conv(ch, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
                    name="conv_in")(z)
        h = L.ResnetBlock2D(ch, num_groups=groups, epsilon=1e-6, dtype=self.dtype, name="mid_res_0")(h)
        h = L.AttentionBlock2D(num_groups=groups, dtype=self.dtype, name="mid_attn")(h)
        h = L.ResnetBlock2D(ch, num_groups=groups, epsilon=1e-6, dtype=self.dtype, name="mid_res_1")(h)
        for i, ch in enumerate(reversed(block_out)):
            for j in range(cfg.vae_layers_per_block + 1):
                h = L.ResnetBlock2D(ch, num_groups=groups, epsilon=1e-6, dtype=self.dtype,
                                    name=f"up_{i}_res_{j}")(h)
            if i < len(block_out) - 1:
                h = L.Upsample2D(ch, dtype=self.dtype, name=f"up_{i}_upsample")(h)
        h = L.GroupNorm(groups, epsilon=1e-6, name="conv_norm_out")(h)
        h = nn.silu(h)
        h = nn.Conv(3, (3, 3), padding=((1, 1), (1, 1)), dtype=self.dtype,
                    name="conv_out")(h)
        return h.astype(jnp.float32)


class AutoencoderKL(nn.Module):
    config: ModelConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.encoder = Encoder(self.config, dtype=self.dtype)
        self.decoder = Decoder(self.config, dtype=self.dtype)

    def encode(self, x: jax.Array) -> DiagonalGaussian:
        moments = self.encoder(x)
        mean, logvar = jnp.split(moments, 2, axis=-1)
        return DiagonalGaussian(mean, logvar)

    def decode(self, z: jax.Array) -> jax.Array:
        return self.decoder(z)

    def __call__(self, x: jax.Array, key: jax.Array) -> jax.Array:
        dist = self.encode(x)
        return self.decode(dist.sample(key))


def vae_scale_factor(cfg: ModelConfig) -> int:
    """Pixel-to-latent downscale (8 for the SD 4-block VAE)."""
    return 2 ** (len(cfg.vae_block_out_channels) - 1)


def init_vae(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32,
             model: "AutoencoderKL | None" = None):
    model = model if model is not None else AutoencoderKL(cfg, dtype=dtype)
    px = vae_scale_factor(cfg) * cfg.sample_size
    x = jnp.zeros((1, px, px, 3))
    # jitted for the reason init_unet gives
    params = jax.jit(model.init)(key, x, jax.random.key(0))["params"]
    return model, params
