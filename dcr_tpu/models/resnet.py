"""ResNet-50 + GeM/projection head — the SSCD copy-detection embedder.

The reference ships SSCD only as opaque TorchScript archives
(diff_retrieval.py:277-285, embedding_search/utils.py:17-25); every headline
copying metric (sim_gt_05pc etc.) is computed on its 512-d embeddings. Here the
architecture is explicit Flax (SSCD = ResNet-50 trunk → GeM pooling → linear
projection, per the SSCD paper "A Self-Supervised Descriptor for Image Copy
Detection", Pizzi et al. 2022), with a weight converter
(models/convert.py) for loading the published checkpoints.

NHWC; BatchNorm runs in inference mode (frozen stats) — these backbones are
feature extractors, never trained here.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


class FrozenBatchNorm(nn.Module):
    """Inference-only batchnorm: y = (x - mean) / sqrt(var + eps) * scale + bias.
    Stats are parameters (loaded from a converted checkpoint), never updated."""

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))
        mean = self.param("mean", nn.initializers.zeros, (c,))
        var = self.param("var", nn.initializers.ones, (c,))
        inv = jax.lax.rsqrt(var + self.epsilon) * scale
        return x * inv + (bias - mean * inv)


class Bottleneck(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with expansion 4."""

    features: int
    strides: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        residual = x
        out = nn.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype,
                      name="conv1")(x)
        out = FrozenBatchNorm(name="bn1")(out)
        out = nn.relu(out)
        out = nn.Conv(self.features, (3, 3), strides=(self.strides, self.strides),
                      padding=((1, 1), (1, 1)), use_bias=False, dtype=self.dtype,
                      name="conv2")(out)
        out = FrozenBatchNorm(name="bn2")(out)
        out = nn.relu(out)
        out = nn.Conv(self.features * 4, (1, 1), use_bias=False, dtype=self.dtype,
                      name="conv3")(out)
        out = FrozenBatchNorm(name="bn3")(out)
        if residual.shape[-1] != self.features * 4 or self.strides != 1:
            residual = nn.Conv(self.features * 4, (1, 1),
                               strides=(self.strides, self.strides),
                               use_bias=False, dtype=self.dtype,
                               name="downsample_conv")(x)
            residual = FrozenBatchNorm(name="downsample_bn")(residual)
        return nn.relu(out + residual)


class ResNet50(nn.Module):
    """Standard ResNet-50 trunk -> [B, H/32, W/32, 2048] feature map."""

    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.Conv(64, (7, 7), strides=(2, 2), padding=((3, 3), (3, 3)),
                    use_bias=False, dtype=self.dtype, name="conv1")(x)
        x = FrozenBatchNorm(name="bn1")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        features = 64
        for stage, num_blocks in enumerate(self.stage_sizes):
            for block in range(num_blocks):
                strides = 2 if stage > 0 and block == 0 else 1
                x = Bottleneck(features, strides=strides, dtype=self.dtype,
                               name=f"layer{stage + 1}_{block}")(x)
            features *= 2
        return x


def gem_pool(x: jax.Array, p: float = 3.0, eps: float = 1e-6) -> jax.Array:
    """Generalized-mean pooling over spatial dims: (mean(x^p))^(1/p)."""
    x = jnp.clip(x, eps, None) ** p
    return jnp.mean(x, axis=(1, 2)) ** (1.0 / p)


class SSCDModel(nn.Module):
    """SSCD descriptor: ResNet-50 -> GeM(p=3) -> Linear(2048->embed_dim).

    Outputs are NOT L2-normalized here; the eval stage normalizes explicitly
    (mirroring the reference's F.normalize at diff_retrieval.py:388-389 — the
    raw TorchScript output is likewise unnormalized)."""

    embed_dim: int = 512
    gem_p: float = 3.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        feats = ResNet50(dtype=self.dtype, name="backbone")(x)
        pooled = gem_pool(feats, self.gem_p)
        return nn.Dense(self.embed_dim, use_bias=True, dtype=self.dtype,
                        name="embeddings")(pooled)


class ResNet50Classifier(nn.Module):
    """ResNet-50 with avgpool head (the reference's plain torchvision resnet50
    option for dino_resnet50-style backbones)."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        feats = ResNet50(dtype=self.dtype, name="backbone")(x)
        return jnp.mean(feats, axis=(1, 2))


def init_sscd(key: jax.Array, embed_dim: int = 512, image_size: int = 224):
    model = SSCDModel(embed_dim=embed_dim)
    # jitted for the reason init_unet gives
    params = jax.jit(model.init)(
        key, jnp.zeros((1, image_size, image_size, 3)))["params"]
    return model, params
