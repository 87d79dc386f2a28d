"""Shared Flax building blocks for the diffusion model zoo.

NHWC layout throughout (TPU-native; XLA tiles convs onto the MXU best with
features-last). The reference consumes these blocks from HF diffusers
(UNet2DConditionModel etc., diff_train.py:370-408) — here they are first-party.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.ops.attention import dot_product_attention


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0,
                       flip_sin_to_cos: bool = True,
                       downscale_freq_shift: float = 0.0) -> jax.Array:
    """Sinusoidal timestep embedding [B] -> [B, dim] (Transformer/DDPM style)."""
    half = dim // 2
    freqs = jnp.exp(
        -jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32)
        / (half - downscale_freq_shift)
    )
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(args), jnp.cos(args)
    emb = jnp.concatenate([cos, sin] if flip_sin_to_cos else [sin, cos], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


class TimestepEmbedding(nn.Module):
    """2-layer MLP lifting the sinusoidal embedding to the UNet's time channels."""

    dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, emb: jax.Array) -> jax.Array:
        emb = nn.Dense(self.dim, dtype=self.dtype, name="linear_1")(emb)
        emb = nn.silu(emb)
        emb = nn.Dense(self.dim, dtype=self.dtype, name="linear_2")(emb)
        return emb


class GroupNorm(nn.Module):
    """GroupNorm computing statistics in f32 always (the point of this wrapper);
    output is cast back to the input's compute dtype."""

    num_groups: int = 32
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        orig_dtype = x.dtype
        x = nn.GroupNorm(num_groups=self.num_groups, epsilon=self.epsilon,
                         dtype=jnp.float32, param_dtype=jnp.float32)(x.astype(jnp.float32))
        return x.astype(orig_dtype)


class ResnetBlock2D(nn.Module):
    """norm→silu→conv→(+time)→norm→silu→conv with learned/1x1 skip."""

    out_channels: int
    num_groups: int = 32
    epsilon: float = 1e-5
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, temb: Optional[jax.Array] = None,
                 deterministic: bool = True) -> jax.Array:
        residual = x
        h = GroupNorm(self.num_groups, self.epsilon, name="norm1")(x)
        h = nn.silu(h)
        h = nn.Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv1")(h)
        if temb is not None:
            temb_proj = nn.Dense(self.out_channels, dtype=self.dtype,
                                 name="time_emb_proj")(nn.silu(temb))
            h = h + temb_proj[:, None, None, :]
        h = GroupNorm(self.num_groups, self.epsilon, name="norm2")(h)
        h = nn.silu(h)
        if self.dropout > 0:
            h = nn.Dropout(self.dropout, deterministic=deterministic)(h)
        h = nn.Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv2")(h)
        if residual.shape[-1] != self.out_channels:
            residual = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype,
                               name="conv_shortcut")(residual)
        return h + residual


class CrossAttention(nn.Module):
    """Multi-head attention; self-attention when context is None.

    When a mesh with a seq axis >1 is attached and the (self-attention)
    sequence reaches seq_parallel_min_seq, dispatches to exact sequence/
    context parallelism over the mesh's `seq` axis — the long-context path
    (SURVEY §5.7; reference's only analogue is single-GPU xformers,
    diff_train.py:578). Two strategies, selected by seq_parallel_mode:

    - "ring": K/V shards rotate via ppermute, online-softmax merge
      (ops/ring_attention.py). No head-count constraint.
    - "ulysses": one all_to_all re-shards seq->heads, full-sequence
      attention per head group (riding the Pallas flash kernel on TPU),
      all_to_all back (ops/ulysses_attention.py). Needs heads % seq == 0;
      falls back to ring when they don't divide.

    Every other site hands the mesh to the dispatcher, which over more than
    one device runs the Pallas kernel under shard_map (ops/attention.py)."""

    num_heads: int
    head_dim: int
    out_dim: int
    use_flash: bool = True
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None
    seq_parallel_min_seq: int = 4096
    seq_parallel_mode: str = "ring"

    def _seq_n(self) -> int:
        from dcr_tpu.parallel.mesh import SEQ_AXIS

        return dict(self.mesh.shape).get(SEQ_AXIS, 1) if self.mesh else 1

    def _ring_ok(self, b: int, sq: int, is_self: bool) -> bool:
        if not is_self or self.mesh is None:
            return False
        from dcr_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS

        shape = dict(self.mesh.shape)
        n_seq = self._seq_n()
        n_batch = shape.get(DATA_AXIS, 1) * shape.get(FSDP_AXIS, 1)
        return (n_seq > 1 and sq >= self.seq_parallel_min_seq
                and sq % n_seq == 0 and b % n_batch == 0)

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array] = None) -> jax.Array:
        is_self = context is None
        context = x if context is None else context
        inner = self.num_heads * self.head_dim
        q = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_q")(x)
        k = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_k")(context)
        v = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_v")(context)
        b, sq = q.shape[0], q.shape[1]
        sk = k.shape[1]
        q = q.reshape(b, sq, self.num_heads, self.head_dim)
        k = k.reshape(b, sk, self.num_heads, self.head_dim)
        v = v.reshape(b, sk, self.num_heads, self.head_dim)
        if self._ring_ok(b, sq, is_self):
            if (self.seq_parallel_mode == "ulysses"
                    and self.num_heads % self._seq_n() == 0):
                from dcr_tpu.ops.ulysses_attention import ulysses_self_attention

                out = ulysses_self_attention(q, k, v, self.mesh,
                                             use_flash=self.use_flash)
            else:
                from dcr_tpu.ops.ring_attention import ring_self_attention

                out = ring_self_attention(q, k, v, self.mesh)
        else:
            out = dot_product_attention(q, k, v, use_flash=self.use_flash,
                                        mesh=self.mesh)
        out = out.reshape(b, sq, inner)
        return nn.Dense(self.out_dim, dtype=self.dtype, name="to_out")(out)


class FeedForward(nn.Module):
    """GEGLU feed-forward (SD transformer blocks)."""

    dim: int
    mult: int = 4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        inner = self.dim * self.mult
        h = nn.Dense(inner * 2, dtype=self.dtype, name="proj_in")(x)
        h, gate = jnp.split(h, 2, axis=-1)
        h = h * nn.gelu(gate)
        return nn.Dense(self.dim, dtype=self.dtype, name="proj_out")(h)


class BasicTransformerBlock(nn.Module):
    """self-attn → cross-attn → ff, each pre-LayerNormed with residuals.
    Only the self-attention (attn1) is eligible for sequence parallelism —
    cross-attention's K/V is the 77-token text context."""

    dim: int
    num_heads: int
    head_dim: int
    use_flash: bool = True
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None
    seq_parallel_min_seq: int = 4096
    seq_parallel_mode: str = "ring"

    @nn.compact
    def __call__(self, x: jax.Array, context: jax.Array) -> jax.Array:
        attn = CrossAttention(self.num_heads, self.head_dim, self.dim,
                              use_flash=self.use_flash, dtype=self.dtype,
                              mesh=self.mesh,
                              seq_parallel_min_seq=self.seq_parallel_min_seq,
                              seq_parallel_mode=self.seq_parallel_mode,
                              name="attn1")
        x = x + attn(nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm1")(x))
        xattn = CrossAttention(self.num_heads, self.head_dim, self.dim,
                               use_flash=self.use_flash, dtype=self.dtype, name="attn2")
        x = x + xattn(nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm2")(x), context)
        ff = FeedForward(self.dim, dtype=self.dtype, name="ff")
        x = x + ff(nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm3")(x))
        return x


class Transformer2D(nn.Module):
    """Spatial transformer: GN → proj in → N blocks → proj out + residual.

    use_linear_projection selects the SD-2.x linear projections (default) or
    the SD-1.x 1x1 convs — same math, different weight shape and apply order
    (conv before the [B,HW,C] reshape), matching diffusers so checkpoints of
    both families convert losslessly."""

    num_heads: int
    head_dim: int
    num_layers: int = 1
    num_groups: int = 32
    use_flash: bool = True
    use_linear_projection: bool = True
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None
    seq_parallel_min_seq: int = 4096
    seq_parallel_mode: str = "ring"

    @nn.compact
    def __call__(self, x: jax.Array, context: jax.Array) -> jax.Array:
        b, h, w, c = x.shape
        residual = x
        inner = self.num_heads * self.head_dim
        # diffusers Transformer2DModel norms with eps=1e-6 (unlike the 1e-5
        # resnet norms); mismatch silently drifts converted SD weights
        out = GroupNorm(self.num_groups, epsilon=1e-6, name="norm")(x)
        if self.use_linear_projection:
            out = out.reshape(b, h * w, c)
            out = nn.Dense(inner, dtype=self.dtype, name="proj_in")(out)
        else:
            out = nn.Conv(inner, (1, 1), dtype=self.dtype, name="proj_in")(out)
            out = out.reshape(b, h * w, inner)
        for i in range(self.num_layers):
            out = BasicTransformerBlock(inner, self.num_heads, self.head_dim,
                                        use_flash=self.use_flash, dtype=self.dtype,
                                        mesh=self.mesh,
                                        seq_parallel_min_seq=self.seq_parallel_min_seq,
                                        seq_parallel_mode=self.seq_parallel_mode,
                                        name=f"blocks_{i}")(out, context)
        if self.use_linear_projection:
            out = nn.Dense(c, dtype=self.dtype, name="proj_out")(out)
            out = out.reshape(b, h, w, c)
        else:
            out = nn.Conv(c, (1, 1), dtype=self.dtype,
                          name="proj_out")(out.reshape(b, h, w, inner))
        return out + residual


class Downsample2D(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.float32
    # diffusers' AutoencoderKL encoder downsamples with padding=0 plus an
    # asymmetric (0,1,0,1) pre-pad (right/bottom only); the UNet downsampler
    # uses symmetric padding=1. Both produce the same output shape for even
    # inputs but sample different taps, so pretrained VAE weights require the
    # asymmetric variant to reproduce reference activations.
    asymmetric_pad: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        pad = ((0, 1), (0, 1)) if self.asymmetric_pad else ((1, 1), (1, 1))
        return nn.Conv(self.out_channels, (3, 3), strides=(2, 2),
                       padding=pad, dtype=self.dtype, name="conv")(x)


class Upsample2D(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, h, w, c = x.shape
        x = jax.image.resize(x, (b, h * 2, w * 2, c), method="nearest")
        return nn.Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                       dtype=self.dtype, name="conv")(x)


class AttentionBlock2D(nn.Module):
    """Single-head (or multi-head) spatial self-attention used in VAE mid blocks."""

    num_heads: int = 1
    num_groups: int = 32
    epsilon: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, h, w, c = x.shape
        residual = x
        out = GroupNorm(self.num_groups, self.epsilon, name="group_norm")(x).reshape(b, h * w, c)
        head_dim = c // self.num_heads
        q = nn.Dense(c, dtype=self.dtype, name="to_q")(out)
        k = nn.Dense(c, dtype=self.dtype, name="to_k")(out)
        v = nn.Dense(c, dtype=self.dtype, name="to_v")(out)
        q = q.reshape(b, h * w, self.num_heads, head_dim)
        k = k.reshape(b, h * w, self.num_heads, head_dim)
        v = v.reshape(b, h * w, self.num_heads, head_dim)
        out = dot_product_attention(q, k, v, use_flash=False).reshape(b, h * w, c)
        out = nn.Dense(c, dtype=self.dtype, name="to_out")(out)
        return out.reshape(b, h, w, c) + residual
