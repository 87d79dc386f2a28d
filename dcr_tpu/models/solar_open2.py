"""Solar Open 2 as a frozen text tower: token ids in, the stack's final hidden
states out, projected to the UNet's cross-attention width.

The published model (upstage/Solar-Open2-250B, config.json) is run as it is
described, causal, as a prefill. Its layers are of two kinds, by the mixer:
the layers listed in `gqa_layers` (every fourth) hold softmax attention with
grouped key/value heads, no position encoding and a sigmoid gate on its
output; every other layer holds the gated delta rule of Kimi Linear (KDA), a
linear mixer with a recurrent state a head. Every layer is pre-norm and its
FFN is the expert layer:

    h = h + Mixer_i(RMSNorm(h))
    h = h + MoE(RMSNorm(h))

KDA, with x a layer's normed input, H heads of width d:

    q_t = d^-1/2 L2norm(SiLU(conv(W_q x))_t),  k_t = L2norm(SiLU(conv(W_k x))_t)
    v_t = SiLU(conv(W_v x))_t         (conv: causal, depthwise, no bias)
    log alpha_t = -exp(A_log) softplus(W_fb W_fa x_t + dt_bias)  [H, d]
    beta_t = 2 sigmoid(W_b x_t)       [H], in (0, 2): negative eigenvalues
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    y_t = W_o [RMSNorm_head(S_t^T q_t) * sigmoid(W_gb W_ga x_t)]

with the low-rank gates (`kda_use_full_proj` false) of rank d; the state
recurrence is `ops/delta_rule.chunked_delta_rule`. Gated GQA:
`y = W_o [softmax(q k^T / sqrt(d) + causal) v * sigmoid(W_g x)]`, query head
h reading key/value head h // (H / H_kv), through `ops/attention`.

The expert layer is openPangu's rule (`lm_layers.SharedExpertMoE`: sigmoid
scores, the chosen weights renormalised and scaled, a shared expert every
token passes), told which contiguous range of the routed experts THIS device
holds. The language-model head is left out: it predicts tokens FROM the final
states, which the tower's consumer reads.

Parameters are held in bfloat16 (core/config.TEXT_TOWERS); the router, the
norms, the gates' decays, the softmax and the delta rule's state compute in
float32.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import ModelConfig, SolarOpen2Config
from dcr_tpu.models.lm_layers import (RMSNorm, SharedExpertMoE, TextTowerOutput,
                                      causal_mask, merge_stats)
from dcr_tpu.ops.attention import dot_product_attention
from dcr_tpu.ops.delta_rule import chunked_delta_rule

#: keeps the L2 norm of q and k off a division by nought
L2_EPS = 1e-6


def a_log_init(key, shape, dtype=jnp.float32):
    """A_log = log U[1, 16]: a head's decay rate (Kimi Linear's)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a draw log-uniform on [1e-3, 1e-1] (Kimi Linear's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _l2norm(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


class ShortConv(nn.Module):
    """Causal depthwise convolution over the sequence, `size` taps, no bias:
    y_t = sum_j kernel[j] x_{t - size + 1 + j} (the last tap on x_t)."""

    size: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (self.size, x.shape[-1]), self.param_dtype)
        s = x.shape[1]
        padded = jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (self.size - 1, 0), (0, 0)))
        w = kernel.astype(jnp.float32)
        return sum(w[j] * padded[:, j:j + s] for j in range(self.size)).astype(self.dtype)


class KDA(nn.Module):
    """The gated delta-rule mixer, scopes `conv`, `gates`, `scan` and `out`
    inside it. `mesh` as `lm_layers.MLA`'s: the scan's kernel runs under
    shard_map over it."""

    cfg: SolarOpen2Config
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = self.cfg
        b, s, hidden = x.shape
        heads, d = c.linear_attn_num_heads, c.linear_attn_head_dim
        width, f32 = heads * d, jnp.float32

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        with jax.named_scope("conv"):
            q, k, v = (nn.silu(ShortConv(
                c.linear_attn_short_conv_kernel_size, self.dtype,
                self.param_dtype, name=f"{n}_conv1d")(dense(width, f"{n}_proj")(x))
            ).reshape(b, s, heads, d) for n in "qkv")
            q = (_l2norm(q) * d ** -0.5).astype(self.dtype)
            k = _l2norm(k).astype(self.dtype)
        with jax.named_scope("gates"):
            a_log = self.param("A_log", a_log_init, (heads,), self.param_dtype)
            dt_bias = self.param("dt_bias", dt_bias_init, (width,), self.param_dtype)
            f = dense(width, "f_b_proj")(dense(d, "f_a_proj")(x))
            log_alpha = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
                (f.astype(f32) + dt_bias.astype(f32)).reshape(b, s, heads, d))
            beta = jax.nn.sigmoid(dense(heads, "b_proj")(x).astype(f32))
            if c.kda_allow_neg_eigval:
                beta = 2.0 * beta
        with jax.named_scope("scan"):
            o = chunked_delta_rule(q, k, v, log_alpha, beta, mesh=self.mesh)
        with jax.named_scope("out"):
            gate = dense(width, "g_b_proj")(dense(d, "g_a_proj")(x))
            o = RMSNorm(c.rms_norm_eps, f32, self.param_dtype, name="o_norm")(o)
            o = o * jax.nn.sigmoid(gate.astype(f32)).reshape(b, s, heads, d)
            return dense(hidden, "o_proj")(o.reshape(b, s, width).astype(self.dtype))


class GatedGQA(nn.Module):
    """Softmax attention with grouped key/value heads, no position encoding,
    causal, and (`use_gqa_gate`) an elementwise sigmoid gate on its output.
    `mesh` as `lm_layers.MLA`'s."""

    cfg: SolarOpen2Config
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array) -> jax.Array:
        c = self.cfg
        b, s, hidden = x.shape
        heads, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        q = dense(heads * d, "q_proj")(x).reshape(b, s, heads, d)
        k = dense(kv * d, "k_proj")(x).reshape(b, s, kv, d)
        v = dense(kv * d, "v_proj")(x).reshape(b, s, kv, d)
        # softmax(q k^T / sqrt(d)) v: the dispatcher's own scaling
        out = dot_product_attention(q, k, v, mask=mask, mesh=self.mesh).reshape(
            b, s, heads * d)
        if c.use_gqa_gate:
            gate = dense(heads * d, "g_proj")(x)
            out = (out.astype(jnp.float32)
                   * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
        return dense(hidden, "o_proj")(out)


class HybridLayer(nn.Module):
    cfg: SolarOpen2Config
    kind: str                       # "gqa" or "kda": the mixer, and its scope
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None

    @nn.compact
    def __call__(self, h: jax.Array, mask: jax.Array):
        """-> (h, the expert layer's routing counts)."""
        c = self.cfg

        def norm(name):
            return RMSNorm(c.rms_norm_eps, self.dtype, self.param_dtype,
                           name=name)

        with jax.named_scope(self.kind):
            n = norm("input_layernorm")(h)
            if self.kind == "gqa":
                y = GatedGQA(c, self.dtype, self.param_dtype, self.mesh,
                             name="gqa")(n, mask)
            else:
                y = KDA(c, self.dtype, self.param_dtype, self.mesh, name="kda")(n)
            h = h + y
        y, stats = SharedExpertMoE(c, self.dtype, self.param_dtype, name="moe")(
            norm("post_attention_layernorm")(h))
        return h + y, stats


class SolarOpen2TextTower(nn.Module):
    """`[B, L] ids -> TextTowerOutput`; `dtype` is the compute type of the
    products and the residual stream, `param_dtype` what the leaves are held
    in."""

    config: ModelConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[jax.sharding.Mesh] = None    # handed down to every mixer

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> TextTowerOutput:
        cfg, c = self.config, self.config.solar
        with jax.named_scope("tower/embed"):
            h = nn.Embed(cfg.text_vocab_size, c.hidden_size, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(input_ids)
        mask = causal_mask(input_ids.shape[1])
        total = None
        for i in range(c.num_hidden_layers):
            h, stats = HybridLayer(
                c, "gqa" if i in c.gqa_layers else "kda", self.dtype,
                self.param_dtype, self.mesh, name=f"layers_{i}")(h, mask)
            total = merge_stats(total, stats)
        h = RMSNorm(c.rms_norm_eps, self.dtype, self.param_dtype, name="norm")(h)
        with jax.named_scope("tower/ctx_proj"):
            ctx = nn.Dense(cfg.cross_attention_dim, use_bias=False,
                           dtype=self.dtype, param_dtype=self.param_dtype,
                           name="ctx_proj")(h)
        return TextTowerOutput(ctx.astype(jnp.float32), total)
