"""LongCat-Flash as a frozen text tower: token ids in, the stack's final hidden
states out, projected to the UNet's cross-attention width.

The published model (meituan-longcat/LongCat-Flash-Chat, config.json) is run
as it is described, causal, as a prefill. A layer is a DOUBLE layer: two
latent attentions (MLA), two dense SwiGLU FFNs, and one mixture of experts
whose input is taken after the first attention and whose output is added
after the second FFN (the shortcut-connected MoE), so the experts can overlap
the dense half:

    for i in (0, 1):
        h = h + MLA_i(RMSNorm(h))
        n = RMSNorm(h)
        if i == 0: s = MoE(n)
        h = h + FFN_i(n)
    h = h + s

The router scores every output (routed experts, then zero-compute experts
that return their input); the expert layer is told which contiguous range of
the routed experts THIS device holds (`LongcatFlashConfig.held_range`),
computes those experts' part for the tokens routed to them and the
zero-compute part for its own tokens, and drops no assignment. What experts
held elsewhere would add is left out; nothing here stands in for the other
chips or their exchange.

Parameters are held in bfloat16 (core/precision.text_param_dtype); the
router, the norms and the softmaxes compute in float32.
"""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import LongcatFlashConfig, ModelConfig
from dcr_tpu.ops.attention import dot_product_attention

#: rows of one expert computed at a time. An expert's tokens are walked in
#: blocks of this many with a trip count read from the routing, so no
#: assignment is ever dropped and no capacity is reserved for a worst case.
EXPERT_ROW_BLOCK = 128


class TextTowerOutput(NamedTuple):
    last_hidden_state: jax.Array        # [B, L, cross_attention_dim] float32
    #: routing counts of this call, summed over the layers (int32 scalars):
    #: assignments, held (computed here), zero (zero-compute experts),
    #: dropped (held assignments that were not computed: always 0), and
    #: held_load_max (the most any one held expert got in one layer)
    moe_stats: dict


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (x32 * scale.astype(jnp.float32)).astype(self.dtype)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding over the last axis of [B, S, H, D] in the
    interleaved layout: the pair (x[2i], x[2i+1]) turns by pos * theta^(-2i/D).
    Angles and the rotation are float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class MLA(nn.Module):
    """Multi-head latent attention: queries and keys/values go through
    low-rank latents; a head's q/k are `nope + rope` wide, its v `v_head_dim`;
    one rotary key is shared by all heads."""

    cfg: LongcatFlashConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array) -> jax.Array:
        c = self.cfg
        b, s, hidden = x.shape
        heads, nope, rope, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                                 c.qk_rope_head_dim, c.v_head_dim)

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        def norm(name):
            return RMSNorm(c.rms_norm_eps, self.dtype, self.param_dtype,
                           name=name)

        cq = norm("q_a_norm")(dense(c.q_lora_rank, "q_a_proj")(x))
        if c.mla_scale_q_lora:
            cq = cq * jnp.asarray((hidden / c.q_lora_rank) ** 0.5, cq.dtype)
        q = dense(heads * (nope + rope), "q_b_proj")(cq).reshape(
            b, s, heads, nope + rope)
        kv = dense(c.kv_lora_rank + rope, "kv_a_proj_with_mqa")(x)
        ckv, k_rope = kv[..., :c.kv_lora_rank], kv[..., c.kv_lora_rank:]
        ckv = norm("kv_a_norm")(ckv)
        if c.mla_scale_kv_lora:
            ckv = ckv * jnp.asarray((hidden / c.kv_lora_rank) ** 0.5, ckv.dtype)
        kvb = dense(heads * (nope + vd), "kv_b_proj")(ckv).reshape(
            b, s, heads, nope + vd)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        q_rope = rotary(q[..., nope:], c.rope_theta)
        k_rope = rotary(k_rope[:, :, None, :], c.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, heads, rope))], axis=-1)
        # softmax(q k^T / sqrt(nope + rope)) v: the dispatcher's own scaling,
        # on its XLA path (a masked site, and v narrower than q/k)
        out = dot_product_attention(q, k, v, mask=mask)
        return dense(hidden, "o_proj")(out.reshape(b, s, heads * vd))


class SwiGLU(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        gate = dense(self.width, "gate_proj")(x)
        up = dense(self.width, "up_proj")(x)
        return dense(x.shape[-1], "down_proj")(nn.silu(gate) * up)


class ExpertKernels(nn.Module):
    """One routed expert's three SwiGLU kernels, as arrays: the expert runs
    inside a `lax.fori_loop`, where no Flax module may be called."""

    width: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, d: int):
        init = nn.initializers.lecun_normal()
        return (self.param("gate_proj", init, (d, self.width), self.param_dtype),
                self.param("up_proj", init, (d, self.width), self.param_dtype),
                self.param("down_proj", init, (self.width, d), self.param_dtype))


class ScMoE(nn.Module):
    """The expert layer of one double layer, for the experts held here."""

    cfg: LongcatFlashConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, n: jax.Array) -> tuple[jax.Array, dict]:
        c = self.cfg
        routed, k = c.n_routed_experts, c.moe_topk
        first, count = c.held_range()
        d = n.shape[-1]
        x = n.reshape(-1, d)
        tokens = x.shape[0]
        f32 = jnp.float32

        with jax.named_scope("router"):
            logits = nn.Dense(
                routed + c.zero_expert_num, use_bias=False, dtype=f32,
                param_dtype=self.param_dtype, precision=jax.lax.Precision.HIGHEST,
                name="router")(x.astype(f32))
            p = jax.nn.softmax(logits, axis=-1)
            bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                              (routed + c.zero_expert_num,), self.param_dtype)
            # choose by the corrected score, weigh by the plain one
            _, idx = jax.lax.top_k(p + bias.astype(f32), k)            # [T, k]
            weight = jnp.take_along_axis(p, idx, axis=1) * c.routed_scaling_factor
            # kept only by a caller that asks for the "routing" collection
            # (a comparison with a reference); nothing otherwise
            self.sow("routing", "scores", p)
            self.sow("routing", "chosen", idx)

        with jax.named_scope("zero"):
            is_zero = idx >= routed
            zero_part = jnp.sum(jnp.where(is_zero, weight, 0.0), axis=1,
                                keepdims=True) * x.astype(f32)

        with jax.named_scope("dispatch"):
            local = idx - first
            # assignments sorted by held expert; those held elsewhere last
            key = jnp.where((local >= 0) & (local < count), local,
                            count).reshape(-1)
            order = jnp.argsort(key, stable=True)
            pad = jnp.zeros((EXPERT_ROW_BLOCK,), jnp.int32)
            token_of = jnp.concatenate([(order // k).astype(jnp.int32), pad])
            weight_of = jnp.concatenate(
                [weight.reshape(-1)[order], pad.astype(f32)])
            load = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
            starts = jnp.cumsum(load) - load

        with jax.named_scope("experts"):
            held_part = jnp.zeros((tokens, d), f32)
            computed = jnp.zeros((), jnp.int32)
            lane = jnp.arange(EXPERT_ROW_BLOCK, dtype=jnp.int32)
            for e in range(count):
                gate, up, down = (w.astype(self.dtype) for w in ExpertKernels(
                    c.expert_ffn_hidden_size, self.param_dtype,
                    name=f"expert_{first + e}")(d))

                def block(i, carry, e=e, gate=gate, up=up, down=down):
                    acc, seen = carry
                    lo = starts[e] + i * EXPERT_ROW_BLOCK
                    rows = jax.lax.dynamic_slice(token_of, (lo,),
                                                 (EXPERT_ROW_BLOCK,))
                    w = jax.lax.dynamic_slice(weight_of, (lo,),
                                              (EXPERT_ROW_BLOCK,))
                    valid = i * EXPERT_ROW_BLOCK + lane < load[e]
                    xs = x[rows]
                    y = ((nn.silu(xs @ gate) * (xs @ up)) @ down).astype(f32)
                    acc = acc.at[rows].add(jnp.where(valid, w, 0.0)[:, None] * y)
                    return acc, seen + jnp.sum(valid, dtype=jnp.int32)

                trips = (load[e] + EXPERT_ROW_BLOCK - 1) // EXPERT_ROW_BLOCK
                held_part, computed = jax.lax.fori_loop(
                    0, trips, block, (held_part, computed))

        with jax.named_scope("combine"):
            out = (held_part + zero_part).astype(self.dtype).reshape(n.shape)
        held = jnp.sum(load)
        stats = {"assignments": jnp.asarray(tokens * k, jnp.int32),
                 "held": held,
                 "zero": jnp.sum(is_zero, dtype=jnp.int32),
                 "dropped": held - computed,
                 "held_load_max": (jnp.max(load) if count
                                   else jnp.zeros((), jnp.int32))}
        return out, stats


class DoubleLayer(nn.Module):
    cfg: LongcatFlashConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array, mask: jax.Array) -> tuple[jax.Array, dict]:
        c = self.cfg
        shortcut, stats = None, None
        for i in (0, 1):
            norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype,  # noqa: E731
                                        self.param_dtype, name=name)
            with jax.named_scope("mla"):
                h = h + MLA(c, self.dtype, self.param_dtype, name=f"mla_{i}")(
                    norm(f"input_norm_{i}")(h), mask)
            n = norm(f"post_attention_norm_{i}")(h)
            if i == 0:
                shortcut, stats = ScMoE(c, self.dtype, self.param_dtype,
                                        name="moe")(n)
            with jax.named_scope("ffn"):
                h = h + SwiGLU(c.ffn_hidden_size, self.dtype, self.param_dtype,
                               name=f"ffn_{i}")(n)
        return h + shortcut, stats


class LongcatFlashTextTower(nn.Module):
    """`[B, L] ids -> TextTowerOutput`; `dtype` is the compute type of the
    products and the residual stream, `param_dtype` what the leaves are held
    in."""

    config: ModelConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> TextTowerOutput:
        cfg, c = self.config, self.config.longcat
        with jax.named_scope("tower/embed"):
            h = nn.Embed(cfg.text_vocab_size, c.hidden_size, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(input_ids)
        s = input_ids.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
        total = None
        for i in range(c.num_layers):
            h, stats = DoubleLayer(c, self.dtype, self.param_dtype,
                                   name=f"layers_{i}")(h, mask)
            total = stats if total is None else {
                name: (jnp.maximum if name == "held_load_max" else jnp.add)(
                    total[name], value) for name, value in stats.items()}
        h = RMSNorm(c.rms_norm_eps, self.dtype, self.param_dtype, name="norm")(h)
        with jax.named_scope("tower/ctx_proj"):
            ctx = nn.Dense(cfg.cross_attention_dim, use_bias=False,
                           dtype=self.dtype, param_dtype=self.param_dtype,
                           name="ctx_proj")(h)
        return TextTowerOutput(ctx.astype(jnp.float32), total)
