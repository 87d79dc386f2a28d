"""openPangu-Ultra-MoE as a frozen text tower: token ids in, the stack's final
hidden states out, projected to the UNet's cross-attention width.

The published model (FreedomIntelligence/openPangu-Ultra-MoE-718B,
config.json) is run as it is described, causal, as a prefill. A layer is
sandwich-normed: a norm before AND after each sublayer, the second on the
sublayer's output before it joins the residual stream:

    h = h + RMSNorm(MLA(RMSNorm(h)))
    h = h + RMSNorm(F(RMSNorm(h)))

`F` is a dense SwiGLU in the first `first_k_dense_replace` layers and the
expert layer in the others: the router scores every routed expert by a
sigmoid, the chosen `num_experts_per_tok` weights are renormalised to sum to
one and scaled by `routed_scaling_factor`, and a shared expert that every
token passes is added to the routed part. The norm, the rotary, MLA, SwiGLU
and the expert layer itself (told which contiguous range of the routed experts
THIS device holds, `OpenPanguUltraMoEConfig.held_range`; it drops no
assignment) are models/lm_layers.py's, shared with the other language-model
tower. The language-model head and the multi-token-prediction layer predict
tokens FROM the final states and have no consumer here: they are left out.

Parameters are held in bfloat16 (core/config.TEXT_TOWERS); the router, the
norms and the softmaxes compute in float32.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import ModelConfig, OpenPanguUltraMoEConfig
from dcr_tpu.models.lm_layers import (MLA, RMSNorm, SwiGLU, TextTowerOutput,
                                      causal_mask, expert_layer, merge_stats)

#: keeps the renormalisation of the chosen weights off a division by nought
NORM_TOPK_EPS = 1e-20


class SharedExpertMoE(nn.Module):
    """The expert layer of one layer, for the routed experts held here, and
    the shared expert."""

    cfg: OpenPanguUltraMoEConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, n: jax.Array) -> tuple[jax.Array, dict]:
        c = self.cfg

        def route(logits):
            s = jax.nn.sigmoid(logits)
            top, idx = jax.lax.top_k(s, c.num_experts_per_tok)
            if c.norm_topk_prob:
                top = top / (jnp.sum(top, axis=1, keepdims=True) + NORM_TOPK_EPS)
            return s, idx, top * c.routed_scaling_factor

        return expert_layer(
            self, n, router_outputs=c.n_routed_experts, route=route,
            held=c.held_range(), expert_width=c.moe_intermediate_size,
            shared_width=c.moe_intermediate_size * c.n_shared_experts,
            count_unheld=True)


class SandwichLayer(nn.Module):
    cfg: OpenPanguUltraMoEConfig
    dense: bool                         # a leading layer: F is a dense SwiGLU
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None

    @nn.compact
    def __call__(self, h: jax.Array, mask: jax.Array):
        """-> (h, the expert layer's routing counts or None)."""
        c = self.cfg

        def norm(name):
            return RMSNorm(c.rms_norm_eps, self.dtype, self.param_dtype,
                           name=name)

        with jax.named_scope("mla"):
            h = h + norm("post_attention_layernorm")(MLA(
                c, self.dtype, self.param_dtype, self.mesh, name="self_attn")(
                    norm("input_layernorm")(h), mask))
        n = norm("pre_mlp_layernorm")(h)
        if self.dense:
            with jax.named_scope("ffn"):
                y, stats = SwiGLU(c.intermediate_size, self.dtype,
                                  self.param_dtype, name="mlp")(n), None
        else:
            y, stats = SharedExpertMoE(c, self.dtype, self.param_dtype,
                                       name="moe")(n)
        return h + norm("post_mlp_layernorm")(y), stats


class OpenPanguUltraMoETextTower(nn.Module):
    """`[B, L] ids -> TextTowerOutput`; `dtype` is the compute type of the
    products and the residual stream, `param_dtype` what the leaves are held
    in."""

    config: ModelConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.bfloat16
    mesh: Optional[jax.sharding.Mesh] = None    # handed down to every MLA

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> TextTowerOutput:
        cfg, c = self.config, self.config.openpangu
        with jax.named_scope("tower/embed"):
            h = nn.Embed(cfg.text_vocab_size, c.hidden_size, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(input_ids)
        mask = causal_mask(input_ids.shape[1])
        total = None
        for i in range(c.num_hidden_layers):
            h, stats = SandwichLayer(
                c, i < c.first_k_dense_replace, self.dtype, self.param_dtype,
                self.mesh, name=f"layers_{i}")(h, mask)
            total = merge_stats(total, stats)
        h = RMSNorm(c.rms_norm_eps, self.dtype, self.param_dtype, name="norm")(h)
        with jax.named_scope("tower/ctx_proj"):
            ctx = nn.Dense(cfg.cross_attention_dim, use_bias=False,
                           dtype=self.dtype, param_dtype=self.param_dtype,
                           name="ctx_proj")(h)
        return TextTowerOutput(ctx.astype(jnp.float32), total or {})
