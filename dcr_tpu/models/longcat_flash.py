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
that return their input): a softmax, the top k chosen by the score plus a
correction bias and weighed by the plain score. The norm, the rotary, MLA,
SwiGLU and the expert layer itself (told which contiguous range of the routed
experts THIS device holds, `LongcatFlashConfig.held_range`; it drops no
assignment) are models/lm_layers.py's, shared with the other language-model
tower; what is here is LongCat's alone: its router rule, its zero-compute
experts and its double layer.

Parameters are held in bfloat16 (core/config.TEXT_TOWERS); the router, the
norms and the softmaxes compute in float32.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.core.config import LongcatFlashConfig, ModelConfig
from dcr_tpu.models.lm_layers import (MLA, RMSNorm, SwiGLU, TextTowerOutput,
                                      causal_mask, expert_layer, merge_stats)


class ScMoE(nn.Module):
    """The expert layer of one double layer, for the experts held here."""

    cfg: LongcatFlashConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, n: jax.Array) -> tuple[jax.Array, dict]:
        c = self.cfg
        outputs = c.router_outputs()

        def route(logits):
            p = jax.nn.softmax(logits, axis=-1)
            bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                              (outputs,), self.param_dtype)
            # choose by the corrected score, weigh by the plain one
            _, idx = jax.lax.top_k(p + bias.astype(jnp.float32), c.moe_topk)
            weight = jnp.take_along_axis(p, idx, axis=1) * c.routed_scaling_factor
            return p, idx, weight

        return expert_layer(
            self, n, router_outputs=outputs, route=route, held=c.held_range(),
            expert_width=c.expert_ffn_hidden_size, zero_from=c.n_routed_experts)


class DoubleLayer(nn.Module):
    cfg: LongcatFlashConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None

    @nn.compact
    def __call__(self, h: jax.Array, mask: jax.Array) -> tuple[jax.Array, dict]:
        c = self.cfg
        shortcut, stats = None, None
        for i in (0, 1):
            norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype,  # noqa: E731
                                        self.param_dtype, name=name)
            with jax.named_scope("mla"):
                h = h + MLA(c, self.dtype, self.param_dtype, self.mesh,
                            name=f"mla_{i}")(norm(f"input_norm_{i}")(h), mask)
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
    mesh: Optional[jax.sharding.Mesh] = None    # handed down to every MLA

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> TextTowerOutput:
        cfg, c = self.config, self.config.longcat
        with jax.named_scope("tower/embed"):
            h = nn.Embed(cfg.text_vocab_size, c.hidden_size, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(input_ids)
        mask = causal_mask(input_ids.shape[1])
        total = None
        for i in range(c.num_layers):
            h, stats = DoubleLayer(c, self.dtype, self.param_dtype, self.mesh,
                                   name=f"layers_{i}")(h, mask)
            total = merge_stats(total, stats)
        h = RMSNorm(c.rms_norm_eps, self.dtype, self.param_dtype, name="norm")(h)
        with jax.named_scope("tower/ctx_proj"):
            ctx = nn.Dense(cfg.cross_attention_dim, use_bias=False,
                           dtype=self.dtype, param_dtype=self.param_dtype,
                           name="ctx_proj")(h)
        return TextTowerOutput(ctx.astype(jnp.float32), total)
