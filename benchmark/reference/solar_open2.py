"""Plain float32 `jax.numpy` reference of Solar Open 2 as a text tower.

Written from the published description of the model (upstage/Solar-Open2-
250B: `config.json`, whose keys are named below in backticks, and Kimi
Linear's gated delta rule, arXiv:2510.26692, which its `kda_*` and
`linear_attn_config` keys name), with no kernels, no chunking, no sorting, no
loops over blocks of rows, and every matrix product at `precision=HIGHEST`.
It imports nothing of `dcr_tpu` (its norm, gated FFN, router rule, expert
layer and `Ops` are the other plain references', `reference/longcat_flash.py`
and `reference/openpangu_ultra_moe.py`); of the program it shares only the
NAMES of the parameters (a nested dict, kernels `[in, out]`), through which
both are handed the same seeded leaves.

With `h` the hidden state and N an RMSNorm with a learned scale
(`rms_norm_eps`), every layer is pre-norm:

    h = h + Mixer_i(N_in(h))
    h = h + MoE(N_post_attn(h))

The mixer of a layer in `gqa_layers` is gated GQA: `num_attention_heads`
query heads and `num_key_value_heads` key/value heads of `head_dim`, query
head h reading key/value head h // (heads / kv heads), written out by
repeating k and v; no position encoding (`use_rope` false);
`softmax(q k^T / sqrt(head_dim))`, causal; an elementwise gate
`sigmoid(W_g x)` on the output (`use_gqa_gate`); `W_o`; no bias.

Every other layer's mixer is KDA, `linear_attn_config`'s `num_heads` heads of
`head_dim` (d) for q, k and v alike (`num_kv_heads` null):

    q_t = d^-1/2 L2(SiLU(conv(W_q x))_t),  k_t = L2(SiLU(conv(W_k x))_t),
    v_t = SiLU(conv(W_v x))_t    (conv: causal depthwise, `short_conv_kernel_size`)
    log alpha_t = -exp(A_log) softplus(W_fb W_fa x_t + dt_bias)   [heads, d]
    beta_t = 2 sigmoid(W_b x_t)  (`kda_allow_neg_eigval`: in (0, 2))
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0
    y_t = W_o [RMSNorm_head(S_t^T q_t) * sigmoid(W_gb W_ga x_t)]

computed position by position (`delta_rule`), the state in float32; the
gates are low-rank of rank d (`kda_use_full_proj` false), L2 has an epsilon
of 1e-6 under its root.

MoE (`first_k_dense_replace` 0: every layer): openPangu's rule,
`reference/openpangu_ultra_moe.route` and `moe_parts`: s = sigmoid(W_r x)
over `n_routed_experts`, the top `num_experts_per_tok`, weights renormalised
(`norm_topk_prob`) and scaled by `routed_scaling_factor`, every `E` a SwiGLU
of width `moe_intermediate_size`, `n_shared_experts` of them shared.

Departures from the published description, each because the system under
test is defined so:
- the model is a text TOWER: there is no language-model head; the final
  RMSNorm's states are projected by `ctx_proj` (no bias) to the UNet's
  cross-attention width;
- this device's share: the router keeps every output and its top k, but only
  the routed experts `[held_first, held_first + held_count)` are computed;
  what experts held elsewhere would add is left out, the shared expert is
  computed whole, and that partial result goes on to the next layer; the
  vocabulary is a slice (ids come from it);
- what the published `config.json` does not state is listed under `assumed`
  in the configuration's file: the gates' shapes, beta's factor of 2, the
  per-head gated norm, the GQA gate's form, the scoring function, no
  correction bias, plain pre-norm, and that `intermediate_size` is read by no
  layer.

Routing and `Ops(quant="fp8")`, the control's switch, are as in
`reference/openpangu_ultra_moe.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.longcat_flash import EXACT, HI, Ops, rms_norm  # noqa: F401
from benchmark.reference.openpangu_ultra_moe import moe_parts, route

L2_EPS = 1e-6


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def short_conv(p, x):
    """Causal depthwise convolution over the sequence of x [B, S, C], kernel
    [taps, C]: y_t = sum_j kernel[j] x_{t - taps + 1 + j}."""
    taps, s = p["kernel"].shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(p["kernel"][j] * padded[:, j:j + s] for j in range(taps))


def delta_rule(q, k, v, log_alpha, beta):
    """The gated delta rule position by position, float32: q, k, log_alpha
    [B, S, H, Dk], v [B, S, H, Dv], beta [B, S, H] -> o [B, S, H, Dv]."""
    b, _, h, dk = q.shape

    def step(s, xs):
        q_t, k_t, v_t, la_t, b_t = xs
        s = jnp.exp(la_t)[..., None] * s                        # Diag(alpha) S
        pred = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HI)
        s = s + b_t[..., None, None] * k_t[..., :, None] * (v_t - pred)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HI)

    xs = [jnp.moveaxis(x.astype(jnp.float32), 1, 0)
          for x in (q, k, v, log_alpha, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def kda(ops, p, c: dict, x):
    """The gated delta-rule mixer, as the header writes it down. x [B, S,
    hidden]."""
    b, s, _ = x.shape
    heads, d = c["linear_attn_num_heads"], c["linear_attn_head_dim"]

    def branch(name):
        y = short_conv(p[f"{name}_conv1d"], ops.dot(x, p[f"{name}_proj"]["kernel"]))
        return jax.nn.silu(y).reshape(b, s, heads, d)

    q = l2norm(branch("q")) * d ** -0.5
    k, v = l2norm(branch("k")), branch("v")
    f = ops.dot(ops.dot(x, p["f_a_proj"]["kernel"]), p["f_b_proj"]["kernel"])
    log_alpha = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (f + p["dt_bias"]).reshape(b, s, heads, d))
    beta = jax.nn.sigmoid(ops.dot(x, p["b_proj"]["kernel"]))
    if c["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = delta_rule(q, k, v, log_alpha, beta)
    gate = ops.dot(ops.dot(x, p["g_a_proj"]["kernel"]), p["g_b_proj"]["kernel"])
    o = rms_norm(p["o_norm"], o, c["rms_norm_eps"]) * jax.nn.sigmoid(
        gate).reshape(b, s, heads, d)
    return ops.dot(o.reshape(b, s, heads * d), p["o_proj"]["kernel"])


def gqa(ops, p, c: dict, x):
    """Gated grouped-query attention without position encoding, causal.
    x [B, S, hidden]."""
    b, s, _ = x.shape
    heads, kv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = ops.dot(x, p["q_proj"]["kernel"]).reshape(b, s, heads, d)
    k, v = (jnp.repeat(ops.dot(x, p[f"{n}_proj"]["kernel"]).reshape(b, s, kv, d),
                       heads // kv, axis=2) for n in "kv")
    logits = ops.einsum("bqhd,bkhd->bhqk", q, k) / d ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    weights = jax.nn.softmax(jnp.where(causal[None, None], logits, -jnp.inf), axis=-1)
    out = ops.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, heads * d)
    if c["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(ops.dot(x, p["g_proj"]["kernel"]))
    return ops.dot(out, p["o_proj"]["kernel"])


def layer(ops, p, c: dict, h, kind: str, *, follow=None, tie_eps: float = 0.0):
    """One pre-norm layer whose mixer is `kind` ('gqa' or 'kda') -> (h, the
    routing of its expert layer)."""
    eps = c["rms_norm_eps"]
    n = rms_norm(p["input_layernorm"], h, eps)
    h = h + (gqa if kind == "gqa" else kda)(ops, p[kind], c, n)
    n = rms_norm(p["post_attention_layernorm"], h, eps)
    x = n.reshape(-1, n.shape[-1])
    routing = route(p["moe"], c, x, follow=follow, tie_eps=tie_eps)
    held, shared = moe_parts(ops, p["moe"], c, x, routing)
    return h + (held + shared).reshape(n.shape), routing


def forward(c: dict, ids, part, *, ops: Ops = EXACT, follow=None,
            tie_eps: float = 0.0) -> dict:
    """ids [B, L] -> {'ctx': [B, L, out], 'routing': [per layer: scores,
    chosen, margin, near_tie, and slack, outside where `follow` is given]}.
    `part(name)` gives the float32 leaves of 'embed', 'layers_<i>', 'norm' or
    'ctx_proj' when asked and may make them anew each time: one layer's leaves
    are alive at a time, so that published widths fit a chip. `follow`: per
    layer, another implementation's [T, k] choice."""
    embed = jax.jit(lambda p, i: p["embedding"][i])
    h = embed(part("embed"), jnp.asarray(ids, jnp.int32))
    layers = {kind: jax.jit(lambda p, h, f, kind=kind: layer(
        ops, p, c, h, kind, follow=f, tie_eps=tie_eps)) for kind in ("gqa", "kda")}
    routings = []
    for i in range(c["num_hidden_layers"]):
        leaves = part(f"layers_{i}")
        h, routing = layers["gqa" if i in c["gqa_layers"] else "kda"](
            leaves, h, None if follow is None else follow[i])
        del leaves
        routings.append({name: value for name, value in routing.items()
                         if name != "weights"})
    head = jax.jit(lambda norm, proj, h: ops.dot(
        rms_norm(norm, h, c["rms_norm_eps"]), proj["kernel"]))
    return {"ctx": head(part("norm"), part("ctx_proj"), h), "routing": routings}
