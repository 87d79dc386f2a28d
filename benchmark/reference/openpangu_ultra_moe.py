"""Plain float32 `jax.numpy` reference of openPangu-Ultra-MoE as a text tower.

Written from the published description of the model (FreedomIntelligence/
openPangu-Ultra-MoE-718B: `config.json`, whose keys are named below in
backticks), with no kernels, no sorting, no loops over blocks of rows, and
every matrix product at `precision=HIGHEST`. It imports nothing of `dcr_tpu`
(its norm, gated FFN, latent attention and `Ops` are the other plain
reference's, `reference/longcat_flash.py`); of the program it shares only the
NAMES of the parameters (a nested dict, kernels `[in, out]`), through which
both are handed the same seeded leaves.

With `h` the hidden state and N an RMSNorm with a learned scale
(`rms_norm_eps`), a layer is sandwich-normed (`sandwich_norm` true: a norm
before AND after each sublayer, the second on the sublayer's output before it
joins the residual stream):

    h = h + N_post_attn(MLA(N_in(h)))
    h = h + N_post_mlp(F(N_pre_mlp(h)))

`F` is a dense SwiGLU of width `intermediate_size` in the first
`first_k_dense_replace` layers and the expert layer in the others.

MLA, `num_attention_heads` heads: `c_q = N(W_qa x)` (`q_lora_rank`),
`q = W_qb c_q`, a head `qk_nope_head_dim + qk_rope_head_dim` wide;
`[c_kv | k_r] = W_kva x` (`kv_lora_rank` + rope), `c_kv = N(c_kv)`,
`[k_nope | v] = W_kvb c_kv`, a head `qk_nope_head_dim + v_head_dim` wide;
rotary (`rope_theta`) on each head's q_rope and on the one k_r all heads
share; `softmax(q k^T / sqrt(nope + rope))`, causal; `W_o`; no bias
(`attention_bias` false) and no scaling of the latents.

Expert layer: `s = sigmoid(W_r x)` over `n_routed_experts`; `I` the
`num_experts_per_tok` largest of `s`; `w_i = routed_scaling_factor * s_i /
(sum_{j in I} s_j + 1e-20)` (`norm_topk_prob` true);
`y = sum_{i in I} w_i E_i(x) + E_shared(x)`, every `E` a SwiGLU (`hidden_act`
silu) of width `moe_intermediate_size`, `n_shared_experts` of them shared.

Departures from the published description, each because the system under
test is defined so:
- the model is a text TOWER: there is no language-model head, and the
  `num_nextn_predict_layers` multi-token-prediction layer is left out: both
  predict tokens FROM the final states, and the tower's consumer (the UNet's
  cross-attention) reads the states; the final RMSNorm's states are projected
  by `ctx_proj` (no bias) to the UNet's cross-attention width;
- this device's share: the router keeps every output and its top k, but only
  the routed experts `[held_first, held_first + held_count)` are computed;
  what experts held elsewhere would add is left out, the shared expert is
  computed whole, and that partial result goes on to the next layer; the
  vocabulary is a slice (ids come from it);
- what the published `config.json` does not state is listed under `assumed`
  in the configuration's file: the sigmoid and the `1e-20`, SwiGLU as the
  form of every FFN, the interleaved rotary layout, where the two post-norms
  sit, no group limit and no correction bias in the router, the final norm.

Routing is a discontinuity: `forward(..., follow=...)` lets the caller hand
in another implementation's choice of experts, which is taken for a token
only where THIS reference's own margin between its k-th and (k+1)-th score is
under `tie_eps` (relative to the k-th) and the other choice is itself a top k
of these scores to within `tie_eps` (`route`); the margins, and how far
outside these scores' top k the other choice lies, are returned as numbers a
token.

`Ops(quant="fp8")` is the control's switch, as in `reference/sd21.py`: the
operands of every matrix product are rounded to e4m3.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the two references share their primitives (float32 `jax.numpy`, HIGHEST):
# the products' switch, RMSNorm, the gated FFN, and the latent attention,
# which is LongCat's with its two latent scaling factors off
from benchmark.reference.longcat_flash import (EXACT, HI, Ops,  # noqa: F401
                                               rms_norm, swiglu)
from benchmark.reference.longcat_flash import mla as _mla


def mla(ops, p, c: dict, x):
    """Multi-head latent attention, causal, as the header writes it down.
    x [B, S, hidden]."""
    return _mla(ops, p, {**c, "mla_scale_q_lora": False,
                         "mla_scale_kv_lora": False}, x)


def route(p, c: dict, x, *, follow=None, tie_eps: float = 0.0):
    """The router, on tokens x [T, hidden] -> dict: `scores` s = sigmoid(W_r
    x) [T, experts] (never quantised: the router is float32 in every
    precision the configuration states), `chosen` [T, k] the top k of s,
    `weights` [T, k] = routed_scaling_factor * s[chosen] / (their sum +
    1e-20), `margin` [T] (the k-th score less the (k+1)-th, over the k-th)
    and `near_tie` [T] (`margin` under `tie_eps`). With `follow` [T, k],
    another implementation's choice: `slack` [T], the best score it left out
    less the worst it took, over the k-th (never above nought for this
    router's own choice; the margin of the exchanged pair for a choice that
    differs), `outside` [T] (`slack` at `tie_eps` or above: no scores within
    `tie_eps` of these have the other choice as their top k), and the choice
    is taken for a token where this router is at a near tie and the other
    choice is not outside."""
    k = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"], precision=HI))
    top, chosen = jax.lax.top_k(scores, k + 1)
    kth = top[:, k - 1]
    margin = (top[:, k - 1] - top[:, k]) / kth
    near_tie = margin < tie_eps
    chosen = chosen[:, :k]
    out = {"scores": scores, "margin": margin, "near_tie": near_tie}
    if follow is not None:
        taken = jnp.any(follow[:, :, None] == jnp.arange(scores.shape[1]), axis=1)
        worst_in = jnp.min(jnp.take_along_axis(scores, follow, axis=1), axis=1)
        best_out = jnp.max(jnp.where(taken, -jnp.inf, scores), axis=1)
        out["slack"] = (best_out - worst_in) / kth
        out["outside"] = out["slack"] >= tie_eps
        chosen = jnp.where((near_tie & ~out["outside"])[:, None], follow, chosen)
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if c["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-20)
    return {**out, "chosen": chosen,
            "weights": weights * c["routed_scaling_factor"]}


def moe_parts(ops, p, c: dict, x, routing: dict):
    """(routed part of the experts held here, the shared expert's part), each
    [T, hidden]. Every held expert is run on every token and weighed by what
    the router gave it there (nought where it was not chosen); the shared
    expert is run on every token with weight one."""
    chosen, weights = routing["chosen"], routing["weights"]
    held = jnp.zeros_like(x)
    for e in range(c["held_experts_first"],
                   c["held_experts_first"] + c["held_experts_count"]):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=1)
        held = held + w_e[:, None] * swiglu(ops, {
            name: {"kernel": p[f"expert_{e}"][name]}
            for name in ("gate_proj", "up_proj", "down_proj")}, x)
    return held, swiglu(ops, p["shared_experts"], x)


def layer(ops, p, c: dict, h, dense: bool, *, follow=None, tie_eps: float = 0.0):
    """One sandwich-normed layer -> (h, routing of its expert layer, or None
    for a leading dense layer)."""
    eps = c["rms_norm_eps"]
    attn = mla(ops, p["self_attn"], c, rms_norm(p["input_layernorm"], h, eps))
    h = h + rms_norm(p["post_attention_layernorm"], attn, eps)
    n = rms_norm(p["pre_mlp_layernorm"], h, eps)
    routing = None
    if dense:
        y = swiglu(ops, p["mlp"], n)
    else:
        x = n.reshape(-1, n.shape[-1])
        routing = route(p["moe"], c, x, follow=follow, tie_eps=tie_eps)
        held, shared = moe_parts(ops, p["moe"], c, x, routing)
        y = (held + shared).reshape(n.shape)
    return h + rms_norm(p["post_mlp_layernorm"], y, eps), routing


def forward(c: dict, ids, part, *, ops: Ops = EXACT, follow=None,
            tie_eps: float = 0.0) -> dict:
    """ids [B, L] -> {'ctx': [B, L, out], 'routing': [per EXPERT layer, in
    order: scores, chosen, margin, near_tie, and slack, outside where `follow`
    is given]}. `part(name)` gives the float32 leaves of 'embed',
    'layers_<i>', 'norm' or 'ctx_proj' when asked and may make them anew each
    time: one layer's leaves are alive at a time, so that published widths
    fit a chip. `follow`: per expert layer, another implementation's [T, k]
    choice (see the module's text)."""
    embed = jax.jit(lambda p, i: p["embedding"][i])
    h = embed(part("embed"), jnp.asarray(ids, jnp.int32))
    dense_layer = jax.jit(lambda p, h: layer(ops, p, c, h, True)[0])
    expert_layer = jax.jit(lambda p, h, f: layer(ops, p, c, h, False, follow=f,
                                                 tie_eps=tie_eps))
    routings = []
    for i in range(c["num_hidden_layers"]):
        leaves = part(f"layers_{i}")
        if i < c["first_k_dense_replace"]:
            h = dense_layer(leaves, h)
        else:
            h, routing = expert_layer(
                leaves, h, None if follow is None else follow[len(routings)])
            routings.append({name: value for name, value in routing.items()
                             if name != "weights"})
        del leaves
    head = jax.jit(lambda norm, proj, h: ops.dot(
        rms_norm(norm, h, c["rms_norm_eps"]), proj["kernel"]))
    return {"ctx": head(part("norm"), part("ctx_proj"), h), "routing": routings}
