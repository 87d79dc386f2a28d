"""Plain float32 `jax.numpy` reference of LongCat-Flash as a text tower.

Written from the published description of the model (meituan-longcat/
LongCat-Flash-Chat: `config.json` and the model card's account of the
shortcut-connected mixture of experts with zero-computation experts and
multi-head latent attention), with no kernels, no sorting, no loops over
blocks of rows, and every matrix product at `precision=HIGHEST`. It imports
nothing of `dcr_tpu`; of the program it shares only the NAMES of the
parameters (a nested dict, kernels `[in, out]`), through which both are
handed the same seeded leaves.

With `h` the hidden state, a (double) layer is

    for i in (0, 1):
        h = h + MLA_i(RMSNorm(h))
        n = RMSNorm(h)
        if i == 0: s = MoE(n)
        h = h + W_down_i(silu(W_gate_i n) * W_up_i n)
    h = h + s

Departures from the published description, each because the system under
test is defined so:
- the model is a text TOWER: there is no language-model head; the final
  RMSNorm's states are projected by `ctx_proj` (no bias) to the UNet's
  cross-attention width;
- this device's share: the router keeps every output and its top k, but only
  the routed experts `[held_first, held_first + held_count)` are computed;
  what experts held elsewhere would add is left out, and that partial result
  goes on to the next layer; the vocabulary is a slice (ids come from it);
- what the published `config.json` does not state is listed under `assumed`
  in the configuration's file: SiLU gates, no renormalisation of the chosen
  weights, the score-correction bias used for the choice only, the
  interleaved rotary layout, `sqrt(hidden / rank)` as the form of the two
  latent scales, the final norm.

Routing is a discontinuity: `forward(..., follow=...)` lets the caller hand
in another implementation's choice of experts, which is taken for a token
only where THIS reference's own margin between its k-th and (k+1)-th
corrected score is under `tie_eps` (relative to the k-th) and the other
choice is itself a top k of these scores to within `tie_eps` (`route`); the
margins, and how far outside these scores' top k the other choice lies, are
returned as numbers a token.

`Ops(quant="fp8")` is the control's switch, as in `reference/sd21.py`: the
operands of every matrix product are rounded to e4m3.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _fake_quant(x, dtype, top: float):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@dataclass(frozen=True)
class Ops:
    """How products are computed: `quant` None (float32, HIGHEST) or 'fp8'
    (operands rounded to e4m3, one scale a tensor, float32 accumulation)."""
    quant: str | None = None

    def q(self, x):
        if self.quant is None:
            return x
        if self.quant != "fp8":
            raise ValueError(self.quant)
        return _fake_quant(x, jnp.float8_e4m3fn, 448.0)

    def dot(self, x, w):
        return jnp.matmul(self.q(x), self.q(w), precision=HI)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HI)


EXACT = Ops()


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def rms_norm(p, x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"]


def swiglu(ops, p, x):
    gate = ops.dot(x, p["gate_proj"]["kernel"])
    up = ops.dot(x, p["up_proj"]["kernel"])
    return ops.dot(jax.nn.silu(gate) * up, p["down_proj"]["kernel"])


def rotary(x, theta: float):
    """[B, S, H, D], interleaved pairs: (x[2i], x[2i+1]) turned by
    pos * theta^(-2i/D)."""
    d = x.shape[-1]
    out = []
    for i in range(d // 2):
        ang = jnp.arange(x.shape[1], dtype=jnp.float32) * (theta ** (-2.0 * i / d))
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        out += [a * cos - b * sin, a * sin + b * cos]
    return jnp.stack(out, axis=-1)


def mla(ops, p, c: dict, x):
    """Multi-head latent attention, causal. x [B, S, hidden]."""
    b, s, hidden = x.shape
    heads, nope, rope, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                             c["qk_rope_head_dim"], c["v_head_dim"])
    eps = c["rms_norm_eps"]
    cq = rms_norm(p["q_a_norm"], ops.dot(x, p["q_a_proj"]["kernel"]), eps)
    if c["mla_scale_q_lora"]:
        cq = cq * (hidden / c["q_lora_rank"]) ** 0.5
    q = ops.dot(cq, p["q_b_proj"]["kernel"]).reshape(b, s, heads, nope + rope)
    kv = ops.dot(x, p["kv_a_proj_with_mqa"]["kernel"])
    ckv = rms_norm(p["kv_a_norm"], kv[..., :c["kv_lora_rank"]], eps)
    if c["mla_scale_kv_lora"]:
        ckv = ckv * (hidden / c["kv_lora_rank"]) ** 0.5
    k_rope = rotary(kv[..., c["kv_lora_rank"]:][:, :, None, :], c["rope_theta"])
    kvb = ops.dot(ckv, p["kv_b_proj"]["kernel"]).reshape(b, s, heads, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], c["rope_theta"])
    logits = (ops.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + ops.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0, :]))
    logits = logits / (nope + rope) ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    weights = jax.nn.softmax(jnp.where(causal[None, None], logits, -jnp.inf), axis=-1)
    out = ops.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, heads * vd)
    return ops.dot(out, p["o_proj"]["kernel"])


def route(p, c: dict, x, *, follow=None, tie_eps: float = 0.0):
    """The router, on tokens x [T, hidden] -> dict: `scores` p = softmax(W_r x)
    [T, outputs] (never quantised: the router is float32 in every precision
    the configuration states), `chosen` [T, k] the top k of p + bias,
    `weights` [T, k] = p[chosen] * routed_scaling_factor, `margin` [T] (the
    k-th corrected score less the (k+1)-th, over the k-th) and `near_tie` [T]
    (`margin` under `tie_eps`). With `follow` [T, k], another
    implementation's choice: `slack` [T], the best corrected score it left
    out less the worst it took, over the k-th (never above nought for this
    router's own choice; the margin of the exchanged pair for a choice that
    differs), `outside` [T] (`slack` at `tie_eps` or above: no scores within
    `tie_eps` of these have the other choice as their top k), and the choice
    is taken for a token where this router is at a near tie and the other
    choice is not outside."""
    k = c["moe_topk"]
    scores = jax.nn.softmax(jnp.matmul(x, p["router"]["kernel"], precision=HI), axis=-1)
    corrected = scores + p["e_score_correction_bias"]
    top, chosen = jax.lax.top_k(corrected, k + 1)
    kth = jnp.abs(top[:, k - 1])
    margin = (top[:, k - 1] - top[:, k]) / kth
    near_tie = margin < tie_eps
    chosen = chosen[:, :k]
    out = {"scores": scores, "margin": margin, "near_tie": near_tie}
    if follow is not None:
        taken = jnp.any(follow[:, :, None] == jnp.arange(scores.shape[1]), axis=1)
        worst_in = jnp.min(jnp.take_along_axis(corrected, follow, axis=1), axis=1)
        best_out = jnp.max(jnp.where(taken, -jnp.inf, corrected), axis=1)
        out["slack"] = (best_out - worst_in) / kth
        out["outside"] = out["slack"] >= tie_eps
        chosen = jnp.where((near_tie & ~out["outside"])[:, None], follow, chosen)
    weights = jnp.take_along_axis(scores, chosen, axis=1) * c["routed_scaling_factor"]
    return {**out, "chosen": chosen, "weights": weights}


def moe_parts(ops, p, c: dict, x, routing: dict):
    """(routed part of the experts held here, zero-compute part), each
    [T, hidden]. Every held expert is run on every token and weighed by what
    the router gave it there (nought where it was not chosen)."""
    routed = c["n_routed_experts_total"]
    chosen, weights = routing["chosen"], routing["weights"]
    held = jnp.zeros_like(x)
    for e in range(c["held_experts_first"],
                   c["held_experts_first"] + c["held_experts_count"]):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=1)
        held = held + w_e[:, None] * swiglu(ops, {
            name: {"kernel": p[f"expert_{e}"][name]}
            for name in ("gate_proj", "up_proj", "down_proj")}, x)
    # zero_expert_type identity: the expert returns its input
    w_zero = jnp.sum(jnp.where(chosen >= routed, weights, 0.0), axis=1)
    return held, w_zero[:, None] * x


def double_layer(ops, p, c: dict, h, *, follow=None, tie_eps: float = 0.0):
    """-> (h, routing of this layer's one expert layer)."""
    eps = c["rms_norm_eps"]
    shortcut = routing = None
    for i in (0, 1):
        h = h + mla(ops, p[f"mla_{i}"], c, rms_norm(p[f"input_norm_{i}"], h, eps))
        n = rms_norm(p[f"post_attention_norm_{i}"], h, eps)
        if i == 0:
            x = n.reshape(-1, n.shape[-1])
            routing = route(p["moe"], c, x, follow=follow, tie_eps=tie_eps)
            held, zero = moe_parts(ops, p["moe"], c, x, routing)
            shortcut = (held + zero).reshape(n.shape)
        h = h + swiglu(ops, p[f"ffn_{i}"], n)
    return h + shortcut, routing


def forward(c: dict, ids, part, *, ops: Ops = EXACT, follow=None,
            tie_eps: float = 0.0) -> dict:
    """ids [B, L] -> {'ctx': [B, L, out], 'routing': [per layer: scores,
    chosen, margin, near_tie, and slack, outside where `follow` is given]}. `part(name)` gives the float32 leaves of 'embed',
    'layers_<i>', 'norm' or 'ctx_proj' when asked and may make them anew each
    time: one layer's leaves are alive at a time, so that published widths
    fit a chip. `follow`: per layer, another implementation's [T, k] choice
    (see the module's text)."""
    embed = jax.jit(lambda p, i: p["embedding"][i])
    h = embed(part("embed"), jnp.asarray(ids, jnp.int32))
    layer = jax.jit(lambda p, h, f: double_layer(ops, p, c, h, follow=f,
                                                 tie_eps=tie_eps))
    routings = []
    for i in range(c["num_layers"]):
        leaves = part(f"layers_{i}")
        h, routing = layer(leaves, h, None if follow is None else follow[i])
        del leaves
        routings.append({name: value for name, value in routing.items()
                         if name != "weights"})
    head = jax.jit(lambda norm, proj, h: ops.dot(
        rms_norm(norm, h, c["rms_norm_eps"]), proj["kernel"]))
    return {"ctx": head(part("norm"), part("ctx_proj"), h), "routing": routings}
