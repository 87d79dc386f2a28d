"""What the language-model text towers share (models/longcat_flash.py,
models/openpangu_ultra_moe.py, models/solar_open2.py): RMSNorm, rotary,
multi-head latent attention, SwiGLU, and the expert layer of a chip that holds
a share of a layer's routed experts, with the one router rule and module of
the towers that route by sigmoid and add a shared expert.

The expert layer is ONE function, :func:`expert_layer`: the tower hands in how
its router turns logits into chosen experts and weights; the layer is told
which contiguous range of the routed experts THIS device holds, routes over
every output, computes those experts' part for the tokens routed to them and
drops no assignment. A tower's optional terms join at the combine: the
zero-compute experts' part (outputs from `zero_from` on return their input)
and a shared expert every token passes. What experts held elsewhere would add
is left out; nothing here stands in for the other chips or their exchange.

Parameters are held in `param_dtype`; the router, the norms and the softmaxes
compute in float32.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dcr_tpu.ops.attention import dot_product_attention

#: keeps the renormalisation of the chosen weights off a division by nought
NORM_TOPK_EPS = 1e-20

#: rows of one expert computed at a time. An expert's tokens are walked in
#: blocks of this many with a trip count read from the routing, so no
#: assignment is ever dropped and no capacity is reserved for a worst case.
EXPERT_ROW_BLOCK = 128


class TextTowerOutput(NamedTuple):
    last_hidden_state: jax.Array        # [B, L, cross_attention_dim] float32
    #: routing counts of this call, summed over the expert layers (int32
    #: scalars): assignments, held (computed here), zero (zero-compute
    #: experts), dropped (held assignments that were not computed: always 0),
    #: held_load_max (the most any one held expert got in one layer) and,
    #: from a tower that asks for it, unheld (tokens none of whose chosen
    #: experts is held here); {} from a stack without an expert layer
    moe_stats: dict


def merge_stats(total: dict | None, stats: dict | None) -> dict | None:
    """A layer's routing counts added to the tower's (a layer without experts
    gives None and counts nothing)."""
    if stats is None:
        return total
    if total is None:
        return stats
    return {name: (jnp.maximum if name == "held_load_max" else jnp.add)(
        total[name], value) for name, value in stats.items()}


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
    one rotary key is shared by all heads. `cfg` is a tower's config block
    under the published keys; `mla_scale_q_lora` / `mla_scale_kv_lora`, where
    a block has them and they are true, multiply the normed latents by
    sqrt(hidden / rank). `mesh` is the mesh the enclosing jit spans, handed
    on to the dispatcher, which sizes its XLA path's row groups from ONE
    device's share of the batch."""

    cfg: object
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    mesh: Optional[jax.sharding.Mesh] = None

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
        if getattr(c, "mla_scale_q_lora", False):
            cq = cq * jnp.asarray((hidden / c.q_lora_rank) ** 0.5, cq.dtype)
        q = dense(heads * (nope + rope), "q_b_proj")(cq).reshape(
            b, s, heads, nope + rope)
        kv = dense(c.kv_lora_rank + rope, "kv_a_proj_with_mqa")(x)
        ckv, k_rope = kv[..., :c.kv_lora_rank], kv[..., c.kv_lora_rank:]
        ckv = norm("kv_a_norm")(ckv)
        if getattr(c, "mla_scale_kv_lora", False):
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
        out = dot_product_attention(q, k, v, mask=mask, mesh=self.mesh)
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


def causal_mask(s: int) -> jax.Array:
    return jnp.tril(jnp.ones((s, s), bool))[None, None]


def expert_layer(module: nn.Module, n: jax.Array, *, router_outputs: int,
                 route: Callable, held: tuple[int, int], expert_width: int,
                 zero_from: int | None = None, shared_width: int = 0,
                 count_unheld: bool = False) -> tuple[jax.Array, dict]:
    """The expert layer of the tower module `module` (called inside its
    compact `__call__`; its `dtype` and `param_dtype` are the layer's, and the
    leaves it makes are the module's own: `router`, `expert_<i>` for the held
    range, `shared_experts` where `shared_width` is set).

    `route(logits [T, router_outputs] f32) -> (scores [T, outputs], chosen
    [T, k], weights [T, k])` is the tower's router rule; it may make leaves of
    its own on `module`. `held` is (first, count) of the routed experts held
    here; outputs from `zero_from` on are zero-compute experts that return
    their input; `count_unheld` adds `unheld` to the counts (one more
    reduction in the program: LongCat's, whose every token gets its
    zero-compute part, is left as the benchmark has measured it). -> (the
    layer's output, shaped like `n`; its routing counts)."""
    first, count = held
    dtype, param_dtype = module.dtype, module.param_dtype
    d = n.shape[-1]
    x = n.reshape(-1, d)
    tokens = x.shape[0]
    f32 = jnp.float32

    with jax.named_scope("router"):
        logits = nn.Dense(
            router_outputs, use_bias=False, dtype=f32, param_dtype=param_dtype,
            precision=jax.lax.Precision.HIGHEST, name="router")(x.astype(f32))
        scores, idx, weight = route(logits)                     # [T, k] each
        k = idx.shape[1]
        # kept only by a caller that asks for the "routing" collection
        # (a comparison with a reference); nothing otherwise
        module.sow("routing", "scores", scores)
        module.sow("routing", "chosen", idx)

    zero_part = is_zero = None
    if zero_from is not None:
        with jax.named_scope("zero"):
            is_zero = idx >= zero_from
            zero_part = jnp.sum(jnp.where(is_zero, weight, 0.0), axis=1,
                                keepdims=True) * x.astype(f32)

    with jax.named_scope("dispatch"):
        local = idx - first
        here = (local >= 0) & (local < count)
        # assignments sorted by held expert; those held elsewhere last
        key = jnp.where(here, local, count).reshape(-1)
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
            gate, up, down = (w.astype(dtype) for w in ExpertKernels(
                expert_width, param_dtype, name=f"expert_{first + e}")(d))

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

    shared_part = None
    if shared_width:
        with jax.named_scope("shared"):
            shared_part = SwiGLU(shared_width, dtype, param_dtype,
                                 name="shared_experts")(x).astype(f32)

    with jax.named_scope("combine"):
        out = held_part
        for part in (zero_part, shared_part):
            if part is not None:
                out = out + part
        out = out.astype(dtype).reshape(n.shape)
    held_total = jnp.sum(load)
    stats = {"assignments": jnp.asarray(tokens * k, jnp.int32),
             "held": held_total,
             "zero": (jnp.zeros((), jnp.int32) if is_zero is None
                      else jnp.sum(is_zero, dtype=jnp.int32)),
             "dropped": held_total - computed,
             "held_load_max": (jnp.max(load) if count
                               else jnp.zeros((), jnp.int32))}
    if count_unheld:
        stats["unheld"] = jnp.sum(~jnp.any(here, axis=1), dtype=jnp.int32)
    return out, stats


def sigmoid_route(logits: jax.Array, k: int, renormalise: bool,
                  scale: float) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The router rule of the towers with a shared expert: scores by a
    sigmoid over every output, the top k chosen, their weights renormalised
    to sum to one where `renormalise` and multiplied by `scale`. -> (scores,
    chosen, weights), as `expert_layer`'s `route` returns them."""
    s = jax.nn.sigmoid(logits)
    top, idx = jax.lax.top_k(s, k)
    if renormalise:
        top = top / (jnp.sum(top, axis=1, keepdims=True) + NORM_TOPK_EPS)
    return s, idx, top * scale


class SharedExpertMoE(nn.Module):
    """The expert layer of one layer routed by `sigmoid_route`, for the
    routed experts held here, and the shared expert. `cfg` is a tower's block
    with `n_routed_experts`, `num_experts_per_tok`, `norm_topk_prob`,
    `routed_scaling_factor`, `moe_intermediate_size`, `n_shared_experts` and
    `held_range()` (openPangu's and Solar Open 2's)."""

    cfg: object
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, n: jax.Array) -> tuple[jax.Array, dict]:
        c = self.cfg
        return expert_layer(
            self, n, router_outputs=c.n_routed_experts,
            route=lambda logits: sigmoid_route(
                logits, c.num_experts_per_tok, c.norm_topk_prob,
                c.routed_scaling_factor),
            held=c.held_range(), expert_width=c.moe_intermediate_size,
            shared_width=c.moe_intermediate_size * c.n_shared_experts,
            count_unheld=True)
