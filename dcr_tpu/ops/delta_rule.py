"""The gated delta rule with a channel-wise decay, computed in chunks.

Per head, with the state S [Dk, Dv] and S_0 = 0, a position t updates and
reads it as

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(alpha_t in (0, 1] a decay per key channel, beta_t a scalar). Run position by
position this reads and writes the whole state every step; the chunked form
below is the same mathematics with no term dropped, arranged so that the
state is read and written once a chunk of `CHUNK` positions and the rest is
products within a chunk (the WY representation with the UT transform, as in
the chunked gated delta rule of arXiv:2412.06464 and Kimi Linear's KDA,
arXiv:2510.26692).

Inside a chunk, with g_r the cumulative log decay from the chunk's start to
position r (inclusive) and S_0 the state the chunk starts from, the state
update can be written S_r = Diag(e^{g_r}) S_0 + sum_{i <= r}
Diag(e^{g_r - g_i}) k_i u_i^T, where

    u_r = beta_r (v_r - S_0^T (e^{g_r} k_r)) - beta_r sum_{i < r} A_ri u_i,
    A_ri = sum_c k_rc k_ic e^{g_rc - g_ic},

so U = (I + Diag(beta) A)^{-1} Diag(beta) (V - K~ S_0): the UT transform,
applied to [V | K~] once for every chunk in parallel, leaves U = U~ - W S_0.
Then o_r = S_0^T (e^{g_r} q_r) + sum_{i <= r} P_ri u_i with P_ri = sum_c
q_rc k_ic e^{g_rc - g_ic}, and the chunk hands on S_C = Diag(e^{g_C}) S_0 +
sum_i Diag(e^{g_C - g_i}) k_i u_i^T. Only that last recurrence runs chunk
after chunk (`lax.scan`).

EVERY exponent formed here is at most 0, however strong the decay: a pair
(r, i) in different sub-chunks of `SUB` positions factors its decay through
the position m before r's sub-chunk (i <= m < r: e^{g_r - g_m} e^{g_m - g_i},
one factor on each operand of a product); a pair in the same sub-chunk takes
its own decay elementwise, masked before the exponential, inside the
reduction that reads it. No e^{-g} is formed, which over a chunk of strong
decay would overflow float32.

The state, the cumulative log decays and the UT transform are float32; the
products take operands in q's dtype with float32 accumulation.

`chunked_delta_rule` is the one entry point and a dispatcher, as
`ops/attention.dot_product_attention` is: on the TPU, where Dk and Dv are
multiples of 128 and the operands are ones the kernel takes, the Pallas
kernel of `ops/delta_rule_kernel.py` runs the same mathematics with each
head's state in VMEM across its chunks ("pallas"); everywhere else the XLA
form below runs as it is ("xla"). The choice reads shapes, dtypes and the
platform only (`path_for`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dcr_tpu.core import tracing
from dcr_tpu.ops import attention, delta_rule_kernel as dk

#: positions a chunk: the state is read and written once a chunk
CHUNK = 64
#: positions a sub-chunk: pairs inside one take their decay elementwise
SUB = 16

HIGHEST = jax.lax.Precision.HIGHEST


def _decay(x: jax.Array) -> jax.Array:
    """e^x for an exponent that is at most 0 by construction; the clamp
    keeps rounding in the cumulative sums from making it a hair above."""
    return jnp.exp(jnp.minimum(x, 0.0))


def _unit_lower_inverse(l: jax.Array) -> jax.Array:
    """(I + l)^-1 for l [..., s, s] strictly lower, by forward substitution
    in float32, elementwise (no product's precision enters)."""
    s = l.shape[-1]
    eye = jnp.eye(s, dtype=l.dtype)
    t = jnp.broadcast_to(eye, l.shape)
    for i in range(1, s):
        # l[i, j] is 0 for j >= i, so the rows of t not yet solved add nothing
        row = eye[i] - jnp.sum(l[..., i, :, None] * t, axis=-2)
        t = t.at[..., i, :].set(row)
    return t


def _xla_form(q: jax.Array, k: jax.Array, v: jax.Array, log_alpha: jax.Array,
              beta: jax.Array, chunk: int, sub: int) -> jax.Array:
    """The chunked scan in jnp and `lax.scan`, `chunk` and `sub` as given."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-t // chunk)
    f32, mm = jnp.float32, q.dtype
    pad = n * chunk - t

    def chunks(x):          # [B, T, H, ...] -> [B, N, H, C, ...]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 2, 3)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    g = jnp.cumsum(chunks(log_alpha.astype(f32)), axis=-2)       # [B,N,H,C,Dk]
    bc = chunks(beta.astype(f32))                                 # [B,N,H,C]
    k32 = kc.astype(f32)
    j = chunk // sub

    # pairs inside a sub-chunk, every sub-chunk at once: A's rows (strictly
    # below the diagonal) and P's (on and below it), each pair's own decay
    def blocks(x):
        return x.reshape(*x.shape[:-2], j, sub, x.shape[-1])

    gs, ks = blocks(g), blocks(k32)
    pos = jnp.arange(sub)

    def within(x, keep):
        """sum_c x_rc k_ic e^{g_rc - g_ic} for the pairs `keep` [r, i]
        admits, nought elsewhere: one reduction, the exponential inside it
        (each reduction forms its own, so none is written out whole)."""
        decay = _decay(jnp.where(keep[:, :, None], gs[..., :, None, :] - gs[..., None, :, :],
                                 -jnp.inf))
        return jnp.sum(x[..., :, None, :] * ks[..., None, :, :] * decay, axis=-1)

    a_in = within(ks, pos[:, None] > pos[None, :])               # [..,J,s,s]
    p_in = within(blocks(qc.astype(f32)), pos[:, None] >= pos[None, :])
    beta_s = blocks(bc[..., None])[..., 0]                       # [..,J,s]
    t_in = _unit_lower_inverse(beta_s[..., None] * a_in)         # [..,J,s,s]

    # the right-hand side of the UT transform: beta [V | e^g K]
    rhs = bc[..., None] * jnp.concatenate(
        [vc.astype(f32), k32 * _decay(g)], axis=-1)              # [..,C,Dv+Dk]
    solved, p_rows = [], []
    for a in range(j):
        lo, hi = a * sub, (a + 1) * sub
        r = rhs[..., lo:hi, :]
        p_row = [p_in[..., a, :, :]]
        if a:
            # pairs in earlier sub-chunks, through m = lo - 1: both factors'
            # exponents are at most 0
            gm = g[..., lo - 1:lo, :]
            left = jnp.stack([kc[..., lo:hi, :], qc[..., lo:hi, :]], axis=-3
                             ).astype(f32) * _decay(g[..., lo:hi, :] - gm)[..., None, :, :]
            right = k32[..., :lo, :] * _decay(gm - g[..., :lo, :])
            off = jnp.einsum("...xrc,...ic->...xri", left.astype(mm),
                             right.astype(mm), preferred_element_type=f32)
            before = jnp.concatenate(solved, axis=-2)            # [..,lo,Dv+Dk]
            r = r - jnp.einsum("...ri,...ic->...rc",
                               bc[..., lo:hi, None] * off[..., 0, :, :], before,
                               precision=HIGHEST)
            p_row.insert(0, off[..., 1, :, :])
        solved.append(jnp.einsum("...ri,...ic->...rc", t_in[..., a, :, :], r,
                                 precision=HIGHEST))
        if hi < chunk:
            p_row.append(jnp.zeros((*r.shape[:-2], sub, chunk - hi), f32))
        p_rows.append(jnp.concatenate(p_row, axis=-1))
    x = jnp.concatenate(solved, axis=-2)
    u_free, w = x[..., :dv], x[..., dv:]                         # U = U~ - W S_0
    p = jnp.concatenate(p_rows, axis=-2)                         # [..,C,C]
    g_end = g[..., -1, :]                                        # [B,N,H,Dk]
    q_dec = qc.astype(f32) * _decay(g)
    k_dec = k32 * _decay(g_end[..., None, :] - g)

    def step(s, xs):
        w, u_free, q_dec, k_dec, p, keep = xs
        s_mm = s.astype(mm)
        u = u_free - jnp.einsum("bhck,bhkv->bhcv", w, s_mm,
                                preferred_element_type=f32)
        u_mm = u.astype(mm)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_dec, s_mm, preferred_element_type=f32)
             + jnp.einsum("bhci,bhiv->bhcv", p, u_mm, preferred_element_type=f32))
        s = keep[..., None] * s + jnp.einsum("bhck,bhcv->bhkv", k_dec, u_mm,
                                             preferred_element_type=f32)
        return s, o

    along = lambda x: jnp.moveaxis(x, 1, 0)                      # noqa: E731
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), (
        along(w.astype(mm)), along(u_free), along(q_dec.astype(mm)),
        along(k_dec.astype(mm)), along(p.astype(mm)), along(_decay(g_end))))
    # [N, B, H, C, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, n * chunk, h, dv)
    return o[:, :t]


def path_for(q, k, v, log_alpha, beta, *, mesh: Optional[Mesh] = None) -> str:
    """"pallas" or "xla": which implementation a site of these operands takes.
    Reads shapes, dtypes and the platform only, so `jax.ShapeDtypeStruct`s
    will do. Over a mesh the kernel is asked about ONE device's rows and
    heads; rows or heads the mesh does not divide cannot be sharded for it,
    so XLA."""
    if not attention._on_tpu() or q.ndim != 4:
        return "xla"
    rows, heads, _ = attention._shards(mesh)
    b, t, h, _ = q.shape
    if b % rows or h % heads:
        return "xla"

    def local(x):
        return jax.ShapeDtypeStruct((x.shape[0] // rows, x.shape[1], x.shape[2] // heads)
                                    + tuple(x.shape[3:]), x.dtype)

    return "pallas" if dk.supported(*map(local, (q, k, v, log_alpha, beta))) else "xla"


@jax.custom_vjp
def _pallas_form(q, k, v, log_alpha, beta):
    """The kernel forward; the XLA form's VJP backward (no cell differentiates
    the scan, and a frozen tower never does)."""
    return dk.delta_rule_fwd(q, k, v, log_alpha, beta)


def _pallas_fwd(q, k, v, log_alpha, beta):
    return _pallas_form(q, k, v, log_alpha, beta), (q, k, v, log_alpha, beta)


def _pallas_bwd(residuals, g):
    _, vjp = jax.vjp(lambda *x: _xla_form(*x, CHUNK, SUB), *residuals)
    return vjp(g)


_pallas_form.defvjp(_pallas_fwd, _pallas_bwd)


def chunked_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                       log_alpha: jax.Array, beta: jax.Array, *,
                       mesh: Optional[Mesh] = None) -> jax.Array:
    """q, k [B, T, H, Dk]; v [B, T, H, Dv]; log_alpha [B, T, H, Dk] (<= 0,
    the log of each key channel's decay); beta [B, T, H]. -> o [B, T, H, Dv]
    float32. T need not be a multiple of the chunk: the last chunk is padded
    with positions that neither decay nor write the state. On the XLA path
    `CHUNK` and `SUB` are read when called (`SUB` divides `CHUNK`); the
    kernel has its own (`delta_rule_kernel.CHUNK`, `.SUB`).

    Counts itself once a trace: `delta_rule/sites_total` and
    `delta_rule/sites_total/<path>`, the chunks of the path taken in
    `delta_rule/chunks_total`, its chunk length in the gauge
    `delta_rule/chunk`.

    `mesh` is the mesh the enclosing jit spans (pmesh axes), or None on one
    device or inside a shard_map. A Mosaic kernel is never partitioned
    automatically, so over more than one device the kernel runs under
    shard_map, each device on its own rows and heads."""
    path = path_for(q, k, v, log_alpha, beta, mesh=mesh)
    chunk = dk.CHUNK if path == "pallas" else CHUNK
    reg = tracing.registry()
    reg.counter("delta_rule/sites_total").inc()
    reg.counter(f"delta_rule/sites_total/{path}").inc()
    reg.counter("delta_rule/chunks_total").inc(-(-q.shape[1] // chunk))
    reg.gauge("delta_rule/chunk").set(chunk)
    if path == "xla":
        return _xla_form(q, k, v, log_alpha, beta, CHUNK, SUB)
    _, _, spec = attention._shards(mesh)
    if spec is None:
        return _pallas_form(q, k, v, log_alpha, beta)
    return jax.shard_map(_pallas_form, mesh=mesh, in_specs=(spec,) * 4 + (P(*spec[:3]),),
                         out_specs=spec, check_vma=False)(q, k, v, log_alpha, beta)
