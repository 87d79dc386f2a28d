"""Plain float32 `jax.numpy` reference of the Stable Diffusion 2.1 stack.

Written from the published descriptions (Rombach et al. 2022 and the
`stabilityai/stable-diffusion-2-1` `unet/`, `vae/` and `text_encoder/`
config.json; DDPM noising, Ho et al. 2020; DPM-Solver++(2M), Lu et al. 2022;
AdamW, Loshchilov & Hutter 2019), with no kernels, no scan, no batching tricks,
and every matrix product and convolution at `precision=HIGHEST`. It imports
nothing of `dcr_tpu`; of the program it shares only the NAMES of the parameters
(a nested dict, features last, images NHWC), which is the interface through
which both are handed the same seeded weights.

Departures from the published model, each because the system under test is
defined so and the cost is the same:
- the UNet's context is the text tower's last hidden state after the final
  LayerNorm, of a 23-layer tower (SD-2.x: the penultimate layer of OpenCLIP
  ViT-H's 24, which is what a 23-layer tower's last layer is);
- the sampling grid is diffusers' `linspace` grid and the last step goes to
  t = 0 (alpha_bar[0]), not to sigma = 0;
- `upcast_attention` of the published UNet config means float32 attention,
  which is what a float32 reference does anyway.
The GEGLU gate uses the exact (erf) GELU as published; the program uses
flax's default tanh approximation there (`gelu="tanh"` reproduces it, for
the one reading in PERF.md that says how much that is).

`Ops(quant=...)` is the control's switch: it rounds the operands of every
matrix product and convolution to a lower precision (the reference put in the
program's place, computed in the precision a later PR would be tempted by).
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------

def _fake_quant(x, dtype, top: float):
    """Round to `dtype` with one scale per tensor (absmax onto `top`)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@dataclass(frozen=True)
class Ops:
    """How products are computed. `quant`: None (float32, HIGHEST) or 'fp8'
    (e4m3, one scale a tensor): operands rounded, accumulation in float32.
    `gelu`: 'erf' or 'tanh' for the GEGLU gate."""
    quant: str | None = None
    gelu: str = "erf"

    def q(self, x):
        """`x` rounded as an operand. Gradients pass straight through the
        rounding (the products of the backward pass use the rounded operands,
        the cotangents stay float32)."""
        if self.quant is None:
            return x
        if self.quant != "fp8":
            raise ValueError(self.quant)
        r = _fake_quant(x, jnp.float8_e4m3fn, 448.0)
        return x + jax.lax.stop_gradient(r - x)

    def dot(self, x, w):
        return jnp.matmul(self.q(x), self.q(w), precision=HI)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HI)

    def conv(self, x, w, stride=1, padding=((1, 1), (1, 1))):
        return jax.lax.conv_general_dilated(
            self.q(x), self.q(w), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


EXACT = Ops()


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def linear(ops, p, x):
    y = ops.dot(x, p["kernel"])
    return y + p["bias"] if "bias" in p else y


def conv(ops, p, x, stride=1, padding=((1, 1), (1, 1))):
    return ops.conv(x, p["kernel"], stride, padding) + p["bias"]


def group_norm(p, x, groups: int, eps: float):
    p = p["GroupNorm_0"]
    b, h, w, c = x.shape
    g = x.reshape(b, h * w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean((g - mean) ** 2, axis=(1, 3), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(b, h, w, c) * p["scale"] + p["bias"]


def layer_norm(p, x, eps: float = 1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(ops, q, k, v, mask=None):
    """softmax(q k^T / sqrt(d)) v over [B, S, H, D] tensors."""
    logits = ops.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    w = jnp.exp(logits)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ops.einsum("bhqk,bkhd->bqhd", w, v)


def resnet(ops, p, x, temb, groups: int, eps: float):
    h = conv(ops, p["conv1"], silu(group_norm(p["norm1"], x, groups, eps)))
    if temb is not None:
        h = h + linear(ops, p["time_emb_proj"], silu(temb))[:, None, None, :]
    h = conv(ops, p["conv2"], silu(group_norm(p["norm2"], h, groups, eps)))
    if "conv_shortcut" in p:
        x = conv(ops, p["conv_shortcut"], x, padding=((0, 0), (0, 0)))
    return h + x


def cross_attention(ops, p, x, context, heads: int):
    context = x if context is None else context
    b, sq, _ = x.shape
    q = linear(ops, p["to_q"], x).reshape(b, sq, heads, -1)
    k = linear(ops, p["to_k"], context).reshape(b, context.shape[1], heads, -1)
    v = linear(ops, p["to_v"], context).reshape(b, context.shape[1], heads, -1)
    out = attention(ops, q, k, v).reshape(b, sq, -1)
    return linear(ops, p["to_out"], out)


def transformer_block(ops, p, x, context, heads: int):
    x = x + cross_attention(ops, p["attn1"], layer_norm(p["norm1"], x), None, heads)
    x = x + cross_attention(ops, p["attn2"], layer_norm(p["norm2"], x), context, heads)
    h = linear(ops, p["ff"]["proj_in"], layer_norm(p["norm3"], x))
    h, gate = jnp.split(h, 2, axis=-1)
    act = gelu_erf if ops.gelu == "erf" else gelu_tanh
    return x + linear(ops, p["ff"]["proj_out"], h * act(gate))


def transformer_2d(ops, p, x, context, heads: int, groups: int, layers: int):
    b, h, w, c = x.shape
    out = group_norm(p["norm"], x, groups, 1e-6).reshape(b, h * w, c)
    out = linear(ops, p["proj_in"], out)
    for i in range(layers):
        out = transformer_block(ops, p[f"blocks_{i}"], out, context, heads)
    return linear(ops, p["proj_out"], out).reshape(b, h, w, c) + x


def upsample_nearest(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def timestep_embedding(t, dim: int):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# the three models; `cfg` is the configuration's file (published keys)
# ---------------------------------------------------------------------------

def unet(ops, p, cfg: dict, x, t, context):
    """Noise prediction. x [B, h, w, 4], t [B] int, context [B, L, 1024]."""
    u = cfg["unet"]
    widths, per_block = u["block_out_channels"], u["layers_per_block"]
    groups = u["norm_num_groups"]
    head_dim = cfg["derived"]["attention_head_width"]
    layers = cfg["derived"]["transformer_layers_per_block"]
    n = len(widths)

    def tf(name, h, ch):
        return transformer_2d(ops, p[name], h, context, ch // head_dim,
                              groups, layers)

    temb = timestep_embedding(t, widths[0])
    temb = linear(ops, p["time_embedding"]["linear_2"],
                  silu(linear(ops, p["time_embedding"]["linear_1"], temb)))
    h = conv(ops, p["conv_in"], x)
    skips = [h]
    for i, ch in enumerate(widths):
        last = i == n - 1
        for j in range(per_block):
            h = resnet(ops, p[f"down_{i}_res_{j}"], h, temb, groups, 1e-5)
            if not last:
                h = tf(f"down_{i}_attn_{j}", h, ch)
            skips.append(h)
        if not last:
            h = conv(ops, p[f"down_{i}_downsample"]["conv"], h, stride=2)
            skips.append(h)
    h = resnet(ops, p["mid_res_0"], h, temb, groups, 1e-5)
    h = tf("mid_attn", h, widths[-1])
    h = resnet(ops, p["mid_res_1"], h, temb, groups, 1e-5)
    for i, ch in enumerate(reversed(widths)):
        b = n - 1 - i
        for j in range(per_block + 1):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = resnet(ops, p[f"up_{b}_res_{j}"], h, temb, groups, 1e-5)
            if i != 0:
                h = tf(f"up_{b}_attn_{j}", h, ch)
        if b > 0:
            h = conv(ops, p[f"up_{b}_upsample"]["conv"], upsample_nearest(h))
    h = silu(group_norm(p["conv_norm_out"], h, groups, 1e-5))
    return conv(ops, p["conv_out"], h)


def _vae_attention(ops, p, x, groups: int):
    b, h, w, c = x.shape
    out = group_norm(p["group_norm"], x, groups, 1e-6).reshape(b, h * w, 1, c)
    q, k, v = (linear(ops, p[n], out) for n in ("to_q", "to_k", "to_v"))
    out = attention(ops, q, k, v).reshape(b, h * w, c)
    return linear(ops, p["to_out"], out).reshape(b, h, w, c) + x


def vae_encode(ops, p, cfg: dict, pixels):
    """(mean, logvar) of the latent Gaussian. pixels [B, H, W, 3] in [-1, 1]."""
    v = cfg["vae"]
    widths, per_block = v["block_out_channels"], v["layers_per_block"]
    groups = min(v["norm_num_groups"], widths[0])
    p = p["encoder"]
    h = conv(ops, p["conv_in"], pixels)
    for i in range(len(widths)):
        for j in range(per_block):
            h = resnet(ops, p[f"down_{i}_res_{j}"], h, None, groups, 1e-6)
        if i < len(widths) - 1:
            # pad right and bottom only, then a stride-2 valid convolution
            h = conv(ops, p[f"down_{i}_downsample"]["conv"], h, stride=2,
                     padding=((0, 1), (0, 1)))
    h = resnet(ops, p["mid_res_0"], h, None, groups, 1e-6)
    h = _vae_attention(ops, p["mid_attn"], h, groups)
    h = resnet(ops, p["mid_res_1"], h, None, groups, 1e-6)
    h = conv(ops, p["conv_out"], silu(group_norm(p["conv_norm_out"], h, groups, 1e-6)))
    h = conv(ops, p["quant_conv"], h, padding=((0, 0), (0, 0)))
    return jnp.split(h, 2, axis=-1)


def vae_decode(ops, p, cfg: dict, z):
    v = cfg["vae"]
    widths, per_block = v["block_out_channels"], v["layers_per_block"]
    groups = min(v["norm_num_groups"], widths[0])
    p = p["decoder"]
    h = conv(ops, p["post_quant_conv"], z, padding=((0, 0), (0, 0)))
    h = conv(ops, p["conv_in"], h)
    h = resnet(ops, p["mid_res_0"], h, None, groups, 1e-6)
    h = _vae_attention(ops, p["mid_attn"], h, groups)
    h = resnet(ops, p["mid_res_1"], h, None, groups, 1e-6)
    for i in range(len(widths)):
        for j in range(per_block + 1):
            h = resnet(ops, p[f"up_{i}_res_{j}"], h, None, groups, 1e-6)
        if i < len(widths) - 1:
            h = conv(ops, p[f"up_{i}_upsample"]["conv"], upsample_nearest(h))
    h = silu(group_norm(p["conv_norm_out"], h, groups, 1e-6))
    return conv(ops, p["conv_out"], h)


def text_encode(ops, p, cfg: dict, ids):
    """Last hidden state after the final LayerNorm. ids [B, L] int."""
    t = cfg["text_encoder"]
    heads, n_layers = t["num_attention_heads"], t["num_hidden_layers"]
    b, length = ids.shape
    x = p["token_embedding"]["embedding"][ids] + p["position_embedding"][None, :length]
    causal = jnp.tril(jnp.ones((length, length), bool))[None, None]
    for i in range(n_layers):
        lp = p[f"layers_{i}"]
        h = layer_norm(lp["ln1"], x)
        a = lp["attn"]
        q, k, v = (ops.einsum("bld,dhk->blhk", h, a[n]["kernel"]) + a[n]["bias"]
                   for n in ("query", "key", "value"))
        o = attention(ops, q, k, v, mask=causal)
        x = x + ops.einsum("blhk,hkd->bld", o, a["out"]["kernel"]) + a["out"]["bias"]
        h = linear(ops, lp["fc1"], layer_norm(lp["ln2"], x))
        h = gelu_erf(h) if t["hidden_act"] == "gelu" else h * jax.nn.sigmoid(1.702 * h)
        x = x + linear(ops, lp["fc2"], h)
    return layer_norm(p["final_layer_norm"], x)


# ---------------------------------------------------------------------------
# diffusion process
# ---------------------------------------------------------------------------

def alphas_cumprod(cfg: dict) -> np.ndarray:
    s = cfg["scheduler"]
    if s["beta_schedule"] != "scaled_linear":
        raise ValueError(s["beta_schedule"])
    betas = np.linspace(s["beta_start"] ** 0.5, s["beta_end"] ** 0.5,
                        s["num_train_timesteps"], dtype=np.float64) ** 2
    return np.cumprod(1.0 - betas).astype(np.float32)


def stream(key, name: str):
    """The program's named RNG substreams (dcr_tpu/core/rng.py), restated:
    fold in the first four bytes of sha256(name), 31 bits."""
    tag = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4],
                         "little") & 0x7FFFFFFF
    return jax.random.fold_in(key, tag)


def step_stream(key, name: str, step):
    return jax.random.fold_in(stream(key, name), jnp.asarray(step, jnp.uint32))


def noised_inputs(cfg: dict, vae_params, pixels, train_key, step: int,
                  ops: Ops = EXACT):
    """What one finetuning step feeds the UNet, from a batch's pixels: the
    latents sampled from the VAE's Gaussian and scaled, the noise, the
    timesteps, the noisy latents and the target."""
    mean, logvar = vae_encode(ops, vae_params, cfg, pixels)
    std = jnp.exp(0.5 * jnp.clip(logvar, -30.0, 20.0))
    eps = jax.random.normal(step_stream(train_key, "vae_sample", step), mean.shape)
    latents = (mean + std * eps) * cfg["vae"]["scaling_factor"]
    noise = jax.random.normal(step_stream(train_key, "noise", step), latents.shape)
    t = jax.random.randint(step_stream(train_key, "timesteps", step),
                           (pixels.shape[0],), 0,
                           cfg["scheduler"]["num_train_timesteps"])
    acp = jnp.asarray(alphas_cumprod(cfg))[t][:, None, None, None]
    noisy = jnp.sqrt(acp) * latents + jnp.sqrt(1.0 - acp) * noise
    kind = cfg["scheduler"]["prediction_type"]
    if kind == "epsilon":
        target = noise
    elif kind == "v_prediction":
        target = jnp.sqrt(acp) * noise - jnp.sqrt(1.0 - acp) * latents
    else:
        raise ValueError(kind)
    return noisy, t, target


def block_loss(ops, unet_params, cfg, noisy, t, context, target, batch: int):
    """This block of rows' part of the batch's mean squared error."""
    pred = unet(ops, unet_params, cfg, noisy, t, context)
    return jnp.sum(jnp.mean((pred - target) ** 2, axis=(1, 2, 3))) / batch


# ---------------------------------------------------------------------------
# AdamW with global-norm clipping, leaf by leaf
# ---------------------------------------------------------------------------

def clip_scale(grad_norm, max_norm: float):
    return jnp.where(grad_norm < max_norm, 1.0, max_norm / grad_norm)


def adamw_leaf(p, g, m, v, count, *, lr, b1, b2, eps, weight_decay):
    """One AdamW update of one leaf; `g` already clipped, `count` the number
    of this update (1 for the first)."""
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    m_hat = m / (1.0 - b1 ** count)
    v_hat = v / (1.0 - b2 ** count)
    update = m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p
    return p - lr * update, m, v


# ---------------------------------------------------------------------------
# DPM-Solver++(2M) with classifier-free guidance
# ---------------------------------------------------------------------------

def sampling_grid(cfg: dict, steps: int) -> tuple[np.ndarray, np.ndarray]:
    big_t = cfg["scheduler"]["num_train_timesteps"]
    ts = np.linspace(0, big_t - 1, steps + 1).round()[::-1][:-1].astype(np.int32)
    return ts, np.concatenate([ts[1:], [0]]).astype(np.int32)


def solver_table(cfg: dict, steps: int) -> tuple[np.ndarray, np.ndarray]:
    """(timesteps [steps], coefficients [steps, 6]) of DPM-Solver++(2M) in the
    data-prediction form, worked out in float64 on the host. Per step, from t
    to s: sqrt(abar_t), sqrt(1 - abar_t), sigma_s / sigma_t,
    alpha_s * expm1(-h), and the weights of this and the previous x0
    prediction in D: (1, 0) at first order (the first step; the last too where
    steps < 15), (1 + 1/2r, 1/2r) at second order, r = h_prev / h."""
    acp = alphas_cumprod(cfg).astype(np.float64)
    ts, prev_ts = sampling_grid(cfg, steps)
    lam = lambda a: 0.5 * (math.log(a) - math.log(1.0 - a))       # noqa: E731
    rows, prev_lam = [], None
    for i, (t, s) in enumerate(zip(ts, prev_ts)):
        a_t, a_s = float(acp[t]), float(acp[s])
        lam_t, lam_s = lam(a_t), lam(a_s)
        h = lam_s - lam_t
        if i == 0 or (steps < 15 and i == steps - 1):
            w_cur, w_prev = 1.0, 0.0
        else:
            r = (lam_t - prev_lam) / h
            w_cur, w_prev = 1.0 + 1.0 / (2.0 * r), 1.0 / (2.0 * r)
        rows.append([math.sqrt(a_t), math.sqrt(1.0 - a_t),
                     math.sqrt(1.0 - a_s) / math.sqrt(1.0 - a_t),
                     math.sqrt(a_s) * math.expm1(-h), w_cur, w_prev])
        prev_lam = lam_t
    return ts, np.asarray(rows, np.float32)


def solver_step(kind: str, x, prev_x0, out, c):
    """One update x_t -> x_s from the model's output at (x_t, t)."""
    if kind == "epsilon":
        x0 = (x - c[1] * out) / c[0]
    elif kind == "v_prediction":
        x0 = c[0] * x - c[1] * out
    else:
        raise ValueError(kind)
    d = c[4] * x0 - c[5] * prev_x0
    return c[2] * x - c[3] * d, x0


def sample_images(ops, params, cfg: dict, ids, uncond_ids, noise, *,
                  steps: int, guidance: float):
    """Images in [0, 1] for prompts `ids` [B, L] from initial noise [B, h, w,
    4]: text encode, `steps` guided DPM-Solver++(2M) steps in a Python loop
    (one jitted step, called `steps` times: no scan), VAE decode."""
    kind = cfg["scheduler"]["prediction_type"]
    scaling = cfg["vae"]["scaling_factor"]

    @jax.jit
    def encode(text, ids, uncond_ids):
        return jnp.concatenate([text_encode(ops, text, cfg, uncond_ids),
                                text_encode(ops, text, cfg, ids)], axis=0)

    @jax.jit
    def step(unet_params, context, x, prev_x0, t, c):
        tb = jnp.full((2 * x.shape[0],), t, jnp.int32)
        pred = unet(ops, unet_params, cfg, jnp.concatenate([x, x], axis=0),
                    tb, context)
        pu, pc = jnp.split(pred, 2, axis=0)
        return solver_step(kind, x, prev_x0, pu + guidance * (pc - pu), c)

    @jax.jit
    def decode(vae, x):
        images = vae_decode(ops, vae, cfg, x / scaling)
        return jnp.clip(images * 0.5 + 0.5, 0.0, 1.0)

    context = encode(params["text"], ids, uncond_ids)
    ts, table = solver_table(cfg, steps)
    x, prev_x0 = noise, jnp.zeros_like(noise)
    for t, c in zip(ts, table):
        x, prev_x0 = step(params["unet"], context, x, prev_x0,
                          jnp.int32(t), jnp.asarray(c))
    return decode(params["vae"], x)
