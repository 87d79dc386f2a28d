"""Diffusion noise schedules + samplers as pure jittable functions.

Capability parity with the scheduler surface the reference uses from diffusers:
DDPMScheduler.add_noise / get_velocity for training (diff_train.py:448,632,650)
and DPMSolverMultistepScheduler / default PNDM-style sampling for inference
(diff_inference.py:93). Implemented from the papers as stateless functions of a
precomputed :class:`NoiseSchedule`, so they compose with jit/scan/vmap — the
sampler loop lives in dcr_tpu.sampling as a ``lax.scan`` over these steps.

Math references: DDPM (Ho et al. 2020), DDIM (Song et al. 2020),
DPM-Solver++ (Lu et al. 2022).
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class NoiseSchedule:
    """Precomputed diffusion coefficients, all shape [T] float32."""

    betas: jax.Array
    alphas_cumprod: jax.Array
    num_train_timesteps: int
    prediction_type: str = "epsilon"  # "epsilon" | "v_prediction" | "sample"

    @property
    def sqrt_alphas_cumprod(self) -> jax.Array:
        return jnp.sqrt(self.alphas_cumprod)

    @property
    def sqrt_one_minus_alphas_cumprod(self) -> jax.Array:
        return jnp.sqrt(1.0 - self.alphas_cumprod)


def make_schedule(num_train_timesteps: int = 1000, beta_schedule: str = "scaled_linear",
                  beta_start: float = 0.00085, beta_end: float = 0.012,
                  prediction_type: str = "epsilon") -> NoiseSchedule:
    if beta_schedule == "linear":
        betas = np.linspace(beta_start, beta_end, num_train_timesteps, dtype=np.float64)
    elif beta_schedule == "scaled_linear":
        # SD's schedule: linear in sqrt(beta)
        betas = np.linspace(beta_start ** 0.5, beta_end ** 0.5, num_train_timesteps,
                            dtype=np.float64) ** 2
    elif beta_schedule == "squaredcos_cap_v2":
        t = np.arange(num_train_timesteps, dtype=np.float64)

        def f(x):
            return np.cos((x / num_train_timesteps + 0.008) / 1.008 * np.pi / 2) ** 2

        betas = np.minimum(1.0 - f(t + 1) / f(t), 0.999)
    else:
        raise ValueError(f"unknown beta_schedule {beta_schedule!r}")
    alphas_cumprod = np.cumprod(1.0 - betas)
    return NoiseSchedule(
        betas=jnp.asarray(betas, jnp.float32),
        alphas_cumprod=jnp.asarray(alphas_cumprod, jnp.float32),
        num_train_timesteps=num_train_timesteps,
        prediction_type=prediction_type,
    )


def _gather(coeffs: jax.Array, t: jax.Array, ndim: int) -> jax.Array:
    """coeffs[t] broadcast against an ndim-rank batched tensor."""
    c = coeffs[t]
    return c.reshape(c.shape + (1,) * (ndim - c.ndim))


def _bcast(v: jax.Array, ndim: int) -> jax.Array:
    """Broadcast a scalar or [B] per-timestep value against an ndim-rank tensor."""
    v = jnp.asarray(v)
    return v.reshape(v.shape + (1,) * (ndim - v.ndim))


def _acp_prev(sched: NoiseSchedule, prev_t: jax.Array, ndim: int) -> jax.Array:
    """alphas_cumprod[prev_t] with prev_t=-1 meaning "fully denoised" (acp=1)."""
    prev_t = jnp.asarray(prev_t)
    acp = sched.alphas_cumprod[jnp.maximum(prev_t, 0)]
    acp = jnp.where(prev_t >= 0, acp, 1.0)
    return _bcast(acp, ndim)


def add_noise(sched: NoiseSchedule, x0: jax.Array, noise: jax.Array,
              t: jax.Array) -> jax.Array:
    """q(x_t | x_0): forward diffusion (reference uses DDPMScheduler.add_noise,
    diff_train.py:632)."""
    a = _gather(sched.sqrt_alphas_cumprod, t, x0.ndim)
    s = _gather(sched.sqrt_one_minus_alphas_cumprod, t, x0.ndim)
    return a * x0.astype(jnp.float32) + s * noise.astype(jnp.float32)


def get_velocity(sched: NoiseSchedule, x0: jax.Array, noise: jax.Array,
                 t: jax.Array) -> jax.Array:
    """v-prediction target (reference diff_train.py:650)."""
    a = _gather(sched.sqrt_alphas_cumprod, t, x0.ndim)
    s = _gather(sched.sqrt_one_minus_alphas_cumprod, t, x0.ndim)
    return a * noise.astype(jnp.float32) - s * x0.astype(jnp.float32)


def training_target(sched: NoiseSchedule, x0: jax.Array, noise: jax.Array,
                    t: jax.Array) -> jax.Array:
    if sched.prediction_type == "epsilon":
        return noise
    if sched.prediction_type == "v_prediction":
        return get_velocity(sched, x0, noise, t)
    if sched.prediction_type == "sample":
        return x0
    raise ValueError(f"unknown prediction_type {sched.prediction_type!r}")


def pred_to_x0_eps(sched: NoiseSchedule, model_out: jax.Array, x_t: jax.Array,
                   t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Convert the model's output under its prediction_type to (x0_hat, eps_hat)."""
    a = _gather(sched.sqrt_alphas_cumprod, t, x_t.ndim)
    s = _gather(sched.sqrt_one_minus_alphas_cumprod, t, x_t.ndim)
    if sched.prediction_type == "epsilon":
        eps = model_out
        x0 = (x_t - s * eps) / a
    elif sched.prediction_type == "v_prediction":
        x0 = a * x_t - s * model_out
        eps = a * model_out + s * x_t
    elif sched.prediction_type == "sample":
        x0 = model_out
        eps = (x_t - a * x0) / s
    else:
        raise ValueError(sched.prediction_type)
    return x0, eps


# ---------------------------------------------------------------------------
# Inference-time timestep grids
# ---------------------------------------------------------------------------

def inference_timesteps(sched: NoiseSchedule, num_inference_steps: int,
                        spacing: str = "leading", steps_offset: int = 1) -> jax.Array:
    """Descending timestep grid [num_inference_steps].

    Mirrors diffusers' ``set_timesteps`` grids so sampled trajectories are
    comparable to the reference pipeline (diff_inference.py:93):

    - ``"leading"``: DDIM/PNDM-family. ``steps_offset`` (1 in SD's shipped
      scheduler configs) shifts the whole grid up by one training timestep;
      clipped to num_train_timesteps-1.
    - ``"linspace"``: DPMSolverMultistep's default — n+1 evenly spaced points
      over [0, T-1], reversed, last dropped. ``steps_offset`` is unused here,
      matching diffusers.
    """
    T = sched.num_train_timesteps
    if num_inference_steps > T:
        raise ValueError(
            f"num_inference_steps={num_inference_steps} exceeds "
            f"num_train_timesteps={T}")
    if spacing == "leading":
        step = T // num_inference_steps
        ts = (np.arange(num_inference_steps) * step).round()[::-1].copy()
        ts = np.minimum(ts + steps_offset, T - 1)
    elif spacing == "linspace":
        ts = np.linspace(0, T - 1, num_inference_steps + 1).round()[::-1][:-1].copy()
    else:
        raise ValueError(f"unknown timestep spacing {spacing!r}")
    return jnp.asarray(ts.astype(np.int32))


# ---------------------------------------------------------------------------
# DDPM ancestral step
# ---------------------------------------------------------------------------

def ddpm_step(sched: NoiseSchedule, model_out: jax.Array, x_t: jax.Array,
              t: jax.Array, prev_t: jax.Array, noise: jax.Array) -> jax.Array:
    """Ancestral update; ``noise`` is the caller's standard-normal draw,
    shaped like ``x_t`` (who keys it, and how, is the sampler's business)."""
    x0, eps = pred_to_x0_eps(sched, model_out, x_t, t)
    x0 = jnp.clip(x0, -1000.0, 1000.0)
    acp = _gather(sched.alphas_cumprod, t, x_t.ndim)
    acp_prev = _acp_prev(sched, prev_t, x_t.ndim)
    alpha_t = acp / acp_prev
    beta_t = 1.0 - alpha_t
    # posterior mean coefficients (Ho et al. eq. 7)
    coef_x0 = jnp.sqrt(acp_prev) * beta_t / (1.0 - acp)
    coef_xt = jnp.sqrt(alpha_t) * (1.0 - acp_prev) / (1.0 - acp)
    mean = coef_x0 * x0 + coef_xt * x_t
    var = beta_t * (1.0 - acp_prev) / (1.0 - acp)
    add_noise_mask = _bcast(jnp.asarray(prev_t) >= 0, x_t.ndim)
    return jnp.where(add_noise_mask,
                     mean + jnp.sqrt(jnp.maximum(var, 1e-20)) * noise, mean)


# ---------------------------------------------------------------------------
# DDIM step (eta=0, deterministic)
# ---------------------------------------------------------------------------

def ddim_step(sched: NoiseSchedule, model_out: jax.Array, x_t: jax.Array,
              t: jax.Array, prev_t: jax.Array) -> jax.Array:
    x0, eps = pred_to_x0_eps(sched, model_out, x_t, t)
    acp_prev = _acp_prev(sched, prev_t, x_t.ndim)
    return jnp.sqrt(acp_prev) * x0 + jnp.sqrt(1.0 - acp_prev) * eps


# ---------------------------------------------------------------------------
# DPM-Solver++ (2M multistep) — the reference's stock-SD sampler
# (diff_inference.py:93). Data-prediction formulation, order 2.
# ---------------------------------------------------------------------------

@flax.struct.dataclass
class DPMState:
    """Carried through the sampling scan (a pytree)."""

    prev_x0: jax.Array   # x0 prediction at the previous step
    prev_lambda: jax.Array
    step_index: jax.Array  # 0 at first step (first-order bootstrap)


def _lambda_of(sched: NoiseSchedule, t: jax.Array) -> jax.Array:
    acp = sched.alphas_cumprod[jnp.maximum(t, 0)]
    acp = jnp.where(t >= 0, acp, 1.0 - 1e-8)
    alpha = jnp.sqrt(acp)
    sigma = jnp.sqrt(1.0 - acp)
    return jnp.log(alpha) - jnp.log(jnp.maximum(sigma, 1e-20))


def dpmpp_2m_step(sched: NoiseSchedule, model_out: jax.Array, x_t: jax.Array,
                  t: jax.Array, prev_t: jax.Array, state: DPMState,
                  force_first_order: jax.Array | bool = False) -> tuple[jax.Array, DPMState]:
    """One DPM-Solver++(2M) update x_t -> x_{prev_t}; t/prev_t scalar or [B].

    First call (state.step_index == 0) falls back to the first-order (DDIM-like)
    update; later calls use the 2nd-order multistep correction. With batched t,
    initialize the state via ``dpm_init_state(x.shape, batch_shape=t.shape)``.

    ``force_first_order`` mirrors diffusers' ``lower_order_final``: the caller
    sets it on the final step of short (<15-step) trajectories for stability.
    """
    nd = x_t.ndim
    x0, _eps = pred_to_x0_eps(sched, model_out, x_t, t)

    lam_t = _lambda_of(sched, t)
    lam_s = _lambda_of(sched, prev_t)
    h = lam_s - lam_t

    prev_t = jnp.asarray(prev_t)
    acp_s = jnp.where(prev_t >= 0, sched.alphas_cumprod[jnp.maximum(prev_t, 0)], 1.0)
    alpha_s = jnp.sqrt(acp_s)
    sigma_s = jnp.sqrt(1.0 - acp_s)
    acp_t = sched.alphas_cumprod[t]
    sigma_t = jnp.sqrt(1.0 - acp_t)

    ratio = _bcast(sigma_s / jnp.maximum(sigma_t, 1e-20), nd)
    phi = _bcast(jnp.expm1(-h), nd)

    # 2nd-order combination of current and previous x0 predictions
    h_last = lam_t - state.prev_lambda
    r = h_last / jnp.where(h == 0, 1e-20, h)
    inv2r = _bcast(1.0 / (2.0 * jnp.maximum(r, 1e-20)), nd)
    # `>= 1`, not `> 0`: inside lax.scan, XLA:TPU (libtpu 0.0.34, jax 0.9.0)
    # evaluates `step_index > 0` as True for the scan-carried counter at 0,
    # which sends step 0 through the multistep term with inv2r = 5e19 and
    # ends every 50-step sample in NaN; `>= 1` compiles right (both seen on
    # a v5e, PR 24; chip_smoke.py's sample phase is the guard)
    use_second = jnp.logical_and(state.step_index >= 1,
                                 jnp.logical_not(force_first_order))
    d = jnp.where(use_second, (1.0 + inv2r) * x0 - inv2r * state.prev_x0, x0)

    x_prev = ratio * x_t - _bcast(alpha_s, nd) * phi * d
    new_state = DPMState(prev_x0=x0,
                         prev_lambda=jnp.broadcast_to(lam_t, state.prev_lambda.shape),
                         step_index=state.step_index + 1)
    return x_prev, new_state


def dpm_init_state(shape: tuple[int, ...], dtype=jnp.float32,
                   batch_shape: tuple[int, ...] = ()) -> DPMState:
    """batch_shape must match t's shape when stepping with batched timesteps."""
    return DPMState(prev_x0=jnp.zeros(shape, dtype),
                    prev_lambda=jnp.zeros(batch_shape),
                    step_index=jnp.zeros((), jnp.int32))
