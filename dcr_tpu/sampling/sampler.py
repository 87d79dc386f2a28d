"""Jitted text-to-image sampler: one scan over denoising steps with CFG.

TPU re-design of the reference's per-prompt diffusers pipeline loop
(diff_inference.py:183-193: python loop over 50 scheduler steps per batch).
Here the whole trajectory is a single compiled scan — no host↔device chatter —
and the prompt batch is sharded over the mesh's data axes, so bulk generation
(BASELINE config 3: 10k samples) is one jit running across chips.

Inference-time mitigation ``rand_noise_lam`` reproduces the reference's Newpipe
(diff_inference.py:3-6): Gaussian noise scaled by λ added to the prompt
embeddings (both the conditional and unconditional halves, matching diffusers'
_encode_prompt which returns the concatenated pair).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core.config import SampleConfig, validate_fast_config
from dcr_tpu.core import rng as rngmod
from dcr_tpu.diffusion.train import DiffusionModels
from dcr_tpu.models import schedulers as S
from dcr_tpu.models.vae import vae_scale_factor
from dcr_tpu.parallel import mesh as pmesh
from dcr_tpu.sampling import fastsample


def embedding_noise(cond: jax.Array, uncond: jax.Array,
                    key: Optional[jax.Array], lam: float):
    """Newpipe noise on both embedding halves: two draws from one split of
    ``key`` (bulk: on the batch; serve: under ``vmap``, one key a request)."""
    if lam <= 0.0:
        return cond, uncond
    assert key is not None
    k1, k2 = jax.random.split(key)
    return (cond + lam * jax.random.normal(k1, cond.shape, cond.dtype),
            uncond + lam * jax.random.normal(k2, uncond.shape, uncond.dtype))


def encode_prompts(models: DiffusionModels, text_params, input_ids: jax.Array,
                   uncond_ids: jax.Array, *, rand_noise_lam: float = 0.0,
                   key: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """(cond, uncond) embeddings [B, L, D]; optional Newpipe-style noise."""
    cond = models.text_encoder.apply({"params": text_params}, input_ids).last_hidden_state
    uncond = models.text_encoder.apply({"params": text_params}, uncond_ids).last_hidden_state
    return embedding_noise(cond, uncond, key, rand_noise_lam)


class SamplerSpec(NamedTuple):
    """What a sampler's name decides: one row of :data:`SAMPLERS`."""
    spacing: str        # S.inference_timesteps grid
    steps_offset: int   # read by the leading grid only
    final_prev_t: int   # the last step's target
    draws_noise: bool   # ancestral: the update takes fresh noise every step
    update: Callable    # (sched, pred, x, t, prev_t, state, force1, noise) -> (x_new, state)


# The single source of the per-sampler diffusers-parity wiring, tested
# directly against the reference fixture in tests/test_scheduler_parity.py;
# the config and serve-bucket validators accept exactly its keys.
# - spacing follows the diffusers scheduler each sampler maps to: linspace
#   for DPMSolverMultistep, leading for DDIM/DDPM;
# - steps_offset=1 is the SD scheduler-config value (DDIM/PNDM family);
#   diffusers' DDPMScheduler uses no offset;
# - final-step target: DPMSolverMultistep steps to t=0, and SD's DDIM config
#   has set_alpha_to_one=False (final acp = alphas_cumprod[0]) — both are our
#   prev_t=0. DDPM's terminal variance uses acp=1 (prev_t=-1).
SAMPLERS: dict[str, SamplerSpec] = {
    "ddim": SamplerSpec("leading", 1, 0, False,
                        lambda sched, pred, x, t, prev_t, state, force1, noise:
                        (S.ddim_step(sched, pred, x, t, prev_t), state)),
    "dpm++": SamplerSpec("linspace", 1, 0, False,
                         lambda sched, pred, x, t, prev_t, state, force1, noise:
                         S.dpmpp_2m_step(sched, pred, x, t, prev_t, state,
                                         force_first_order=force1)),
    "ddpm": SamplerSpec("leading", 0, -1, True,
                        lambda sched, pred, x, t, prev_t, state, force1, noise:
                        (S.ddpm_step(sched, pred, x, t, prev_t, noise), state)),
}


def _spec(sampler: str) -> SamplerSpec:
    if sampler not in SAMPLERS:
        raise ValueError(f"unknown sampler {sampler!r}")
    return SAMPLERS[sampler]


def sampler_grid(sampler: str, sched, num_inference_steps: int):
    """(ts, prev_ts, lower_order_final) for a sampler name, from its row of
    :data:`SAMPLERS`. lower_order_final mirrors diffusers: first-order final
    step when <15 steps."""
    spec = _spec(sampler)
    ts = S.inference_timesteps(sched, num_inference_steps, spacing=spec.spacing,
                               steps_offset=spec.steps_offset)
    prev_ts = jnp.concatenate([ts[1:], jnp.array([spec.final_prev_t], ts.dtype)])
    return ts, prev_ts, num_inference_steps < 15


def fast_plan_grid(sampler: str, sched, num_inference_steps: int,
                   reuse_ratio: float = 0.0):
    """:func:`sampler_grid` plus the fast-sampling step plan: ``(ts,
    prev_ts, lower_order_final, plan)`` where ``plan[i]`` is True for a
    full (UNet-calling) step and False for a score-reuse step
    (:mod:`dcr_tpu.sampling.fastsample`). The timestep grids are EXACTLY
    ``sampler_grid``'s — fast sampling skips score evaluations, never
    solver steps' positions — so ``reuse_ratio=0`` returns the identical
    grid with an all-full plan (tested)."""
    ts, prev_ts, lower_order_final = sampler_grid(sampler, sched,
                                                  num_inference_steps)
    plan = fastsample.fast_plan(num_inference_steps, reuse_ratio)
    return ts, prev_ts, lower_order_final, plan


def scheduler_step(sampler: str, sched, pred: jax.Array, x: jax.Array,
                   t, prev_t, dpm_state, *, force_first_order=False,
                   noise: Optional[jax.Array] = None):
    """One denoising update ``x_t -> x_{prev_t}`` for a sampler name: its
    :data:`SAMPLERS` row's ``update``, so a scheduler-parity fix lands in
    every generation path at once. Returns ``(x_new, dpm_state)``; ``noise``
    (shaped like ``x``) is required only where the row ``draws_noise``. Runs
    under ``jax.named_scope("scheduler_step")`` (it is no Flax module, so
    nothing else names it in a device trace)."""
    spec = _spec(sampler)
    assert noise is not None or not spec.draws_noise, f"{sampler} draws noise"
    with jax.named_scope("scheduler_step"):
        return spec.update(sched, pred, x, t, prev_t, dpm_state,
                           force_first_order, noise)


def denoise_images(models: DiffusionModels, params, ctx: jax.Array, x: jax.Array,
                   *, sampler: str, steps: int, guidance: float,
                   fast_ratio: float = 0.0, fast_order: int = 2,
                   step_noise: Optional[Callable[[jax.Array], jax.Array]] = None):
    """The denoise loop, written once: initial latents ``x`` [B, h, w, c] under
    ``ctx`` [2B, L, D] (uncond first) -> images [B, H, W, 3] float32 in [0, 1];
    params = {"unet", "vae"}. Both :func:`make_sampler` and the serving
    worker's per-bucket sampler trace THIS function inside their jit, so the
    program the benchmark's sample cells measure is the one a server runs;
    they differ in where ``ctx`` comes from, how ``x`` is keyed, and
    ``step_noise(step_idx) -> [B, h, w, c]``, which is called only for a
    sampler whose :data:`SAMPLERS` row ``draws_noise``."""
    sched = models.schedule
    # host arithmetic on static config (see fast_plan_grid; all-full unless
    # fast_ratio > 0), evaluated while tracing: the grid enters the program
    # as constants, as when it was built outside the trace
    with jax.ensure_compile_time_eval():
        ts, prev_ts, lower_order_final, plan = fast_plan_grid(
            sampler, sched, steps, fast_ratio)
    # dense plan => build the ORIGINAL scan body (no cond, no score bank in
    # the carry): the fast-disabled program is bit-identical by construction;
    # a reuse plan is a distinct compiled program
    use_fast = not fastsample.is_dense(plan)
    draws_noise = SAMPLERS[sampler].draws_noise

    def denoise(carry, step_idx):
        if use_fast:
            x, dpm_state, bank = carry
        else:
            x, dpm_state = carry
        t = ts[step_idx]
        prev_t = prev_ts[step_idx]

        def predict():
            tb = jnp.full((2 * x.shape[0],), t, jnp.int32)
            pred = models.unet.apply({"params": params["unet"]},
                                     jnp.concatenate([x, x], axis=0), tb, ctx)
            with jax.named_scope("cfg"):
                pred_uncond, pred_cond = jnp.split(pred, 2, axis=0)
                return pred_uncond + guidance * (pred_cond - pred_uncond)

        if use_fast:
            # elementwise over the batch, plan uniform per program: row i's
            # reuse/extrapolation depends only on row i's banked scores, so
            # serve's batch-composition bit-independence survives
            pred, bank = fastsample.predict_or_reuse(
                plan, step_idx, t, bank, fast_order, predict)
        else:
            pred = predict()
        force1 = jnp.logical_and(lower_order_final, step_idx == len(ts) - 1)
        x_new, dpm_new = scheduler_step(
            sampler, sched, pred, x, t, prev_t, dpm_state,
            force_first_order=force1,
            noise=step_noise(step_idx) if draws_noise else None)
        if use_fast:
            return (x_new, dpm_new, bank), ()
        return (x_new, dpm_new), ()

    init = (x, S.dpm_init_state(x.shape))
    if use_fast:
        init = init + (fastsample.bank_init(x.shape),)
    (x, *_), _ = jax.lax.scan(denoise, init, jnp.arange(len(ts)))

    images = models.vae.apply(
        {"params": params["vae"]}, x / models.vae.config.vae_scaling_factor,
        method=models.vae.decode)
    return jnp.clip(images * 0.5 + 0.5, 0.0, 1.0)


@compile_surface("sample/sampler")
def make_sampler(cfg: SampleConfig, models: DiffusionModels, mesh):
    """Build the jitted sampler: (params, input_ids, uncond_ids, key) -> images.

    images: [B, H, W, 3] float32 in [0, 1]. params = {"unet", "vae", "text"}.
    The bulk path's set-up (ids encoded in the program, one key a batch)
    is here; the loop is :func:`denoise_images`.

    The UNet's module mesh is reconciled with the sampling mesh here, for
    every caller: ring/Ulysses sequence-parallel attention and the flash
    kernel's per-device shard_map gate on ``module.mesh``, so an absent one
    would silently sample dense under a seq-axis mesh (or hand the compiler a
    Mosaic kernel it cannot partition), and a stale one (e.g. a training mesh
    captured at build_models time) would shard_map over the wrong device set.
    Modules are static config — rebuilding is free.
    """
    target_mesh = mesh if mesh.size > 1 else None
    if models.unet.mesh is not target_mesh:
        from dcr_tpu.models.unet2d import UNet2DCondition

        models = models._replace(
            unet=UNet2DCondition(models.unet.config, dtype=models.unet.dtype,
                                 mesh=target_mesh))
    latent_size = cfg.resolution // vae_scale_factor(models.vae.config)
    latent_ch = models.vae.config.vae_latent_channels
    batch_spec = pmesh.batch_sharding(mesh)

    # bad fast knobs fail HERE, loudly and typed — the serve path gets this
    # from validate_bucket, and an invalid order must never silently run as
    # a different order (reuse_score treats order<2 as plain reuse)
    validate_fast_config(cfg.fast)

    def sample_fn(params, input_ids, uncond_ids, key):
        input_ids = jax.lax.with_sharding_constraint(input_ids, batch_spec)
        kp, kn, ks = (rngmod.stream_key(key, n) for n in ("emb_noise", "init", "steps"))
        cond, uncond = encode_prompts(models, params["text"], input_ids, uncond_ids,
                                      rand_noise_lam=cfg.rand_noise_lam, key=kp)
        ctx = jnp.concatenate([uncond, cond], axis=0)  # [2B, L, D]

        x = jax.random.normal(
            kn, (input_ids.shape[0], latent_size, latent_size, latent_ch))
        # (diffusers scales initial noise by init_noise_sigma = 1 for DDPM-family)
        return denoise_images(
            models, params, ctx, x, sampler=cfg.sampler,
            steps=cfg.num_inference_steps, guidance=cfg.guidance_scale,
            fast_ratio=cfg.fast.reuse_ratio if cfg.fast.enabled else 0.0,
            fast_order=cfg.fast.order,
            # ancestral noise: ONE batch-shaped draw a step
            step_noise=lambda step_idx: jax.random.normal(
                jax.random.fold_in(ks, step_idx), x.shape, x.dtype))

    return jax.jit(sample_fn)
