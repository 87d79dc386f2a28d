"""Jitted text-to-image sampler: one lax.scan over denoising steps with CFG.

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

from typing import Optional

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


def encode_prompts(models: DiffusionModels, text_params, input_ids: jax.Array,
                   uncond_ids: jax.Array, *, rand_noise_lam: float = 0.0,
                   key: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """(cond, uncond) embeddings [B, L, D]; optional Newpipe-style noise."""
    cond = models.text_encoder.apply({"params": text_params}, input_ids).last_hidden_state
    uncond = models.text_encoder.apply({"params": text_params}, uncond_ids).last_hidden_state
    if rand_noise_lam > 0.0:
        assert key is not None
        k1, k2 = jax.random.split(key)
        cond = cond + rand_noise_lam * jax.random.normal(k1, cond.shape, cond.dtype)
        uncond = uncond + rand_noise_lam * jax.random.normal(k2, uncond.shape, uncond.dtype)
    return cond, uncond


def sampler_grid(sampler: str, sched, num_inference_steps: int):
    """(ts, prev_ts, lower_order_final) for a sampler name — the single source
    of the per-sampler diffusers-parity wiring, tested directly against the
    reference fixture in tests/test_scheduler_parity.py.

    - spacing follows the diffusers scheduler each sampler maps to: linspace
      for DPMSolverMultistep, leading for DDIM/DDPM;
    - steps_offset=1 is the SD scheduler-config value (DDIM/PNDM family);
      diffusers' DDPMScheduler uses no offset;
    - final-step target: DPMSolverMultistep steps to t=0, and SD's DDIM config
      has set_alpha_to_one=False (final acp = alphas_cumprod[0]) — both are our
      prev_t=0. DDPM's terminal variance uses acp=1 (prev_t=-1);
    - lower_order_final mirrors diffusers: first-order final step when <15 steps.
    """
    spacing = "linspace" if sampler == "dpm++" else "leading"
    offset = 0 if sampler == "ddpm" else 1
    ts = S.inference_timesteps(sched, num_inference_steps, spacing=spacing,
                               steps_offset=offset)
    final_prev = -1 if sampler == "ddpm" else 0
    prev_ts = jnp.concatenate([ts[1:], jnp.array([final_prev], ts.dtype)])
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
                   noise_key: Optional[jax.Array] = None):
    """One denoising update ``x_t -> x_{prev_t}`` for a sampler name —
    the single dispatch both the bulk pipeline (:func:`make_sampler`) and the
    serving worker (dcr_tpu/serve/worker.py) call, so a scheduler-parity fix
    lands in every generation path at once. Returns ``(x_new, dpm_state)``;
    ``noise_key`` is required only for the ancestral ``ddpm`` sampler. The
    update runs under ``jax.named_scope("scheduler_step")`` (it is no Flax
    module, so nothing else names it in a device trace)."""
    with jax.named_scope("scheduler_step"):
        if sampler == "ddim":
            return S.ddim_step(sched, pred, x, t, prev_t), dpm_state
        if sampler == "dpm++":
            return S.dpmpp_2m_step(sched, pred, x, t, prev_t, dpm_state,
                                   force_first_order=force_first_order)
        if sampler == "ddpm":
            assert noise_key is not None, "ddpm needs a per-step noise key"
            return (S.ddpm_step(sched, pred, x, t, prev_t, noise_key),
                    dpm_state)
    raise ValueError(f"unknown sampler {sampler!r}")


@compile_surface("sample/sampler")
def make_sampler(cfg: SampleConfig, models: DiffusionModels, mesh):
    """Build the jitted sampler: (params, input_ids, uncond_ids, key) -> images.

    images: [B, H, W, 3] float32 in [0, 1]. params = {"unet", "vae", "text"}.

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
    sched = models.schedule
    latent_size = cfg.resolution // vae_scale_factor(models.vae.config)
    latent_ch = models.vae.config.vae_latent_channels
    scaling = models.vae.config.vae_scaling_factor
    guidance = cfg.guidance_scale
    batch_spec = pmesh.batch_sharding(mesh)

    # bad fast knobs fail HERE, loudly and typed — the serve path gets this
    # from validate_bucket, and an invalid order must never silently run as
    # a different order (reuse_score treats order<2 as plain reuse)
    validate_fast_config(cfg.fast)
    # host-precomputed timestep grid [T] + fast step plan (see fast_plan_grid;
    # all-full unless cfg.fast enables score reuse)
    reuse_ratio = cfg.fast.reuse_ratio if cfg.fast.enabled else 0.0
    ts, prev_ts, lower_order_final, plan = fast_plan_grid(
        cfg.sampler, sched, cfg.num_inference_steps, reuse_ratio)
    # dense plan => build the ORIGINAL scan body (no cond, no score bank in
    # the carry): the fast-disabled program is bit-identical by construction
    use_fast = not fastsample.is_dense(plan)

    def sample_fn(params, input_ids, uncond_ids, key):
        input_ids = jax.lax.with_sharding_constraint(input_ids, batch_spec)
        bsz = input_ids.shape[0]
        kp, kn, ks = (rngmod.stream_key(key, n) for n in ("emb_noise", "init", "steps"))
        cond, uncond = encode_prompts(models, params["text"], input_ids, uncond_ids,
                                      rand_noise_lam=cfg.rand_noise_lam, key=kp)
        ctx = jnp.concatenate([uncond, cond], axis=0)  # [2B, L, D]

        x = jax.random.normal(kn, (bsz, latent_size, latent_size, latent_ch))
        # (diffusers scales initial noise by init_noise_sigma = 1 for DDPM-family)

        def denoise(carry, step_idx):
            if use_fast:
                x, dpm_state, bank = carry
            else:
                x, dpm_state = carry
            t = ts[step_idx]
            prev_t = prev_ts[step_idx]

            def predict():
                tb = jnp.full((2 * bsz,), t, jnp.int32)
                pred = models.unet.apply({"params": params["unet"]},
                                         jnp.concatenate([x, x], axis=0), tb, ctx)
                with jax.named_scope("cfg"):
                    pred_uncond, pred_cond = jnp.split(pred, 2, axis=0)
                    return pred_uncond + guidance * (pred_cond - pred_uncond)

            if use_fast:
                pred, bank = fastsample.predict_or_reuse(
                    plan, step_idx, t, bank, cfg.fast.order, predict)
            else:
                pred = predict()
            force1 = jnp.logical_and(lower_order_final,
                                     step_idx == len(ts) - 1)
            x_new, dpm_new = scheduler_step(
                cfg.sampler, sched, pred, x, t, prev_t, dpm_state,
                force_first_order=force1,
                noise_key=jax.random.fold_in(ks, step_idx))
            if use_fast:
                return (x_new, dpm_new, bank), ()
            return (x_new, dpm_new), ()

        init = (x, S.dpm_init_state(x.shape))
        if use_fast:
            init = init + (fastsample.bank_init(x.shape),)
        (x, *_), _ = jax.lax.scan(denoise, init, jnp.arange(len(ts)))

        images = models.vae.apply({"params": params["vae"]}, x / scaling,
                                  method=models.vae.decode)
        return jnp.clip(images * 0.5 + 0.5, 0.0, 1.0)

    return jax.jit(sample_fn)
