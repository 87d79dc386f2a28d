"""The diffusion finetuning train step + state, GSPMD-sharded.

TPU-native re-design of the reference trainer's hot loop (diff_train.py:613-666):
one jitted function computes vae-encode → q-sample → text-encode (+ embedding
mitigations) → unet → mse(ε|v) → adamw-with-clip, with gradient sync emitted by
GSPMD over the mesh's data axes instead of DDP/NCCL (SURVEY.md §2.2). Train-time
mitigations (arXiv:2305.20086):

- ``rand_noise_lam``: Gaussian noise added to text embeddings
  (reference diff_train.py:637-638)
- ``mixup_noise_lam``: Beta(λ,1)-weighted mixup of text embeddings across the
  batch (reference diff_train.py:639-642) — here the Beta draw and permutation
  happen inside jit with explicit keys.

Unlike the reference (which saves weights only and cannot resume,
SURVEY.md §5.4), TrainState carries params + optimizer + step + EMA and is the
unit of checkpointing.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import optax

from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core.config import OptimConfig, TrainConfig
from dcr_tpu.core.precision import policy_from_string
from dcr_tpu.core import rng as rngmod
from dcr_tpu.models import schedulers as S
from dcr_tpu.models.unet2d import UNet2DCondition
from dcr_tpu.models.vae import AutoencoderKL
from dcr_tpu.parallel import mesh as pmesh


class DiffusionModels(NamedTuple):
    """Static module bundle (hashable; safe to close over in jit)."""

    unet: UNet2DCondition
    vae: AutoencoderKL
    text_encoder: nn.Module              # models/text_tower.build_text_tower
    schedule: S.NoiseSchedule


@flax.struct.dataclass
class TrainState:
    step: jax.Array                       # int32 optimizer-step counter
    unet_params: Any
    text_params: Any                      # trainable iff cfg.train_text_encoder
    vae_params: Any                       # always frozen
    opt_state: Any
    ema_params: Optional[Any] = None      # EMA of unet_params when enabled


def trainable_of(state: TrainState, train_text_encoder: bool) -> dict:
    t = {"unet": state.unet_params}
    if train_text_encoder:
        t["text_encoder"] = state.text_params
    return t


def resolve_scale_lr(cfg: TrainConfig) -> TrainConfig:
    """Fold the reference's scale_lr semantics (lr × grad-accum × per-device
    batch × device count) into a NEW config with scale_lr cleared. Called by
    every optimizer-building path so direct train.py users get it too; the
    caller's config object is never mutated."""
    if not cfg.optim.scale_lr:
        return cfg
    import dataclasses

    new_optim = dataclasses.replace(
        cfg.optim, scale_lr=False,
        learning_rate=cfg.optim.learning_rate
        * cfg.optim.gradient_accumulation_steps
        * cfg.train_batch_size * jax.device_count())
    return dataclasses.replace(cfg, optim=new_optim)


def make_lr_schedule(cfg: OptimConfig) -> optax.Schedule:
    """The reference's get_scheduler surface (diff_train.py:506-511)."""
    lr = cfg.learning_rate
    warmup = cfg.lr_warmup_steps
    if cfg.lr_scheduler == "constant":
        return optax.constant_schedule(lr)
    if cfg.lr_scheduler == "constant_with_warmup":
        return optax.join_schedules(
            [optax.linear_schedule(0.0, lr, warmup), optax.constant_schedule(lr)],
            [warmup])
    if cfg.lr_scheduler == "linear":
        return optax.join_schedules(
            [optax.linear_schedule(0.0, lr, warmup),
             optax.linear_schedule(lr, 0.0, 10 ** 9)], [warmup])
    if cfg.lr_scheduler == "cosine":
        return optax.join_schedules(
            [optax.linear_schedule(0.0, lr, warmup),
             optax.cosine_decay_schedule(lr, 10 ** 6)], [warmup])
    raise ValueError(f"unknown lr_scheduler {cfg.lr_scheduler!r}")


def _scoped(name: str, inner: optax.GradientTransformation
            ) -> optax.GradientTransformation:
    """``inner`` with its update under ``jax.named_scope(name)``: the device
    trace can then tell AdamW's elementwise traffic from GroupNorm's (neither
    is a Flax module, whose calls Flax names itself). Metadata only: state,
    arithmetic and the lowered text are ``inner``'s."""
    def update(updates, state, params=None):
        with jax.named_scope(name):
            return inner.update(updates, state, params)

    return optax.GradientTransformation(inner.init, update)


def make_optimizer(cfg: OptimConfig) -> optax.GradientTransformation:
    """AdamW with global-norm clipping and optional scan-free grad accumulation
    (reference: AdamW diff_train.py:424-446, clip 657-663, accumulate 618;
    --use_8bit_adam -> blockwise 8-bit moment state, core/adam8bit.py)."""
    if cfg.use_8bit_adam:
        from dcr_tpu.core.adam8bit import adamw8bit as adam_factory
    else:
        adam_factory = optax.adamw
    adam = adam_factory(
        learning_rate=make_lr_schedule(cfg),
        b1=cfg.adam_beta1, b2=cfg.adam_beta2,
        eps=cfg.adam_epsilon, weight_decay=cfg.adam_weight_decay,
    )
    tx = optax.chain(
        _scoped("grad_clip", optax.clip_by_global_norm(cfg.max_grad_norm)),
        _scoped("optimizer", adam))
    if cfg.gradient_accumulation_steps > 1:
        tx = optax.MultiSteps(tx, cfg.gradient_accumulation_steps)
    return tx


def init_train_state(cfg: TrainConfig, models: DiffusionModels, *,
                     unet_params, text_params, vae_params) -> TrainState:
    cfg = resolve_scale_lr(cfg)
    tx = make_optimizer(cfg.optim)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        unet_params=unet_params,
        text_params=text_params,
        vae_params=vae_params,
        # jitted: un-jitted, optax fills every moment leaf with a dispatch of
        # its own, and on a TPU each distinct shape of those is a compile
        opt_state=jax.jit(tx.init)(
            trainable_of(
                TrainState(jnp.zeros((), jnp.int32), unet_params, text_params,
                           vae_params, None),
                cfg.train_text_encoder)),
        ema_params=jax.tree.map(jnp.copy, unet_params) if cfg.ema_decay > 0 else None,
    )
    return state


def shard_train_state(state: TrainState, mesh) -> TrainState:
    """Place params/opt-state on the mesh: tensor-parallel rules for the UNet's
    transformer projections when the tensor axis exists, the FSDP
    largest-axis rule elsewhere, replicated otherwise; step replicated."""
    from dcr_tpu.parallel.sharding import params_sharding

    tp = mesh.shape[pmesh.TENSOR_AXIS] > 1
    param_sharding = params_sharding(
        mesh, {"unet": state.unet_params, "text": state.text_params,
               "vae": state.vae_params, "opt": state.opt_state,
               "ema": state.ema_params}, tensor_parallel=tp)
    rep = pmesh.replicated(mesh)
    return TrainState(
        step=jax.device_put(state.step, rep),
        unet_params=jax.tree.map(jax.device_put, state.unet_params,
                                 param_sharding["unet"]),
        text_params=jax.tree.map(jax.device_put, state.text_params,
                                 param_sharding["text"]),
        vae_params=jax.tree.map(jax.device_put, state.vae_params,
                                param_sharding["vae"]),
        opt_state=jax.tree.map(jax.device_put, state.opt_state,
                               param_sharding["opt"]),
        ema_params=None if state.ema_params is None else jax.tree.map(
            jax.device_put, state.ema_params, param_sharding["ema"]),
    )


@compile_surface("train/step")
def make_train_step(cfg: TrainConfig, models: DiffusionModels,
                    mesh) -> Callable:
    """Build the jitted train step: (state, batch, root_key) -> (state, metrics).

    batch: pixel_values [B,H,W,3] f32, input_ids [B,L] int32 — globally sharded
    on the mesh batch axes (use parallel.shard_batch).
    """
    cfg = resolve_scale_lr(cfg)
    policy = policy_from_string(cfg.mixed_precision)
    tx = make_optimizer(cfg.optim)
    lr_schedule = make_lr_schedule(cfg.optim)
    sched = models.schedule
    batch_spec = pmesh.batch_sharding(mesh)
    use_remat = cfg.remat
    accum_steps = max(1, cfg.optim.gradient_accumulation_steps)

    def step_fn(state: TrainState, batch: dict, root_key: jax.Array):
        pixels = jax.lax.with_sharding_constraint(batch["pixel_values"], batch_spec)
        input_ids = jax.lax.with_sharding_constraint(batch["input_ids"], batch_spec)
        bsz = pixels.shape[0]
        step = state.step

        keys = {name: rngmod.step_key(rngmod.stream_key(root_key, name), step)
                for name in ("vae_sample", "noise", "timesteps", "emb_noise",
                             "mixup_beta", "mixup_perm")}

        # frozen VAE encode (outside grad; reference relies on requires_grad_(False))
        vae_params_c = policy.cast_to_compute(state.vae_params)
        dist = models.vae.apply({"params": vae_params_c}, policy.cast_to_compute(pixels),
                                method=models.vae.encode)
        latents = dist.sample(keys["vae_sample"]) * models.vae.config.vae_scaling_factor
        latents = latents.astype(jnp.float32)

        with jax.named_scope("noising"):
            noise = jax.random.normal(keys["noise"], latents.shape)
            timesteps = jax.random.randint(keys["timesteps"], (bsz,), 0,
                                           sched.num_train_timesteps)
            noisy_latents = S.add_noise(sched, latents, noise, timesteps)
            target = S.training_target(sched, latents, noise, timesteps)

        def text_encode(text_params):
            out = models.text_encoder.apply(
                {"params": policy.cast_to_compute(text_params)}, input_ids)
            return out.last_hidden_state

        def loss_fn(trainable):
            if cfg.train_text_encoder:
                ctx = text_encode(trainable["text_encoder"])
            else:
                ctx = jax.lax.stop_gradient(text_encode(state.text_params))
            # train-time embedding mitigations
            if cfg.rand_noise_lam > 0:
                ctx = ctx + cfg.rand_noise_lam * jax.random.normal(
                    keys["emb_noise"], ctx.shape, ctx.dtype)
            if cfg.mixup_noise_lam > 0:
                lam = jax.random.beta(keys["mixup_beta"], cfg.mixup_noise_lam, 1.0)
                perm = jax.random.permutation(keys["mixup_perm"], bsz)
                ctx = lam * ctx + (1.0 - lam) * ctx[perm]

            unet_apply = lambda p, x, t, c: models.unet.apply({"params": p}, x, t, c)
            if use_remat:
                unet_apply = jax.checkpoint(unet_apply)
            pred = unet_apply(policy.cast_to_compute(trainable["unet"]),
                              policy.cast_to_compute(noisy_latents), timesteps,
                              policy.cast_to_compute(ctx))
            with jax.named_scope("loss"):
                return jnp.mean((pred.astype(jnp.float32) - target) ** 2)

        trainable = trainable_of(state, cfg.train_text_encoder)
        loss, grads = jax.value_and_grad(loss_fn)(trainable)
        with jax.named_scope("grad_clip"):      # the norm the clip takes too
            grad_norm = optax.global_norm(grads)
        # `tx` scopes its own parts (make_optimizer): grad_clip, optimizer
        updates, new_opt_state = tx.update(grads, state.opt_state, trainable)
        with jax.named_scope("optimizer"):
            new_trainable = optax.apply_updates(trainable, updates)

        new_unet = new_trainable["unet"]
        new_ema = state.ema_params
        if state.ema_params is not None:
            d = cfg.ema_decay
            # blend only on real optimizer updates: under MultiSteps accumulation,
            # mini_step wraps to 0 exactly when the inner adamw applied
            if accum_steps > 1:
                applied = new_opt_state.mini_step == 0
            else:
                applied = jnp.asarray(True)
            with jax.named_scope("ema"):
                new_ema = jax.tree.map(
                    lambda e, p: jnp.where(applied, d * e + (1.0 - d) * p, e),
                    state.ema_params, new_unet)
        new_state = TrainState(
            step=step + 1,
            unet_params=new_unet,
            text_params=new_trainable.get("text_encoder", state.text_params),
            vae_params=state.vae_params,
            opt_state=new_opt_state,
            ema_params=new_ema,
        )
        # the adamw schedule inside MultiSteps advances once per accumulation
        # boundary, so report the lr actually applied
        metrics = {"loss": loss, "grad_norm": grad_norm,
                   "lr": lr_schedule(step // accum_steps)}
        return new_state, metrics

    return jax.jit(step_fn, donate_argnums=(0,))
