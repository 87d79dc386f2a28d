"""dcr-pipe: pipelined training — frozen-encoder producer + denoiser hot step.

The fused train step (diffusion/train.py) pays the frozen VAE encode and
(when ``train_text_encoder=False``) the frozen text encode inside the single
jitted program, every step of every run — even though the paper's experiment
matrix finetunes the *same* images under many duplication/caption/mitigation
regimes. Following DiffusionPipe (PAPERS.md: partition the frozen components
out of the hot loop of large diffusion-model training), this module splits
that program in two:

- :func:`make_encode_stage` — the **producer**: VAE-encode + frozen
  text-encode as its own ``@compile_surface`` program, run by
  :class:`EncodeProducer` on a background thread one-or-more steps ahead of
  the trainer, feeding a bounded device-side prefetch ring (the loader's
  threaded-prefetch discipline, one level up the pipeline);
- :func:`make_denoise_step` — the **consumer**: the pure denoiser+optimizer
  hot step over a :class:`HotState` (step / unet / opt / EMA — the frozen
  params never enter, so nothing frozen is donated and the producer shares
  the same frozen buffers);
- :func:`make_cache_stage` — the producer's latent-cache fast path: given
  precomputed VAE posterior moments + text embeddings
  (data/latent_cache.py), reconstruct the per-occurrence latent sample with
  the encoders never executed.

**RNG stream ownership is explicit** so the draws are unchanged between the
fused and pipelined programs: the producer owns the ``vae_sample`` stream
(keyed on the global micro-step it is encoding for), the denoiser owns
``noise`` / ``timesteps`` / ``emb_noise`` / ``mixup_beta`` / ``mixup_perm``
(keyed on ``hot.step`` exactly as the fused step keys them on
``state.step``) — the q-sample draws of step N are bit-identical either
way. The pipelined-off path does not import this module at all: the trainer
builds the original fused step body, so disabled mode is bit-identical by
construction (the fused ``train/step`` HLO digest in compile_manifest.json
does not move).

Pipelining telemetry: the producer emits ``train/data_wait`` (time blocked
on the host loader) and ``train/encode`` spans on its own thread; the
consumer emits ``train/encode_wait`` (time blocked on the ring — the
pipeline bubble) and the ``data/queue_depth`` gauge tracks ring occupancy.
``tools/trace_report.py`` renders these as the "Pipeline" section.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core.config import TrainConfig
from dcr_tpu.core.precision import policy_from_string
from dcr_tpu.core import resilience as R
from dcr_tpu.core import rng as rngmod
from dcr_tpu.core import tracing
from dcr_tpu.diffusion.train import (DiffusionModels, TrainState,
                                     make_lr_schedule, make_optimizer,
                                     resolve_scale_lr)
from dcr_tpu.models import schedulers as S
from dcr_tpu.parallel import mesh as pmesh

#: streams drawn by the producer stage; the denoiser owns the rest. One
#: list, asserted against train.py's key dict by tests, so a new stream
#: must be assigned an owner before it can ship.
PRODUCER_STREAMS = ("vae_sample",)
DENOISER_STREAMS = ("noise", "timesteps", "emb_noise", "mixup_beta",
                    "mixup_perm")


@flax.struct.dataclass
class HotState:
    """The denoiser hot step's state: everything the optimizer touches,
    nothing frozen. Donated every step; the frozen params (VAE, and the
    text encoder unless it is being trained) live OUTSIDE so the producer
    can keep encoding against the same buffers while the consumer donates."""

    step: jax.Array
    unet_params: Any
    opt_state: Any
    text_params: Optional[Any] = None   # present iff cfg.train_text_encoder
    ema_params: Optional[Any] = None


def split_state(state: TrainState, train_text_encoder: bool):
    """TrainState -> (HotState, frozen dict). Pure re-referencing: no copies,
    the views share buffers with the input state."""
    hot = HotState(
        step=state.step, unet_params=state.unet_params,
        opt_state=state.opt_state,
        text_params=state.text_params if train_text_encoder else None,
        ema_params=state.ema_params)
    frozen = {"vae": state.vae_params,
              "text": None if train_text_encoder else state.text_params}
    return hot, frozen


def merge_state(hot: HotState, frozen: dict,
                train_text_encoder: bool) -> TrainState:
    """(HotState, frozen) -> TrainState — the checkpoint/export view."""
    return TrainState(
        step=hot.step, unet_params=hot.unet_params,
        text_params=(hot.text_params if train_text_encoder
                     else frozen["text"]),
        vae_params=frozen["vae"], opt_state=hot.opt_state,
        ema_params=hot.ema_params)


def _text_out(models: DiffusionModels, policy, text_params, input_ids):
    return models.text_encoder.apply(
        {"params": policy.cast_to_compute(text_params)}, input_ids)


def _text_ctx(models: DiffusionModels, policy, text_params, input_ids):
    return _text_out(models, policy, text_params, input_ids).last_hidden_state


@compile_surface("train/encode")
def make_encode_stage(cfg: TrainConfig, models: DiffusionModels, mesh, *,
                      emit: str = "latents") -> Callable:
    """Build the producer program: (frozen, batch, root_key, step) -> enc.

    ``emit="latents"`` (training) draws the per-occurrence VAE posterior
    sample with the ``vae_sample`` stream keyed on ``step`` — the identical
    key the fused step would derive at that micro-step, so the draw is
    unchanged. ``emit="moments"`` (the ``dcr-precompute-latents`` path)
    returns the posterior mean/std instead of a sample: the sample stays a
    per-occurrence train-time draw, which is what lets ONE cache serve every
    epoch and every duplication regime without freezing the latent noise.

    enc carries ``ctx`` (frozen text embedding) when the text encoder is
    frozen, or passes ``input_ids`` through when it is being trained (the
    denoiser then encodes with the live trainable params).
    """
    policy = policy_from_string(cfg.mixed_precision)
    batch_spec = pmesh.batch_sharding(mesh)

    def encode_fn(frozen: dict, batch: dict, root_key: jax.Array,
                  step: jax.Array) -> dict:
        pixels = jax.lax.with_sharding_constraint(batch["pixel_values"],
                                                  batch_spec)
        input_ids = jax.lax.with_sharding_constraint(batch["input_ids"],
                                                     batch_spec)
        vae_params_c = policy.cast_to_compute(frozen["vae"])
        dist = models.vae.apply({"params": vae_params_c},
                                policy.cast_to_compute(pixels),
                                method=models.vae.encode)
        enc: dict = {"index": batch["index"]}
        if emit == "moments":
            std = jnp.exp(0.5 * jnp.clip(dist.logvar, -30.0, 20.0))
            enc["mean"] = dist.mean.astype(jnp.float32)
            enc["std"] = std.astype(jnp.float32)
        else:
            key_vae = rngmod.step_key(
                rngmod.stream_key(root_key, "vae_sample"), step)
            latents = dist.sample(key_vae) * models.vae.config.vae_scaling_factor
            enc["latents"] = latents.astype(jnp.float32)
        if cfg.train_text_encoder:
            enc["input_ids"] = input_ids
        else:
            out = _text_out(models, policy, frozen["text"], input_ids)
            enc["ctx"] = out.last_hidden_state
            if emit == "moments" and hasattr(out, "moe_stats"):
                # an expert tower's routing counts ride beside the outputs;
                # the caller adds them to the moe/* counters at its fetch
                enc["moe"] = out.moe_stats
        return enc

    return jax.jit(encode_fn)


@compile_surface("train/encode_cached")
def make_cache_stage(cfg: TrainConfig, models: DiffusionModels,
                     mesh) -> Callable:
    """Build the latent-cache producer program:
    (moments, root_key, step) -> enc — the encoders never execute.

    Reconstructs the per-occurrence latent sample from cached posterior
    moments with the SAME ``vae_sample`` stream/step key the live encode
    stage would use: ``mean + std * N(key)`` in the compute dtype, scaled
    and cast exactly like ``DiagonalGaussian.sample`` — so a cache-fed run
    draws the latents a live-encode run would.
    """
    policy = policy_from_string(cfg.mixed_precision)
    batch_spec = pmesh.batch_sharding(mesh)
    if cfg.train_text_encoder:
        raise ValueError("latent-cache training requires a frozen text "
                         "encoder (validate_pipe_config enforces this)")

    def cache_fn(moments: dict, root_key: jax.Array,
                 step: jax.Array) -> dict:
        mean = jax.lax.with_sharding_constraint(moments["mean"], batch_spec)
        std = jax.lax.with_sharding_constraint(moments["std"], batch_spec)
        ctx = jax.lax.with_sharding_constraint(moments["ctx"], batch_spec)
        key_vae = rngmod.step_key(
            rngmod.stream_key(root_key, "vae_sample"), step)
        mean_c = policy.cast_to_compute(mean)
        std_c = policy.cast_to_compute(std)
        eps = jax.random.normal(key_vae, mean_c.shape, mean_c.dtype)
        latents = (mean_c + std_c * eps) * models.vae.config.vae_scaling_factor
        return {"latents": latents.astype(jnp.float32),
                "ctx": policy.cast_to_compute(ctx),
                "index": moments["index"]}

    return jax.jit(cache_fn)


@compile_surface("train/denoise")
def make_denoise_step(cfg: TrainConfig, models: DiffusionModels,
                      mesh) -> Callable:
    """Build the hot step: (hot, enc, root_key) -> (hot', metrics).

    The fused step body (diffusion/train.py) minus the frozen encoders: the
    q-sample draws (``noise``/``timesteps``) and the embedding-mitigation
    draws key on ``hot.step`` through the same streams the fused step keys
    on ``state.step``, so step N's draws are identical. Donates the hot
    state only — enc and the frozen params are never donated, which is what
    lets the producer run ahead against stable buffers.
    """
    cfg = resolve_scale_lr(cfg)
    policy = policy_from_string(cfg.mixed_precision)
    tx = make_optimizer(cfg.optim)
    lr_schedule = make_lr_schedule(cfg.optim)
    sched = models.schedule
    batch_spec = pmesh.batch_sharding(mesh)
    use_remat = cfg.remat
    accum_steps = max(1, cfg.optim.gradient_accumulation_steps)

    def hot_trainable(hot: HotState) -> dict:
        t = {"unet": hot.unet_params}
        if cfg.train_text_encoder:
            t["text_encoder"] = hot.text_params
        return t

    def step_fn(hot: HotState, enc: dict, root_key: jax.Array):
        latents = jax.lax.with_sharding_constraint(enc["latents"], batch_spec)
        bsz = latents.shape[0]
        step = hot.step

        keys = {name: rngmod.step_key(rngmod.stream_key(root_key, name), step)
                for name in DENOISER_STREAMS}

        with jax.named_scope("noising"):
            noise = jax.random.normal(keys["noise"], latents.shape)
            timesteps = jax.random.randint(keys["timesteps"], (bsz,), 0,
                                           sched.num_train_timesteps)
            noisy_latents = S.add_noise(sched, latents, noise, timesteps)
            target = S.training_target(sched, latents, noise, timesteps)

        def loss_fn(trainable):
            if cfg.train_text_encoder:
                ids = jax.lax.with_sharding_constraint(enc["input_ids"],
                                                       batch_spec)
                ctx = _text_ctx(models, policy, trainable["text_encoder"], ids)
            else:
                ctx = jax.lax.with_sharding_constraint(enc["ctx"], batch_spec)
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

        trainable = hot_trainable(hot)
        loss, grads = jax.value_and_grad(loss_fn)(trainable)
        # the same scopes as the fused step (train.py); `tx` scopes its own
        with jax.named_scope("grad_clip"):
            grad_norm = optax.global_norm(grads)
        updates, new_opt_state = tx.update(grads, hot.opt_state, trainable)
        with jax.named_scope("optimizer"):
            new_trainable = optax.apply_updates(trainable, updates)

        new_unet = new_trainable["unet"]
        new_ema = hot.ema_params
        if hot.ema_params is not None:
            d = cfg.ema_decay
            # blend only on real optimizer updates (see train.py): under
            # MultiSteps, mini_step wraps to 0 exactly when adamw applied
            if accum_steps > 1:
                applied = new_opt_state.mini_step == 0
            else:
                applied = jnp.asarray(True)
            with jax.named_scope("ema"):
                new_ema = jax.tree.map(
                    lambda e, p: jnp.where(applied, d * e + (1.0 - d) * p, e),
                    hot.ema_params, new_unet)
        new_hot = HotState(
            step=step + 1,
            unet_params=new_unet,
            opt_state=new_opt_state,
            text_params=new_trainable.get("text_encoder", hot.text_params),
            ema_params=new_ema,
        )
        metrics = {"loss": loss, "grad_norm": grad_norm,
                   "lr": lr_schedule(step // accum_steps)}
        return new_hot, metrics

    return jax.jit(step_fn, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# The producer ring
# ---------------------------------------------------------------------------

class EncodeProducer:
    """Bounded producer ring: host batches -> device encode -> the trainer.

    One background thread pulls host batches from ``source`` (a loader epoch
    iterator), runs ``encode(batch, step)`` (the live encode stage or the
    latent-cache stage — injected, so both producers share this machinery),
    and parks the encoded device batch in a ``depth``-bounded queue. The
    loader's threaded-prefetch discipline, one level up: ``safe_put``
    re-checks the stop event so teardown can never leave the producer pinned
    in ``put`` holding device buffers, and every producer-side error
    (encode failure, loader error, TooManyBadSamples) surfaces on the
    consumer's next :meth:`get`.

    Telemetry: ``train/data_wait`` + ``train/encode`` spans on the producer
    thread, the ``data/queue_depth`` gauge on every ring transition; the
    consumer-side ``train/encode_wait`` span (inside :meth:`get`) is the
    pipeline bubble trace_report's "Pipeline" section reports.
    """

    _DONE = object()

    def __init__(self, source: Iterator, encode: Callable[[Any, int], Any],
                 *, depth: int, start_step: int):
        self._source = source
        self._encode = encode
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._start_step = start_step
        self._gauge = tracing.registry().gauge("data/queue_depth")
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="encode-producer")
        self._thread.start()

    def _safe_put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                self._gauge.set(float(self._q.qsize()))
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        step = self._start_step
        try:
            while not self._stop.is_set():
                # host time blocked on the data pipeline — the span the
                # fused loop emitted from the train thread moves here with
                # the wait itself
                with tracing.span("train/data_wait", step=step):
                    batch = next(self._source, None)
                if batch is None:
                    break
                with tracing.span("train/encode", step=step) as sp:
                    # dcr-hbm: hbm_peak/hbm_delta attrs on the producer's
                    # hot region (no-op where the backend has no stats)
                    from dcr_tpu.obs import memwatch

                    with memwatch.span_hbm(sp):
                        enc = self._encode(batch, step)
                if not self._safe_put((step, enc, None)):
                    return
                step += 1
        except BaseException as e:  # surface loader/encode errors to consumer
            self._safe_put((step, None, e))
            return
        self._safe_put((step, self._DONE, None))

    def get(self, step: int):
        """The encoded batch for ``step`` (producer and consumer advance in
        lockstep order), or None at end of epoch. Producer-side errors
        re-raise here, on the train thread."""
        with tracing.span("train/encode_wait", step=step):
            got_step, enc, err = self._q.get()
        self._gauge.set(float(self._q.qsize()))
        if err is not None:
            raise err
        if enc is self._DONE:
            return None
        if got_step != step:
            raise RuntimeError(
                f"encode ring out of order: got step {got_step}, "
                f"expected {step}")
        return enc

    def stop(self) -> None:
        """Tear down promptly on every exit path (preemption, NaN abort,
        epoch end): set stop, drain until the thread exits."""
        self._stop.set()
        while self._thread.is_alive():
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)


def live_encode(encode_fn: Callable, frozen: dict, mesh,
                root_key: jax.Array) -> Callable[[Any, int], Any]:
    """Producer callable running the real encoder program per batch."""
    def encode(batch, step: int):
        sharded = pmesh.shard_batch(mesh, dict(batch))
        return encode_fn(frozen, sharded, root_key, np.uint32(step))

    return encode


def cached_encode(cache_fn: Callable, reader, mesh, root_key: jax.Array,
                  fallback: Callable[[Any, int], Any]
                  ) -> Callable[[Any, int], Any]:
    """Producer callable serving latents from a verified latent cache.

    A batch whose every index is cached goes through the cache stage (the
    encoders never execute). A batch touching any missing index — a shard
    that failed verification and was quarantined, or an index the
    precompute never covered — falls back to ``fallback`` (the live encode
    stage) for the WHOLE batch and counts ``latentcache/batch_recompute``:
    the deterministic recompute path a corrupt cache degrades to.
    """
    def encode(batch, step: int):
        idx = np.asarray(batch["index"])
        rows = reader.lookup(idx)
        if rows is None:
            R.bump_counter("latentcache/batch_recompute")
            R.log_event("latent_cache_batch_recompute", step=int(step),
                        indices=[int(i) for i in idx[:8]])
            return fallback(batch, step)
        mean, std, ctx = rows
        moments = pmesh.shard_batch(
            mesh, {"mean": mean, "std": std, "ctx": ctx, "index": idx})
        return cache_fn(moments, root_key, np.uint32(step))

    return encode
