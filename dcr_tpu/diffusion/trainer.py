"""The Trainer: wiring loop around the jitted train step.

Library-level equivalent of diff_train.py:main (328-733): builds models/data/
optimizer from a TrainConfig, runs the epoch loop with periodic sample-image
grids (reference 669-701), periodic checkpoints (709-716), metric logging
(703-705) — plus what the reference lacks: full-state resume (SURVEY.md §5.4)
and multi-host awareness (one process per host, GSPMD over the mesh).
"""

from __future__ import annotations

import logging
import os
import time
import zlib
from pathlib import Path
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

import json as _json

from dcr_tpu.core import coordination as C
from dcr_tpu.core import dist
from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing
from dcr_tpu.core.checkpoint import (CheckpointManager, export_hf_layout,
                                     host_copy)
from dcr_tpu.core.config import TrainConfig, run_name, save_config, to_dict, validate_train_config
from dcr_tpu.core.metrics import MetricWriter
from dcr_tpu.core import rng as rngmod
from dcr_tpu.utils import faults
from dcr_tpu.utils import profiling
from dcr_tpu.data.dataset import ObjectAttributeDataset
from dcr_tpu.data.loader import DataLoader
from dcr_tpu.data.tokenizer import TokenizerBase, load_tokenizer
from dcr_tpu.diffusion import train as T
from dcr_tpu.models import schedulers as S
from dcr_tpu.models.text_tower import init_text_tower
from dcr_tpu.models.unet2d import init_unet
from dcr_tpu.models.vae import init_vae, vae_scale_factor
from dcr_tpu.obs import memwatch
from dcr_tpu.parallel import mesh as pmesh

log = logging.getLogger("dcr_tpu")


@compile_surface("train/params_finite")
@jax.jit
def _params_finite(tree) -> jax.Array:
    """True iff every floating leaf is finite (on-device reduction; used to
    reject poisoned checkpoints during NaN rollback)."""
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)
              if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)]
    return jnp.all(jnp.stack(leaves)) if leaves else jnp.asarray(True)


def state_fingerprint(state: "T.TrainState") -> str:
    """crc32 over this host's view of (unet params, step): a cheap cross-host
    divergence probe. Logged at end-of-run on multi-host jobs — where params
    are replicated, equal fingerprints on every rank prove the replicas
    stayed bit-identical through whatever recovery actions the run took
    (FSDP-sharded leaves hash only the local shards, so those fingerprints
    are per-host by construction). Uses the checkpoint layer's host view so
    non-addressable sharded arrays never hit a raising device_get."""
    from dcr_tpu.core.checkpoint import _host_view

    crc = 0
    for leaf in jax.tree.leaves({"unet": state.unet_params, "step": state.step}):
        arr, _, _ = _host_view(leaf)
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return f"{crc:08x}"


def build_modules(cfg: TrainConfig, mesh=None) -> "T.DiffusionModels":
    """Construct the module bundle WITHOUT initializing any params.

    Module objects are static pytree-less config holders; the only arrays here
    are the (tiny) noise-schedule tables. Pairs with abstract_train_state for
    zero-memory lowering (FLOPs accounting, compiles for a described chip)."""
    from dcr_tpu.core.precision import policy_from_string
    from dcr_tpu.models.text_tower import build_text_tower
    from dcr_tpu.models.unet2d import UNet2DCondition
    from dcr_tpu.models.vae import AutoencoderKL

    sched = S.make_schedule(
        num_train_timesteps=cfg.model.num_train_timesteps,
        beta_schedule=cfg.model.beta_schedule,
        beta_start=cfg.model.beta_start, beta_end=cfg.model.beta_end,
        prediction_type=cfg.model.prediction_type)
    return T.DiffusionModels(
        unet=UNet2DCondition(cfg.model, dtype=jnp.float32, mesh=mesh),
        vae=AutoencoderKL(cfg.model, dtype=jnp.float32),
        text_encoder=build_text_tower(
            cfg.model, policy_from_string(cfg.mixed_precision).compute_dtype,
            mesh=mesh),
        schedule=sched)


def abstract_train_state(cfg: TrainConfig, key: Optional[jax.Array] = None) -> "T.TrainState":
    """Shape-only TrainState (ShapeDtypeStruct leaves, zero device memory).

    Runs the full build_models + init_train_state pipeline under
    jax.eval_shape, so optimizer/EMA slots match the real thing exactly.
    Used to lower the train step for XLA cost analysis without allocating
    the ~GBs of SD-2.1 params."""
    def mk(k):
        models, params = build_models(cfg, k)
        return T.init_train_state(cfg, models, unet_params=params["unet"],
                                  text_params=params["text"],
                                  vae_params=params["vae"])

    return jax.eval_shape(mk, key if key is not None else jax.random.key(0))


def build_models(cfg: TrainConfig, key: jax.Array, mesh=None, *,
                 pretrained: Optional[dict] = None,
                 parts: tuple[str, ...] = ("unet", "vae", "text")):
    """Initialize the module bundle + params (random init; finetuning loads a
    converted checkpoint over these via models/convert.py). Passing the mesh
    enables ring-attention sequence parallelism in the UNet when its seq axis
    is >1 (cfg.model.seq_parallel_min_seq).

    A tree handed in under `pretrained` is taken as it is and its
    initialiser never runs (a language-model tower and its random twin do not
    fit one chip together); `parts` names the trees the caller needs (the
    encode leg holds no UNet). The other parts draw what they always drew."""
    models = build_modules(cfg, mesh=mesh)
    ku, kv, kt = jax.random.split(key, 3)
    init = {
        "unet": lambda: init_unet(cfg.model, ku, model=models.unet)[1],
        "vae": lambda: init_vae(cfg.model, kv, model=models.vae)[1],
        "text": lambda: init_text_tower(cfg.model, kt, models.text_encoder),
    }
    pretrained = pretrained or {}
    return models, {name: pretrained[name] if name in pretrained
                    else init[name]() for name in parts}


class Trainer:
    def __init__(self, cfg: TrainConfig, *,
                 dataset: Optional[ObjectAttributeDataset] = None,
                 tokenizer: Optional[TokenizerBase] = None,
                 sample_hook: Optional[Callable] = None,
                 pretrained_params: Optional[dict] = None):
        validate_train_config(cfg)
        dist.initialize()
        # resolve scale_lr into a private copy (the caller's config object is
        # left untouched); the serialized config.json records the effective lr
        cfg = T.resolve_scale_lr(cfg)
        self.cfg = cfg
        # lockstep-replica mode: on backends whose compiler cannot span
        # processes (CPU PJRT — this environment's 2-process resilience
        # tests), every host computes the SAME global batch on a LOCAL mesh,
        # so replicas stay bit-identical with no cross-process XLA at all,
        # while the control plane (rendezvous, agreement, barriers,
        # checkpoint commits) runs for real over the coordination service.
        self.replica_mode = (jax.process_count() > 1
                             and not dist.xla_multiprocess_supported())
        if self.replica_mode:
            log.warning(
                "backend %r cannot compile cross-process XLA: running as "
                "lockstep replicas (local mesh per host, identical data, "
                "coordination-service control plane)", jax.default_backend())
        self.mesh = pmesh.make_mesh(
            cfg.mesh, devices=jax.local_devices() if self.replica_mode else None)
        self.out_dir = Path(cfg.output_dir)
        if dist.is_primary():
            self.out_dir.mkdir(parents=True, exist_ok=True)
            save_config(cfg, self.out_dir / "config.json")
            from dcr_tpu.utils.provenance import stamp

            stamp(self.out_dir)
        self.tokenizer = tokenizer or load_tokenizer(
            cfg.pretrained_model or None,
            vocab_size=cfg.model.text_vocab_size,
            model_max_length=cfg.model.text_max_length)
        if self.tokenizer.vocab_size > cfg.model.text_vocab_size:
            # XLA gathers clamp out-of-range ids instead of failing, so a
            # too-small embedding table would train silently wrong
            raise ValueError(
                f"tokenizer vocab ({self.tokenizer.vocab_size}) exceeds "
                f"model.text_vocab_size ({cfg.model.text_vocab_size})")
        if dist.is_primary():
            self._publish_tokenizer()
        # per-run quarantine manifest: the durable record of every recovered
        # failure (bad samples, bad checkpoints, rollbacks); one file per
        # process so loader workers on every host can record locally
        pidx = dist.process_index()
        qname = "quarantine.jsonl" if pidx == 0 else f"quarantine.p{pidx}.jsonl"
        self.quarantine = R.QuarantineManifest(self.out_dir / qname)
        # span tracing + flight recorder: per-process trace.jsonl under the
        # run dir (DCR_TRACE=0 keeps the flight-recorder ring only), and the
        # anchor for flightrec_<rank>.json on every fatal path
        tracing.configure(self.out_dir, rank=pidx)
        # dcr-hbm: periodic device.memory_stats() -> dcr_device_mem_* gauges
        # (graceful no-op on backends that report none, e.g. XLA:CPU)
        memwatch.start_sampler()
        self.dataset = dataset or ObjectAttributeDataset(
            cfg.data, self.tokenizer, fault=cfg.fault)
        # train_batch_size is per-device (reference semantics: per-GPU batch ×
        # num_processes, diff_train.py:556); each process loads for its local
        # chips. Replica mode: no slicing — every host loads the identical
        # full plan, which is what keeps the replicas bit-identical.
        local_bs = cfg.train_batch_size * jax.local_device_count()
        self.loader = DataLoader(
            self.dataset, batch_size=local_bs,
            num_workers=cfg.data.num_workers, seed=cfg.data.seed,
            process_index=0 if self.replica_mode else dist.process_index(),
            process_count=1 if self.replica_mode else dist.process_count(),
            fault=cfg.fault, quarantine=self.quarantine,
            # sliced multi-host loaders must abort via the pod agreement, not
            # a unilateral worker raise (replica mode raises symmetrically —
            # identical plans — so its local abort stays safe)
            defer_budget_abort=(dist.process_count() > 1
                                and not self.replica_mode))
        root = rngmod.root_key(cfg.seed)
        self.models, params = build_models(cfg, rngmod.stream_key(root, "init"),
                                           mesh=self.mesh,
                                           pretrained=pretrained_params)
        self.state = T.init_train_state(
            cfg, self.models, unet_params=params["unet"],
            text_params=params["text"], vae_params=params["vae"])
        self.state = T.shard_train_state(self.state, self.mesh)
        # dcr-pipe: pipelined mode splits the fused step into a frozen-
        # encoder producer stage + a denoiser-only hot step
        # (diffusion/encode_stage.py). Single-host only: the producer thread
        # dispatching device programs concurrently with the consumer is a
        # collective-ordering hazard on a pod, and the fused path there is
        # already correct.
        self.pipelined = bool(cfg.pipe.enabled or cfg.pipe.latent_cache)
        if self.pipelined and jax.process_count() > 1:
            if cfg.pipe.latent_cache:
                # an explicitly configured cache must never be discarded
                # silently — the whole point of the cache contract is that
                # "slower than asked for" is an error, not a degrade
                raise ValueError(
                    "pipe.latent_cache is single-host for now (the producer "
                    "thread's device dispatch is a collective-ordering "
                    "hazard on a pod) — drop the flag on multi-host runs "
                    "or run the regime matrix on single-host workers")
            log.warning("pipelined training disabled: %d processes (the "
                        "producer thread is single-host only; training "
                        "continues on the fused step)", jax.process_count())
            R.log_event("pipelined_disabled_multihost",
                        processes=jax.process_count())
            self.pipelined = False
        if self.pipelined:
            from dcr_tpu.diffusion import encode_stage as E

            self._E = E
            self.encode_fn = E.make_encode_stage(cfg, self.models, self.mesh)
            self.denoise_fn = E.make_denoise_step(cfg, self.models, self.mesh)
            # the fused program is deliberately NOT built in pipelined mode
            # (one less resident executable); pipelined-off builds ONLY the
            # fused program, whose HLO is unchanged by this feature
            self.step_fn = None
            self._denoise_call = self.denoise_fn
            self._cache_reader = None
            self._cache_fn = None
        else:
            self.step_fn = T.make_train_step(cfg, self.models, self.mesh)
        # what the loop actually calls: the jit function by default, replaced
        # by a warm-cache AOT executable (with a one-way jit fallback) when
        # cfg.warm.dir is set (_warm_start, after restore) — so a preempted
        # pod resumes without re-paying XLA. _pf_fn mirrors this for the
        # params-finite rollback check.
        self._step_call = self.step_fn
        self._pf_fn = _params_finite
        self.train_key = rngmod.stream_key(root, "train")
        # same wandb project name as the reference trainer (diff_train.py:545)
        self.writer = MetricWriter(self.out_dir / "logs", config=to_dict(cfg),
                                   use_wandb=cfg.use_wandb,
                                   wandb_project="diffrep_ft",
                                   run_name=run_name(cfg))
        # -- distributed resilience coordinator (core/coordination.py) -------
        # every recovery decision below (NaN rollback, preemption stop,
        # bad-sample abort, fallback-restore choice) goes through a pod-wide
        # agreement so all hosts act identically at identical steps; on one
        # host the agreement degenerates to pure local logic (no collectives)
        hang_timeout = float(os.environ.get("DCR_HANG_TIMEOUT_S",
                                            cfg.fault.hang_timeout_s) or 0.0)
        coord_timeout = hang_timeout if hang_timeout > 0 else cfg.fault.barrier_timeout_s
        self.coord = C.Coordinator(timeout_s=coord_timeout,
                                   abort_on_timeout=hang_timeout > 0)
        self.coord.bad_sample_budget = (
            self.loader.epoch_bad_budget()
            if cfg.fault.max_bad_sample_frac > 0 else None)
        self.watchdog = C.HangWatchdog(hang_timeout, coordinator=self.coord)
        self.ckpt = CheckpointManager(self.out_dir / "checkpoints",
                                      max_to_keep=cfg.checkpoints_total_limit,
                                      verify=cfg.fault.verify_checkpoints,
                                      quarantine=self.quarantine,
                                      coordinator=self.coord)
        self.sample_hook = sample_hook
        # recovery counters, surfaced through MetricWriter at every log
        # boundary (faults/bad_samples rides self.loader.bad_samples)
        self._rollbacks = 0
        self._ckpt_fallbacks = 0
        self._nan_pending = False
        # set when a coordinated preemption wrote the final checkpoint; the
        # CLI turns it into coordination.EXIT_PREEMPTED for restart wrappers
        self.preempted_exit = False

    def _publish_tokenizer(self) -> None:
        """Copy BPE vocab/merges into <output_dir>/tokenizer so every
        downstream stage (dcr-sample/mitigate on --model_path=<run>) picks up
        the SAME tokenizer automatically — the diffusers checkpoint-dir
        contract the reference relies on (diff_train.py:370-374)."""
        import shutil

        paths = (getattr(self.tokenizer, "vocab_path", None),
                 getattr(self.tokenizer, "merges_path", None))
        if all(p is not None for p in paths):
            tok_dir = self.out_dir / "tokenizer"
            tok_dir.mkdir(parents=True, exist_ok=True)
            for src, dst in zip(paths, ("vocab.json", "merges.txt")):
                src = Path(src)
                if src.resolve() == (tok_dir / dst).resolve():
                    continue
                if src.suffix == ".gz":
                    # republish decompressed — the destination name has no
                    # .gz, so a verbatim copy would be unreadable downstream
                    import gzip

                    (tok_dir / dst).write_text(
                        gzip.open(src, "rt", encoding="utf-8").read())
                else:
                    shutil.copyfile(src, tok_dir / dst)

    # -- checkpoint/resume ---------------------------------------------------

    def save(self, force: bool = False) -> None:
        self.ckpt.save(int(jax.device_get(self.state.step)), self.state, force=force)

    def maybe_resume(self) -> int:
        latest = self.ckpt.latest_step()
        if jax.process_count() > 1:
            # entry into the coordinated restore must be SYMMETRIC: agree on
            # whether anyone sees a checkpoint before any host branches. A
            # host that sees none while a peer sees step N falls through into
            # the restore agreement, which fails fast on every host with the
            # per-rank proposals — instead of the two hosts deadlocking in
            # different collectives.
            views = self.coord.agree_int(-1 if latest is None else int(latest),
                                         "resume_latest")
            if max(views) < 0:
                return 0  # genuinely fresh run on every host
        elif latest is None:
            return 0
        # walk back to the newest VALID checkpoint: a torn/corrupt latest
        # step is quarantined (logged + recorded) and the previous one is
        # restored instead of crashing the resume. Raises only when EVERY
        # checkpoint is invalid — restarting from scratch silently would
        # mask the loss of the whole run.
        state, step, skipped = self.ckpt.restore_latest_valid(self.state)
        self.state = state
        self._ckpt_fallbacks += len(skipped)
        if skipped:
            log.warning("resume fell back past %d corrupt checkpoint(s): %s",
                        len(skipped), [s for s, _ in skipped])
        log.info("resumed from checkpoint step %d", step)
        return step

    def _rollback_possible(self) -> bool:
        """Cheap pre-agreement eligibility check, mirroring the guards at the
        top of :meth:`_rollback_after_nan`. Shared-filesystem checkpoints and
        a deterministic rollback counter make the answer identical on every
        host, so one NaN-seeing host can answer for the pod."""
        if self._rollbacks >= self.cfg.fault.max_rollbacks:
            return False
        self.ckpt.wait()
        return self.ckpt.latest_step() is not None

    def _rollback_after_nan(self, step: int, loss: float) -> bool:
        """NaN rollback-and-skip (opt-in via fault.max_rollbacks): restore the
        last good checkpoint, keep the data pointer at ``step`` so the window
        that produced the non-finite loss is fast-forwarded past, and continue.
        Returns False when rollback is disabled, exhausted, or impossible
        (no checkpoint yet) — the caller then fails fast exactly as the seed.
        Multi-host: callers reach here only under an agreed ROLLBACK decision,
        and the restore itself goes through the coordinated
        ``restore_latest_valid`` (all hosts restore the same step).
        """
        ft = self.cfg.fault
        if self._rollbacks >= ft.max_rollbacks:
            return False
        self.ckpt.wait()  # flush pending async writes before reading steps
        if self.ckpt.latest_step() is None:
            R.log_event("nan_rollback_impossible", at_step=step,
                        reason="no checkpoint to roll back to")
            return False
        skipped_total = 0
        while True:
            try:
                state, ckpt_step, skipped = self.ckpt.restore_latest_valid(self.state)
            except FileNotFoundError as e:
                R.log_event("nan_rollback_impossible", at_step=step, reason=repr(e))
                self._ckpt_fallbacks += skipped_total
                return False
            skipped_total += len(skipped)
            # a checkpoint written between the unchecked window's boundaries
            # can itself carry non-finite params (checksums only prove the
            # bytes round-tripped, not that they were ever sane) — rolling
            # back to it would just re-trip the guard, so quarantine it and
            # keep walking
            if self._pf_fn(T.trainable_of(state, self.cfg.train_text_encoder)):
                break
            self.ckpt._quarantine_step(
                ckpt_step, f"non-finite params (rollback from step {step})")
        self._ckpt_fallbacks += skipped_total
        self._rollbacks += 1
        # params/opt/EMA come from ckpt_step; the step counter is fast-
        # forwarded to the failure point so the loader (and the per-step rng
        # streams, which key off state.step) continue past the bad window
        new_step = jax.device_put(
            jnp.asarray(step, jnp.asarray(state.step).dtype), state.step.sharding)
        self.state = state.replace(step=new_step)
        self.quarantine.record(
            "nan_rollback", at_step=step, restored_step=ckpt_step, loss=loss,
            rollback=self._rollbacks, max_rollbacks=ft.max_rollbacks,
            skipped_steps=step - ckpt_step)
        return True

    def export_checkpoint(self, tag: str = "checkpoint") -> Path:
        """HF-style directory-of-subfolders export (reference save format,
        diff_train.py:709-716) for the sampler/eval stages. When EMA is enabled
        the EMA weights are what gets exported (they're the point of EMA —
        sampling uses them, matching the diffusers copy-into-unet-on-save flow)."""
        out = self.out_dir / tag
        unet_to_export = (self.state.ema_params if self.state.ema_params is not None
                          else self.state.unet_params)
        if dist.is_primary():
            model_config = to_dict(self.cfg.model)
            # one component at a time, and no host copy left cached on the
            # state: the exporter holds a component about three times over
            # while it writes both layouts (10 GB for SD-2.1's UNet), and all
            # three at once beside the cached copies ran a 40 GiB v5e host
            # out of memory (PR 24)
            for name, tree in (("unet", unet_to_export),
                               ("vae", self.state.vae_params),
                               ("text_encoder", self.state.text_params)):
                export_hf_layout(out, model_config=model_config,
                                 **{name: host_copy(tree)})
            export_hf_layout(
                out,
                scheduler_config={
                    "num_train_timesteps": self.cfg.model.num_train_timesteps,
                    "beta_schedule": self.cfg.model.beta_schedule,
                    "beta_start": self.cfg.model.beta_start,
                    "beta_end": self.cfg.model.beta_end,
                    "prediction_type": self.cfg.model.prediction_type,
                },
                model_config=model_config,
            )
        # bounded: a peer that died mid-export must become a BarrierTimeout,
        # not an eternal hang. barrier_timeout_s defaults to 0 (= wait
        # forever), so fall back to the generous allgather bound; operators
        # can still opt out globally with DCR_ALLGATHER_TIMEOUT_S=0.
        dist.barrier("export",
                     timeout_s=(self.cfg.fault.barrier_timeout_s
                                or dist.default_allgather_timeout_s()))
        return out

    def _step_flops(self, sharded_batch) -> float:
        """Per-device FLOPs of the compiled train step (0 if unavailable);
        feeds the MFU telemetry (SURVEY.md §5.1 — absent in the reference).

        Called BEFORE the first step, it compiles the step once and the loop
        then runs that executable: calling the jit function and lowering it
        again for the cost analysis traced and lowered the step twice (19 s
        of tracing at SD-2.1 widths on a v5e host, PR 24, and a second full
        compile wherever no persistent cache is set)."""
        from dcr_tpu.utils.profiling import flops_of_jitted

        if self._step_call is not self.step_fn:
            # warm start already put an AOT executable in the loop
            return flops_of_jitted(self.step_fn, self.state, sharded_batch,
                                   self.train_key)
        from dcr_tpu.core import warmcache

        compiled = self.step_fn.lower(self.state, sharded_batch,
                                      self.train_key).compile()
        self._step_call = warmcache.guarded(compiled, self.step_fn,
                                            "train/step")
        return memwatch.flops_of_compiled(compiled)

    def _denoise_flops(self, enc) -> float:
        """Pipelined-mode MFU numerator: FLOPs of the denoiser-only hot step
        — the point of the split is exactly that this excludes the frozen
        encoders, so the reported MFU is the hot loop's."""
        from dcr_tpu.utils.profiling import flops_of_jitted

        return flops_of_jitted(self.denoise_fn, self._hot, enc,
                               self.train_key)

    # -- preemption ----------------------------------------------------------

    def install_preemption_handler(self, signals=None) -> None:
        """SIGTERM/SIGINT → finish the current step, checkpoint, exit cleanly —
        what preemptible TPU pods need (SURVEY.md §5.3; the reference has no
        recovery story at all). Installed by the train CLI; library users
        opt in explicitly.

        The first signal sets the flag and restores the default disposition, so
        a second Ctrl-C/TERM aborts immediately (e.g. while stuck in a long
        compile before any step boundary). Handlers are uninstalled when
        train() exits. Multi-host: the flag propagates through the
        fault-agreement word (core/coordination.py) at the periodic sync
        point before anyone branches, so one host's signal can't
        desynchronize the pod's collectives — the pod writes ONE synchronized
        final checkpoint and every rank exits with
        ``coordination.EXIT_PREEMPTED``."""
        import signal as _signal

        self._preempted = False
        self._preempt_signals = tuple(signals or (_signal.SIGTERM, _signal.SIGINT))

        def handler(signum, frame):
            log.warning("received signal %d: will checkpoint and stop at the "
                        "next sync point (send again to abort immediately)",
                        signum)
            self._preempted = True
            _signal.signal(signum, _signal.SIG_DFL)

        for sig in self._preempt_signals:
            _signal.signal(sig, handler)

    def _uninstall_preemption_handler(self) -> None:
        import signal as _signal

        for sig in getattr(self, "_preempt_signals", ()):
            _signal.signal(sig, _signal.SIG_DFL)
        self._preempt_signals = ()

    # -- the loop ------------------------------------------------------------

    def _global_bad_count(self) -> int:
        """This host's contribution to the pod-global bad-sample agreement.
        Replica mode: every host quarantines the IDENTICAL samples (same
        data plan), so only the primary contributes — summing all replicas
        would double-count each bad sample once per host."""
        if self.replica_mode and not dist.is_primary():
            return 0
        return self.loader.epoch_bad_count

    def _make_producer(self, epoch_iter, start_step: int):
        """dcr-pipe: the per-epoch producer — live frozen-encoder stage, or
        the latent-cache stage (with the live stage as the recompute
        fallback for quarantined/uncached indices) when a cache is loaded."""
        E = self._E
        live = E.live_encode(self.encode_fn, self._frozen, self.mesh,
                             self.train_key)
        if self._cache_reader is not None:
            encode = E.cached_encode(self._cache_fn, self._cache_reader,
                                     self.mesh, self.train_key, live)
        else:
            encode = live
        return E.EncodeProducer(epoch_iter, encode,
                                depth=self.cfg.pipe.depth,
                                start_step=start_step)

    def _warm_start(self) -> None:
        """Resolve the train step and the params-finite check through the
        persistent executable cache (core/warmcache.py): with ``warm.dir``
        set, a restarted/preempted run loads serialized executables keyed on
        avals/shardings/donation/static-config/topology instead of paying
        XLA again. Any cache problem degrades to the normal jit path —
        warm start can slow a boot down by at most one fingerprint check."""
        cfg = self.cfg
        if not cfg.warm.dir:
            return
        if jax.process_count() > 1:
            # multi-host lowering/dispatch must stay byte-identical across
            # ranks; a per-host cache hit racing a peer's compile is a skew
            # risk not worth the win here — preemption recovery on pods is
            # already coordinated at the checkpoint layer
            R.log_event("warmcache_skipped_multihost",
                        processes=jax.process_count())
            return
        from dcr_tpu.core import warmcache

        cache = warmcache.WarmCache(cfg.warm.dir)
        bs = pmesh.batch_sharding(self.mesh)
        local_bs = cfg.train_batch_size * jax.local_device_count()
        px = cfg.data.resolution
        # the EXACT pytree the loop feeds the step: the loader's Batch dict —
        # pixel_values, input_ids AND the (jit-unused but aval-relevant)
        # sample index — after pmesh.shard_batch placement
        batch_avals = {
            "pixel_values": jax.ShapeDtypeStruct(
                (local_bs, px, px, 3), jnp.float32, sharding=bs),
            "input_ids": jax.ShapeDtypeStruct(
                (local_bs, cfg.model.text_max_length), jnp.int32,
                sharding=bs),
            "index": jax.ShapeDtypeStruct(
                (local_bs,),
                # the loader stamps int64; device placement canonicalizes it
                # (int32 unless x64 is enabled) — mirror that, or the aval
                # would never match the real batch
                jax.dtypes.canonicalize_dtype(jnp.int64), sharding=bs),
        }
        static = {
            "mixed_precision": cfg.mixed_precision,
            "remat": cfg.remat,
            "train_text_encoder": cfg.train_text_encoder,
            "ema_decay": cfg.ema_decay,
            "rand_noise_lam": cfg.rand_noise_lam,
            "mixup_noise_lam": cfg.mixup_noise_lam,
            "gradient_accumulation_steps":
                cfg.optim.gradient_accumulation_steps,
            "use_8bit_adam": cfg.optim.use_8bit_adam,
            "max_grad_norm": cfg.optim.max_grad_norm,
            "train_batch_size": cfg.train_batch_size,
        }
        with R.stage("train_warm"):
            if self.pipelined:
                self._warm_start_pipelined(cache, batch_avals, static)
                res = None
            else:
                res = warmcache.aot_compile(
                    "train/step", self.step_fn,
                    (self.state, batch_avals, self.train_key),
                    static_config=static, cache=cache)
                self._step_call = warmcache.guarded(res.fn, self.step_fn,
                                                    "train/step")
            tree = T.trainable_of(self.state, cfg.train_text_encoder)
            pf = warmcache.aot_compile("train/params_finite", _params_finite,
                                       (tree,), static_config={}, cache=cache)
            self._pf_fn = warmcache.guarded(pf.fn, _params_finite,
                                            "train/params_finite")
        if res is not None:
            log.info("warm start: train/step %s in %.2fs, params_finite %s "
                     "(cache %s)", res.source, res.build_s, pf.source,
                     cfg.warm.dir)

    def _enc_avals(self, local_bs: int):
        """The encoded-batch pytree avals the denoiser hot step consumes —
        the encode stage's output contract, mirrored for AOT lowering."""
        from dcr_tpu.core.precision import policy_from_string
        from dcr_tpu.models.vae import vae_scale_factor

        cfg = self.cfg
        bs = pmesh.batch_sharding(self.mesh)
        lat = cfg.data.resolution // vae_scale_factor(cfg.model)
        policy = policy_from_string(cfg.mixed_precision)
        enc = {
            "latents": jax.ShapeDtypeStruct(
                (local_bs, lat, lat, cfg.model.vae_latent_channels),
                jnp.float32, sharding=bs),
            "index": jax.ShapeDtypeStruct(
                (local_bs,), jax.dtypes.canonicalize_dtype(jnp.int64),
                sharding=bs),
        }
        if cfg.train_text_encoder:
            enc["input_ids"] = jax.ShapeDtypeStruct(
                (local_bs, cfg.model.text_max_length), jnp.int32, sharding=bs)
        else:
            enc["ctx"] = jax.ShapeDtypeStruct(
                (local_bs, cfg.model.text_max_length,
                 cfg.model.cross_attention_dim), policy.compute_dtype,
                sharding=bs)
        return enc

    def _warm_start_pipelined(self, cache, batch_avals: dict,
                              static: dict) -> None:
        """dcr-pipe warm start: pre-populate the denoiser hot step and the
        producer stage (live encode, or the latent-cache stage when a cache
        is configured) from the persistent executable cache."""
        from dcr_tpu.core import warmcache

        cfg = self.cfg
        E = self._E
        local_bs = cfg.train_batch_size * jax.local_device_count()
        enc_avals = self._enc_avals(local_bs)
        hot, frozen = E.split_state(self.state, cfg.train_text_encoder)
        # NOTE: pipe.depth is host-side ring capacity, not baked into any
        # program — keeping it out of the key means retuning the ring never
        # invalidates the warm cache (and matches surfaces.py's statics)
        step_aval = jax.ShapeDtypeStruct((), jnp.uint32)
        res = warmcache.aot_compile(
            "train/denoise", self.denoise_fn,
            (hot, enc_avals, self.train_key),
            static_config=static, cache=cache)
        self._denoise_call = warmcache.guarded(res.fn, self.denoise_fn,
                                               "train/denoise")
        if self._cache_fn is not None:
            moments = dict(self._moments_avals(local_bs),
                           index=enc_avals["index"])
            stage = warmcache.aot_compile(
                "train/encode_cached", self._cache_fn,
                (moments, self.train_key, step_aval),
                static_config=static, cache=cache)
            self._cache_fn = warmcache.guarded(stage.fn, self._cache_fn,
                                               "train/encode_cached")
        else:
            stage = warmcache.aot_compile(
                "train/encode", self.encode_fn,
                (frozen, batch_avals, self.train_key, step_aval),
                static_config=static, cache=cache)
            self.encode_fn = warmcache.guarded(stage.fn, self.encode_fn,
                                               "train/encode")
        log.info("warm start (pipelined): train/denoise %s in %.2fs, "
                 "producer stage %s in %.2fs (cache %s)", res.source,
                 res.build_s, stage.source, stage.build_s, cfg.warm.dir)

    def _moments_avals(self, local_bs: int) -> dict:
        """Latent-cache moments avals (mean/std/ctx) for AOT lowering."""
        from dcr_tpu.models.vae import vae_scale_factor

        cfg = self.cfg
        bs = pmesh.batch_sharding(self.mesh)
        lat = cfg.data.resolution // vae_scale_factor(cfg.model)
        moment = jax.ShapeDtypeStruct(
            (local_bs, lat, lat, cfg.model.vae_latent_channels), jnp.float32,
            sharding=bs)
        ctx = jax.ShapeDtypeStruct(
            (local_bs, cfg.model.text_max_length,
             cfg.model.cross_attention_dim), jnp.float32, sharding=bs)
        return {"mean": moment, "std": moment, "ctx": ctx}

    def train(self) -> dict:
        try:
            return self._train_impl()
        except Exception as e:
            # dcr-hbm: XLA RESOURCE_EXHAUSTED anywhere in the loop (step,
            # encode producer, restore) becomes the typed OOM fatal path —
            # a flight-recorder dump enriched with the device-memory
            # snapshot and live-surface footprints, then exit 85, so a
            # restart wrapper can tell "shrink the batch" apart from a
            # crash. Every other exception keeps its existing semantics.
            if memwatch.is_oom_error(e):
                self.watchdog.stop()
                try:
                    at = int(jax.device_get(self.state.step))
                except Exception:  # state buffers may be donated/deleted
                    # mid-step when the allocator failed — the dump's last
                    # spans carry the step anyway
                    at = -1
                memwatch.oom_abort(f"train step {at}", e)
            raise
        finally:
            # watchdog must die with the loop on EVERY exit path: a fail-fast
            # exception (FloatingPointError, TooManyBadSamples, loader errors)
            # stops the heartbeats, and a still-armed watchdog would then
            # os._exit(EXIT_HANG) mid-unwind, masking the real failure
            self.watchdog.stop()

    def _train_impl(self) -> dict:
        cfg = self.cfg
        start_step = self.maybe_resume()
        if jax.process_count() > 1:
            # startup health check: divergent resume steps (one host restored
            # a checkpoint a peer can't see) would desynchronize every
            # collective that follows — fail fast with the per-rank values
            self.coord.assert_same("resume_step", start_step)
        # dcr-pipe: resolve the latent cache BEFORE warm start (the cache
        # stage is one of the programs to warm) and AFTER restore (the
        # fingerprint hashes the restored frozen params). A cache that
        # cannot serve this run raises LatentCacheError — training against
        # the wrong latents silently is never an option.
        if self.pipelined and cfg.pipe.latent_cache:
            from dcr_tpu.data import latent_cache as LC

            expected = LC.cache_fingerprint(
                cfg, self.dataset, self.tokenizer,
                vae_params=self.state.vae_params,
                text_params=self.state.text_params)
            with R.stage("latent_cache_load"):
                self._cache_reader = LC.LatentCacheReader(
                    cfg.pipe.latent_cache, expected)
            self._cache_fn = self._E.make_cache_stage(cfg, self.models,
                                                      self.mesh)
            cached, total = self._cache_reader.coverage()
            log.info("latent cache %s: %d/%d indices cached (misses "
                     "re-encode live)", cfg.pipe.latent_cache, cached, total)
        # dcr-warm: pre-populate the step programs from the persistent
        # executable cache AFTER restore (the state's avals/shardings are
        # final here), so a preempted pod's first step is a cache load, not
        # a recompile
        self._warm_start()
        if self.pipelined:
            self._hot, self._frozen = self._E.split_state(
                self.state, cfg.train_text_encoder)
        self.watchdog.start()
        steps_per_epoch = self.loader.steps_per_epoch()
        # All periodic cadences (log_every / save_steps / modelsavesteps /
        # max_train_steps) count SYNC steps — completed optimizer updates —
        # matching the reference's accelerate global_step semantics
        # (diff_train.py:669): with gradient_accumulation_steps=N the
        # observable cadence is every N micro-batches. Internal counting
        # (state.step, checkpoint labels, resume) stays in micro-steps so a
        # mid-accumulation preemption resumes exactly where it left off.
        accum = max(1, cfg.optim.gradient_accumulation_steps)
        # stop at whichever comes first in MICRO-batches: the requested number
        # of optimizer steps, or the end of the requested epochs (a trailing
        # partial accumulation at the epoch boundary is simply not applied —
        # accelerate's dataloader-end behavior)
        max_micro = min(cfg.max_train_steps * accum,
                        cfg.num_train_epochs * steps_per_epoch)
        max_sync = max_micro // accum
        step = start_step
        t_last, imgs_last = time.time(), 0
        last_metrics: dict = {}
        # replica mode: every host computes the same batch, so the effective
        # global batch is one replica's (counting all replicas would double-
        # count identical samples in the throughput telemetry)
        global_bs = cfg.train_batch_size * (
            jax.local_device_count() if self.replica_mode else jax.device_count())
        flops_per_step: float | None = None  # filled after first compiled step
        # on-demand device profiling (dcr-scope): DCR_PROFILE_AT_STEP=K arms
        # a jax.profiler capture around micro-steps [K, K+DCR_PROFILE_STEPS)
        # via the same utils/profiling armer serve's POST /debug/profile
        # uses; the artifact lands under <output_dir>/profile
        profile_at = int(os.environ.get("DCR_PROFILE_AT_STEP", "-1") or -1)
        profile_steps = int(os.environ.get("DCR_PROFILE_STEPS", "1") or 1)
        log.info("training: %d optimizer steps (micro-batch accum %d, "
                 "%d micro/epoch), global batch %d",
                 max_sync, accum, steps_per_epoch, global_bs)
        producer = None
        while step < max_micro:
            epoch = step // steps_per_epoch
            epoch_iter = self.loader.epoch(epoch,
                                           start_step=step % steps_per_epoch)
            # dcr-pipe: in pipelined mode the producer thread owns the
            # loader wait (train/data_wait moves to its thread) and runs the
            # frozen-encoder stage up to pipe.depth steps ahead; the train
            # thread's wait on the ring is the train/encode_wait bubble
            producer = (self._make_producer(epoch_iter, start_step=step)
                        if self.pipelined else None)
            try:
                while True:
                    if producer is None:
                        # span around the fetch: host time spent WAITING on
                        # the data pipeline (the loader's own decode work
                        # runs on its worker threads and is traced there as
                        # data/batch spans)
                        with tracing.span("train/data_wait", step=step):
                            batch = next(epoch_iter, None)
                        if batch is None:
                            break
                    else:
                        enc = producer.get(step)
                        if enc is None:
                            break
                        if flops_per_step is None:
                            # before the step: the hot state is donated by
                            # the call below, and lowering needs live avals
                            flops_per_step = self._denoise_flops(enc)
                    if step == profile_at:
                        try:
                            profiling.arm(str(self.out_dir / "profile"),
                                          profile_steps)
                            R.log_trace("profile_armed", at_step=step,
                                        steps=profile_steps)
                        except (RuntimeError, ValueError) as e:
                            R.log_event("profile_arm_failed", error=repr(e))
                    with profiling.capture():
                        # dcr-hbm: hbm_peak/hbm_delta span attrs (no-op on
                        # stats-less backends) — trace_report's Memory
                        # section aggregates resident deltas from these
                        with tracing.span("train/step", step=step) as sp, \
                                memwatch.span_hbm(sp):
                            if producer is None:
                                sharded = pmesh.shard_batch(self.mesh,
                                                            dict(batch))
                                if flops_per_step is None:
                                    flops_per_step = self._step_flops(sharded)
                                self.state, metrics = self._step_call(
                                    self.state, sharded, self.train_key)
                            else:
                                self._hot, metrics = self._denoise_call(
                                    self._hot, enc, self.train_key)
                                # keep the checkpoint/export view current:
                                # pure re-referencing of live buffers, no
                                # copies
                                self.state = self._E.merge_state(
                                    self._hot, self._frozen,
                                    cfg.train_text_encoder)
                    step += 1
                    imgs_last += global_bs
                    self.watchdog.beat(step)
                    # deterministic fault-injection hooks (zero-cost when
                    # DCR_FAULTS is unset): nan_loss poisons the next observed
                    # loss; sigterm drives the real preemption path; hang wedges
                    # this host to drive the collective-hang watchdog; all accept
                    # an @rank= coordinate for single-host faults on a pod
                    if faults.fire("nan_loss", step=step):
                        self._nan_pending = True
                    if faults.fire("oom", step=step):
                        # deterministic RESOURCE_EXHAUSTED: propagates to
                        # train()'s OOM catch exactly like the real thing
                        # (memory-enriched flight-rec dump, exit 85)
                        raise memwatch.InjectedOom(f"train step {step}")
                    if faults.fire("sigterm", step=step):
                        import signal as _signal

                        os.kill(os.getpid(), _signal.SIGTERM)
                    if faults.fire("hang", step=step):
                        C.simulate_hang(f"injected hang at step {step}")
                    at_sync = step % accum == 0
                    sync = step // accum
                    decision: Optional[C.Decision] = None
                    if (at_sync and sync % cfg.log_every == 0) or step == max_micro:
                        metrics = jax.device_get(metrics)
                        if self._nan_pending:
                            metrics["loss"] = float("nan")
                            self._nan_pending = False
                        # ONE agreement round per boundary carries the whole fault
                        # word (nan + preempt + bad samples). On a pod EVERY host
                        # exchanges here even with a locally-finite loss — a
                        # single rank's NaN must move the whole pod in lockstep,
                        # and an un-entered collective is itself a hang. One host:
                        # the exchange is pure local logic, entered only when a
                        # local flag is set.
                        nan_here = not np.isfinite(metrics["loss"])
                        if (nan_here or getattr(self, "_preempted", False)
                                or jax.process_count() > 1):
                            if nan_here:
                                self.coord.note_nan(
                                    step, rollback_ok=self._rollback_possible())
                            if getattr(self, "_preempted", False):
                                self.coord.note_preempt()
                            self.coord.note_bad_samples(self._global_bad_count())
                            decision = self.coord.exchange(step, tag="sync")
                            if decision.action is C.Action.ROLLBACK and \
                                    self._rollback_after_nan(
                                        decision.nan_step, float(metrics["loss"])):
                                # params restored, data pointer kept at the agreed
                                # step — the offending window is skipped; continue
                                if producer is not None:
                                    # re-derive the HOT view from the
                                    # restored state but KEEP the original
                                    # frozen buffers: the live producer's
                                    # closure pins them (bit-equal values —
                                    # frozen params never train), and
                                    # re-merging over them drops the
                                    # restore's duplicate frozen copy
                                    # instead of holding both in HBM until
                                    # the epoch ends
                                    self._hot, _ = self._E.split_state(
                                        self.state, cfg.train_text_encoder)
                                    self.state = self._E.merge_state(
                                        self._hot, self._frozen,
                                        cfg.train_text_encoder)
                                t_last, imgs_last = time.time(), 0
                                continue
                            if decision.action in (C.Action.ROLLBACK, C.Action.FAIL):
                                # fail fast instead of training on garbage (the
                                # reference has no such guard, SURVEY §5.2). Do NOT
                                # save: params already absorbed the non-finite
                                # update — the last periodic checkpoint is the
                                # recovery point. All hosts raise together (same
                                # decision), so no peer is left in a collective.
                                self.ckpt.wait()  # flush pending async writes
                                # fatal path: preserve the last moments (spans,
                                # fault counters) before the raise unwinds
                                tracing.dump_flight_recorder(
                                    f"nan_abort: step {decision.nan_step} loss "
                                    f"{metrics['loss']}")
                                raise FloatingPointError(
                                    f"non-finite loss {metrics['loss']} at step "
                                    f"{decision.nan_step} (ranks {list(decision.nan_ranks)}); "
                                    f"resume from the last good checkpoint "
                                    f"(step {self.ckpt.latest_step()}) under "
                                    f"{self.out_dir}/checkpoints")
                        dt = time.time() - t_last
                        metrics["images_per_sec"] = imgs_last / max(dt, 1e-9)
                        if flops_per_step:
                            from dcr_tpu.utils.profiling import chip_peak_tflops

                            # flops_per_step is the per-chip share (post-partition
                            # cost analysis): per-chip achieved / per-chip peak =
                            # MFU. Bare tflops_per_sec is PER-DEVICE, _total is
                            # the job.
                            steps_done = imgs_last / global_bs
                            per_chip = flops_per_step * steps_done / max(dt, 1e-9)
                            metrics["tflops_per_sec"] = per_chip / 1e12
                            metrics["tflops_per_sec_total"] = (
                                per_chip * jax.device_count() / 1e12)
                            peak = chip_peak_tflops()
                            if peak:    # None on the CPU: no mfu there
                                metrics["mfu"] = per_chip / 1e12 / peak
                        # recovery counters: no retry/rollback is ever silent —
                        # each also logged a structured [fault] line when it fired
                        metrics["faults/bad_samples"] = self.loader.bad_samples
                        metrics["faults/rollbacks"] = self._rollbacks
                        metrics["faults/ckpt_fallbacks"] = self._ckpt_fallbacks
                        # process-wide counters bumped below the Trainer (decode
                        # fast-path fallbacks, kv teardown/gc errors, ...)
                        for name, count in R.counters().items():
                            metrics[f"faults/{name}"] = count
                        if jax.process_count() > 1:
                            # pod-wide fault view: aggregate every host's counters
                            # over the coordination-service KV store (pure gRPC,
                            # timeout-bounded — no XLA collectives in the control
                            # plane). Symmetric: every rank reaches this boundary
                            # in lockstep, so the round can't wedge a peer.
                            rows = dist.kv_allgather(
                                _json.dumps(R.counters()), "fault_counters",
                                timeout_s=dist.default_allgather_timeout_s())
                            pod = tracing.merge_counter_rows(
                                _json.loads(r) for r in rows)
                            for name, count in pod.items():
                                metrics[f"faults_pod/{name}"] = count
                        self.writer.scalars(sync, metrics)
                        last_metrics = {k: float(np.asarray(v)) for k, v in metrics.items()}
                        t_last, imgs_last = time.time(), 0
                    if self.sample_hook and at_sync and sync % cfg.save_steps == 0:
                        self.sample_hook(self, sync)
                    # single-host preemption BETWEEN log boundaries keeps the
                    # seed's act-at-the-very-next-step behavior (pure local
                    # "exchange", no collectives). Multi-host never enters this:
                    # its agreement ran at the uniform log boundary above — a
                    # local flag alone must not start a collective.
                    if (decision is None and jax.process_count() == 1
                            and getattr(self, "_preempted", False)):
                        self.coord.note_preempt()
                        self.coord.note_bad_samples(self._global_bad_count())
                        decision = self.coord.exchange(step, tag="sync")
                    # act on the agreed decision BEFORE the periodic save so the
                    # same step is never written twice inside the shutdown window
                    if decision is not None:
                        if decision.action is C.Action.ABORT_BAD_SAMPLES:
                            from dcr_tpu.data.loader import TooManyBadSamples

                            raise TooManyBadSamples(
                                f"epoch {epoch}: {decision.bad_total} bad samples "
                                f"across {jax.process_count()} hosts exceed the "
                                f"GLOBAL quarantine budget of "
                                f"{self.coord.bad_sample_budget} "
                                f"(max_bad_sample_frac="
                                f"{cfg.fault.max_bad_sample_frac})")
                        if decision.action is C.Action.CHECKPOINT_AND_EXIT:
                            log.warning(
                                "preemption: checkpointing at step %d and "
                                "stopping (resume picks up here; signaled on "
                                "ranks %s)", step, list(decision.preempt_ranks))
                            self.save(force=True)
                            self.ckpt.wait()
                            if jax.process_count() > 1:
                                log.info("state fingerprint at step %d: %s", step,
                                         state_fingerprint(self.state))
                            self.writer.close()
                            self._uninstall_preemption_handler()
                            self.watchdog.stop()
                            self.preempted_exit = True
                            # exit-83 path: the final checkpoint is safe; record
                            # the run's last moments for the restart's operator
                            tracing.dump_flight_recorder(
                                f"preempted: checkpointed at step {step}")
                            return last_metrics
                    if at_sync and sync % cfg.modelsavesteps == 0:
                        self.save()
                    if step >= max_micro:
                        break
            finally:
                # every exit path — epoch end, preemption return, NaN abort,
                # loader error — must tear the producer down promptly so no
                # daemon thread is left dispatching device programs
                if producer is not None:
                    producer.stop()
        self.watchdog.stop()  # export/teardown below has no step heartbeat
        # export BEFORE the final save: orbax's device-to-host transfer
        # leaves a host copy cached on every leaf of the live state (12 GB at
        # SD-2.1 widths, until the state dies), and the exporter's own copies
        # on top of that ran a 40 GiB v5e host out of memory (PR 24)
        self.export_checkpoint()
        self.save(force=True)
        self.ckpt.wait()
        if jax.process_count() > 1:
            log.info("state fingerprint at step %d: %s", step,
                     state_fingerprint(self.state))
        self.writer.close()
        self._uninstall_preemption_handler()
        return last_metrics
