"""Resident generation worker: compiled-sampler registry + batch execution.

The economics of online diffusion serving (DiffusionPipe, arXiv:2405.01248;
PFDiff, arXiv:2408.08822) are all amortization: compilation is paid once per
bucket, the text tower once per unique prompt, and the UNet scan — the real
work — runs over dynamically formed batches. This module is that resident
core, HTTP-free so benches and tests drive it in-process:

- one jitted sampler per :class:`~dcr_tpu.serve.queue.GenBucket`, compiled at
  a FIXED batch shape (``max_batch``, padded). One shape means one program
  AND bit-reproducible results: XLA fuses differently per batch size, so
  variable shapes would make an image depend on who it shared a batch with;
- per-request PRNG keys: every random draw for request i derives from
  ``fold_in(root, seed_i)`` and is generated per-row (vmap), so a prompt
  sampled alone is bit-identical to the same prompt inside a mixed batch;
- the prompt-embedding LRU (:mod:`dcr_tpu.serve.cache`) skips the CLIP text
  tower for repeated prompts;
- a wedged device step trips the coordination hang path (stack dump + exit
  89) via :func:`dcr_tpu.core.resilience.watchdog` instead of hanging the
  port until the scheduler notices.
"""

from __future__ import annotations

import contextlib
import logging
import os
import signal
import threading
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from dcr_tpu.core import resilience as R
from dcr_tpu.core import rng as rngmod
from dcr_tpu.core import tracing
from dcr_tpu.core import warmcache
from dcr_tpu.core.compile_surface import compile_surface
from dcr_tpu.core.config import ServeConfig
from dcr_tpu.core.metrics import LatencyTracker, MetricWriter
from dcr_tpu.models.vae import vae_scale_factor
from dcr_tpu.obs import memwatch
from dcr_tpu.sampling import fastsample
from dcr_tpu.sampling.pipeline import GenerationStack
from dcr_tpu.sampling.sampler import (SAMPLERS, denoise_images,
                                      embedding_noise)
from dcr_tpu.serve.batcher import Batcher
from dcr_tpu.serve.cache import EmbeddingCache, embedding_key, mitigation_tag
from dcr_tpu.serve.queue import (AdmissionError, BucketLimitError,
                                 DrainingError, GenBucket,
                                 InvalidRequestError, MemoryBudgetError,
                                 Request, RequestQueue)
from dcr_tpu.utils import profiling

log = logging.getLogger("dcr_tpu")

MAX_STEPS = 1000        # more denoising steps than train timesteps is nonsense
MAX_RESOLUTION = 4096


def validate_bucket(bucket: GenBucket, *, vae_scale: int) -> None:
    """Reject client-controlled bucket parameters BEFORE they reach jit:
    an invalid value must be a typed 400-class error, not a cryptic compile
    failure (500) — and never a compiled-and-cached degenerate program."""
    if bucket.sampler not in SAMPLERS:
        raise InvalidRequestError(
            f"sampler must be one of {tuple(SAMPLERS)}, got {bucket.sampler!r}")
    if not 1 <= bucket.steps <= MAX_STEPS:
        raise InvalidRequestError(
            f"steps must be in [1, {MAX_STEPS}], got {bucket.steps}")
    if not (vae_scale <= bucket.resolution <= MAX_RESOLUTION
            and bucket.resolution % vae_scale == 0):
        raise InvalidRequestError(
            f"resolution must be a multiple of {vae_scale} in "
            f"[{vae_scale}, {MAX_RESOLUTION}], got {bucket.resolution}")
    if not 0.0 <= bucket.guidance <= 100.0:
        raise InvalidRequestError(
            f"guidance must be in [0, 100], got {bucket.guidance}")
    if not 0.0 <= bucket.rand_noise_lam <= 10.0:
        raise InvalidRequestError(
            f"rand_noise_lam must be in [0, 10], got {bucket.rand_noise_lam}")
    if not 0.0 <= bucket.fast_ratio <= fastsample.MAX_REUSE_RATIO:
        raise InvalidRequestError(
            f"fast_ratio must be in [0, {fastsample.MAX_REUSE_RATIO}], "
            f"got {bucket.fast_ratio}")
    if bucket.fast_order not in (1, 2):
        raise InvalidRequestError(
            f"fast_order must be 1 or 2, got {bucket.fast_order}")


@compile_surface("serve/batch_sampler")
def make_batch_sampler(bucket: GenBucket, models, root_seed: int,
                       batch_size: int):
    """Jitted ``(params, cond, uncond, seeds) -> images`` for one bucket.

    cond/uncond: [B, L, D] prompt embeddings (already encoded/cached);
    seeds: [B] uint32 per-request seeds. Every stochastic draw for row i uses
    only ``fold_in(root_key(root_seed), seeds[i])``-derived keys, generated
    per-row, so row i's image is a pure function of (params, cond[i],
    seeds[i]) — batch composition cannot perturb it. This builder owns the
    per-request keying; the loop is the bulk sampler's
    :func:`~dcr_tpu.sampling.sampler.denoise_images`.
    """
    latent_size = bucket.resolution // vae_scale_factor(models.vae.config)
    latent_ch = models.vae.config.vae_latent_channels
    lam = bucket.rand_noise_lam

    def sample_fn(params, cond, uncond, seeds):
        if cond.shape[0] != batch_size:  # dcr-lint: disable=DCR007 — branch on a STATIC shape, not a traced value: this is the trace-time guard that RAISES before a second batch shape can compile (the exact recompile hazard DCR007 polices)
            # trace-time guard for the load-bearing fixed-shape invariant:
            # a caller skipping execute()'s padding would otherwise silently
            # compile a second program and break batch-composition
            # bit-reproducibility (XLA fuses differently per shape)
            raise ValueError(
                f"batch sampler for {bucket} is compiled at batch="
                f"{batch_size}; got {cond.shape[0]} rows — pad the batch")
        root = rngmod.root_key(root_seed)
        keys = jax.vmap(lambda s: jax.random.fold_in(root, s))(seeds)
        # Newpipe mitigation noise, per-request: fresh noise even for a
        # cache-hit embedding, independent of the rest of the batch
        cond, uncond = jax.vmap(lambda c, u, k: embedding_noise(
            c, u, rngmod.stream_key(k, "emb_noise"), lam))(cond, uncond, keys)
        ctx = jnp.concatenate([uncond, cond], axis=0)      # [2B, L, D]

        x = jax.vmap(lambda k: jax.random.normal(
            rngmod.stream_key(k, "init"),
            (latent_size, latent_size, latent_ch)))(keys)  # [B, h, w, c]
        step_keys = jax.vmap(lambda k: rngmod.stream_key(k, "steps"))(keys)
        return denoise_images(
            models, params, ctx, x, sampler=bucket.sampler,
            steps=bucket.steps, guidance=bucket.guidance,
            fast_ratio=bucket.fast_ratio, fast_order=bucket.fast_order,
            # per-row keys via vmap: the ancestral noise of request i must
            # not depend on batch position or neighbors (the bulk pipeline
            # draws ONE batch-shaped noise per step instead)
            step_noise=lambda step_idx: jax.vmap(
                lambda k: jax.random.normal(jax.random.fold_in(k, step_idx),
                                            x.shape[1:], x.dtype))(step_keys))

    return jax.jit(sample_fn)


@compile_surface("serve/encode")
def make_text_encoder(models):
    """Jitted ``(text_params, ids) -> [B, L, D]`` prompt-embedding step — the
    text tower every cache miss pays. One compiled program per ids shape;
    the service always tokenizes to the model's fixed max length, so in
    practice it compiles once per process."""
    return jax.jit(
        lambda text_params, ids: models.text_encoder.apply(
            {"params": text_params}, ids).last_hidden_state)


class ServeMetrics:
    """Counters + latency reservoir behind one lock; snapshots feed both the
    /metrics endpoint and the MetricWriter scalars."""

    def __init__(self):
        self._lock = threading.Lock()
        self.requests_total = 0
        self.rejected_overload = 0
        self.rejected_draining = 0
        self.rejected_invalid = 0
        self.rejected_bucket_limit = 0
        self.rejected_memory_budget = 0
        self.completed_total = 0
        self.failed_total = 0
        self.batches_total = 0
        self.occupancy_last = 0.0
        self.occupancy_max = 0.0
        self._occupancy_sum = 0.0
        # named: registers in the process-wide telemetry registry, so request
        # latency percentiles ride Prometheus scrapes and flight-rec dumps
        self.latency = LatencyTracker(name="serve/request_latency_s")

    def note_submitted(self) -> None:
        with self._lock:
            self.requests_total += 1

    def note_rejected(self, error: AdmissionError) -> None:
        with self._lock:
            if isinstance(error, DrainingError):
                self.rejected_draining += 1
            elif isinstance(error, InvalidRequestError):
                self.rejected_invalid += 1
            elif isinstance(error, BucketLimitError):
                self.rejected_bucket_limit += 1
            elif isinstance(error, MemoryBudgetError):
                self.rejected_memory_budget += 1
            else:
                self.rejected_overload += 1

    def note_batch(self, n_real: int, batch_size: int, ok: bool) -> None:
        occ = n_real / max(1, batch_size)
        with self._lock:
            self.batches_total += 1
            self.occupancy_last = occ
            self.occupancy_max = max(self.occupancy_max, occ)
            self._occupancy_sum += occ
            if ok:
                self.completed_total += n_real
            else:
                self.failed_total += n_real

    def snapshot(self) -> dict:
        with self._lock:
            batches = self.batches_total
            d = {
                "requests_total": self.requests_total,
                "rejected_overload": self.rejected_overload,
                "rejected_draining": self.rejected_draining,
                "rejected_invalid": self.rejected_invalid,
                "rejected_bucket_limit": self.rejected_bucket_limit,
                "rejected_memory_budget": self.rejected_memory_budget,
                "completed_total": self.completed_total,
                "failed_total": self.failed_total,
                "batches_total": batches,
                "batch_occupancy_last": self.occupancy_last,
                "batch_occupancy_max": self.occupancy_max,
                "batch_occupancy_avg": (self._occupancy_sum / batches
                                        if batches else 0.0),
            }
        pct = self.latency.percentiles((50, 99))
        d["latency_ms"] = {k: round(v * 1000.0, 3) for k, v in pct.items()}
        return d


class GenerationService:
    """The resident serving core: queue + batcher + cache + compiled samplers.

    HTTP-free by design — :mod:`dcr_tpu.serve.server` fronts it for network
    traffic, while benches and tests call :meth:`submit`/:meth:`execute`
    directly. One worker thread drains the queue; handler threads only
    tokenize-and-wait.
    """

    def __init__(self, cfg: ServeConfig, stack: GenerationStack, *,
                 writer: Optional[MetricWriter] = None):
        self.cfg = cfg
        self.stack = stack
        self.queue = RequestQueue(cfg.queue_depth)
        self.batcher = Batcher(cfg.max_batch, cfg.max_wait_ms / 1000.0)
        self.cache = EmbeddingCache(cfg.cache_entries)
        self.metrics = ServeMetrics()
        self._writer = writer
        self._samplers: dict[GenBucket, object] = {}
        # buckets counted against max_compiled_buckets at ADMISSION time, not
        # first compile — otherwise a burst of novel buckets all passes the
        # budget check before the worker compiles any of them
        self._admitted_buckets: set[GenBucket] = set()
        self._samplers_lock = threading.Lock()
        self._vae_scale = vae_scale_factor(stack.models.vae.config)
        # a misconfigured default bucket must fail at STARTUP, not boot a
        # healthy-looking replica that 400s every default request
        validate_bucket(self.default_bucket(), vae_scale=self._vae_scale)
        # dcr-hbm: live dcr_device_mem_* gauges for /metrics and the fleet
        # scrape (graceful no-op where the backend reports no stats)
        memwatch.start_sampler()
        # persistent executable cache (dcr-warm): compiled samplers/encoder
        # are loaded from disk when a verified entry exists, so a respawn
        # reaches ready without paying XLA again
        self._warmcache = (warmcache.WarmCache(cfg.warm.dir)
                           if cfg.warm.dir else None)
        # serializes AOT compiles; kept separate from _samplers_lock so a
        # multi-second compile never blocks admission threads checking the
        # bucket budget
        self._build_lock = threading.Lock()
        # warm-start readiness: begin_warm() computes the plan and flips
        # health to "warming"; warm_start() compiles it and flips back. The
        # event starts SET so in-process services that never warm (tests,
        # benches) report "ok" exactly as before dcr-warm.
        self._warm_plan: Optional[list[GenBucket]] = None
        self._warm_complete = threading.Event()
        self._warm_complete.set()
        self._encode_jit = make_text_encoder(stack.models)
        self._encode = self._encode_jit
        self._tok_fp = stack.tokenizer.fingerprint()
        # copy-risk scoring (dcr-watch): the train-embedding index loads in
        # the BACKGROUND — a multi-GB index (or its SSCD compile) must never
        # delay the port or admission. Until it terminalizes, batches go
        # unscored (copy_risk: null); a failed load degrades to
        # scoring-disabled with a counter, never a dead worker.
        self._risk = None
        self._risk_status = "absent"
        self._risk_done = threading.Event()
        self._pump = None             # IngestPump (dcr-live), risk+ingest on
        self._evidence = None
        self._risk_thread: Optional[threading.Thread] = None
        if cfg.risk.index_path or cfg.risk.store_dir:
            self._risk_status = "loading"
            self._risk_thread = threading.Thread(
                target=self._load_risk_index, daemon=True,
                name="risk-index-load")
            self._risk_thread.start()
        else:
            self._risk_done.set()
        self._uncond: Optional[np.ndarray] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # per-process batch index: the `batch` coordinate of the serve-side
        # fault kinds (worker_crash / worker_hang / slow_step)
        self._batch_index = 0

    # -- request plumbing ----------------------------------------------------

    def default_bucket(self) -> GenBucket:
        c = self.cfg
        ratio, order = fastsample.canonical_plan_params(
            c.num_inference_steps,
            c.fast.reuse_ratio if c.fast.enabled else 0.0, c.fast.order)
        return GenBucket(resolution=c.resolution, steps=c.num_inference_steps,
                         guidance=c.guidance_scale, sampler=c.sampler,
                         rand_noise_lam=c.rand_noise_lam,
                         fast_ratio=ratio, fast_order=order)

    def submit(self, prompt: str, *, seed: int = 0,
               bucket: Optional[GenBucket] = None,
               trace_ctx: Optional[dict] = None) -> Request:
        """Admit a request. Typed AdmissionError on every rejection path:
        InvalidRequestError (bad bucket params), BucketLimitError (would
        compile past the resident-program budget), QueueFullError (overload),
        DrainingError (SIGTERM seen).

        ``trace_ctx`` is the distributed trace context a fleet supervisor
        ships with a dispatched batch (:func:`dcr_tpu.core.tracing.
        wire_context`): when present, this worker's ``serve/request`` span
        joins the supervisor's trace — same trace id, ``remote_parent``
        naming the supervisor root span, ``attempt`` tagging requeued
        re-executions as siblings — instead of starting a disconnected tree.
        """
        bucket = bucket or self.default_bucket()
        try:
            validate_bucket(bucket, vae_scale=self._vae_scale)
            with self._samplers_lock:
                bucket_added = bucket not in self._admitted_buckets
                if bucket_added:
                    if (len(self._admitted_buckets)
                            >= self.cfg.max_compiled_buckets):
                        raise BucketLimitError(
                            f"bucket {bucket} would exceed the resident "
                            f"compiled-sampler budget "
                            f"({self.cfg.max_compiled_buckets}); use an "
                            "already-served parameter combination")
                    # dcr-hbm containment: a NOVEL bucket is a new resident
                    # compiled program — consult the live-surface footprints
                    # before admitting it, so one adversarial request can't
                    # OOM a warm worker (typed 503, never a dead port)
                    self._check_memory_budget(bucket)
                    self._admitted_buckets.add(bucket)
            req = Request(prompt=prompt, seed=int(seed) & 0xFFFFFFFF,
                          bucket=bucket)
            trace_attrs: dict = {}
            if trace_ctx and trace_ctx.get("trace_id"):
                req.trace_id = str(trace_ctx["trace_id"])
                if trace_ctx.get("parent_span") is not None:
                    trace_attrs["remote_parent"] = int(trace_ctx["parent_span"])
                if trace_ctx.get("attempt") is not None:
                    trace_attrs["attempt"] = int(trace_ctx["attempt"])
            else:
                req.trace_id = tracing.new_trace_id()
            # root of this request's span tree (admission -> queue wait ->
            # device step -> respond), closed by the future callback whichever
            # thread resolves it — so the root span's duration IS the
            # request's in-service latency. Attached BEFORE queue.submit
            # publishes the request: the worker can flush a full bucket and
            # read req.span before this thread runs another line. A rejected
            # request's handle is simply never ended (nothing is recorded).
            root = tracing.begin_span("serve/request", parent=None,
                                      trace=req.trace_id,
                                      request_id=req.id, seed=req.seed,
                                      bucket=str(tuple(bucket)), **trace_attrs)
            req.span = root
            try:
                self.queue.submit(req)
            except AdmissionError:
                # a never-queued novel bucket must not consume a resident-
                # program slot (and, under dcr-hbm, a phantom byte
                # reservation) forever. Kept when a concurrently-queued
                # request or a resident sampler still carries it — the rare
                # concurrent-admit race then at worst over-counts by the
                # one slot left registered (the supervisor makes the same
                # trade).
                if bucket_added:
                    with self._samplers_lock:
                        if (bucket not in self._samplers
                                and not self.queue.has_bucket(bucket)):
                            self._admitted_buckets.discard(bucket)
                raise
        except AdmissionError as e:
            self.metrics.note_rejected(e)
            tracing.event("serve/rejected", error=type(e).__name__)
            raise
        self.metrics.note_submitted()
        # safe after submit: add_done_callback fires immediately on an
        # already-resolved future, and .end() is idempotent
        req.future.add_done_callback(
            lambda f: root.end(error=repr(f.exception()))
            if f.exception() is not None else root.end())
        return req

    def _check_memory_budget(self, bucket: GenBucket) -> None:
        """Reject a novel bucket whose estimated footprint exceeds remaining
        device memory (caller holds ``_samplers_lock``). The estimate is the
        largest non-argument footprint among this process's live
        ``serve/batch_sampler`` programs (same model, same padded batch
        shape — only baked-in statics differ); no live sibling or no
        backend stats means no check, exactly the pre-dcr-hbm behavior.

        Admitted-but-not-yet-compiled novel buckets RESERVE the estimate:
        live stats only move once a program actually compiles, so without
        the reservation a burst of distinct novel buckets would all pass
        against the same unchanged reading and OOM together — the exact
        hole this check exists to close."""
        estimate = memwatch.estimate_surface_bytes("serve/batch_sampler")
        if estimate is None:
            return
        remaining = memwatch.remaining_device_bytes()
        if remaining is None:
            return
        pending = sum(1 for b in self._admitted_buckets
                      if b not in self._samplers)
        needed = estimate * (pending + 1)
        if needed > remaining:
            tracing.registry().counter(
                "serve/rejected_memory_budget").inc()
            R.log_event("memory_budget_rejected", bucket=str(tuple(bucket)),
                        estimate_bytes=estimate, pending_compiles=pending,
                        needed_bytes=needed, remaining_bytes=remaining)
            raise MemoryBudgetError(
                f"bucket {bucket} would compile a new resident program "
                f"(~{estimate} bytes estimated from live surfaces; "
                f"{pending} admitted compile(s) already pending) past "
                f"remaining device memory ({remaining} bytes); use an "
                "already-served parameter combination")

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serve-worker")
        self._thread.start()

    def begin_drain(self) -> None:
        """Stop admission; the worker keeps going until the queue is empty."""
        self.queue.close()
        self._stop.set()

    def join_drained(self, timeout: Optional[float] = None) -> bool:
        """Wait for the worker to finish the backlog; True when fully drained."""
        if self._thread is None:
            return True
        self._thread.join(timeout)
        return not self._thread.is_alive() and self.queue.empty()

    def stop(self, timeout: Optional[float] = None) -> bool:
        self.begin_drain()
        drained = self.join_drained(timeout)
        pump = self._pump
        if pump is not None:
            # after the worker drained: the pump finishes its queued
            # backlog (WAL-durable) and releases the writer lease
            pump.stop()
        return drained

    @property
    def draining(self) -> bool:
        return self.queue.closed

    # -- execution -----------------------------------------------------------

    def _sampler_for(self, bucket: GenBucket):
        with self._samplers_lock:
            fn = self._samplers.get(bucket)
        if fn is not None:
            return fn
        with self._build_lock:
            # double-checked: the worker thread and warm_start can race on
            # the same bucket; the second builder reuses the first's program
            with self._samplers_lock:
                fn = self._samplers.get(bucket)
                if fn is not None:
                    return fn
            fn = self._build_sampler(bucket)
            with self._samplers_lock:
                self._samplers[bucket] = fn
        return fn

    def _build_sampler(self, bucket: GenBucket):
        """AOT-lower the bucket's sampler and resolve it through the warm
        cache: a verified cache entry deserializes in O(load); otherwise XLA
        compiles now and the executable is persisted for the next
        incarnation. Returns a ready-to-call program (with a one-way degrade
        to the plain jit path should the executable ever reject its inputs)."""
        L = self.stack.model_cfg.text_max_length
        D = self.stack.model_cfg.cross_attention_dim
        jit_fn = make_batch_sampler(bucket, self.stack.models,
                                    self.cfg.seed, self.cfg.max_batch)
        emb = jax.ShapeDtypeStruct((self.cfg.max_batch, L, D), jnp.float32)
        seeds = jax.ShapeDtypeStruct((self.cfg.max_batch,), jnp.uint32)
        res = warmcache.aot_compile(
            "serve/batch_sampler", jit_fn,
            (self.stack.params, emb, emb, seeds),
            static_config={
                "resolution": bucket.resolution, "steps": bucket.steps,
                "guidance": bucket.guidance, "sampler": bucket.sampler,
                "rand_noise_lam": bucket.rand_noise_lam,
                "max_batch": self.cfg.max_batch,
                # the fast plan is derived from these: a different plan is a
                # different program, so it must be a different cache key
                "fast_ratio": bucket.fast_ratio,
                "fast_order": bucket.fast_order,
            },
            cache=self._warmcache)
        if res.source == "cache":
            log.info("serve: bucket %s warm-loaded from cache in %.2fs "
                     "(batch=%d)", bucket, res.build_s, self.cfg.max_batch)
        else:
            # trace_report counts these per bucket AND per process
            # incarnation (os_pid): a warm respawn must show zero
            log.info("serve: compiled sampler for bucket %s at batch=%d "
                     "in %.2fs", bucket, self.cfg.max_batch, res.build_s)
            tracing.event("serve/compile", bucket=str(tuple(bucket)),
                          max_batch=self.cfg.max_batch, os_pid=os.getpid())
        if self._warmcache is not None and self._warm_complete.is_set():
            # record a lazily admitted bucket for the NEXT incarnation's
            # warm plan. LRU + budget-capped: active buckets move to the
            # manifest tail, stale ones age out the front — a long-lived
            # shared cache dir can never fill every future incarnation's
            # resident-program budget with history. During the warm phase
            # itself this is skipped: warm_start() records the whole plan in
            # ONE batched update instead of a read-merge-rewrite per bucket.
            warmcache.update_warm_manifest(
                self.cfg.warm.dir, [list(tuple(bucket))],
                max_entries=self.cfg.max_compiled_buckets)
        return warmcache.guarded(res.fn, jit_fn, "serve/batch_sampler")

    # -- warm-start readiness (dcr-warm) -------------------------------------

    def begin_warm(self) -> int:
        """Enter the warming state and compute the warm plan: the default
        bucket plus valid buckets from the previous incarnation's warm
        manifest NEWEST-first (the manifest is LRU-ordered), capped by the
        compiled-bucket budget. /healthz reports "warming" from here until
        :meth:`warm_start` finishes. Returns the plan size (0 = warm start
        disabled)."""
        if not self.cfg.warm.warm_start:
            return 0
        plan = [self.default_bucket()]
        if self._warmcache is not None:
            from dcr_tpu.serve.fleet import bucket_from_tuple

            for entry in reversed(
                    warmcache.read_warm_manifest(self.cfg.warm.dir)):
                try:
                    b = bucket_from_tuple(entry)
                    validate_bucket(b, vae_scale=self._vae_scale)
                except (TypeError, ValueError, InvalidRequestError) as e:
                    # a stale hint (config change, hand edit) costs a log
                    # line, never a boot
                    R.log_event("warm_manifest_entry_invalid", entry=entry,
                                error=repr(e))
                    R.bump_counter("warmcache/manifest_entry_invalid")
                    continue
                if b not in plan:
                    plan.append(b)
        # the plan must leave ADMISSION HEADROOM: warm buckets enter
        # _admitted_buckets (they are resident programs), and compiled
        # programs never evict — a plan that filled the whole budget with
        # the previous incarnation's traffic would 503 every novel bucket
        # for this process's lifetime AND keep the manifest from ever
        # learning the new traffic (rejected buckets never compile). One
        # reserved slot breaks that wedge: the novel bucket admits,
        # compiles, and the LRU manifest warms it next incarnation.
        cap = max(1, self.cfg.max_compiled_buckets - 1)
        if len(plan) > cap:
            R.log_event("warm_plan_over_budget", planned=len(plan), cap=cap,
                        budget=self.cfg.max_compiled_buckets)
            plan = plan[:cap]
        self._warm_plan = plan
        self._warm_complete.clear()
        return len(plan)

    def warm_start(self) -> dict:
        """Execute the warm plan: text encoder + uncond embedding first
        (every batch needs them), then one resident program per planned
        bucket — each from the persistent cache when a verified entry
        exists. Flips /healthz from "warming" to "ok" when done."""
        if not self.cfg.warm.warm_start:
            return {"buckets_warm": 0, "buckets_total": 0, "seconds": 0.0}
        if self._warm_plan is None:
            self.begin_warm()
        t0 = time.monotonic()
        self._warm_encoder()
        self._uncond_embedding()
        for bucket in self._warm_plan:
            with self._samplers_lock:
                self._admitted_buckets.add(bucket)
            self._sampler_for(bucket)
        if self._warmcache is not None:
            # one batched manifest update for the whole plan (per-bucket
            # updates during warming are suppressed in _build_sampler)
            warmcache.update_warm_manifest(
                self.cfg.warm.dir,
                [list(tuple(b)) for b in self._warm_plan],
                max_entries=self.cfg.max_compiled_buckets)
        self._warm_complete.set()
        doc = {"buckets_warm": len(self._warm_plan),
               "buckets_total": len(self._warm_plan),
               "seconds": round(time.monotonic() - t0, 3)}
        R.log_trace("warm_start_done", **doc)
        return doc

    def _warm_encoder(self) -> None:
        """AOT the text-encoder program through the warm cache (the tower
        every cache-miss embedding pays)."""
        ids = self.stack.tokenizer([""])
        res = warmcache.aot_compile(
            "serve/encode", self._encode_jit,
            (self.stack.params["text"], ids),
            static_config={
                "text_max_length": self.stack.model_cfg.text_max_length},
            cache=self._warmcache)
        self._encode = warmcache.guarded(res.fn, self._encode_jit,
                                         "serve/encode")

    def health(self) -> str:
        if self.draining:
            return "draining"
        if not self._warm_complete.is_set():
            return "warming"
        return "ok"

    def health_doc(self) -> dict:
        """The /healthz document: never plain "ok" before the warm plan is
        compiled — balancers and the fleet supervisor gate on it."""
        with self._samplers_lock:
            warm = len(self._samplers)
        total = max(len(self._warm_plan or ()), warm)
        doc = {"status": self.health(), "buckets_warm": warm,
               "buckets_total": total, "risk": self._risk_status}
        if self._pump is not None:
            doc["ingest"] = self._pump.stats()
        return doc

    def _uncond_embedding(self) -> np.ndarray:
        if self._uncond is None:
            ids = self.stack.tokenizer([""])
            self._uncond = np.asarray(
                self._encode(self.stack.params["text"], ids))[0]
        return self._uncond

    def _cond_embedding(self, req: Request, mitigation: str) -> np.ndarray:
        key = embedding_key(self._tok_fp, req.prompt, mitigation)
        emb = self.cache.get(key)
        req.cache_hit = emb is not None
        if emb is None:
            ids = self.stack.tokenizer([req.prompt])
            emb = np.asarray(self._encode(self.stack.params["text"], ids))[0]
            self.cache.put(key, emb)
        return emb

    # -- copy-risk scoring (dcr-watch) ---------------------------------------

    def _load_risk_index(self) -> None:
        """Background loader: dump -> verified index -> compiled pipeline
        (extractor + top-k scorer through warmcache). Flips risk status
        loading -> ok|failed; /healthz and the fleet lease report it."""
        from dcr_tpu.obs.copyrisk import CopyRiskIndex, EvidenceRecorder

        cfg = self.cfg
        source = cfg.risk.store_dir or cfg.risk.index_path
        try:
            with R.stage("risk_index_load"):
                index = CopyRiskIndex.load(cfg.risk, batch=cfg.max_batch,
                                           warm_dir=cfg.warm.dir)
        except Exception as e:
            R.log_event("risk_index_load_failed", path=source,
                        error=repr(e))
            R.bump_counter("copy_risk/index_load_failed")
            self._risk_status = "failed"
            self._risk_done.set()
            return
        ev_dir = cfg.risk.evidence_dir
        if not ev_dir:
            base = tracing.trace_dir()
            ev_dir = str(base / "risk_evidence") if base is not None else ""
        self._evidence = EvidenceRecorder(ev_dir or None,
                                          cfg.risk.max_evidence)
        if cfg.risk.ann and cfg.slo.enabled:
            # dcr-slo: sampled shadow-exact recall probe rides the ANN
            # scoring path — the full-probe query is its own exact oracle
            from dcr_tpu.obs.recall_probe import RecallProbe

            index.recall_probe = RecallProbe(
                every_n=cfg.slo.recall_probe_every_n,
                k=cfg.slo.recall_probe_k,
                window=cfg.slo.recall_probe_window)
        self._risk = index
        self._risk_status = "ok"
        self._risk_done.set()
        log.info("serve: copy-risk index ok — %d train embeddings from %s "
                 "(threshold %.3f%s)", len(index), source,
                 cfg.risk.threshold,
                 f", evidence -> {ev_dir}" if ev_dir else "")
        if cfg.ingest.enabled and cfg.risk.store_dir:
            self._start_ingest(index)

    def _start_ingest(self, index) -> None:
        """dcr-live: stream every scored generation's SSCD embedding into
        the store. The pump owns the writer lease and the compaction loop;
        the index's live-tail hook makes acked-but-uncompacted rows visible
        to `/check` and per-response scoring immediately."""
        from dcr_tpu.serve.ingest import IngestPump

        icfg = self.cfg.ingest
        pump = IngestPump(
            self.cfg.risk.store_dir, embed_dim=index._store.embed_dim,
            queue_max=icfg.queue_max, batch_rows=icfg.batch_rows,
            seal_rows=icfg.seal_rows, compact_rows=icfg.compact_rows,
            lease_s=icfg.lease_s,
            owner=f"serve-worker.{os.getpid()}",
            on_snapshot=lambda v: index.refresh_store())
        index.live_tail = pump.tail
        self._pump = pump.start()
        log.info("serve: live ingest on — store %s (queue %d, compact "
                 "every %d rows)", self.cfg.risk.store_dir, icfg.queue_max,
                 icfg.compact_rows)

    def risk_status(self) -> str:
        """absent | loading | ok | failed."""
        return self._risk_status

    def wait_risk_ready(self, timeout: float) -> bool:
        """True once the index load terminalized (ok OR failed)."""
        return self._risk_done.wait(timeout)

    def _score_risk(self, requests: list[Request], images: np.ndarray,
                    ids: list, traces: list) -> None:
        """Score one finished batch against the train index: `copy_risk` on
        each request, sim histogram + flagged counters, a `risk/flagged`
        event and bounded evidence dump per over-threshold generation. Any
        failure is counted and the batch ships unscored — scoring must
        never fail generation."""
        from dcr_tpu.obs import copyrisk

        index = self._risk
        if index is None:
            return
        rcfg = self.cfg.risk
        try:
            with tracing.span("serve/risk_score", batch=len(requests),
                              request_ids=ids, trace_ids=traces) as sp:
                scores, feats = index.score_batch_with_features(images)
                agg = copyrisk.observe_scores(scores, rcfg.threshold)
                # per-row sims/prompts ride the span: tools/risk_report's
                # per-prompt breakdown and trace_report's percentiles come
                # from here
                sp.attrs.update(
                    sims=[round(s.max_sim, 6) for s in scores],
                    prompts=[r.prompt for r in requests],
                    flagged=agg["flagged"])
        except Exception as e:
            R.log_event("risk_score_failed", batch=len(requests),
                        error=repr(e))
            R.bump_counter("copy_risk/score_failed")
            return
        for req, score, img in zip(requests, scores, images):
            req.risk = score.doc(rcfg.threshold)
            if score.max_sim >= rcfg.threshold:
                tracing.event("risk/flagged", trace=req.trace_id,
                              request_id=req.id, seed=req.seed,
                              prompt=req.prompt,
                              max_sim=round(score.max_sim, 6),
                              top_key=score.top_key,
                              threshold=rcfg.threshold)
                if self._evidence is not None:
                    self._evidence.record(
                        img, score, rcfg.threshold, request_id=req.id,
                        prompt=req.prompt, seed=req.seed,
                        bucket=list(tuple(req.bucket)), trace=req.trace_id)
        pump = self._pump
        if pump is not None:
            # enqueue-and-forget: offer() never blocks — a full queue drops
            # the row and bumps dcr_ingest_dropped_total, generation latency
            # is untouched (the bench_ingest p99 gate)
            for req, row in zip(requests, feats):
                pump.offer(row, f"gen/{req.trace_id or req.id}")

    def check(self, body: dict) -> dict:
        """``POST /check``: score ONE submitted image against the train
        index — ROADMAP item 5's online "is this a copy?" query. Body:
        ``{"image_png_b64": <base64 image>}``. Raises RiskUnavailableError
        (503) while the index is absent/loading/failed, ValueError (400) on
        an undecodable body."""
        from dcr_tpu.obs.copyrisk import (RiskUnavailableError,
                                          decode_image_b64)

        index = self._risk
        if index is None:
            raise RiskUnavailableError(
                f"risk index is {self._risk_status} (source="
                f"{(self.cfg.risk.store_dir or self.cfg.risk.index_path)!r})",
                status=self._risk_status)
        image = decode_image_b64(body)
        with tracing.span("serve/risk_score", source="check", batch=1) as sp:
            score = index.score_batch(image[None])[0]
            sp.attrs.update(sims=[round(score.max_sim, 6)])
        reg = tracing.registry()
        reg.counter("copy_risk/checked_total").inc()
        reg.histogram("copy_risk/sim").observe(score.max_sim)
        return {**score.doc(self.cfg.risk.threshold),
                "threshold": self.cfg.risk.threshold,
                "index_size": len(index)}

    def execute(self, requests: list[Request]) -> np.ndarray:
        """Run one bucket-coherent batch; returns float32 [n, H, W, 3].

        Pads to the fixed ``max_batch`` shape with uncond-embedding rows
        (results discarded), so every batch of a bucket hits the same
        compiled program regardless of occupancy.
        """
        if not requests:
            return np.zeros((0,), np.float32)
        bucket = requests[0].bucket
        assert all(r.bucket == bucket for r in requests), \
            "execute() requires a bucket-coherent batch"
        n = len(requests)
        pad = self.cfg.max_batch - n
        if pad < 0:
            raise ValueError(f"batch of {n} exceeds max_batch={self.cfg.max_batch}")
        fn = self._sampler_for(bucket)
        ids = [r.id for r in requests]
        traces = [r.trace_id for r in requests]
        # batch assembly: tokenize + text tower (or cache hit) + padding.
        # Batch-level spans carry the member request ids AND trace ids (the
        # fleet merge attributes batch time to each member's tree through
        # them); the per-request children (queue wait, respond) parent on
        # each request's root span.
        with tracing.span("serve/assemble", batch=n, request_ids=ids,
                          trace_ids=traces):
            mitigation = mitigation_tag(bucket)
            uncond_row = self._uncond_embedding()
            cond = np.stack([self._cond_embedding(r, mitigation) for r in requests]
                            + [uncond_row] * pad)
            uncond = np.stack([uncond_row] * self.cfg.max_batch)
            seeds = np.asarray([r.seed for r in requests] + [0] * pad, np.uint32)
        # profiling.capture is a no-op unless /debug/profile (or the trainer's
        # DCR_PROFILE_AT_STEP) armed a jax.profiler window over the next K
        # device steps
        # fast-sampling accounting: the plan is static per bucket, so the
        # denoiser-call reduction is known on the host without touching the
        # device. One sample/fast span per accelerated batch execution
        # (args.batch = trajectories in it) feeds trace_report's "Fast
        # sampling" section; dense-bucket traces keep their pre-fast shape.
        plan = fastsample.fast_plan(bucket.steps, bucket.fast_ratio)
        calls = fastsample.unet_calls(plan)
        fast_span = (tracing.span("sample/fast", steps=bucket.steps,
                                  unet_calls=calls, batch=n,
                                  fast_ratio=bucket.fast_ratio,
                                  fast_order=bucket.fast_order,
                                  sampler=bucket.sampler)
                     if calls < bucket.steps else contextlib.nullcontext())
        with profiling.capture():
            # dcr-hbm: hbm_peak/hbm_delta attrs on the device step (no-op
            # where the backend reports no memory stats)
            with tracing.span("serve/device_step", batch=n, request_ids=ids,
                              trace_ids=traces,
                              bucket=str(tuple(bucket))) as dsp, \
                    memwatch.span_hbm(dsp):
                with fast_span:
                    # np.asarray forces the transfer, so these spans close
                    # only when the device work is actually done — real
                    # step time, not dispatch
                    images = np.asarray(
                        fn(self.stack.params, cond, uncond, seeds))
        images = images[:n]
        # copy-risk scoring runs on the HOST COPY after the device step:
        # generation is already done, so images are bit-identical with
        # scoring on or off
        self._score_risk(requests, images, ids, traces)
        return images

    # -- the drain loop ------------------------------------------------------

    def _on_hang(self) -> None:
        from dcr_tpu.core.coordination import hang_abort

        hang_abort("serve_batch",
                   detail=f"sampler step exceeded {self.cfg.hang_timeout_s}s")

    def _inject_batch_faults(self, batch_index: int) -> None:
        """Serve-side deterministic fault hooks (utils/faults.py), fired
        inside the batch watchdog window so a wedge is caught by the same
        machinery a real one would be. ``worker_crash`` is a true SIGKILL —
        no drain, no flush, no exit handler — because that is the death a
        fleet supervisor must requeue around; ``worker_hang`` wedges this
        thread exactly like a dead collective; ``slow_step`` is a straggler
        (DCR_SLOW_STEP_S, default 30s) for latency/SLO chaos."""
        from dcr_tpu.utils import faults

        if faults.fire("worker_crash", batch=batch_index):
            os.kill(os.getpid(), signal.SIGKILL)
        if faults.fire("worker_hang", batch=batch_index):
            from dcr_tpu.core.coordination import simulate_hang

            simulate_hang(f"worker_hang@batch={batch_index}")
        if faults.fire("slow_step", batch=batch_index):
            time.sleep(float(os.environ.get("DCR_SLOW_STEP_S", "30")))
        if faults.fire("oom", batch=batch_index):
            # deterministic RESOURCE_EXHAUSTED through the real batch path:
            # _process's OOM catch dumps the memory-enriched flight recorder
            # and exits 85 — the typed death a fleet supervisor requeues
            # around with zero drops
            raise memwatch.InjectedOom(f"serve batch {batch_index}")

    def _process(self, batch: list[Request]) -> None:
        t0 = time.monotonic()
        now_wall = time.time()
        batch_index = self._batch_index
        self._batch_index += 1
        for req in batch:
            # queue wait measured from the admission stamp, recorded
            # retroactively under the request's root span: the number the
            # batcher's deadline policy is supposed to bound
            waited = t0 - req.enqueued_at
            tracing.complete_span(
                "serve/queue_wait", start_wall=now_wall - waited, dur_s=waited,
                parent=req.span.id if req.span is not None else None,
                trace=req.trace_id, request_id=req.id)
        try:
            # the watchdog turns a wedged device step into a structured
            # post-mortem + EXIT_HANG instead of a silently dead port
            with R.watchdog("serve:batch", self.cfg.hang_timeout_s,
                            on_timeout=self._on_hang):
                self._inject_batch_faults(batch_index)
                images = self.execute(batch)
        except Exception as e:
            if memwatch.is_oom_error(e):
                # dcr-hbm fatal path: the device allocator failed — this
                # process cannot promise any further batch, so die TYPED
                # (exit 85) with a memory-enriched post-mortem instead of
                # failing one batch and serving the next from a poisoned
                # allocator. In a fleet the supervisor requeues the
                # journaled in-flight requests onto survivors (zero drops);
                # futures are deliberately left for the death to break.
                with self._samplers_lock:
                    buckets = [tuple(b) for b in self._samplers]
                memwatch.oom_abort(
                    f"serve batch {batch_index} bucket {batch[0].bucket}",
                    e, buckets=buckets)
            R.log_event("serve_batch_failed", batch=len(batch),
                        bucket=str(batch[0].bucket), error=repr(e))
            for req in batch:
                if not req.future.done():
                    req.future.set_exception(e)
            self.metrics.note_batch(len(batch), self.cfg.max_batch, ok=False)
            return
        now = time.monotonic()
        for req, img in zip(batch, images):
            self.metrics.latency.observe(now - req.enqueued_at)
            req.future.set_result(img)
        self.metrics.note_batch(len(batch), self.cfg.max_batch, ok=True)
        log.info("serve: batch of %d/%d in %.3fs (queue depth %d)",
                 len(batch), self.cfg.max_batch, now - t0, self.queue.depth())
        if self._writer is not None:
            try:
                snap = self.metrics.snapshot()
                cache = self.cache.stats()
                self._writer.scalars(snap["batches_total"], {
                    "serve/queue_depth": self.queue.depth(),
                    "serve/batch_occupancy": snap["batch_occupancy_last"],
                    "serve/cache_hit_rate": cache["hit_rate"],
                    "serve/latency_p50_ms": snap["latency_ms"]["p50"],
                    "serve/latency_p99_ms": snap["latency_ms"]["p99"],
                })
            except Exception as e:
                # telemetry must never stop serving (a full disk under
                # --logdir is not a generation failure) — the requests were
                # already answered above
                R.log_event("serve_metrics_write_failed", error=repr(e))
                R.bump_counter("serve_metrics_write_failed")

    def _run(self) -> None:
        while True:
            batch = self.batcher.next_batch(self.queue, stop=self._stop)
            if batch is None:
                break
            try:
                self._process(batch)
            except Exception as e:
                # last-resort guard: _process already converts generation
                # failures into per-request exceptions, so anything landing
                # here is a serving-layer bug — fail the batch's futures and
                # keep the port alive rather than dying silently with
                # /healthz still reporting ok
                R.log_event("serve_worker_error", error=repr(e),
                            batch=len(batch))
                for req in batch:
                    if not req.future.done():
                        req.future.set_exception(e)
        log.info("serve: worker drained and stopped")

    # -- on-demand device profiling ------------------------------------------

    def profile(self, body: dict) -> dict:
        """Arm a ``jax.profiler`` capture around the next K
        ``serve/device_step`` executions (``POST /debug/profile``). Body:
        ``{"steps"?: int, "logdir"?: str}``. Returns the armed status doc
        including the artifact directory the trace will land in; poll
        ``GET /debug/profile`` until ``artifact`` is set."""
        steps = int(body.get("steps", 1))
        logdir = body.get("logdir")
        if not logdir:
            base = tracing.trace_dir()
            if base is None:
                raise ValueError(
                    "no profile destination: pass 'logdir' or run the "
                    "worker with --logdir")
            logdir = str(base / "profile")
        return profiling.arm(logdir, steps)

    def profile_status(self) -> dict:
        return profiling.status()

    # -- introspection -------------------------------------------------------

    def status(self) -> dict:
        """The /metrics document."""
        d = self.metrics.snapshot()
        d["queue_depth"] = self.queue.depth()
        d["draining"] = self.draining
        d["cache"] = self.cache.stats()
        risk = self._risk
        d["risk"] = {"status": self._risk_status,
                     "index_size": len(risk) if risk is not None else 0}
        if self._pump is not None:
            d["ingest"] = self._pump.stats()
        with self._samplers_lock:     # worker thread mutates concurrently
            d["compiled_buckets"] = [tuple(b) for b in self._samplers]
        return d
