"""Profiling + MFU telemetry.

The reference has no tracing at all (SURVEY.md §5.1 — its closest artifact is
MetricLogger's iter/data timing). Here: the chips' published peaks, the
per-device FLOPs of a jitted step from XLA's cost analysis (the trainer's
``mfu``), and an armer that captures the next K hot regions with
``jax.profiler`` (``DCR_PROFILE_AT_STEP``, ``POST /debug/profile``); the
capture shows core/tracing's spans as ``dcr/<name>`` host events beside the
device ops.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

# Dense bf16 peak TFLOP/s of one chip, keyed by the device_kind JAX reports
# (lower-cased); the MFU denominator. Source of each peak beside it.
PEAK_TFLOPS = {
    "tpu v4": 275.0,        # Google Cloud docs, "TPU v4": 275 TFLOP/s bf16
    "tpu v5 lite": 197.0,   # Google Cloud docs, "TPU v5e": 197 TFLOP/s bf16
    "tpu v5e": 197.0,       # same chip, the kind newer runtimes report
    "tpu v5p": 459.0,       # Google Cloud docs, "TPU v5p": 459 TFLOP/s bf16
    "tpu v6 lite": 918.0,   # Google Cloud docs, "TPU v6e": 918 TFLOP/s bf16
}


def chip_peak_tflops() -> Optional[float]:
    """Peak of the chip this process runs on; None on the CPU, which has no
    peak on record, so CPU runs report no MFU at all. Any other device_kind
    missing from the table is an error, never a default: an MFU against a
    made-up peak is not a measurement."""
    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    if kind not in PEAK_TFLOPS:
        raise ValueError(
            f"no peak TFLOP/s on record for device_kind {kind!r}; add it to "
            f"PEAK_TFLOPS with its source (known: {sorted(PEAK_TFLOPS)})")
    return PEAK_TFLOPS[kind]


class _ProfileArmer:
    """On-demand device profiling: arm once, capture the next K hot regions.

    For long-lived processes where nobody can wrap the hot loop in a
    ``with`` block after the fact: a serve
    worker arms via ``POST /debug/profile``, the trainer via
    ``DCR_PROFILE_AT_STEP`` — both then pass every hot region (device step /
    train step) through :meth:`capture`, which starts the jax.profiler trace
    on the first armed region, counts K regions, and stops. Unarmed,
    :meth:`capture` is two attribute reads — safe to leave permanently in
    the hot path.

    Profiler failures (an unsupported backend, a second concurrent session)
    disarm loudly into ``status()['error']`` instead of breaking the region
    they wrap: profiling must never fail the workload it measures."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._logdir: Optional[str] = None
        self._remaining = 0
        self._active = False
        self._artifact: Optional[str] = None
        self._error: Optional[str] = None

    def arm(self, logdir: str, steps: int = 1) -> dict:
        if steps < 1:
            raise ValueError(f"profile steps must be >= 1, got {steps}")
        with self._lock:
            if self._remaining or self._active:
                raise RuntimeError(
                    f"profiler already armed ({self._remaining} step(s) "
                    f"remaining into {self._logdir})")
            self._logdir = str(logdir)
            self._remaining = int(steps)
            self._artifact = None
            self._error = None
        return self.status()

    def status(self) -> dict:
        with self._lock:
            return {
                "armed": bool(self._remaining or self._active),
                "remaining": self._remaining,
                "logdir": self._logdir,
                "artifact": self._artifact,
                "error": self._error,
            }

    @contextlib.contextmanager
    def capture(self):
        """Pass one hot region through the armer. Starts the profiler trace
        when armed and not yet started; after the K-th region, stops it and
        records the artifact path."""
        if not self._remaining and not self._active:   # fast path: unarmed
            yield
            return
        start = False
        with self._lock:
            if self._remaining > 0 and not self._active:
                self._active = True
                start = True
            logdir = self._logdir
        if start:
            try:
                jax.profiler.start_trace(logdir)
            except Exception as e:      # profiler failure must not fail serving
                with self._lock:
                    self._active = False
                    self._remaining = 0
                    self._error = repr(e)
                yield
                return
        try:
            yield
        finally:
            stop = False
            with self._lock:
                if self._active and self._remaining > 0:
                    self._remaining -= 1
                    if self._remaining == 0:
                        stop = True
            if stop:
                try:
                    jax.profiler.stop_trace()
                    with self._lock:
                        self._active = False
                        self._artifact = logdir
                except Exception as e:
                    with self._lock:
                        self._active = False
                        self._error = repr(e)


_armer = _ProfileArmer()


def arm(logdir: str, steps: int = 1) -> dict:
    """Arm the process-wide profiler for the next ``steps`` captured regions
    (serve ``/debug/profile``, trainer ``DCR_PROFILE_AT_STEP``)."""
    return _armer.arm(logdir, steps)


def status() -> dict:
    return _armer.status()


def capture():
    """Context manager every profileable hot region wraps itself in; no-op
    unless :func:`arm` ran."""
    return _armer.capture()


def flops_of_jitted(jitted_fn, *args, **kwargs) -> float:
    """Per-device FLOPs of an already-jitted function from XLA's cost analysis
    (post-GSPMD-partitioning, so this is the per-chip share). 0 if
    unavailable. Extraction (list-vs-dict analysis shapes) lives in
    obs/memwatch.flops_of_compiled, the one implementation."""
    from dcr_tpu.obs.memwatch import flops_of_compiled

    try:
        return flops_of_compiled(jitted_fn.lower(*args, **kwargs).compile())
    except Exception:
        return 0.0
