"""dcr-hbm: memory observability — static HBM accounting, live device-memory
telemetry, and OOM forensics.

The stack measured only the FLOPs half of the efficiency ledger
(utils/profiling.py cost analysis); the memory half — the axis the serve
scale-out and bigger-effective-batch arcs are actually bound by — was
invisible: ``compiled.memory_analysis()`` was never called, no
``device.memory_stats()`` gauge existed, and an OOM was an opaque crash with
none of the flight-recorder forensics every other fatal path gets. This
module is the one home for all three:

- **Static accounting** — :func:`memory_block` reduces XLA's
  ``memory_analysis()`` of a compiled program to a plain byte dict
  (argument/output/temp/generated-code/alias + total), and
  :func:`flops_of_compiled` is the ONE ``cost_analysis()`` extraction
  (utils/profiling.py shares it).
  ``core/warmcache.aot_compile`` and ``tools/check/surfaces.py`` capture a
  block per compiled surface: the warm path feeds the live-surface registry
  below (and a ``memwatch/surface_memory`` trace event), the check path
  banks a ``memory`` block per ``compile_manifest.json`` entry so an HBM
  regression on any surface is a readable CI diff against a per-surface
  byte budget (tools/check/manifest.diff_manifests), not a production OOM.
- **Live telemetry** — :func:`device_memory_stats` normalizes
  ``device.memory_stats()`` across local devices into
  ``{bytes_in_use, peak_bytes, bytes_limit}`` (None where the backend
  returns none — XLA:CPU here — so every consumer degrades gracefully);
  :class:`MemorySampler` feeds the ``device_mem/*`` registry gauges
  (``dcr_device_mem_{in_use,peak,limit}_bytes`` in Prometheus text) on a
  period, riding serve ``/metrics`` and the dcr-scope fleet scrape with no
  further wiring; :func:`span_hbm` annotates a hot-region span
  (``train/step``, ``train/encode``, ``serve/device_step``) with
  ``hbm_peak``/``hbm_delta`` attrs that tools/trace_report.py's "Memory"
  section aggregates.
- **OOM forensics + containment** — :func:`is_oom_error` recognizes XLA
  RESOURCE_EXHAUSTED (and the deterministic ``oom`` fault kind's
  :class:`InjectedOom`); :func:`oom_abort` writes a flight-recorder dump
  enriched with the memory snapshot, the footprints of every live compiled
  surface, and the resident bucket set, then exits with
  ``coordination.EXIT_OOM`` (85) — a typed code the fleet supervisor treats
  like a crash, so journaled in-flight requests requeue with zero drops.
  :func:`admission_headroom` is the serve-side containment: before a NOVEL
  bucket is admitted (= a new resident compiled program), its footprint is
  estimated from the live serve surfaces and checked against remaining
  device memory, so one adversarial request cannot OOM a warm worker
  (serve/queue.MemoryBudgetError -> typed 503).

Test/CI hook: ``DCR_MEMWATCH_FAKE`` (a JSON object with any of
``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``) substitutes for
the backend's ``memory_stats()`` — how the gauge, span-attr, admission and
OOM paths are driven deterministically on the CPU CI rig, where the real
call returns None.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Optional

import logging

from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing

log = logging.getLogger("dcr_tpu")

#: env override for device_memory_stats (JSON dict) — the deterministic
#: test/CI substitute on backends whose memory_stats() is None
FAKE_ENV = "DCR_MEMWATCH_FAKE"

#: sampler period (seconds); 0 disables the sampler thread entirely
PERIOD_ENV = "DCR_MEMWATCH_PERIOD_S"
DEFAULT_PERIOD_S = 10.0

# the CompiledMemoryStats fields banked per surface (device-side only: the
# host_* twins are zero everywhere we run and would just double the diff
# surface). A backend whose analysis lacks a field simply omits it — every
# consumer (manifest diff, OOM dump, trace_report) does present-field checks.
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


# ---------------------------------------------------------------------------
# Static accounting: memory_analysis() + the one cost_analysis() extraction
# ---------------------------------------------------------------------------

def flops_of_analysis(analysis: Any) -> float:
    """FLOPs out of a ``cost_analysis()`` result, whatever its shape: older
    jax returns a per-device list of dicts, newer a single dict; either may
    be None or lack the key. The ONE implementation, behind
    utils/profiling.flops_of_jitted (the trainer's MFU)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if analysis is None:
        return 0.0
    try:
        return float(analysis.get("flops", 0.0))
    except (AttributeError, TypeError, ValueError) as e:
        R.log_event("memwatch_cost_analysis_unreadable", error=repr(e))
        return 0.0


def flops_of_compiled(compiled: Any) -> float:
    """Per-device FLOPs of a compiled/lowered object via its
    ``cost_analysis()`` (0.0 when unavailable — some backends/objects have
    none)."""
    try:
        return flops_of_analysis(compiled.cost_analysis())
    except Exception as e:  # backend-dependent failure: accounting is
        # best-effort and must never fail the compile path it decorates
        log.debug("memwatch: cost_analysis unavailable: %r", e)
        return 0.0


def memory_block(compiled: Any) -> Optional[dict]:
    """XLA's ``memory_analysis()`` of a compiled program as a plain dict of
    byte counts (plus ``total_bytes`` over the present fields and the
    program's per-device ``flops``), or None when the backend offers no
    analysis. Fields a backend omits are absent, not zero-filled — consumers
    degrade to present-field checks."""
    try:
        analysis = compiled.memory_analysis()
    except Exception as e:  # cache-loaded executables on some backends
        # expose no analysis hook — accounting degrades, loading must not
        log.debug("memwatch: memory_analysis unavailable: %r", e)
        return None
    if analysis is None:
        return None
    out: dict = {}
    for attr, name in _MEMORY_FIELDS:
        value = getattr(analysis, attr, None)
        if value is not None:
            out[name] = int(value)
    if not out:
        return None
    out["total_bytes"] = sum(
        out.get(k, 0) for k in ("argument_bytes", "output_bytes",
                                "temp_bytes", "generated_code_bytes"))
    flops = flops_of_compiled(compiled)
    if flops:
        out["flops"] = flops
    return out


# ---------------------------------------------------------------------------
# Live-surface footprint registry (what THIS process holds resident)
# ---------------------------------------------------------------------------

_surfaces_lock = threading.Lock()
_live_surfaces: dict[str, dict] = {}


def note_surface(surface: str, key: str, mem: dict) -> None:
    """Record a compiled surface's footprint for this process — the
    "manifest footprints of live surfaces" an OOM dump carries, and the
    data the serve admission estimate reads. Keyed ``surface@key`` so two
    buckets of one surface family are separate rows."""
    with _surfaces_lock:
        _live_surfaces[f"{surface}@{key}"] = dict(mem)


def live_footprints() -> dict[str, dict]:
    """Snapshot of every compiled surface this process recorded."""
    with _surfaces_lock:
        return {k: dict(v) for k, v in _live_surfaces.items()}


def resident_program_bytes() -> int:
    """Total non-argument footprint of the live surfaces (temp + output +
    generated code; arguments are the shared params, counted once by the
    device allocator, not per program)."""
    total = 0
    for mem in live_footprints().values():
        total += (mem.get("temp_bytes", 0) + mem.get("output_bytes", 0)
                  + mem.get("generated_code_bytes", 0))
    return total


def estimate_surface_bytes(surface_prefix: str) -> Optional[int]:
    """Footprint estimate for compiling ONE MORE program of a surface
    family: the max non-argument footprint among that family's live
    programs (same model, same batch shape — a novel bucket differs only in
    baked-in statics, so the largest sibling is the honest upper-ish bound
    available without compiling). None when nothing of the family is live
    yet (the first program is the readiness phase's to pay, not
    admission's)."""
    best: Optional[int] = None
    for key, mem in live_footprints().items():
        if not key.startswith(surface_prefix):
            continue
        est = (mem.get("temp_bytes", 0) + mem.get("output_bytes", 0)
               + mem.get("generated_code_bytes", 0))
        best = est if best is None else max(best, est)
    return best


# ---------------------------------------------------------------------------
# Live telemetry: device memory stats, gauges, sampler, span attrs
# ---------------------------------------------------------------------------

# one-shot latch: once the backend answered None with no fake configured,
# skip the per-call device walk (the answer cannot change within a process)
_stats_absent = False


def device_memory_stats() -> Optional[dict]:
    """Normalized live device-memory stats summed over local devices:
    ``{"bytes_in_use", "peak_bytes", "bytes_limit"}`` — or None where the
    backend reports none (XLA:CPU). ``DCR_MEMWATCH_FAKE`` (JSON) substitutes
    deterministic numbers for tests/CI on stats-less backends."""
    global _stats_absent
    fake = os.environ.get(FAKE_ENV)
    if fake:
        try:
            doc = json.loads(fake)
            return {
                "bytes_in_use": int(doc.get("bytes_in_use", 0)),
                "peak_bytes": int(doc.get("peak_bytes_in_use",
                                          doc.get("bytes_in_use", 0))),
                "bytes_limit": int(doc.get("bytes_limit", 0)),
            }
        except (ValueError, TypeError, AttributeError) as e:
            R.log_event("memwatch_bad_fake_env", value=fake[:200],
                        error=repr(e))
            return None
    if _stats_absent:
        return None
    try:
        import jax

        rows = [d.memory_stats() for d in jax.local_devices()]
    except Exception as e:  # jax absent/uninitialized in harness contexts
        log.debug("memwatch: device stats unavailable: %r", e)
        return None
    rows = [r for r in rows if r]
    if not rows:
        _stats_absent = True
        return None
    return {
        "bytes_in_use": sum(int(r.get("bytes_in_use", 0)) for r in rows),
        "peak_bytes": sum(int(r.get("peak_bytes_in_use",
                                    r.get("bytes_in_use", 0)))
                          for r in rows),
        "bytes_limit": sum(int(r.get("bytes_limit", 0)) for r in rows),
    }


def peak_bytes() -> Optional[int]:
    """Peak device bytes in use so far (None on stats-less backends) — the
    ``hbm_peak_bytes`` field the bench rungs bank.

    MONOTONIC per process (XLA exposes no peak reset): when several bench
    legs share one process, each leg's value is the run's high-water mark
    AS OF that leg's end — the step from the previous leg's value bounds
    the leg's own contribution; the values are not independent per-leg
    peaks."""
    stats = device_memory_stats()
    return int(stats["peak_bytes"]) if stats else None


def remaining_device_bytes() -> Optional[int]:
    """limit - in_use, or None when either side is unknown (no stats, or a
    backend that reports usage but no limit)."""
    stats = device_memory_stats()
    if not stats or not stats.get("bytes_limit"):
        return None
    return int(stats["bytes_limit"]) - int(stats["bytes_in_use"])


def update_memory_gauges() -> Optional[dict]:
    """One sample -> the ``device_mem/*`` registry gauges (Prometheus:
    ``dcr_device_mem_{in_use,peak,limit}_bytes``). Returns the sample."""
    stats = device_memory_stats()
    if stats is None:
        return None
    reg = tracing.registry()
    reg.gauge("device_mem/in_use_bytes").set(stats["bytes_in_use"])
    reg.gauge("device_mem/peak_bytes").set(stats["peak_bytes"])
    reg.gauge("device_mem/limit_bytes").set(stats["bytes_limit"])
    return stats


class MemorySampler:
    """Periodic ``device.memory_stats()`` -> registry-gauge feed.

    A graceful no-op where the backend has no stats: the first sample
    decides — None means the thread exits immediately and ``active`` stays
    False (nothing spins forever polling a backend that cannot answer)."""

    def __init__(self, period_s: float = DEFAULT_PERIOD_S):
        self.period_s = max(0.1, float(period_s))
        self.active = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> bool:
        """Sample once; when the backend answers, keep sampling on a daemon
        thread. Returns whether sampling is active."""
        if self._thread is not None:
            return self.active
        if update_memory_gauges() is None:
            R.log_trace("memwatch_sampler_noop",
                        reason="backend reports no memory stats")
            return False
        self.active = True
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="memwatch-sampler")
        self._thread.start()
        return True

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            update_memory_gauges()

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)


_sampler_lock = threading.Lock()
_sampler: Optional[MemorySampler] = None


def start_sampler(period_s: Optional[float] = None) -> bool:
    """Start the process-wide sampler (idempotent — the trainer and an
    in-process serve service may both ask). ``DCR_MEMWATCH_PERIOD_S``
    overrides the period; 0 disables. Returns whether live sampling is on
    (False on stats-less backends — the graceful no-op)."""
    global _sampler
    env = os.environ.get(PERIOD_ENV)
    if period_s is None:
        period_s = float(env) if env else DEFAULT_PERIOD_S
    if period_s <= 0:
        return False
    with _sampler_lock:
        if _sampler is None:
            _sampler = MemorySampler(period_s)
            return _sampler.start()
        return _sampler.active


def reset_for_tests() -> None:
    """Scenario isolation: stop the sampler, clear the live-surface registry
    and the stats-absent latch (mirrors tracing.reset_for_tests)."""
    global _sampler, _stats_absent
    with _sampler_lock:
        if _sampler is not None:
            _sampler.stop()
        _sampler = None
    with _surfaces_lock:
        _live_surfaces.clear()
    _stats_absent = False


class span_hbm:
    """Annotate an open span with ``hbm_peak`` / ``hbm_delta`` (bytes) —
    peak usage at exit and the resident-memory delta across the region::

        with tracing.span("serve/device_step", ...) as sp, \\
                memwatch.span_hbm(sp):
            ...

    On stats-less backends both reads are None and the span keeps its
    pre-dcr-hbm shape (no attrs added) — trace_report's Memory section
    simply stays absent, exactly like the other optional sections."""

    __slots__ = ("handle", "_before")

    def __init__(self, handle):
        self.handle = handle
        self._before: Optional[dict] = None

    def __enter__(self):
        self._before = device_memory_stats()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._before is None:
            return False
        after = device_memory_stats()
        if after is not None:
            self.handle.attrs.update(
                hbm_peak=int(after["peak_bytes"]),
                hbm_delta=int(after["bytes_in_use"]
                              - self._before["bytes_in_use"]))
        return False


# ---------------------------------------------------------------------------
# OOM forensics + typed exit
# ---------------------------------------------------------------------------

class InjectedOom(RuntimeError):
    """The deterministic ``oom`` fault kind's payload (utils/faults.py):
    message-shaped like the real thing so :func:`is_oom_error` and every
    downstream consumer treat it identically, raised only by injection
    hooks, never by production code."""

    def __init__(self, where: str):
        super().__init__(
            f"RESOURCE_EXHAUSTED: Out of memory (injected oom fault at "
            f"{where})")


# substrings that identify an XLA allocator failure across backends/versions
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "out of memory",
                "Out of memory", "OOM when allocating",
                "Failed to allocate")


def is_oom_error(e: BaseException) -> bool:
    """True for XLA RESOURCE_EXHAUSTED / allocator-failure errors (and the
    injected fault's :class:`InjectedOom`). Matched on the message because
    jaxlib surfaces these as XlaRuntimeError with the status code in text —
    there is no stable exception subclass to catch across versions."""
    if isinstance(e, InjectedOom):
        return True
    if isinstance(e, MemoryError):
        return True
    text = f"{type(e).__name__}: {e}"
    return any(marker in text for marker in _OOM_MARKERS)


def memory_snapshot_doc() -> dict:
    """The forensic memory document every flight-recorder dump carries:
    live device stats (None where the backend has none), the footprints of
    every compiled surface this process holds, and their non-argument
    total."""
    return {
        "device_memory_stats": device_memory_stats(),
        "live_surfaces": live_footprints(),
        "resident_program_bytes": resident_program_bytes(),
    }


def oom_abort(where: str, error: BaseException, *,
              buckets: Optional[list] = None,
              exit_fn=os._exit) -> None:
    """The OOM fatal path: one structured ``[fault]`` line, a flight-
    recorder dump enriched with the memory snapshot / live-surface
    footprints / resident bucket set, then a hard exit with
    ``coordination.EXIT_OOM`` (85).

    ``os._exit`` for the same reason hang_abort uses it: the trainer's
    producer thread or a serve worker's handler threads must not get a
    chance to wedge the dying process — the supervisor's requeue starts
    from the process's death, and a slow death is dropped availability."""
    from dcr_tpu.core.coordination import EXIT_OOM

    R.log_event("oom_abort", where=where, error=repr(error),
                exit_code=EXIT_OOM)
    # only the OOM-specific fields ride the extra: dump_flight_recorder
    # itself attaches the full memory snapshot (device stats + live-surface
    # footprints) as the top-level "memory" key on every dump
    extra = {"oom": {
        "where": where,
        "error": repr(error),
        "compiled_buckets": [list(b) for b in (buckets or [])],
    }}
    try:
        tracing.dump_flight_recorder(f"oom: {where}: {error!r}", extra=extra)
    except Exception as dump_err:  # the dump must never block the exit
        log.warning("[fault] oom_dump_failed %r", dump_err)
    exit_fn(EXIT_OOM)
