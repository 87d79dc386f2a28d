"""dcr-obs: span tracing, process-wide telemetry registry, flight recorder.

The reference stack's only telemetry is wandb scalars plus MetricLogger
console meters (SURVEY §5.1) — it cannot answer "where did the step time
go", "why did the pod hang at 03:00", or "which serve request waited in
which queue". This module is the measurement substrate every perf PR cites
numbers from:

- **Span tracer** — ``with span("train/step", step=n): ...`` records one
  structured span per region: ids/parents propagated via :mod:`contextvars`
  (so nesting is automatic within a thread), rank/thread tags. A span's
  start and duration are both read on ``time.perf_counter()``, the clock
  the benchmark's window is taken on (the record's ``ts`` is a wall stamp
  beside it, for ``trace.jsonl`` alone), and :func:`span` enters a
  ``jax.profiler.TraceAnnotation("dcr/<name>")`` for its block, so a
  profiler capture shows the program's spans on the host plane of the same
  ``.xplane.pb`` as the device ops. Every span also lands in a per-name
  in-memory record (count, total seconds, a bounded timeline of
  ``(start, seconds)`` pairs: :func:`timeline`, :func:`span_totals`), which
  is what the benchmark's per-layer metrics read. Spans append to a per-process
  ``trace.jsonl`` under the run directory once :func:`configure` has run;
  ``tools/trace_report.py`` turns the files into a stage-time breakdown and
  a Chrome-trace/Perfetto export. Spans may additionally carry a
  **distributed trace id** (:func:`new_trace_id`, inherited via contextvars,
  shipped across processes with :func:`wire_context`) — the fleet
  supervisor stamps one per request so supervisor and worker trace files
  merge into one span tree per request. The file is size-capped:
  ``DCR_TRACE_MAX_MB`` rotates it into ``trace.jsonl.1..N``
  (``DCR_TRACE_KEEP``, default 3) so a weeks-long serve worker cannot fill
  the disk.
- **Telemetry registry** — one process-wide home for counters, gauges and
  histograms. ``resilience.bump_counter`` feeds ``faults/*`` counters here,
  ``MetricWriter.scalars`` mirrors every scalar into a gauge, and named
  :class:`~dcr_tpu.core.metrics.LatencyTracker` instances register as
  histograms — so the trainer, loader, checkpoint manager, eval runner and
  the serve worker all report through the same API, and serve's
  ``/metrics?format=prometheus`` renders the lot in Prometheus text format.
- **Flight recorder** — a bounded ring of the last N spans/events (always
  on, even when no trace file is configured). Fatal paths — NaN abort,
  watchdog exit 89, preemption exit 83, unhandled exceptions — call
  :func:`dump_flight_recorder`, which writes ``flightrec_<rank>.json`` with
  the final seconds of activity plus a registry snapshot, the timeline the
  post-mortems of core/coordination.py previously lacked.

Performance notes: a span is one dict build + two deque appends + (when a
trace file is configured) one buffered ``write`` — no locks are held across
user code, and PERF.md gives the microseconds. Set ``DCR_TRACE=0`` to keep
the ring buffer but skip the file on runs where even that is too much. With
no profiler session the annotation is a flag test; ``jax.profiler`` is
imported at the first span, never at import, and no backend is brought up.
On-device dispatch is asynchronous, so a span around a jitted call measures
dispatch (plus any host sync inside the region): a span that is to time the
device blocks on the result inside its block (``search/device_wait``,
``xfer/device_wait``).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import logging
import os
import re
import sys
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Mapping, Optional

import numpy as np

log = logging.getLogger("dcr_tpu")

TRACE_VERSION = 1
# record fields, pinned by tools/trace_schema.json (CI validates every line)
_PH_SPAN = "X"
_PH_EVENT = "i"


def _detect_rank() -> int:
    """Lazy rank: jax.distributed may not be initialized when the first span
    fires (CLI startup), and tracing must never force a backend up."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # jax absent/uninitialized in some harness contexts
        return int(os.environ.get("PROCESS_ID", "0") or 0)


class _TraceState:
    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.dir: Optional[Path] = None
        self.file = None
        self.path: Optional[Path] = None
        self.rank: Optional[int] = None
        self.ring: deque = deque(
            maxlen=int(os.environ.get("DCR_FLIGHTREC_SPANS", "256") or 256))
        self.ids = itertools.count(1)
        self.dumped: Optional[Path] = None
        # size-capped rotation: a long-lived serve worker must not grow
        # trace.jsonl without bound. 0 = unlimited (training runs are short
        # relative to serve's weeks).
        self.max_bytes = 0
        self.keep = 3
        self.bytes_written = 0


_state = _TraceState()

#: timeline entries kept per span name; older ones fall off the front
TIMELINE_SPANS = 32768


class _SpanRecord:
    """What the process remembers of one span name. The lock is the name's
    own, so spans of different names never wait for each other, and never
    for ``_state.lock`` (which a slow trace file can hold)."""

    __slots__ = ("lock", "count", "seconds", "timeline")

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        self.timeline: deque = deque(maxlen=TIMELINE_SPANS)

    def add(self, start: float, seconds: float) -> None:
        with self.lock:
            self.count += 1
            self.seconds += seconds
            self.timeline.append((start, seconds))


_span_records: dict[str, _SpanRecord] = {}


def _span_record(name: str) -> _SpanRecord:
    rec = _span_records.get(name)
    # setdefault is atomic: two threads' first spans of a name share a record
    return rec if rec is not None else _span_records.setdefault(
        name, _SpanRecord())


def timeline(name: str) -> list[tuple[float, float]]:
    """``(start, seconds)`` on ``time.perf_counter()`` of the last
    ``TIMELINE_SPANS`` finished spans called ``name``, in the order they
    ended. Spans of :func:`span` and :func:`begin_span`; a
    :func:`complete_span` was measured elsewhere and has no start on this
    clock."""
    rec = _span_records.get(name)
    if rec is None:
        return []
    with rec.lock:
        return list(rec.timeline)


def span_totals() -> dict[str, dict]:
    """{name: {"count", "seconds"}} over the whole life of the process (the
    timeline forgets; these do not). Rides every flight-recorder dump."""
    out = {}
    for name, rec in sorted(list(_span_records.items())):   # list(): atomic
        with rec.lock:
            out[name] = {"count": rec.count, "seconds": rec.seconds}
    return out


_trace_annotation: Any = None


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation("dcr/<name>")``: an event on the host
    plane of a profiler capture, a flag test when no session is open.
    Imported at the first span (importing jax starts no backend)."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation("dcr/" + name)


_current_span: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "dcr_current_span", default=None)
# the distributed trace id (a 16-hex-char token) the current span belongs to.
# Propagated like the parent id: automatic within a process via contextvars,
# explicit across processes via the wire context the fleet supervisor injects
# into every dispatched batch (serve/supervisor.py) — which is what stitches
# supervisor and worker trace files into one span tree per request.
_current_trace: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "dcr_current_trace", default=None)


def new_trace_id() -> str:
    """Fresh 64-bit distributed-trace id. os.urandom, not the random module:
    trace ids must never perturb (or depend on) any seeded RNG stream."""
    return os.urandom(8).hex()


def configure(directory: str | Path, *, rank: Optional[int] = None) -> Optional[Path]:
    """Start writing spans/events to ``<directory>/trace.jsonl`` (rank 0) or
    ``trace.p<rank>.jsonl`` (peers — one file per process, mirroring the
    quarantine-manifest naming), and anchor flight-recorder dumps there.

    Idempotent and re-targetable (a second configure closes the previous
    file). ``DCR_TRACE=0`` disables the file sink — spans still feed the
    flight-recorder ring. Returns the trace path (None when disabled)."""
    rank = _detect_rank() if rank is None else int(rank)
    directory = Path(directory)
    name = "trace.jsonl" if rank == 0 else f"trace.p{rank}.jsonl"
    # hook before any early return: ring-only mode (DCR_TRACE=0) exists FOR
    # the unhandled-exception dump, so it needs the excepthook most of all
    install_excepthook()
    with _state.lock:
        _state.rank = rank
        _state.dir = directory
        if _state.file is not None:
            try:
                _state.file.close()
            except OSError as e:
                log.warning("[trace] trace_file_close_failed %r", e)
            _state.file = None
            _state.path = None
        if os.environ.get("DCR_TRACE", "1") == "0":
            return None
        directory.mkdir(parents=True, exist_ok=True)
        path = directory / name
        _state.max_bytes = int(
            float(os.environ.get("DCR_TRACE_MAX_MB", "0") or 0) * 1e6)
        _state.keep = max(1, int(os.environ.get("DCR_TRACE_KEEP", "3") or 3))
        _state.bytes_written = path.stat().st_size if path.exists() else 0
        _state.path = path
        _state.file = path.open("a", buffering=1)  # line-buffered: crash-safe
    return path


def trace_dir() -> Optional[Path]:
    return _state.dir


def _rank() -> int:
    r = _state.rank
    return _detect_rank() if r is None else r


def _rotate_locked() -> None:
    """Shift ``trace.jsonl`` -> ``.1`` -> ... -> ``.keep`` (oldest dropped)
    and reopen a fresh file. Caller holds ``_state.lock``. Rotation failures
    are loud but non-fatal: telemetry must never kill the workload."""
    path = _state.path
    try:
        _state.file.close()
    except OSError as e:
        log.warning("[trace] trace_file_close_failed during rotate %r", e)
    _state.file = None
    try:
        for i in range(_state.keep - 1, 0, -1):
            seg = path.with_name(f"{path.name}.{i}")
            if seg.exists():
                os.replace(seg, path.with_name(f"{path.name}.{i + 1}"))
        os.replace(path, path.with_name(f"{path.name}.1"))
        _state.file = path.open("a", buffering=1)
        _state.bytes_written = 0
    except OSError as e:
        log.warning("[trace] trace_rotate_failed (ring-only from here): %r", e)


def _emit(rec: dict) -> None:
    with _state.lock:
        _state.ring.append(rec)
        f = _state.file
        if f is not None:
            try:
                line = json.dumps(rec, default=str) + "\n"
                f.write(line)
                _state.bytes_written += len(line)
                if _state.max_bytes and _state.bytes_written > _state.max_bytes:
                    _rotate_locked()
            except (OSError, ValueError) as e:  # full disk / closed file:
                # telemetry must never kill the workload — drop to ring-only
                _state.file = None
                log.warning("[trace] trace_write_failed (ring-only from "
                            "here): %r", e)


class SpanHandle:
    """An open span whose end is decoupled from lexical scope — the
    cross-thread form (e.g. one ``serve/request`` root per request id,
    begun on the HTTP handler thread and ended by the future's callback).
    Prefer :func:`span` whenever a ``with`` block fits."""

    __slots__ = ("name", "id", "parent", "trace", "attrs", "_t0_wall", "_t0",
                 "_done")

    def __init__(self, name: str, parent: Optional[int],
                 trace: Optional[str], attrs: dict):
        self.name = name
        self.id = next(_state.ids)
        self.parent = parent
        self.trace = trace
        self.attrs = attrs
        self._t0_wall = time.time()         # the record's `ts`, nothing else
        self._t0 = time.perf_counter()
        self._done = False

    def end(self, **extra: Any) -> None:
        if self._done:          # idempotent: future callbacks can race .end()
            return
        self._done = True
        dur = time.perf_counter() - self._t0
        _span_record(self.name).add(self._t0, dur)
        rec = {"ph": _PH_SPAN, "name": self.name, "id": self.id,
               "parent": self.parent, "ts": round(self._t0_wall * 1e6),
               "dur": round(dur * 1e6), "pid": _rank(),
               "tid": threading.get_ident(),
               "tname": threading.current_thread().name,
               "args": {**self.attrs, **extra}}
        if self.trace is not None:
            rec["trace"] = self.trace
        _emit(rec)


def begin_span(name: str, *, parent: Optional[int] = None,
               trace: Optional[str] = None, **attrs: Any) -> SpanHandle:
    """Open a :class:`SpanHandle`; the caller owns ``.end()``. ``trace``
    defaults to the enclosing span's distributed-trace id (contextvars)."""
    return SpanHandle(name, parent if parent is not None else _current_span.get(),
                      trace if trace is not None else _current_trace.get(),
                      attrs)


class _BlockSpan(SpanHandle):
    """What :func:`span` returns: a :class:`SpanHandle` that, for its
    ``with`` block, is the current span (contextvars) and a ``dcr/<name>``
    annotation in a profiler capture. A class and not a generator: a span
    sits on hot paths (seven of them in one 17 ms search call)."""

    __slots__ = ("_token", "_trace_token", "_annotation")

    def __enter__(self) -> SpanHandle:
        self._token = _current_span.set(self.id)
        self._trace_token = _current_trace.set(self.trace)
        self._annotation = _annotation(self.name)
        self._annotation.__enter__()
        self._t0_wall = time.time()     # the block's start, not the call's
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._annotation.__exit__(exc_type, exc, tb)
        _current_trace.reset(self._trace_token)
        _current_span.reset(self._token)
        if exc is None:
            self.end()
        else:
            self.end(error=repr(exc))


def span(name: str, *, parent: Optional[int] = None,
         trace: Optional[str] = None, **attrs: Any) -> _BlockSpan:
    """Record a ``with`` block as one span. Parent (and distributed-trace id)
    default to the enclosing span in this context (contextvars), so nesting
    is automatic; an exception in the block is recorded as an ``error`` attr
    and re-raised unchanged. The block is also a ``dcr/<name>`` annotation
    in a profiler capture (a :func:`begin_span`, whose end is not lexical,
    is not)."""
    return _BlockSpan(
        name, parent if parent is not None else _current_span.get(),
        trace if trace is not None else _current_trace.get(), attrs)


def event(name: str, *, parent: Optional[int] = None,
          trace: Optional[str] = None,
          attrs: Optional[Mapping[str, Any]] = None, **kw: Any) -> None:
    """Instant (zero-duration) trace event — compiles, faults, decisions.

    Attributes ride as keywords; pass ``attrs=`` for dicts whose keys could
    collide with ``name``/``parent`` (e.g. resilience.log_event fields)."""
    rec = {"ph": _PH_EVENT, "name": name, "id": next(_state.ids),
           "parent": parent if parent is not None else _current_span.get(),
           "ts": round(time.time() * 1e6), "pid": _rank(),
           "tid": threading.get_ident(),
           "tname": threading.current_thread().name,
           "args": {**(attrs or {}), **kw}}
    trace = trace if trace is not None else _current_trace.get()
    if trace is not None:
        rec["trace"] = trace
    _emit(rec)


def complete_span(name: str, *, start_wall: float, dur_s: float,
                  parent: Optional[int] = None, trace: Optional[str] = None,
                  **attrs: Any) -> None:
    """Record a span measured elsewhere (e.g. queue wait reconstructed from a
    request's admission stamp when the batch finally forms)."""
    rec = {"ph": _PH_SPAN, "name": name, "id": next(_state.ids),
           "parent": parent, "ts": round(start_wall * 1e6),
           "dur": round(max(dur_s, 0.0) * 1e6), "pid": _rank(),
           "tid": threading.get_ident(),
           "tname": threading.current_thread().name, "args": attrs}
    if trace is not None:
        rec["trace"] = trace
    _emit(rec)


def current_span_id() -> Optional[int]:
    return _current_span.get()


def current_trace_id() -> Optional[str]:
    return _current_trace.get()


def wire_context(span: SpanHandle, attempt: int = 1) -> dict:
    """The cross-process trace context a dispatcher ships with work: enough
    for the receiving process to parent its own root span under ``span``
    even though span ids are process-local. ``attempt`` tags requeued
    re-executions so they merge as sibling children of the same root."""
    return {"trace_id": span.trace, "parent_span": span.id,
            "attempt": int(attempt)}


# ---------------------------------------------------------------------------
# Telemetry registry: counters / gauges / histograms
# ---------------------------------------------------------------------------

class Counter:
    """Monotonic process-wide counter."""

    __slots__ = ("_lock", "_value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self._value += n
            return self._value

    @property
    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge:
    """Last-value-wins instantaneous measurement."""

    __slots__ = ("_lock", "_value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Thread-safe sliding-window reservoir with percentile snapshots.

    The storage model of serving's LatencyTracker (which subclasses this):
    a bounded deque, so long-lived processes never grow memory with
    observation count, while ``count``/``total`` stay lifetime-accurate."""

    def __init__(self, window: int = 1024):
        self._values: deque = deque(maxlen=window)
        self._lock = threading.Lock()
        self.count = 0
        self.total = 0.0

    def observe(self, value: float) -> None:
        with self._lock:
            self._values.append(float(value))
            self.count += 1
            self.total += float(value)

    def percentiles(self, qs: tuple = (50, 99)) -> dict[str, float]:
        """{"p50": v, "p99": v, ...} over the window (0.0 when empty)."""
        with self._lock:
            vals = list(self._values)
        if not vals:
            return {f"p{q}": 0.0 for q in qs}
        arr = np.asarray(vals)
        return {f"p{q}": float(np.percentile(arr, q)) for q in qs}

    def snapshot(self) -> dict:
        with self._lock:
            count, total = self.count, self.total
        return {"count": count, "sum": total,
                **self.percentiles((50, 90, 99))}


def sanitize_metric_name(name: str) -> str:
    """Internal slash-style metric name (``faults/x``, ``stage/eval``) ->
    valid Prometheus identifier ``[a-zA-Z_:][a-zA-Z0-9_:]*``. The ``dcr_``
    prefix both namespaces the export and guarantees a legal first char."""
    return "dcr_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


def sanitize_label_name(name: str) -> str:
    """Label-name form of :func:`sanitize_metric_name` (labels may not
    contain colons and may not start with a digit)."""
    s = re.sub(r"[^a-zA-Z0-9_]", "_", name)
    return s if s and not s[0].isdigit() else "_" + s


def prometheus_value(v: float) -> str:
    """Render a sample value; Python's ``inf``/``nan`` spellings are not
    valid exposition-format tokens."""
    f = float(v)
    if f != f:
        return "NaN"
    if f == float("inf"):
        return "+Inf"
    if f == float("-inf"):
        return "-Inf"
    return repr(f) if isinstance(v, float) else str(v)


def prometheus_escape_help(text: str) -> str:
    return text.replace("\\", "\\\\").replace("\n", "\\n")


class TelemetryRegistry:
    """The process-wide metric home. Every sink registers here so one
    snapshot answers for the whole process, whichever subsystem is asked
    (trainer MetricWriter boundary, serve /metrics, flight-recorder dump)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            return self._counters.setdefault(name, Counter())

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            return self._gauges.setdefault(name, Gauge())

    def histogram(self, name: str, window: int = 1024) -> Histogram:
        with self._lock:
            return self._histograms.setdefault(name, Histogram(window))

    def register_histogram(self, name: str, hist: Histogram) -> Histogram:
        """Adopt an externally-created histogram (LatencyTracker(name=...))."""
        with self._lock:
            self._histograms[name] = hist
            return hist

    def remove(self, name: str) -> None:
        with self._lock:
            for d in (self._counters, self._gauges, self._histograms):
                d.pop(name, None)

    def counters(self, prefix: str = "") -> dict[str, int]:
        with self._lock:
            items = list(self._counters.items())
        return {k: c.value for k, c in items if k.startswith(prefix)}

    def reset(self, prefix: str = "") -> None:
        """Test hook: drop metrics under ``prefix`` ("" clears everything)."""
        with self._lock:
            for d in (self._counters, self._gauges, self._histograms):
                for k in [k for k in d if k.startswith(prefix)]:
                    del d[k]

    def snapshot(self) -> dict:
        with self._lock:
            counters = list(self._counters.items())
            gauges = list(self._gauges.items())
            hists = list(self._histograms.items())
        return {
            "counters": {k: c.value for k, c in counters},
            "gauges": {k: g.value for k, g in gauges},
            "histograms": {k: h.snapshot() for k, h in hists},
        }

    def prometheus_text(self) -> str:
        """The registry in Prometheus text exposition format. Counters/gauges
        map 1:1; histograms render as summaries (quantile labels + _sum/_count).
        ``dcr_faults_total`` is always present (0 when clean) so a scrape can
        alert on its rate before the first fault ever fires.

        Exposition hygiene: every metric gets a ``# HELP`` line naming the
        internal (slash-style) metric it was sanitized from, non-finite
        values render as Prometheus ``+Inf``/``-Inf``/``NaN`` tokens, and two
        internal names that sanitize to the same identifier share one
        HELP/TYPE header instead of emitting an invalid duplicate."""
        snap = self.snapshot()
        lines: list[str] = []
        headered: set[str] = set()

        def header(m: str, orig: str, kind: str) -> None:
            if m in headered:
                return
            headered.add(m)
            lines.append(f"# HELP {m} dcr_tpu internal metric "
                         f"{prometheus_escape_help(orig)!r}")
            lines.append(f"# TYPE {m} {kind}")

        for name, value in sorted(snap["counters"].items()):
            m = sanitize_metric_name(name)
            header(m, name, "counter")
            lines.append(f"{m} {prometheus_value(value)}")
        header("dcr_faults_total", "sum of faults/* counters", "counter")
        faults_total = sum(v for k, v in snap["counters"].items()
                           if k.startswith("faults/"))
        lines.append(f"dcr_faults_total {prometheus_value(faults_total)}")
        for name, value in sorted(snap["gauges"].items()):
            m = sanitize_metric_name(name)
            header(m, name, "gauge")
            lines.append(f"{m} {prometheus_value(value)}")
        for name, h in sorted(snap["histograms"].items()):
            m = sanitize_metric_name(name)
            header(m, name, "summary")
            for q in (50, 90, 99):
                lines.append(
                    f'{m}{{quantile="0.{q}"}} {prometheus_value(h[f"p{q}"])}')
            lines.append(f"{m}_sum {prometheus_value(h['sum'])}")
            lines.append(f"{m}_count {prometheus_value(h['count'])}")
        return "\n".join(lines) + "\n"


_registry = TelemetryRegistry()


def registry() -> TelemetryRegistry:
    return _registry


def update_gauges(values: Mapping[str, Any], prefix: str = "") -> None:
    """Mirror a (possibly nested) scalar mapping into registry gauges —
    how MetricWriter scalars and serve status docs land in /metrics."""
    for k, v in values.items():
        if isinstance(v, Mapping):
            update_gauges(v, prefix=f"{prefix}{k}/")
        elif isinstance(v, bool):
            _registry.gauge(f"{prefix}{k}").set(1.0 if v else 0.0)
        elif isinstance(v, (int, float)):
            _registry.gauge(f"{prefix}{k}").set(float(v))


def merge_counter_rows(rows) -> dict[str, int]:
    """Pure reduce for the pod-wide fault-counter aggregation: sum each
    counter across per-host dicts (hosts that never saw a kind contribute
    nothing). Unit-testable without collectives; the transport is the
    trainer's timeout-bounded ``dist.kv_allgather`` round."""
    out: dict[str, int] = {}
    for row in rows:
        for name, count in row.items():
            out[name] = out.get(name, 0) + int(count)
    return out


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def flight_records() -> list[dict]:
    """Snapshot of the bounded last-N span/event ring (newest last)."""
    with _state.lock:
        return list(_state.ring)


def dump_flight_recorder(reason: str, *,
                         directory: Optional[str | Path] = None,
                         extra: Optional[dict] = None) -> Optional[Path]:
    """Write ``flightrec_<rank>.json`` — the last N spans/events, a registry
    snapshot, a best-effort device-memory snapshot and the abort reason — to
    ``directory`` (default: the configured trace dir, else
    ``DCR_FLIGHTREC_DIR``). The post-mortem for every fatal path: NaN abort,
    watchdog exit 89, preemption exit 83, OOM exit 85, unhandled exceptions.
    Never raises (it runs while the process is dying); returns None when no
    destination is configured or the write fails. ``extra`` merges
    caller-supplied forensic sections into the document (the OOM path ships
    its enriched memory/footprint/bucket view through it).

    First dump wins: the record closest to the fault is the post-mortem of
    record — a NaN abort's explicit dump must not be overwritten by the
    excepthook firing for the same exception one frame up."""
    if _state.dumped is not None:
        return _state.dumped
    d = directory or _state.dir or os.environ.get("DCR_FLIGHTREC_DIR")
    if not d:
        return None
    rank = _rank()
    # fleet workers are all rank 0 and may share a dump directory (the fleet
    # dir when no --logdir is set): the worker index must be in the filename
    # or one crashing worker clobbers another's post-mortem
    widx = os.environ.get("DCR_WORKER_INDEX")
    name = (f"flightrec_{rank}.json" if widx is None
            else f"flightrec_w{widx}_{rank}.json")
    path = Path(d) / name
    # best-effort memory forensics on EVERY fatal path, not just OOM: a NaN
    # abort or hang post-mortem answering "how full was the device" for free
    # is the whole point of having the sampler machinery resident
    try:
        from dcr_tpu.obs import memwatch

        memory = memwatch.memory_snapshot_doc()
    except Exception as e:  # the dump must survive a broken accounting layer
        log.warning("[trace] flightrec_memory_snapshot_failed %r", e)
        memory = None
    doc = {
        "version": TRACE_VERSION,
        "reason": reason,
        "time": time.time(),
        "rank": rank,
        "os_pid": os.getpid(),
        "memory": memory,
        "records": flight_records(),
        "span_totals": span_totals(),
        "registry": _registry.snapshot(),
        **(extra or {}),
    }
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(doc, indent=1, default=str))
        tmp.replace(path)      # atomic: a dump raced by the exit never tears
    except OSError as e:
        log.warning("[trace] flightrec_write_failed %r", e)
        return None
    _state.dumped = path
    log.warning("[trace] flight_recorder_dumped path=%s reason=%s records=%d",
                path, reason, len(doc["records"]))
    return path


def last_span_names(n: int = 8) -> list[str]:
    """The most recent n record names — folded into hang post-mortems so the
    'where was it' answer survives even when the dump file can't be read."""
    return [r["name"] for r in flight_records()[-n:]]


_orig_excepthook = None
_hook_lock = threading.Lock()


def _excepthook(exc_type, exc, tb) -> None:
    dump_flight_recorder(f"unhandled_exception: {exc_type.__name__}: {exc}")
    if _orig_excepthook is not None:
        _orig_excepthook(exc_type, exc, tb)


def install_excepthook() -> None:
    """Dump the flight recorder on any unhandled exception, then defer to the
    previous hook. SystemExit never reaches sys.excepthook, so clean exits
    (and the deliberate preemption exit 83) do not produce a dump here —
    those paths dump explicitly with their own reason."""
    global _orig_excepthook
    with _hook_lock:
        if sys.excepthook is _excepthook:
            return
        _orig_excepthook = sys.excepthook
        sys.excepthook = _excepthook


def reset_for_tests() -> None:
    """Close the trace file, clear the ring and the registry — scenario
    isolation for unit tests (mirrors faults.clear())."""
    with _state.lock:
        if _state.file is not None:
            try:
                _state.file.close()
            except OSError:
                log.warning("[trace] trace_file_close_failed during reset")
        _state.file = None
        _state.path = None
        _state.dir = None
        _state.rank = None
        _state.dumped = None
        _state.max_bytes = 0
        _state.bytes_written = 0
        _state.ring.clear()
    _span_records.clear()
    _registry.reset()
