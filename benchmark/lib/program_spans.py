"""The program's own spans, as the per-layer metrics of source `program_span`
read them: `dcr_tpu.core.tracing.timeline(name)`, a list of `(start, seconds)`
pairs on `time.perf_counter()`, the clock the measured window is taken on. The
spans fire inside functions the drivers call (`engine.query`, the trainer's
`loader.epoch()`, `pmesh.to_host`), so no driver knows of them.

Every reader here answers `None` where there is nothing to read: off the chip
(`run.peaks is None`: a rehearsal names no number of the program's speed), in
a checkout whose program keeps no timeline (the parent of the PR that brought
these metrics), and where the span never fired in the measured window.
"""
from __future__ import annotations

import bisect

Span = tuple[float, float]               # start, seconds


def timeline(name: str) -> list[Span]:
    from dcr_tpu.core import tracing

    read = getattr(tracing, "timeline", None)
    return sorted(read(name)) if read is not None else []


def gauge(name: str) -> float | None:
    from dcr_tpu.core import tracing

    return tracing.registry().snapshot()["gauges"].get(name)


def bounds(run) -> tuple[float, float]:
    return run.window.t0, run.window.t0 + run.window.seconds


def started_in(run, name: str) -> list[Span]:
    """The spans `name` that started inside the measured window."""
    t0, t1 = bounds(run)
    return [(s, d) for s, d in timeline(name) if t0 <= s <= t1]


def per_call(run, parent: str, child: str) -> tuple[list[Span], list[float]] | None:
    """(the window's spans `parent`, the seconds of `child` inside each), or
    None where either never fired, and off the chip."""
    if run.peaks is None:
        return None
    calls, children = started_in(run, parent), timeline(child)
    if not calls or not children:
        return None
    return calls, seconds_inside(calls, children)


def seconds_in(run, name: str) -> float | None:
    """Seconds of spans `name` that fall inside the measured window (a span
    that straddles an end counts with the part inside), or None."""
    if run.peaks is None or run.window.seconds <= 0:
        return None
    t0, t1 = bounds(run)
    parts = [min(s + d, t1) - max(s, t0) for s, d in timeline(name)
             if s + d > t0 and s < t1]
    return sum(parts) if parts else None


def window_share(run, name: str, lanes: float = 1.0) -> float | None:
    """Seconds inside spans `name` over the window's seconds (times `lanes`,
    for spans that run on several threads at once), in percent."""
    seconds = seconds_in(run, name)
    if seconds is None or lanes <= 0:
        return None
    return 100.0 * seconds / (run.window.seconds * lanes)


def seconds_inside(parents: list[Span], children: list[Span]) -> list[float]:
    """For each parent, the summed seconds of the children that START inside
    it (one caller: parents do not overlap). Both lists sorted by start."""
    starts = [s for s, _ in children]
    out = []
    for s, d in parents:
        a, b = bisect.bisect_left(starts, s), bisect.bisect_right(starts, s + d)
        out.append(sum(c for _, c in children[a:b]))
    return out

