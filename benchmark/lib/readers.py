"""Arithmetic that several per-layer metrics share. Each metric still has a
file of its own under benchmark/metrics/, found by its name; a later PR that
adds a metric adds such a file and may import from here."""
from __future__ import annotations

from benchmark.lib import trace as tracelib


def step_mfu(run) -> float | None:
    """The whole step's share of the chip's peak: the algorithm's FLOPs of one
    unit (from shapes, the driver's counter `flops_per_unit`) x units of the
    measured window / its seconds / peak bf16 FLOP/s / chips, in percent. The
    profiler is off in that window."""
    if run.peaks is None or not run.window.units or run.window.seconds <= 0:
        return None
    flops = run.counters.get("flops_per_unit")
    if not flops:
        return None
    achieved = flops * run.window.units / run.window.seconds
    return 100.0 * achieved / (run.peaks["bf16_flops_per_s"] * run.cell.chips)


def span_share(run, name: str) -> float | None:
    """Host seconds inside spans `name` over the measured window, in percent."""
    if run.window.seconds <= 0 or name not in run.spans:
        return None
    return 100.0 * sum(d for _, d in run.spans[name]) / run.window.seconds


def device_idle_share(run) -> float | None:
    """1 - union of device-op intervals / traced window, in percent; nothing
    where no operation ran on a device in the trace (a CPU rehearsal)."""
    if run.trace is None or run.peaks is None:
        return None
    return tracelib.idle_share(run.trace)


def roofline_share(min_seconds: float, device_seconds: float) -> float | None:
    if device_seconds <= 0 or min_seconds <= 0:
        return None
    return 100.0 * min_seconds / device_seconds
