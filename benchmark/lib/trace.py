"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the metrics read.

What a TPU v5e trace looks like (found with `benchmark/tools/record_trace.py`,
my chip run, PR 25): one plane per chip, `/device:TPU:<n>`, with the lines
`XLA Modules` (one event per run of a jitted program, named
`jit_<fn>(<fingerprint>)`), `XLA Ops` (one event per HLO instruction that ran,
named by the instruction's whole text, `%name = shape op(...)`), and
`Async XLA Ops`. Host threads are lines of the plane `/host:CPU`; a
`jax.profiler.TraceAnnotation` is an event on the Python thread's line.
Host and device events share one clock to within a millisecond or two (the
first device event of a dispatch can read up to ~1 ms before the host span
that dispatched it), so gaps are attributed, not measured, by host spans.

Everything here is plain Python over `(name, start_ns, duration_ns)` tuples,
so that the tests can feed it a hand-made timeline as well as a recorded file.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench/"
TRACED = "bench/traced"
#: instructions that only contain others; they count for "busy", never as an
#: operation of their own in the breakdown
CONTAINERS = ("while", "conditional", "call")

Event = tuple[str, float, float]          # name, start_ns, duration_ns


@dataclass
class Trace:
    ops: dict[int, list[Event]] = field(default_factory=dict)       # chip -> XLA Ops
    modules: dict[int, list[Event]] = field(default_factory=dict)   # chip -> XLA Modules
    host: list[Event] = field(default_factory=list)                 # bench/* annotations

    def window(self) -> tuple[float, float]:
        """The traced stretch: the `bench/traced` annotation where there is
        one, else from the first device event to the end of the last."""
        marks = [e for e in self.host if e[0] == TRACED]
        if marks:
            return marks[0][1], marks[-1][1] + marks[-1][2]
        every = [e for evs in self.ops.values() for e in evs] or [
            e for evs in self.modules.values() for e in evs]
        if not every:
            return 0.0, 0.0
        return (min(e[1] for e in every), max(e[1] + e[2] for e in every))


def find_xplane(trace_dir: str | Path) -> str | None:
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read(path: str | Path) -> Trace:
    """Read an `.xplane.pb` with nothing but JAX."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[chip] = [(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events]
                elif line.name == MODULES_LINE:
                    trace.modules[chip] = [(e.name, e.start_ns, e.duration_ns)
                                           for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        trace.host.append((e.name, e.start_ns, e.duration_ns))
    trace.host.sort(key=lambda e: e[1])
    return trace


# -- intervals ----------------------------------------------------------------

def union(events: list[Event], t0: float, t1: float) -> list[tuple[float, float]]:
    """Merged busy intervals of `events`, clipped to [t0, t1]."""
    spans = sorted((max(s, t0), min(s + d, t1)) for _, s, d in events
                   if s + d > t0 and s < t1 and d > 0)
    merged: list[list[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(events: list[Event], t0: float, t1: float) -> float:
    return sum(b - a for a, b in union(events, t0, t1)) / 1e9


def busy_and_window(trace: Trace) -> tuple[float, float]:
    """(seconds in which an operation ran, averaged over the chips in the
    trace; seconds of the traced window)."""
    t0, t1 = trace.window()
    chips = trace.ops or trace.modules
    if not chips or t1 <= t0:
        return 0.0, max(0.0, (t1 - t0) / 1e9)
    busy = [busy_seconds(evs, t0, t1) for evs in chips.values()]
    return sum(busy) / len(busy), (t1 - t0) / 1e9


def idle_share(trace: Trace) -> float | None:
    busy, window = busy_and_window(trace)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)


# -- operations ---------------------------------------------------------------

_INSTR = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = (?:\([^=]*?\)|\S+) ([\w\-]+)\(")


def op_kind(name: str) -> tuple[str, str]:
    """('fusion', 'fusion') for '%fusion.12 = f32[..] fusion(...)': the
    instruction's name without its number, and its opcode."""
    m = _INSTR.match(name)
    if not m:
        return name.split(" ")[0].lstrip("%"), ""
    return m.group(1), m.group(2)


def op_label(name: str) -> str:
    """A short stable label for the breakdown: name, opcode, and for a fusion
    its kind, for a custom call its target."""
    base, opcode = op_kind(name)
    extra = ""
    m = re.search(r'custom_call_target="([^"]+)"', name)
    if m:
        extra = m.group(1)
    else:
        m = re.search(r"kind=(k\w+)", name)
        if m:
            extra = m.group(1)
    label = base if opcode in ("", base) else f"{base}:{opcode}"
    return f"{label}:{extra}" if extra else label


def in_window(events: list[Event], t0: float, t1: float) -> list[Event]:
    return [e for e in events if e[1] + e[2] > t0 and e[1] < t1]


def seconds_by(events: list[Event], pattern: str) -> tuple[float, int, list[Event]]:
    """(summed device seconds, count, events) of the events whose name
    matches the regular expression."""
    rx = re.compile(pattern)
    hit = [e for e in events if rx.search(e[0])]
    return sum(e[2] for e in hit) / 1e9, len(hit), hit


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """[[label, seconds], ...] of the operations that took most device time
    in the traced window, summed over chips, containers left out."""
    t0, t1 = trace.window()
    total: dict[str, float] = {}
    for evs in trace.ops.values():
        for name, s, d in in_window(evs, t0, t1):
            if op_kind(name)[1] in CONTAINERS:
                continue
            label = op_label(name)
            total[label] = total.get(label, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


# -- gaps ---------------------------------------------------------------------

def gaps(events: list[Event], t0: float, t1: float,
         min_ns: float = 20_000.0) -> list[tuple[float, float]]:
    """Idle intervals of one chip inside [t0, t1], at least `min_ns` long."""
    out, cursor = [], t0
    for a, b in union(events, t0, t1):
        if a - cursor >= min_ns:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if t1 - cursor >= min_ns:
        out.append((cursor, t1))
    return out


def attribute_gaps(trace: Trace, n: int = 10) -> list[list]:
    """[[what the host was doing, idle seconds], ...]: each idle gap of the
    first chip goes to the `bench/*` annotation that overlaps it most,
    `bench/traced` itself aside; a gap no annotation touches is
    'unattributed'."""
    t0, t1 = trace.window()
    chips = trace.ops or trace.modules
    if not chips:
        return []
    events = chips[min(chips)]
    spans = [e for e in trace.host if e[0] != TRACED]
    total: dict[str, float] = {}
    for a, b in gaps(events, t0, t1):
        best, best_overlap = "unattributed", 0.0
        for name, s, d in spans:
            overlap = min(b, s + d) - max(a, s)
            if overlap > best_overlap:
                best, best_overlap = name[len(ANNOTATION_PREFIX):], overlap
        total[best] = total.get(best, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": attribute_gaps(trace)}


# -- shapes out of an instruction's text ---------------------------------------

_SHAPE = re.compile(r"\b(bf16|f16|f32|s32|u32|s8|u8|pred)\[([\d,]*)\]")
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1, "pred": 1}


def shapes_in(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every `dtype[d0,d1,...]` in an instruction's text, in order: the
    result's shapes first, then the operands'."""
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE.findall(text)]
