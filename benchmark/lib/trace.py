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

Each device op also has a SCOPE PATH: the stat `tf_op` of its event's
metadata, the op's `op_name` as the program's `jax.named_scope`s and Flax
modules built it (`jit(encode)/.../layers_1/moe/experts/while/body/dot_general`;
`ProfileData` does not show it, so `benchmark/lib/xplane.py` decodes it from
the file), kept in `Trace.scopes[chip]` parallel to `Trace.ops[chip]`, "" for
an op without one.

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
#: the program's own spans (`dcr_tpu.core.tracing`), read for idle gaps
PROGRAM_PREFIX = "dcr/"
TRACED = "bench/traced"
#: instructions that only contain others; they count for "busy", never as an
#: operation of their own in the breakdown
CONTAINERS = ("while", "conditional", "call")

Event = tuple[str, float, float]          # name, start_ns, duration_ns


@dataclass
class Trace:
    ops: dict[int, list[Event]] = field(default_factory=dict)       # chip -> XLA Ops
    modules: dict[int, list[Event]] = field(default_factory=dict)   # chip -> XLA Modules
    host: list[Event] = field(default_factory=list)     # bench/* and dcr/* annotations
    scopes: dict[int, list[str]] = field(default_factory=dict)      # chip -> a path an op

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
    """Read an `.xplane.pb`: events by JAX's `ProfileData`, the ops' scope
    paths by `xplane`."""
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
                    if e.name.startswith((ANNOTATION_PREFIX, PROGRAM_PREFIX)):
                        trace.host.append((e.name, e.start_ns, e.duration_ns))
    trace.host.sort(key=lambda e: e[1])
    trace.scopes = scope_paths(path, trace.ops)
    return trace


def scope_paths(path: str | Path, ops: dict[int, list[Event]]) -> dict[int, list[str]]:
    """chip -> the scope path of each of `ops[chip]`, in order: the `tf_op`
    stat of the event's metadata without its `:<type>` tail, "" where the op
    has none. A line whose events do not pair one for one with `ops` (by
    name) gives every op "": no path is better than a wrong one."""
    from benchmark.lib import xplane

    out = {chip: [""] * len(evs) for chip, evs in ops.items()}
    for plane in xplane.read(path, lambda name: DEVICE_PLANE.match(name)):
        chip = int(DEVICE_PLANE.match(plane.name).group(1))
        line = next((x for x in plane.lines if x.name == OPS_LINE), None)
        if chip not in ops or line is None or len(line.events) != len(ops[chip]):
            continue
        metas = [plane.event_metadata.get(mid) for mid, _, _ in line.events]
        if any(m is None or m.name != name
               for m, (name, _, _) in zip(metas, ops[chip])):
            continue
        paths = {}
        for m in metas:
            if id(m) not in paths:
                tf_op = plane.stat(m, "tf_op")
                paths[id(m)] = (tf_op.rpartition(":")[0] if ":" in tf_op
                                else tf_op) if isinstance(tf_op, str) else ""
        out[chip] = [paths[id(m)] for m in metas]
    return out


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
#: `/*index=5*/`, which XLA writes into a long tuple's shape
_COMMENT = re.compile(r"/\*.*?\*/")


def op_kind(name: str) -> tuple[str, str]:
    """('fusion', 'fusion') for '%fusion.12 = f32[..] fusion(...)': the
    instruction's name without its number, and its opcode."""
    m = _INSTR.match(_COMMENT.sub("", name))
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
    in the traced window, summed over chips, containers left out. An op with
    a scope path is labelled by the part of the program it ran for as well
    (`experts:fusion:kOutput`, `scope_name`)."""
    t0, t1 = trace.window()
    total: dict[str, float] = {}
    labels: dict[tuple[str, str], str | None] = {}     # an op's text repeats
    for chip, evs in trace.ops.items():
        for (name, s, d), path in zip(evs, paths_of(trace, chip)):
            if not (s + d > t0 and s < t1):
                continue
            if (name, path) not in labels:
                base, opcode = op_kind(name)
                label, part = op_label(name), scope_name(path)
                labels[name, path] = (None if opcode in CONTAINERS else
                                      f"{part}:{label}" if part and part != base
                                      else label)
            label = labels[name, path]
            if label is not None:
                total[label] = total.get(label, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


# -- scopes -------------------------------------------------------------------

#: components of a path that name no part of the program: what JAX's
#: control flow puts in (a transform's own, `jit(f)`, `vmap()`,
#: `transpose(jvp(...))`, is told by its parenthesis)
UNNAMED = frozenset(("while", "body", "cond", "closed_call", "checkpoint",
                     "remat"))


def paths_of(trace: Trace, chip: int) -> list[str]:
    """The scope path of each of `trace.ops[chip]`; "" for every op where the
    trace holds none."""
    paths = trace.scopes.get(chip, [])
    return paths if len(paths) == len(trace.ops[chip]) else [""] * len(trace.ops[chip])


def in_scope(path: str, scope: str) -> bool:
    """Whether the components of `scope` stand in the op's path one after
    the other, each a whole component, before the op's kind: `moe/experts` is
    in `.../moe/experts/while/body/dot_general` and not in
    `.../moe/experts_x/dot_general`. A path of several joined by `;` (XLA's,
    for an op made of several) is in the scope where one of them is."""
    want = scope.split("/")
    n = len(want)
    for one in path.split(";"):
        parts = one.split("/")[:-1]
        if any(parts[i:i + n] == want for i in range(len(parts) - n + 1)):
            return True
    return False


def scope_name(path: str) -> str:
    """The last named component of an op's path before its kind: `experts`
    for `.../moe/experts/while/body/dot_general`, `q_a_proj` for
    `.../mla/self_attn/q_a_proj/dot_general`; "" where none is named."""
    for part in reversed(path.split(";")[0].split("/")[:-1]):
        if part and "(" not in part and part not in UNNAMED \
                and not part.startswith("branch_"):
            return part
    return ""


def scoped_seconds(trace: Trace, keep) -> tuple[float, float] | None:
    """(device seconds of the ops whose path `keep` accepts, device seconds
    of all ops), each the union of their intervals inside the traced stretch,
    summed over chips: a `while` and the ops of its body count once. None
    where no op of the stretch carries a path."""
    t0, t1 = trace.window()
    kept = total = 0.0
    pathed = False
    verdict: dict[str, bool] = {}                       # a path repeats
    for chip, evs in trace.ops.items():
        pairs = [(e, p) for e, p in zip(evs, paths_of(trace, chip))
                 if e[1] + e[2] > t0 and e[1] < t1]
        pathed = pathed or any(p for _, p in pairs)
        for _, p in pairs:
            if p not in verdict:
                verdict[p] = keep(p)
        kept += busy_seconds([e for e, p in pairs if verdict[p]], t0, t1)
        total += busy_seconds([e for e, _ in pairs], t0, t1)
    return (kept, total) if pathed and total > 0 else None


def scope_share(trace: Trace, scope: str) -> float | None:
    """The device time inside `scope` as a share of all device time of the
    traced stretch, in percent; None where no op carries a path or none is
    in the scope."""
    got = scoped_seconds(trace, lambda p: bool(p) and in_scope(p, scope))
    return None if got is None or got[0] <= 0 else 100.0 * got[0] / got[1]


def unscoped_share(trace: Trace) -> float | None:
    """The device time that no op with a path covers, as a share of all
    device time of the traced stretch, in percent: what no scope metric can
    see (a `while` without a path whose body ops have one is not in it)."""
    got = scoped_seconds(trace, bool)
    return None if got is None else 100.0 * (1.0 - got[0] / got[1])


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
    first chip goes to a host span that overlaps it, the benchmark's
    `bench/*` and the program's `dcr/*` alike (`bench/traced` itself aside),
    named without its prefix: the one that overlaps it most, and then, while
    spans that lie inside that one overlap the gap too, the innermost of
    them (`search/fetch`, not the `search/query` round it); a gap no span
    touches is 'unattributed'."""
    t0, t1 = trace.window()
    chips = trace.ops or trace.modules
    if not chips:
        return []
    events = chips[min(chips)]
    spans = [e for e in trace.host if e[0] != TRACED]
    total: dict[str, float] = {}
    for a, b in gaps(events, t0, t1):
        over = [(min(b, s + d) - max(a, s), s, s + d, name)
                for name, s, d in spans if min(b, s + d) - max(a, s) > 0]
        best = max(over, key=lambda o: o[0], default=None)
        while best is not None:
            inner = [o for o in over if best[1] <= o[1] and o[2] <= best[2]
                     and o[2] - o[1] < best[2] - best[1]]
            if not inner:
                break
            best = max(inner, key=lambda o: o[0])
        label = "unattributed" if best is None else best[3].partition("/")[2]
        total[label] = total.get(label, 0.0) + (b - a) / 1e9
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
