"""The harness: one cell, once, as a fresh process.

`run.py` calls :func:`main`. Everything that belongs to one configuration, one
traffic mix, one driver or one per-layer metric is a file of its own, found by
the name `BENCHMARK.json` gives it:

    benchmark/configs/<config>.json     the sizes as they are run
    benchmark/workloads/<traffic>.json  the traffic: a driver's name and its parameters
    benchmark/drivers/<driver>.py       class Driver: how a kind of job is set up and driven
    benchmark/metrics/<metric>.py       read(run): one per-layer metric, or None
    benchmark/reference/...             the plain references the drivers compare with

so a later PR adds a cell, a configuration, a driver or a metric by adding files
and entries, and edits nothing that is here.

The tests steer this module by patching ``ROOT`` and ``PLATFORM`` (as
tests/test_chip_smoke.py does with ``chip_smoke.SIZE``); `run.py` has no option
for either.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

#: the checkout: what `git archive` would unpack
ROOT = Path(__file__).resolve().parents[2]
#: the only platform a measurement may come from
PLATFORM = "tpu"
#: where a run keeps what it leaves behind (all git-ignored)
WORK_DIR = "benchmark/.work"          # per-run scratch, removed when the run ends
CACHE_DIR = "benchmark/.cache"        # built once per checkout: the embedding store
JAX_CACHE_DIR = ".jax_cache"          # JAX's persistent compilation cache

PROCESS_START = time.perf_counter()   # run.py imports this module first of all


class BenchFailure(RuntimeError):
    """The run cannot give a result (no chip, unknown device, bad cell)."""


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict                      # the workload file: driver + parameters
    end_to_end: list[dict]             # metric entries this cell reports
    per_layer: list[dict]
    root: Path


def metrics_of(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """(end-to-end entries, per-layer entries) that `cell` reports: an entry
    with a `workloads` key belongs to the cells it lists; one without belongs
    to every cell (end to end) or to every cell that reports the end-to-end
    metric it moves (per layer)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def load_cell(name: str, root: Path | None = None) -> Cell:
    root = Path(root or ROOT)
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchFailure(
            f"no workload {name!r} in BENCHMARK.json (there: "
            f"{[w['name'] for w in bench['workloads']]})")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e, per = metrics_of(bench, name)
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=config["name"],
                config=load_json(root / config["file"]),
                traffic=load_json(root / "benchmark" / "workloads"
                                  / f"{entry['traffic']}.json"),
                end_to_end=e2e, per_layer=per, root=root)


def load_module(kind: str, name: str, root: Path | None = None):
    """`benchmark/<kind>/<name>.py` of the checkout, loaded by its path."""
    path = Path(root or ROOT) / "benchmark" / kind / f"{name}.py"
    if not path.is_file():
        raise BenchFailure(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_peaks(root: Path | None = None) -> dict:
    return load_json(Path(root or ROOT) / "benchmark" / "peaks.json")


def peaks_for(device, root: Path | None = None) -> dict | None:
    """The chip's published peaks. None off the TPU (a rehearsal reports no
    share of any peak); an unknown TPU is an error."""
    if device.platform != "tpu":
        return None
    table = load_peaks(root)
    kind = device.device_kind.lower()
    if kind not in table:
        raise BenchFailure(
            f"no peaks on record for device_kind {device.device_kind!r}; add "
            f"it to benchmark/peaks.json with its source "
            f"(known: {sorted(k for k in table if not k.startswith('_'))})")
    return table[kind]


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def seed31(seed: int) -> int:
    """`--seed` folded into what a 32-bit signed field holds (the program's
    configs and `jax.random.key` take no more); distinct for distinct seeds
    below 2**31 - 19."""
    return int(seed) % 2147483629


# ---------------------------------------------------------------------------
# compile cache and its meter
# ---------------------------------------------------------------------------

def setup_compile_cache(root: Path | None = None) -> str:
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else at a
    fixed git-ignored path in the checkout (the path is part of the key).
    Every program is kept, however quickly it compiled, and nothing is
    evicted: a second run of a cell has to find all of them."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(Path(root or ROOT) / JAX_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


class CompileMeter:
    """Compile seconds, compilations, and persistent-cache hits and misses,
    from `jax.monitoring` (copied from chip_smoke.CompileMeter)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compile_seconds = 0.0
        self.compilations = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, seconds: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += seconds
            self.compilations += 1

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_seconds": self.compile_seconds,
                "compilations": self.compilations,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def host_rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except OSError:
        return 0


def host_peak_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


# ---------------------------------------------------------------------------
# the object a driver works with
# ---------------------------------------------------------------------------

@dataclass
class Window:
    seconds: float = 0.0                # of the measured (untraced) window
    units: int = 0
    unit_times: list = field(default_factory=list)   # (start, seconds) per unit
    t0: float = 0.0
    traced_units: int = 0
    traced_seconds: float = 0.0
    compilations: int = 0               # inside the window: must be 0


class Bench:
    """What a driver sees of the run: the cell, the seed, the devices, a work
    directory, spans and log lines."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: list, meter: CompileMeter):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.device = devices[0]
        self.meter = meter
        self.peaks = peaks_for(self.device, cell.root)
        self.work = cell.root / WORK_DIR / cell.name
        self.cache = cell.root / CACHE_DIR
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.window = Window()
        self._recording = False

    # -- output ---------------------------------------------------------
    def log(self, what: str, **fields) -> None:
        """One JSON line before the last: phase seconds, counts, losses."""
        print(json.dumps({"bench": what, "t": round(
            time.perf_counter() - PROCESS_START, 3), **fields}), flush=True)

    # -- spans ------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span round a call into a layer: on the host's clock for the
        metrics, and as a TraceAnnotation so that a traced run can say what
        the host was doing in a device gap."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/" + name):
            try:
                yield
            finally:
                if self._recording:
                    self.spans.setdefault(name, []).append(
                        (t0, time.perf_counter() - t0))

    def span_seconds(self, name: str) -> float:
        return sum(d for _, d in self.spans.get(name, []))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def run_window(bench: Bench, driver) -> tuple[Window, Any]:
    """Drive `driver.unit()` for `--seconds`, from a unit boundary to the first
    unit boundary at or after it, the device drained at both ends. With
    `--trace 1` a short stretch under the profiler comes AFTER the measured
    window: a profiler session changes the host path for the rest of the
    process (a search call took 14.1 ms after one, 16.9 ms before: my chip
    runs, PR 25), so the window of a traced run, which the `*_mfu` shares are
    taken over, has to close before the first session opens if it is to read
    the speed a plain run reads."""
    import jax

    window = bench.window
    trace = None
    traced_units = int(bench.cell.traffic.get("traced_units", 2))
    driver.drain()
    # some units lead in, so that the window opens with the pipeline as full
    # as it keeps it (a train loop runs some steps ahead of the device)
    for _ in range(traced_units):
        driver.unit()
    window.traced_units = traced_units
    driver.drain()
    gc.collect()            # no collection of set-up's garbage inside the window
    before = bench.meter.snapshot()["compilations"]
    bench._recording = True
    window.t0 = t0 = time.perf_counter()
    while True:
        u0 = time.perf_counter()
        driver.unit()
        now = time.perf_counter()
        window.unit_times.append((u0, now - u0))
        window.units += 1
        if now - t0 >= bench.seconds:
            break
    driver.drain()
    window.seconds = time.perf_counter() - t0
    bench._recording = False
    window.compilations = bench.meter.snapshot()["compilations"] - before
    if bench.trace:
        trace_dir = bench.work / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        # as many units again lead in under the profiler, so that the stretch
        # that is read opens with the pipeline full: from a drained start a
        # train loop's first dispatches read as idle time
        for _ in range(traced_units):
            driver.unit()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/traced"):
            for _ in range(traced_units):
                driver.unit()
            driver.drain()
        window.traced_seconds = time.perf_counter() - t0
        window.traced_units += 2 * traced_units
        jax.profiler.stop_trace()
        from benchmark.lib import trace as tracelib

        path = tracelib.find_xplane(trace_dir)
        t0 = time.perf_counter()
        trace = tracelib.read(path) if path else None
        if trace is not None:
            bench.log("trace_read", seconds=round(time.perf_counter() - t0, 3),
                      bytes=Path(path).stat().st_size, ops=sum(
                          len(evs) for evs in trace.ops.values()),
                      unscoped_busy_share=tracelib.unscoped_share(trace))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return window, trace


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

@dataclass
class Run:
    """What a per-layer metric's reader may read."""
    cell: Cell
    peaks: dict | None
    window: Window
    spans: dict
    counters: dict                      # the driver's: flops and bytes of a unit, ...
    trace: Any                          # benchmark.lib.trace.Trace, or None
    group: str = ""                     # the metric's dotted suffix


def read_per_layer(bench: Bench, counters: dict, trace) -> dict:
    out = {}
    for entry in bench.cell.per_layer:
        base, _, group = entry["name"].partition(".")
        reader = load_module("metrics", base, bench.cell.root)
        value = reader.read(Run(bench.cell, bench.peaks, bench.window,
                                bench.spans, counters, trace, group))
        if value is None or not math.isfinite(value):
            continue            # nothing to read: the metric is left out
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def check(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": float(value), "limit": float(limit)}


def checks_pass(checks: list[dict]) -> bool:
    # `not (v <= limit)`: a NaN fails
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def find_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        raise BenchFailure(
            f"platform is {devices[0].platform!r}: this benchmark measures "
            f"on {PLATFORM!r} only and falls back to nothing")
    if len(devices) < chips:
        raise BenchFailure(f"{len(devices)} device(s), the cell needs {chips}")
    return devices[:chips]


def trim_host_memory() -> None:
    """Give back what set-up and the window left on the host's heap (compile
    arenas, dropped executables) before the reference compiles and runs: a
    cold training run stood at 33.8 GB of the machine's 40 GiB here and died
    in the reference (my chip run, PR 25)."""
    import ctypes

    import jax

    jax.clear_caches()
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def device_memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    cell = load_cell(workload)
    cache_dir = setup_compile_cache(cell.root)
    meter = CompileMeter()
    devices = find_devices(cell.chips)
    bench = Bench(cell, seed, seconds, trace, devices, meter)
    shutil.rmtree(bench.work, ignore_errors=True)
    bench.work.mkdir(parents=True, exist_ok=True)
    bench.log("start", workload=workload, seed=seed, seconds=seconds,
              trace=int(trace), device_kind=bench.device.device_kind,
              devices=len(devices), compile_cache_dir=cache_dir)
    driver = load_module("drivers", cell.traffic["driver"],
                         cell.root).Driver(bench)
    try:
        driver.setup()
        setup_s = time.perf_counter() - PROCESS_START
        at_open = meter.snapshot()
        bench.log("setup", setup_s=round(setup_s, 3), **at_open,
                  host_peak_rss_bytes=host_peak_rss_bytes())
        window, tr = run_window(bench, driver)
        memory_peak = device_memory_peak(devices)
        end_to_end = dict(driver.end_to_end(window))
        end_to_end["setup_s"] = setup_s
        counters = driver.counters(window)
        bench.log("window", seconds=window.seconds, units=window.units,
                  compilations_in_window=window.compilations,
                  cache_misses=at_open["cache_misses"],
                  memory_peak_bytes=memory_peak,
                  host_peak_rss_bytes=host_peak_rss_bytes(),
                  **{k: v for k, v in end_to_end.items()})
        failed = int(getattr(driver, "failed", 0))
        # the reference runs last: the window is closed, the peak is read,
        # and the program's state is freed first
        driver.release()
        trim_host_memory()
        bench.log("released", host_rss_bytes=host_rss_bytes(),
                  device_bytes_in_use=(bench.device.memory_stats() or {}).get(
                      "bytes_in_use"))
        t0 = time.perf_counter()
        checks = driver.verify(window)
        bench.log("verify", seconds=round(time.perf_counter() - t0, 3))
    finally:
        driver.close()
        shutil.rmtree(bench.work, ignore_errors=True)
    if window.compilations:
        checks.append(check("compilations_in_window", window.compilations, 0))
    on_chip = bench.device.platform == "tpu"
    device = {"platform": bench.device.platform,
              "kind": bench.device.device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    extra: dict = {}
    if trace:
        metrics = read_per_layer(bench, counters, tr)
        if tr is not None:
            from benchmark.lib import trace as tracelib

            busy, span = tracelib.busy_and_window(tr)
            if busy > 0:
                device["busy_s"], device["window_s"] = busy, span
            extra["breakdown"] = tracelib.breakdown(tr)
    else:
        names = {m["name"]: m["unit"] for m in cell.end_to_end}
        missing = sorted(set(names) - set(end_to_end))
        if missing:
            raise BenchFailure(f"driver reported no {missing}")
        metrics = {n: {"value": float(end_to_end[n]), "unit": u}
                   for n, u in names.items()}
    if not on_chip:
        # a rehearsal (the tests): no number of it may stand under the name
        # of a device metric
        extra["rehearsal"] = sorted(metrics)     # the names read, no numbers
        metrics = {}
    return {"correct": checks_pass(checks),
            "attempted": window.units + window.traced_units,
            "failed": failed, "metrics": metrics, "device": device, **extra,
            "checks": {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in checks}}


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description="run one benchmark cell once")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
