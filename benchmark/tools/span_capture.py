#!/usr/bin/env python3
"""Run by hand on the chip (PR 26): what the program's spans cost, what a
profiler capture of `engine.query` calls holds, and which stat of a device
event carries the `jax.named_scope` path.

    python3 benchmark/tools/span_capture.py cost      # microseconds of one span
    python3 benchmark/tools/span_capture.py search    # the search cell's engine
    python3 benchmark/tools/span_capture.py scopes    # the tiny train step
    python3 benchmark/tools/span_capture.py wait      # what the wait before the fetch costs
    python3 benchmark/tools/span_capture.py insitu <parent checkout>   # spans against none, in one process

`search` opens the search cell's store as its driver does, times `calls`
back-to-back `engine.query` calls with no profiler session, then as many with
one open, then as many after it has closed (medians, ms), and reads the
capture: the `dcr/*` host events, the `jit_topk` device events, and every idle
gap of the chip laid against the program's spans. It runs against any checkout
it is copied into: in one whose program has no `dcr/` annotations (the parent
of PR 26) the gaps fall under no span and it says so. Everything is printed as
JSON lines; nothing here is read by a metric.
"""
from __future__ import annotations

import glob
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

LEAVES = ("search/put", "search/dispatch", "search/device_wait", "search/fetch",
          "search/merge")


def say(what: str, **fields) -> None:
    print(json.dumps({"capture": what, **fields}), flush=True)


def capture(fn, trace_dir: Path):
    """Run `fn()` under a profiler session; the `.xplane.pb` as ProfileData."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    paths = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True))
    return out, jax.profiler.ProfileData.from_file(paths[-1])


def events_of(data, plane_name: str, line_name: str | None = None):
    return [(e.name, e.start_ns, e.duration_ns, line.name)
            for plane in data.planes if plane.name == plane_name
            for line in plane.lines if line_name in (None, line.name)
            for e in line.events]


# -- cost ---------------------------------------------------------------------

def span_cost(n: int = 50_000) -> float:
    from dcr_tpu.core import tracing

    for _ in range(2_000):
        with tracing.span("cost/probe"):
            pass
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.span("cost/probe"):
            pass
    return 1e6 * (time.perf_counter() - t0) / n


def cost() -> None:
    import jax

    from dcr_tpu.core import tracing

    jax.devices()
    work = Path(tempfile.mkdtemp(prefix="span_cost_"))
    say("span_us", file=False, session=False,
        us=[span_cost() for _ in range(3)])
    capture(lambda: say("span_us", file=False, session=True,
                        us=[span_cost(20_000)]), work / "p1")
    tracing.configure(work / "trace", rank=0)
    say("span_us", file=True, session=False,
        us=[span_cost() for _ in range(3)])
    capture(lambda: say("span_us", file=True, session=True,
                        us=[span_cost(20_000)]), work / "p2")
    shutil.rmtree(work, ignore_errors=True)


# -- search -------------------------------------------------------------------

def open_search_engine():
    from benchmark.drivers import store_search
    from benchmark.lib import harness
    from dcr_tpu.search.shardindex import open_engine

    cell = harness.load_cell("sscd-laion12m-share-search", REPO)
    c, t = cell.config, cell.traffic
    rows, dim = int(c["rows"]), int(c["embed_dim"])
    store = REPO / harness.CACHE_DIR / "stores" / (
        f"{cell.config_name}-r{rows}-d{dim}-s{c['corpus_seed']}")
    if not (store / "store_manifest.json").exists():
        store_search.build_store(store, rows, dim, int(c["corpus_seed"]),
                                 int(t["store_shard_rows"]))
    pool = store_search.query_pool(
        12345, int(c["corpus_seed"]), rows, dim, int(t["pool_batches"]),
        int(t["query_batch"]), float(t["near_copy_share"]),
        float(t["near_copy_noise"]))
    engine = open_engine(store, top_k=int(t["top_k"]),
                         query_batch=int(t["query_batch"]), segment_rows=rows)
    return engine, pool


def timed_calls(engine, pool, calls: int) -> list[float]:
    out = []
    for i in range(calls):
        t0 = time.perf_counter()
        engine.query(pool[i % len(pool)])
        out.append(1e3 * (time.perf_counter() - t0))
    return out


def lay_gaps(device, host, t0: float, t1: float) -> tuple[dict, list]:
    """Idle nanoseconds of the chip inside [t0, t1] by the program's leaf
    span they fell under (a gap is split where it crosses spans), and the
    gaps themselves with the span that covers most of each."""
    from benchmark.lib import trace as tracelib

    spans = sorted((s, s + d, n[len("dcr/"):]) for n, s, d, _ in host
                   if n.startswith("dcr/") and n[len("dcr/"):] in LEAVES)
    total: dict[str, float] = {}
    gaps = []
    for a, b in tracelib.gaps([(n, s, d) for n, s, d, _ in device], t0, t1,
                              min_ns=5_000.0):
        covered, best = 0.0, ("no span", 0.0)
        for s, e, name in spans:
            over = min(b, e) - max(a, s)
            if over > 0:
                total[name] = total.get(name, 0.0) + over
                covered += over
                if over > best[1]:
                    best = (name, over)
        total["no span"] = total.get("no span", 0.0) + (b - a) - covered
        gaps.append((a, b, best[0]))
    return total, gaps


def search(calls: int = 300) -> None:
    import jax

    engine, pool = open_search_engine()
    say("engine_open", rows=engine.total, resident=engine.resident,
        device=jax.devices()[0].device_kind)
    timed_calls(engine, pool, 20)                      # warm
    plain = timed_calls(engine, pool, calls)
    work = Path(tempfile.mkdtemp(prefix="span_capture_"))
    under, data = capture(lambda: timed_calls(engine, pool, calls), work)
    after = timed_calls(engine, pool, calls)
    q = lambda xs: [round(x, 4) for x in statistics.quantiles(xs, n=4)]   # noqa: E731
    say("call_ms", calls=calls, no_session=q(plain), session_open=q(under),
        after_session=q(after))

    host = events_of(data, "/host:CPU")
    dcr = [e for e in host if e[0].startswith("dcr/")]
    names: dict[str, int] = {}
    for n, *_ in dcr:
        names[n] = names.get(n, 0) + 1
    modules = events_of(data, "/device:TPU:0", "XLA Modules")
    ops = events_of(data, "/device:TPU:0", "XLA Ops")
    topk = [e for e in modules if e[0].startswith("jit_topk(")]
    say("capture_holds", planes=[p.name for p in data.planes],
        dcr_events=names, jit_topk_events=len(topk), xla_ops_events=len(ops),
        first_jit_topk=topk[0][0] if topk else None)
    if not topk:
        return
    # the stretch from the first to the last program run: every gap in it is
    # the host's, between two runs of the one program
    t0, t1 = topk[0][1], topk[-1][1] + topk[-1][2]
    total, gaps = lay_gaps(ops or modules, host, t0, t1)
    runs = len(topk)
    say("idle_by_span", runs=runs, stretch_ms=(t1 - t0) / 1e6,
        idle_ms_a_call={k: round(v / 1e6 / (runs - 1), 4)
                        for k, v in sorted(total.items(), key=lambda kv: -kv[1])},
        idle_share=round(100 * sum(total.values()) / (t1 - t0), 2))
    # one call's gaps, from the middle of the stretch, each with its span
    queries = sorted((s, s + d) for n, s, d, _ in dcr if n == "dcr/search/query")
    if queries:
        qs, qe = queries[len(queries) // 2]
        say("one_call", query_ms=(qe - qs) / 1e6, gaps=[
            {"from_ms": round((a - qs) / 1e6, 4), "ms": round((b - a) / 1e6, 4),
             "span": name} for a, b, name in gaps if a < qe and b > qs],
            spans=[{"span": n[4:], "from_ms": round((s - qs) / 1e6, 4),
                    "ms": round(d / 1e6, 4)} for n, s, d, _ in sorted(
                        dcr, key=lambda e: e[1]) if qs <= s <= qe])
    shutil.rmtree(work, ignore_errors=True)


def wait(calls: int = 250) -> None:
    """What `search/device_wait` costs a call: the engine's own program and
    placed segment, driven as `_scan_segment` drives them, with the fetch
    alone (the parent's path), with a wait on both results first, and with a
    wait on one; in turn, twice over, no profiler session, no span."""
    import jax
    import numpy as np

    engine, pool = open_search_engine()
    feats, valid, _, _ = engine._dev_segments[0]

    def call(q, how: str) -> None:
        chunk = jax.device_put(q, engine._q_sharding)
        scores, idx = engine._fn(feats, valid, chunk)
        if how == "wait_both":
            jax.block_until_ready((scores, idx))
        elif how == "wait_one":
            scores.block_until_ready()
        np.asarray(scores)
        np.asarray(idx)

    for how in ("fetch_only", "wait_both", "wait_one"):
        call(pool[0], how)
    for lap in range(2):
        for how in ("fetch_only", "wait_both", "wait_one", "engine.query"):
            ms = []
            for i in range(calls):
                t0 = time.perf_counter()
                if how == "engine.query":
                    engine.query(pool[i % len(pool)])
                else:
                    call(pool[i % len(pool)], how)
                ms.append(1e3 * (time.perf_counter() - t0))
            say("wait_ms", lap=lap, how=how, calls=calls,
                quartiles=[round(x, 4) for x in statistics.quantiles(ms, n=4)])


def insitu(parent: str, calls: str = "250", laps: str = "3") -> None:
    """`engine.query` on ONE engine in one process, in turn: as it is; with
    `tracing.span` a no-op (the wait before the fetch stays); and through the
    `ShardedTopK` of another checkout (the parent commit's, which has neither).
    A process sits at a level of its own and changes it within a run (PERF.md,
    the call's two speeds), so only laps inside one process tell 0.1 ms; many
    short laps (`insitu <parent> 100 150`) tell whether one of the paths meets
    the fast mode more often than another."""
    import contextlib
    import importlib.util

    from dcr_tpu.core import tracing

    calls, laps = int(calls), int(laps)
    engine, pool = open_search_engine()
    # under the module's own name (its compile surface registers by name and
    # refuses a second owner), and not put into sys.modules
    spec = importlib.util.spec_from_file_location(
        "dcr_tpu.search.shardindex",
        Path(parent) / "dcr_tpu" / "search" / "shardindex.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    here, there, real_span = type(engine), module.ShardedTopK, tracing.span

    @contextlib.contextmanager
    def no_span(name, **_):
        yield None

    timed_calls(engine, pool, 20)
    medians: dict[str, list[float]] = {"as_is": [], "no_span": [], "parent": []}
    for lap in range(laps):
        for how in medians:
            tracing.span = no_span if how == "no_span" else real_span
            engine.__class__ = there if how == "parent" else here
            try:
                ms = timed_calls(engine, pool, calls)
            finally:
                tracing.span, engine.__class__ = real_span, here
            medians[how].append(round(statistics.median(ms), 3))
    for how, meds in medians.items():
        say("insitu_ms", how=how, calls=calls, laps=laps,
            median_of_lap_medians=statistics.median(meds),
            laps_under_15_5_ms=sum(m < 15.5 for m in meds), lap_medians=meds)


# -- scopes -------------------------------------------------------------------

def scopes() -> None:
    """The tiny train step on the chip under the profiler: every stat that
    `jax.profiler.ProfileData` shows of an `XLA Ops` event (three, none the
    scope path: my chip run, PR 26), and the `.xplane.pb` itself, in whose
    event METADATA the stat `tf_op` carries it (`jit(step_fn)/optimizer/add:`)."""
    import jax
    import numpy as np

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.core.config import MeshConfig, ModelConfig, TrainConfig
    from dcr_tpu.diffusion import train as T
    from dcr_tpu.diffusion.trainer import build_models
    from dcr_tpu.parallel import mesh as pmesh

    cfg = TrainConfig()
    cfg.model = ModelConfig.tiny()
    cfg.mixed_precision = "no"
    models, params = build_models(cfg, jax.random.key(0))
    mesh = pmesh.make_mesh(MeshConfig())
    state = T.shard_train_state(T.init_train_state(
        cfg, models, unet_params=params["unet"], text_params=params["text"],
        vae_params=params["vae"]), mesh)
    px = 8 * 2 ** (len(cfg.model.vae_block_out_channels) - 1)
    rs = np.random.default_rng(0)
    batch = pmesh.shard_batch(mesh, {
        "pixel_values": rs.uniform(-1, 1, (8, px, px, 3)).astype(np.float32),
        "input_ids": rs.integers(0, cfg.model.text_vocab_size,
                                 (8, cfg.model.text_max_length)).astype(np.int32)})
    step = T.make_train_step(cfg, models, mesh)
    key = rngmod.root_key(0)
    state, metrics = step(state, batch, key)
    jax.block_until_ready(metrics)
    work = Path(tempfile.mkdtemp(prefix="span_scopes_"))

    def run():
        s = state
        for _ in range(2):
            s, m = step(s, batch, key)
        jax.block_until_ready(m)

    _, data = capture(run, work)
    # ProfileData shows an event's own stats; its METADATA's stats (where the
    # HLO op_name lives) need the protobuf itself: keep the file for that
    out = REPO / "chiprun_out" / "pr26"
    out.mkdir(parents=True, exist_ok=True)
    for path in glob.glob(str(work / "**" / "*.xplane.pb"), recursive=True):
        shutil.copy(path, out / "scopes.xplane.pb")
        say("xplane_kept", path=str(out / "scopes.xplane.pb"),
            bytes=Path(path).stat().st_size)
    ops = [e for plane in data.planes if plane.name == "/device:TPU:0"
           for line in plane.lines if line.name == "XLA Ops"
           for e in line.events]
    for e in ops[:3]:
        say("xla_op", name=e.name[:160],
            stats={k: str(v)[:240] for k, v in e.stats})
    say("xla_ops", events=len(ops))
    shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    {"cost": cost, "search": search, "scopes": scopes, "wait": wait,
     "insitu": insitu}[sys.argv[1]](*sys.argv[2:])
