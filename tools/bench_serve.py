"""Serving-throughput bench: dynamic batching vs one-request-at-a-time —
plus a ``--chaos`` mode that proves availability under worker churn.

Default mode drives the real GenerationService in-process (no HTTP overhead
in the numbers): a sequential baseline completes each request before
submitting the next (max_batch=1 — the offline-loop serving model dcr-serve
replaces), then the batched run submits the same workload concurrently
against max_batch=N dynamic batching. Compilation is paid up front for both
and excluded. Writes BENCH_SERVE.json. Acceptance: batched > sequential.

``--chaos`` drives a real fleet (in-process FleetSupervisor, real worker
SUBPROCESSES spawned through ``dcr_tpu.cli.serve``): the same fixed request
load runs twice — once uninjected (baseline p99), once while a kill loop
SIGKILLs a READY worker every K seconds (targets found via the fleet lease
directory). Both runs share one dcr-warm persistent executable cache: the
baseline populates it cold, so its boot-to-ready times are the COLD numbers,
while every churn boot and respawn must come up WARM. Writes
BENCH_SERVE_CHAOS.json with availability %, the dropped-accepted-request
count replayed from the durable journal (MUST be 0 — the process exits 1
otherwise), p99 with/without churn, whether every churn-run response was
bit-identical to the uninjected run (it must be: every image is a pure
function of (ckpt, prompt, seed, bucket)), per-kill crash-to-ready and
crash-to-first-completion times (cold vs warm cache), and the trace-verified
compile count per process incarnation — a warm respawn that recompiles ANY
bucket fails the bench.

``--risk`` banks the cost of dcr-watch online copy-risk scoring: the same
batched workload runs with scoring off and with a synthetic train-embedding
index loaded (SSCD forward + top-k matmul after every device step), and
BENCH_RISK.json records throughput for both plus the overhead percentage.
Acceptance: overhead < 15% of batched throughput (the process exits 1
otherwise). The default knobs use more denoising steps than the throughput
bench — scoring cost is per-IMAGE while generation cost scales with steps,
so a 2-step tiny-model run would measure a regime no real deployment is in
(SD-2.1 at 50 steps amortizes SSCD to well under 1%).

``--fast`` banks the dcr-fast serving win next to the chaos/risk legs: the
same batched workload runs once on the dense default bucket and once with
the fast plan on (``FastSampleConfig`` defaults: reuse_ratio 0.5, order 2),
and BENCH_SERVE_FAST.json records throughput for both, the speedup, and
the per-trajectory UNet-call reduction. The fidelity side of the same
operating point is gated separately by tools/bench_fastsample.py — this
leg is the wall-clock half of that story.

Usage: python tools/bench_serve.py [--chaos|--risk|--fast]
Env knobs (default mode): BENCH_SERVE_REQUESTS (default 32),
BENCH_SERVE_BATCH (default 8), BENCH_SERVE_STEPS (default 4),
BENCH_SERVE_RES (default 16, tiny model).
Env knobs (--chaos): BENCH_SERVE_CHAOS_REQUESTS (default 24),
BENCH_SERVE_CHAOS_WORKERS (default 2), BENCH_SERVE_CHAOS_KILL_EVERY_S
(default 10), BENCH_SERVE_STEPS / BENCH_SERVE_RES as above.
Env knobs (--risk): BENCH_RISK_REQUESTS (default 48), BENCH_RISK_STEPS
(default 24), BENCH_RISK_IMAGE_SIZE (default 32), BENCH_RISK_INDEX_N
(default 4096), BENCH_SERVE_BATCH / BENCH_SERVE_RES as above.
Env knobs (--fast): BENCH_FAST_SERVE_REQUESTS (default 32),
BENCH_FAST_SERVE_STEPS (default 32 — the UNet-dominated regime fast
sampling targets), BENCH_FAST_REPS (median-of-N workload passes per leg,
default 3), BENCH_SERVE_BATCH / BENCH_SERVE_RES as above.
"""

from __future__ import annotations

import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

OUT = Path(__file__).resolve().parent.parent / "BENCH_SERVE.json"
OUT_CHAOS = Path(__file__).resolve().parent.parent / "BENCH_SERVE_CHAOS.json"
OUT_RISK = Path(__file__).resolve().parent.parent / "BENCH_RISK.json"
OUT_FAST = Path(__file__).resolve().parent.parent / "BENCH_SERVE_FAST.json"


def _build_stack():
    import jax

    from dcr_tpu.core.config import MeshConfig, ModelConfig, TrainConfig
    from dcr_tpu.data.tokenizer import HashTokenizer
    from dcr_tpu.diffusion.trainer import build_models
    from dcr_tpu.parallel import mesh as pmesh
    from dcr_tpu.sampling.pipeline import GenerationStack

    tiny = ModelConfig.tiny()
    tcfg = TrainConfig(mixed_precision="no")
    tcfg.model = tiny
    models, params = build_models(tcfg, jax.random.key(0))
    tok = HashTokenizer(vocab_size=tiny.text_vocab_size,
                        model_max_length=tiny.text_max_length)
    return GenerationStack(models, params, tiny, tok,
                           pmesh.make_mesh(MeshConfig()))


def _service(stack, *, max_batch: int, steps: int, res: int, risk=None,
             fast=None):
    from dcr_tpu.core.config import FastSampleConfig, RiskConfig, ServeConfig
    from dcr_tpu.serve.worker import GenerationService

    cfg = ServeConfig(resolution=res, num_inference_steps=steps,
                      sampler="ddim", max_batch=max_batch, max_wait_ms=25.0,
                      queue_depth=256, seed=0,
                      risk=risk if risk is not None else RiskConfig(),
                      fast=fast if fast is not None else FastSampleConfig())
    svc = GenerationService(cfg, stack)
    svc.start()
    return svc


def _prompts(n: int) -> list[str]:
    # 4 unique prompts cycled: a realistic repeat-heavy stream, so the
    # embedding cache participates in both legs identically
    uniq = ["a red square", "a blue circle", "a green triangle",
            "a yellow star"]
    return [uniq[i % len(uniq)] for i in range(n)]


def _peak_bytes():
    """dcr-hbm: peak device bytes so far (None on stats-less backends) —
    the HBM number every banked leg carries. Monotonic per process (no
    XLA peak reset): legs sharing one process bank the high-water mark as
    of THEIR end, so compare consecutive legs' steps, not absolute
    values."""
    from dcr_tpu.obs.memwatch import peak_bytes

    return peak_bytes()


def main() -> None:
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "32"))
    max_batch = int(os.environ.get("BENCH_SERVE_BATCH", "8"))
    steps = int(os.environ.get("BENCH_SERVE_STEPS", "4"))
    res = int(os.environ.get("BENCH_SERVE_RES", "16"))

    import jax

    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    print(f"bench_serve: {n_requests} requests, max_batch={max_batch}, "
          f"steps={steps}, res={res}, devices={len(jax.devices())}", flush=True)

    stack = _build_stack()
    prompts = _prompts(n_requests)
    result: dict = {"requests": n_requests, "max_batch": max_batch,
                    "steps": steps, "resolution": res, "sampler": "ddim",
                    "model": "tiny"}

    from dcr_tpu.serve.queue import Request

    def warmup(svc):
        # pay the compile outside the queue so timing AND latency telemetry
        # (p50/p99) reflect steady-state serving only
        svc.execute([Request(prompt="warmup", seed=0,
                             bucket=svc.default_bucket())])

    # -- sequential baseline: one request at a time, batch shape 1 ----------
    seq = _service(stack, max_batch=1, steps=steps, res=res)
    warmup(seq)
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        seq.submit(p, seed=i).future.result(timeout=600)
    seq_s = time.perf_counter() - t0
    seq.stop(timeout=60)
    result["sequential"] = {
        "total_s": round(seq_s, 3),
        "requests_per_s": round(n_requests / seq_s, 3),
        "cache": seq.cache.stats(),
        # dcr-hbm: peak device bytes after the leg (null without backend
        # memory stats — XLA:CPU)
        "hbm_peak_bytes": _peak_bytes(),
    }
    print("sequential:", json.dumps(result["sequential"]), flush=True)

    # -- batched: same workload submitted concurrently ----------------------
    bat = _service(stack, max_batch=max_batch, steps=steps, res=res)
    warmup(bat)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=min(32, n_requests)) as ex:
        futs = list(ex.map(lambda a: bat.submit(a[1], seed=a[0]).future,
                           enumerate(prompts)))
        for f in futs:
            f.result(timeout=600)
    bat_s = time.perf_counter() - t0
    snap = bat.metrics.snapshot()
    bat.stop(timeout=60)
    result["batched"] = {
        "total_s": round(bat_s, 3),
        "requests_per_s": round(n_requests / bat_s, 3),
        "batch_occupancy_avg": round(snap["batch_occupancy_avg"], 3),
        "batch_occupancy_max": snap["batch_occupancy_max"],
        "latency_ms": snap["latency_ms"],
        "cache": bat.cache.stats(),
        "hbm_peak_bytes": _peak_bytes(),
    }
    result["speedup"] = round(seq_s / bat_s, 3)
    print("batched:", json.dumps(result["batched"]), flush=True)
    print(f"speedup: {result['speedup']}x", flush=True)

    OUT.write_text(json.dumps(result, indent=2) + "\n")
    print(f"wrote {OUT}", flush=True)


# ---------------------------------------------------------------------------
# --chaos: availability under worker churn (real fleet, real SIGKILLs)
# ---------------------------------------------------------------------------

def _export_tiny_ckpt(dirpath: Path) -> Path:
    """HF-layout tiny checkpoint the spawned worker subprocesses load —
    the exact exporter the serve/fleet tests use (one source of truth for
    the tiny model's layout; the repo root is already on sys.path)."""
    from tests.test_serve import _export_tiny_ckpt as export

    return export(dirpath)


def _chaos_config(ckpt: Path, fleet_dir: Path, *, workers: int, steps: int,
                  res: int, warm_dir: Path):
    from dcr_tpu.core.config import (FleetConfig, ServeConfig,
                                     WarmCacheConfig)

    # churn-friendly knobs: quick death detection (tight lease), quick
    # respawn (short backoff, high budget — the bench wants churn, not
    # retirement), and enough dispatch attempts that a request surviving
    # several kills still completes rather than 500s. The shared warm_dir is
    # the persistent executable cache: the baseline run populates it cold,
    # and every churn (re)spawn must reach ready from it with ZERO compiles.
    return ServeConfig(
        model_path=str(ckpt), resolution=res, num_inference_steps=steps,
        sampler="ddim", max_batch=4, max_wait_ms=50.0, queue_depth=512,
        request_timeout_s=600.0, seed=0,
        warm=WarmCacheConfig(dir=str(warm_dir)),
        fleet=FleetConfig(workers=workers, dir=str(fleet_dir),
                          heartbeat_s=0.5, lease_s=3.0,
                          dispatch_timeout_s=300.0, spawn_timeout_s=300.0,
                          max_attempts=8, respawn_max=50,
                          respawn_base_delay_s=0.5, respawn_max_delay_s=2.0))


def _kill_loop(paths, workers: int, every_s: float, stop, kills: list) -> None:
    """SIGKILL one READY worker every ``every_s`` seconds, targets found the
    way any out-of-process chaos tool would: the lease directory. The victim
    is the LONGEST-ALIVE worker (oldest ``started_at``): killing the first
    alive index would keep executing a fresh respawn the moment it joined,
    which models a crash-looping binary rather than churn — under that
    regime nothing can complete anywhere and "availability" measures the
    kill cadence, not the fleet.

    First blood lands deterministically MID-FLIGHT: the loop watches the
    durable journal for the first ``dispatch`` record before striking. With
    the dcr-warm executable cache a fully warm fleet can finish the entire
    workload in well under a second — any fixed first-kill delay races the
    workload, and a churn run with zero kills proves nothing (chaos_main
    fails it)."""
    import signal

    from dcr_tpu.serve.fleet import read_lease

    def ready_leases():
        # only READY leases are victims: killing a still-warming spawn would
        # measure spawn time, not crash-to-ready recovery
        return [l for l in (read_lease(paths, i) for i in range(workers))
                if l is not None and not l.expired() and l.ready]

    def dispatched() -> bool:
        # parsed, not substring-matched: the trigger must not couple to
        # json.dumps separator defaults (the journal is tiny this early —
        # admission has barely begun)
        try:
            lines = paths.journal.read_text().splitlines()
        except OSError:
            return False
        for line in lines:
            try:
                if line.strip() and json.loads(line).get("op") == "dispatch":
                    return True
            except ValueError:
                continue
        return False

    while not stop.wait(0.02):
        if dispatched():
            break
    while not stop.wait(0.02 if not kills else every_s):
        for lease in sorted(ready_leases(), key=lambda l: l.started_at):
            try:
                os.kill(lease.pid, signal.SIGKILL)
            except OSError:
                continue             # already gone — pick the next victim
            kills.append({"t": time.time(), "worker": lease.index,
                          "pid": lease.pid})
            print(f"chaos: SIGKILL worker {lease.index} (pid {lease.pid})",
                  flush=True)
            break


def _watch_leases(paths, workers: int, stop, events: list) -> None:
    """Record every (worker, pid, ready) lease transition with a wall-clock
    stamp — the out-of-process observer the time-to-ready numbers come from
    (the same files any ops tooling would watch)."""
    from dcr_tpu.serve.fleet import read_lease

    seen: dict = {}
    while not stop.wait(0.05):
        for i in range(workers):
            lease = read_lease(paths, i)
            if lease is None:
                continue
            cur = (lease.pid, bool(lease.ready))
            if seen.get(i) != cur:
                seen[i] = cur
                events.append({"t": time.time(), "worker": i,
                               "pid": lease.pid, "ready": bool(lease.ready)})


def _journal_ack_times(journal_path) -> list:
    """[(t, worker)] for every ack in the durable journal — the
    time-to-first-completion anchor after a respawn."""
    acks = []
    for line in Path(journal_path).read_text().splitlines():
        if not line.strip():
            continue
        rec = json.loads(line)
        if rec.get("op") == "ack":
            acks.append((rec["t"], rec.get("worker", -1)))
    return sorted(acks)


def _respawn_metrics(kills: list, lease_events: list, acks: list) -> list:
    """Per-kill crash-to-ready and crash-to-first-completion times, from the
    lease transitions and the journal alone."""
    out = []
    for k in kills:
        w, t_kill = k["worker"], k["t"]
        ready = next((e for e in lease_events
                      if e["worker"] == w and e["ready"] and e["t"] > t_kill
                      and e["pid"] != k["pid"]), None)
        row = {"worker": w,
               "time_to_ready_s": (round(ready["t"] - t_kill, 3)
                                   if ready else None),
               "time_to_first_completion_s": None,
               "respawn_pid": ready["pid"] if ready else None}
        if ready is not None:
            ack = next((t for t, aw in acks
                        if aw == w and t > ready["t"]), None)
            if ack is not None:
                row["time_to_first_completion_s"] = round(ack - t_kill, 3)
        out.append(row)
    return out


def _compiles_by_pid(fleet_dir: Path) -> dict:
    """XLA compiles per process incarnation across the fleet's trace files
    (tools/trace_report's recompile-budget counter)."""
    from tools import trace_report as TR

    records, errors, _ = TR.load_fleet([Path(fleet_dir)], TR.load_schema())
    if errors:
        print(f"chaos: {len(errors)} invalid trace record(s) under "
              f"{fleet_dir} (first: {errors[0]})", flush=True)
    return TR.compiles_per_incarnation(records)


def _run_fleet_workload(cfg, jobs, *, kill_every_s=None) -> dict:
    """One fleet run: submit every (prompt, seed) job concurrently, return
    response docs keyed by job plus availability/latency/journal numbers."""
    import threading

    from dcr_tpu.serve.fleet import RequestJournal
    from dcr_tpu.serve.supervisor import FleetSupervisor

    t_start = time.time()
    sup = FleetSupervisor(cfg)
    sup.start()
    stop_watch = threading.Event()
    lease_events: list = []
    watcher = threading.Thread(
        target=_watch_leases,
        args=(sup.paths, cfg.fleet.workers, stop_watch, lease_events),
        daemon=True, name="chaos-lease-watch")
    watcher.start()
    deadline = time.monotonic() + cfg.fleet.spawn_timeout_s
    while sup.health() != "ok" or sup.status()["workers_alive"] == 0:
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"fleet did not come up: health={sup.health()!r} "
                f"status={sup.status()!r}")
        time.sleep(0.25)

    stop_kills = threading.Event()
    kills: list = []
    killer = None
    if kill_every_s:
        killer = threading.Thread(
            target=_kill_loop,
            args=(sup.paths, cfg.fleet.workers, kill_every_s, stop_kills,
                  kills),
            daemon=True, name="chaos-killer")
        killer.start()

    t0 = time.perf_counter()
    accepted, rejected, completed, failed = [], 0, {}, {}
    for prompt, seed in jobs:
        try:
            accepted.append(((prompt, seed), sup.submit(prompt, seed=seed)))
        except Exception as e:
            rejected += 1
            print(f"chaos: rejected ({prompt!r}, {seed}): {e!r}", flush=True)
    for job, req in accepted:
        try:
            completed[job] = req.future.result(
                timeout=cfg.request_timeout_s)
        except Exception as e:
            failed[f"{job[0]}#{job[1]}"] = repr(e)   # str key: JSON-safe
    total_s = time.perf_counter() - t0
    # latency percentiles snapshot BEFORE the post-respawn probe phase: the
    # banked p50/p99 must describe the measured workload only, or the churn
    # run's tail would be diluted by probes the baseline never sends
    pct = sup.metrics.latency.percentiles((50, 99))

    stop_kills.set()
    if killer is not None:
        killer.join(timeout=2 * (kill_every_s or 1.0))
    # observe crash-to-ready recovery BEFORE draining: a short workload can
    # finish on survivors while the victim is still respawning — without
    # this wait the bench would bank nulls instead of time-to-ready. Then a
    # probe workload gives the respawned worker completions, so
    # time-to-first-completion is measurable too.
    probe_done = 0
    if kills:
        deadline = time.monotonic() + 90.0
        def respawn_ready(k):
            return any(e["worker"] == k["worker"] and e["ready"]
                       and e["pid"] != k["pid"] and e["t"] > k["t"]
                       for e in lease_events)
        while (not all(respawn_ready(k) for k in kills)
               and time.monotonic() < deadline):
            time.sleep(0.1)
        probe_reqs = []
        for i in range(2 * cfg.fleet.workers * cfg.max_batch):
            try:
                probe_reqs.append(sup.submit("post-respawn probe",
                                             seed=100_000 + i))
            except Exception as e:
                print(f"chaos: probe rejected: {e!r}", flush=True)
        for req in probe_reqs:
            try:
                req.future.result(timeout=cfg.request_timeout_s)
                probe_done += 1
            except Exception as e:
                print(f"chaos: probe failed: {e!r}", flush=True)
    sup.begin_drain()
    sup.join_drained(cfg.request_timeout_s)
    sup.shutdown()
    stop_watch.set()
    watcher.join(timeout=2.0)
    replay = RequestJournal.replay(sup.paths.journal)
    acks = _journal_ack_times(sup.paths.journal)
    # crash-to-ready / crash-to-first-completion per kill, and initial
    # boot-to-ready per worker (the cold-vs-warm cache comparison)
    first_ready = {}
    first_pids = {}
    for e in lease_events:
        first_pids.setdefault(e["worker"], e["pid"])
        if e["ready"] and e["worker"] not in first_ready:
            first_ready[e["worker"]] = e["t"]
    boot_ttr = [round(t - t_start, 3) for _, t in sorted(first_ready.items())]
    # compiles per incarnation from the fleet's trace files, split into the
    # first (boot) incarnation of each worker vs respawns: a warm respawn
    # performing ANY compile is a bench failure (chaos_main enforces it)
    compiles = _compiles_by_pid(Path(cfg.fleet.dir))
    boot_pids = {str(p) for p in first_pids.values()}
    respawn_compiles = {
        inc: n for inc, n in compiles.items()
        if inc.rpartition("@pid")[2] not in boot_pids and n > 0}

    n_acc = len(accepted)
    return {
        "attempted": len(jobs),
        "accepted": n_acc,
        "rejected": rejected,
        "completed": len(completed),
        "failed": failed,
        "availability_pct": round(100.0 * len(completed) / max(1, n_acc), 3),
        "total_s": round(total_s, 3),
        "requests_per_s": round(len(completed) / total_s, 3),
        "latency_ms": {k: round(v * 1000.0, 3) for k, v in pct.items()},
        "kills": kills,
        "journal": replay["counts"],
        "boot_time_to_ready_s": boot_ttr,
        "respawns": _respawn_metrics(kills, lease_events, acks),
        "probes_completed": probe_done,
        "compiles_per_incarnation": compiles,
        "respawn_compiles": respawn_compiles,
        "results": completed,
    }


def _response_key(doc: dict) -> tuple:
    # the content that must be bit-identical across runs/workers; id, worker,
    # cache_hit, and latency legitimately differ
    return (doc.get("image_png_b64"), doc.get("width"), doc.get("height"))


def chaos_main() -> None:
    import tempfile

    n_requests = int(os.environ.get("BENCH_SERVE_CHAOS_REQUESTS", "24"))
    workers = int(os.environ.get("BENCH_SERVE_CHAOS_WORKERS", "2"))
    # the interval must leave a worker's survivors room to actually finish
    # batches between kills: on this CPU a respawned worker takes ~10s to
    # rejoin and a batch runs for several seconds, so sub-5s cadences degrade
    # into a crash loop where nothing completes anywhere
    kill_every_s = float(os.environ.get("BENCH_SERVE_CHAOS_KILL_EVERY_S",
                                        "10"))
    steps = int(os.environ.get("BENCH_SERVE_STEPS", "4"))
    res = int(os.environ.get("BENCH_SERVE_RES", "16"))

    # deliberately NO JAX persistent compile cache: dcr-warm's executable
    # cache is the thing under test, the baseline leg must be genuinely
    # COLD, and with XLA's cache active this jaxlib's CPU backend emits
    # executables whose raw serialization is broken — every entry would
    # degrade to the export tier, whose compile-on-load is (correctly)
    # counted by the recompile budget and would fail the zero-compile
    # respawn gate below. Strip the vars in case the caller's shell set them.
    for k in list(os.environ):
        if k.startswith("JAX_COMPILATION") or k.startswith("JAX_PERSISTENT"):
            os.environ.pop(k)
    # ... and the workers' setup_compile_cache() would otherwise turn it on
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

    print(f"bench_serve --chaos: {n_requests} requests, {workers} workers, "
          f"kill every {kill_every_s}s, steps={steps}, res={res}", flush=True)
    jobs = [(p, i) for i, p in enumerate(_prompts(n_requests))]

    with tempfile.TemporaryDirectory(prefix="dcr-chaos-") as td:
        tmp = Path(td)
        ckpt = _export_tiny_ckpt(tmp)
        # one persistent executable cache shared across BOTH runs: the
        # baseline populates it cold (its boot_time_to_ready_s is the cold
        # number), then every churn spawn AND respawn must come up warm —
        # zero compiles, trace-verified below
        warm_dir = tmp / "warmcache"
        baseline = _run_fleet_workload(
            _chaos_config(ckpt, tmp / "fleet_baseline", workers=workers,
                          steps=steps, res=res, warm_dir=warm_dir), jobs)
        print("baseline:", json.dumps({k: v for k, v in baseline.items()
                                       if k != "results"}), flush=True)
        churn = _run_fleet_workload(
            _chaos_config(ckpt, tmp / "fleet_churn", workers=workers,
                          steps=steps, res=res, warm_dir=warm_dir), jobs,
            kill_every_s=kill_every_s)
        print("churn:", json.dumps({k: v for k, v in churn.items()
                                    if k != "results"}), flush=True)

    mismatched = [job for job in baseline["results"]
                  if job in churn["results"]
                  and _response_key(baseline["results"][job])
                  != _response_key(churn["results"][job])]
    result = {
        "requests": n_requests, "workers": workers,
        "kill_every_s": kill_every_s, "steps": steps, "resolution": res,
        "sampler": "ddim", "model": "tiny",
        "baseline": {k: v for k, v in baseline.items() if k != "results"},
        "churn": {k: v for k, v in churn.items() if k != "results"},
        "kills": len(churn["kills"]),
        "dropped_accepted_requests": churn["journal"]["dropped"],
        "requeued": churn["journal"]["requeued_total"],
        "availability_pct": churn["availability_pct"],
        "p99_ms_baseline": baseline["latency_ms"].get("p99"),
        "p99_ms_churn": churn["latency_ms"].get("p99"),
        "bit_identical_responses": not mismatched,
        "mismatched_jobs": [list(j) for j in mismatched],
        # crash-to-ready recovery (dcr-warm): baseline boots are COLD (empty
        # executable cache), churn boots and every respawn are WARM
        "cold_boot_time_to_ready_s": baseline["boot_time_to_ready_s"],
        "warm_boot_time_to_ready_s": churn["boot_time_to_ready_s"],
        "warm_respawn_time_to_ready_s": [
            r["time_to_ready_s"] for r in churn["respawns"]],
        "warm_respawn_time_to_first_completion_s": [
            r["time_to_first_completion_s"] for r in churn["respawns"]],
        "respawn_compiles": churn["respawn_compiles"],
    }
    OUT_CHAOS.write_text(json.dumps(result, indent=2) + "\n")
    print(f"wrote {OUT_CHAOS}", flush=True)

    problems = []
    if churn["journal"]["dropped"] != 0:
        problems.append(
            f"dropped accepted requests: {churn['journal']['dropped']}")
    if churn["availability_pct"] < 100.0:
        problems.append(f"availability {churn['availability_pct']}% "
                        f"(failed: {churn['failed']})")
    if mismatched:
        problems.append(f"{len(mismatched)} response(s) not bit-identical "
                        f"to the uninjected run")
    if not churn["kills"]:
        problems.append("kill loop never fired — the churn run proved "
                        "nothing (workload too short for the cadence?)")
    if churn["respawn_compiles"]:
        problems.append(
            f"warm respawn recompiled: {churn['respawn_compiles']} — the "
            "persistent executable cache did not serve the respawned worker")
    if problems:
        print("CHAOS FAIL: " + "; ".join(problems), flush=True)
        raise SystemExit(1)
    print(f"CHAOS OK: {len(churn['kills'])} kill(s), "
          f"{churn['journal']['requeued_total']} requeue(s), 0 drops, "
          f"bit-identical responses", flush=True)


# ---------------------------------------------------------------------------
# --risk: online copy-risk scoring overhead (dcr-watch)
# ---------------------------------------------------------------------------

def _timed_batched_leg(stack, prompts, *, max_batch, steps, res, risk=None):
    """One batched serving leg (the same shape as main()'s): build, warm,
    submit the whole workload concurrently. Returns (wall seconds,
    seconds spent inside the risk-scoring path, service) — scoring time is
    measured around the service's own ``_score_risk`` so the overhead
    number comes from ONE leg and cannot be polluted by machine-load drift
    between two separately-timed runs (this box is a noisy shared core)."""
    from dcr_tpu.serve.queue import Request

    svc = _service(stack, max_batch=max_batch, steps=steps, res=res,
                   risk=risk)
    if risk is not None:
        if not svc.wait_risk_ready(timeout=600):
            raise RuntimeError("risk index never terminalized")
        if svc.risk_status() != "ok":
            raise RuntimeError(f"risk index load: {svc.risk_status()}")
    scoring = {"s": 0.0}
    orig_score = svc._score_risk

    def timed_score(*args, **kw):
        t = time.perf_counter()
        try:
            return orig_score(*args, **kw)
        finally:
            scoring["s"] += time.perf_counter() - t

    svc._score_risk = timed_score
    # warm outside the timed window: sampler compile AND (risk leg) the
    # first scored batch, so both legs time steady-state serving only
    svc.execute([Request(prompt="warmup", seed=0,
                         bucket=svc.default_bucket())])
    scoring["s"] = 0.0
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=min(32, len(prompts))) as ex:
        futs = list(ex.map(lambda a: svc.submit(a[1], seed=a[0]).future,
                           enumerate(prompts)))
        for f in futs:
            f.result(timeout=600)
    elapsed = time.perf_counter() - t0
    return elapsed, scoring["s"], svc


def risk_main() -> None:
    import tempfile

    import numpy as np

    n_requests = int(os.environ.get("BENCH_RISK_REQUESTS", "48"))
    max_batch = int(os.environ.get("BENCH_SERVE_BATCH", "8"))
    # steps calibrates the generation:scoring work ratio. Measured on this
    # 1-core CPU: SSCD-at-32px scoring costs ~170ms per batch of 8 while a
    # 24-step tiny-model batch generates in ~290ms — a ratio ~10^4 MORE
    # pessimistic than any real deployment (SD-2.1 at 256px/50 steps is
    # ~70 TFLOPs of denoising per image vs ~0.1 GFLOPs of SSCD). 128 steps
    # still under-states generation cost by orders of magnitude but keeps
    # the bench honest about the scoring path's absolute cost.
    steps = int(os.environ.get("BENCH_RISK_STEPS", "128"))
    res = int(os.environ.get("BENCH_SERVE_RES", "16"))
    image_size = int(os.environ.get("BENCH_RISK_IMAGE_SIZE", "32"))
    index_n = int(os.environ.get("BENCH_RISK_INDEX_N", "4096"))

    import jax

    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    print(f"bench_serve --risk: {n_requests} requests, max_batch={max_batch},"
          f" steps={steps}, res={res}, index_n={index_n}, "
          f"image_size={image_size}", flush=True)

    stack = _build_stack()
    prompts = _prompts(n_requests)
    result: dict = {"requests": n_requests, "max_batch": max_batch,
                    "steps": steps, "resolution": res, "sampler": "ddim",
                    "model": "tiny", "index_n": index_n,
                    "image_size": image_size}

    with tempfile.TemporaryDirectory(prefix="dcr-bench-risk-") as td:
        # synthetic train index at a realistic-for-CPU size: deterministic
        # features (jax PRNG, not global numpy RNG), threshold above 1 so
        # the timed loop never pays evidence I/O — this bench measures
        # SCORING, the flag path is covered by tests
        from dcr_tpu.core.config import RiskConfig
        from dcr_tpu.obs.copyrisk import EMBED_DIM
        from dcr_tpu.search.embed import save_embeddings

        feats = np.asarray(jax.random.normal(
            jax.random.key(7), (index_n, EMBED_DIM)), np.float32)
        index_path = Path(td) / "embedding.npz"
        save_embeddings(index_path, feats,
                        [f"train/{i}" for i in range(index_n)])

        off_s, _, svc_off = _timed_batched_leg(
            stack, prompts, max_batch=max_batch, steps=steps, res=res)
        snap_off = svc_off.metrics.snapshot()
        svc_off.stop(timeout=60)
        result["scoring_off"] = {
            "total_s": round(off_s, 3),
            "requests_per_s": round(n_requests / off_s, 3),
            "latency_ms": snap_off["latency_ms"],
            "hbm_peak_bytes": _peak_bytes(),
        }
        print("scoring off:", json.dumps(result["scoring_off"]), flush=True)

        risk = RiskConfig(index_path=str(index_path), image_size=image_size,
                          threshold=2.0, max_evidence=0)
        on_s, score_s, svc_on = _timed_batched_leg(
            stack, prompts, max_batch=max_batch, steps=steps, res=res,
            risk=risk)
        snap_on = svc_on.metrics.snapshot()
        scored = svc_on.status()["risk"]
        svc_on.stop(timeout=60)
        result["scoring_on"] = {
            "total_s": round(on_s, 3),
            "requests_per_s": round(n_requests / on_s, 3),
            "scoring_s": round(score_s, 3),
            "latency_ms": snap_on["latency_ms"],
            "risk": scored,
            "hbm_peak_bytes": _peak_bytes(),
        }
        print("scoring on:", json.dumps(result["scoring_on"]), flush=True)

    # the load-bearing number comes from ONE leg: scoring seconds vs the
    # same leg's non-scoring (generation) seconds. The serving pipeline is
    # a single worker thread, so this ratio IS the steady-state throughput
    # overhead — and unlike wall-clock A/B between two legs it cannot be
    # polluted by the shared box speeding up or slowing down between runs
    # (observed swings > 25% leg-to-leg on this 1-core container). The
    # off leg is banked as a reference point.
    overhead = 100.0 * score_s / max(1e-9, on_s - score_s)
    result["scoring_overhead_pct"] = round(overhead, 2)
    result["wall_delta_pct"] = round(100.0 * (on_s - off_s) / off_s, 2)
    print(f"scoring overhead: {result['scoring_overhead_pct']}% of batched "
          f"throughput (wall-clock A/B delta {result['wall_delta_pct']}%)",
          flush=True)
    OUT_RISK.write_text(json.dumps(result, indent=2) + "\n")
    print(f"wrote {OUT_RISK}", flush=True)
    if overhead >= 15.0:
        print(f"RISK BENCH FAIL: scoring overhead {overhead:.1f}% >= 15% "
              "of batched throughput", flush=True)
        raise SystemExit(1)
    print("RISK BENCH OK", flush=True)


# ---------------------------------------------------------------------------
# --fast: serving throughput with the dcr-fast score-reuse plan on
# ---------------------------------------------------------------------------

def fast_main() -> None:
    from dcr_tpu.core.config import FastSampleConfig
    from dcr_tpu.sampling import fastsample
    from dcr_tpu.serve.queue import Request

    n_requests = int(os.environ.get("BENCH_FAST_SERVE_REQUESTS", "32"))
    max_batch = int(os.environ.get("BENCH_SERVE_BATCH", "8"))
    # more steps than the throughput bench: fast sampling's win scales with
    # the denoiser fraction of a request, and a 4-step run measures batching
    # overhead, not sampling
    steps = int(os.environ.get("BENCH_FAST_SERVE_STEPS", "32"))
    res = int(os.environ.get("BENCH_SERVE_RES", "16"))

    import jax

    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    print(f"bench_serve --fast: {n_requests} requests, max_batch={max_batch},"
          f" steps={steps}, res={res}", flush=True)

    stack = _build_stack()
    prompts = _prompts(n_requests)
    fast_cfg = FastSampleConfig(enabled=True)        # the default operating
    plan = fastsample.fast_plan(steps, fast_cfg.reuse_ratio)  # point
    calls = fastsample.unet_calls(plan)
    result: dict = {"requests": n_requests, "max_batch": max_batch,
                    "steps": steps, "resolution": res, "sampler": "ddim",
                    "model": "tiny", "reuse_ratio": fast_cfg.reuse_ratio,
                    "order": fast_cfg.order, "unet_calls_per_trajectory": calls,
                    "call_reduction": round(steps / max(1, calls), 3)}

    import statistics

    reps = int(os.environ.get("BENCH_FAST_REPS", "3"))

    def leg(fast=None) -> dict:
        # median of `reps` workload passes per leg: cross-leg wall A/B on
        # this shared box swings ±25% (see the --risk leg's rationale), so
        # a single-shot comparison would gate on machine-load noise
        svc = _service(stack, max_batch=max_batch, steps=steps, res=res,
                       fast=fast)
        svc.execute([Request(prompt="warmup", seed=0,
                             bucket=svc.default_bucket())])
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=min(32, n_requests)) as ex:
                futs = list(ex.map(
                    lambda a: svc.submit(a[1], seed=a[0]).future,
                    enumerate(prompts)))
                for f in futs:
                    f.result(timeout=600)
            walls.append(time.perf_counter() - t0)
        elapsed = statistics.median(walls)
        snap = svc.metrics.snapshot()
        svc.stop(timeout=60)
        return {"total_s": round(elapsed, 3),
                "reps": reps,
                "requests_per_s": round(n_requests / elapsed, 3),
                "latency_ms": snap["latency_ms"],
                "hbm_peak_bytes": _peak_bytes()}

    result["dense"] = leg()
    print("dense:", json.dumps(result["dense"]), flush=True)
    result["fast"] = leg(fast=fast_cfg)
    print("fast:", json.dumps(result["fast"]), flush=True)
    result["speedup"] = round(result["dense"]["total_s"]
                              / result["fast"]["total_s"], 3)
    print(f"fast-plan speedup: {result['speedup']}x at "
          f"{result['call_reduction']}x fewer UNet calls", flush=True)
    OUT_FAST.write_text(json.dumps(result, indent=2) + "\n")
    print(f"wrote {OUT_FAST}", flush=True)
    if result["speedup"] <= 1.0:
        # the plan skips real work; slower-than-dense means the machinery
        # broke (or the box is so loaded the numbers are meaningless)
        print("FAST BENCH FAIL: fast leg not faster than dense", flush=True)
        raise SystemExit(1)
    print("FAST BENCH OK", flush=True)


if __name__ == "__main__":
    if "--chaos" in sys.argv[1:]:
        chaos_main()
    elif "--risk" in sys.argv[1:]:
        risk_main()
    elif "--fast" in sys.argv[1:]:
        fast_main()
    else:
        main()
