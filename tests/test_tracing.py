"""dcr-obs tests: span tracer, telemetry registry, flight recorder, report.

Fast tier: pure-logic units — registry snapshot semantics, Prometheus text,
span parenting via contextvars, ring-buffer bounding, dump semantics,
log_event/log_trace level routing, trace_report aggregation + schema
validation + Chrome export.

Slow tier (the CI `observability` job): a tiny CPU train run and a real
dcr-serve session each produce a schema-valid trace.jsonl that
tools/trace_report.py renders (exit 0) and exports to loadable Chrome-trace
JSON; an injected hang (DCR_FAULTS) exits 89 with a flight-recorder dump
holding the last spans; an injected NaN fail-fast dumps with the nan_abort
reason; serve's /metrics?format=prometheus parses and includes faults
counters. Training/serve legs run as real CLI subprocesses (one process per
scenario — the production model, and required here: see the Orbax SIGABRT
note in tests/test_fault_injection.py).
"""

import json
import logging
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing
from tools import trace_report

pytest_plugins: list = []


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


# ---------------------------------------------------------------------------
# telemetry registry
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_registry_counter_gauge_histogram_snapshot():
    reg = tracing.registry()
    assert reg.counter("faults/x").inc() == 1
    assert reg.counter("faults/x").inc(2) == 3
    reg.gauge("loss").set(0.25)
    h = reg.histogram("lat", window=64)
    for v in range(1, 101):
        h.observe(v / 100.0)
    snap = reg.snapshot()
    assert snap["counters"]["faults/x"] == 3
    assert snap["gauges"]["loss"] == 0.25
    hs = snap["histograms"]["lat"]
    # lifetime count vs windowed percentiles: the reservoir holds 64, the
    # counter remembers all 100
    assert hs["count"] == 100
    assert hs["sum"] == pytest.approx(sum(v / 100.0 for v in range(1, 101)))
    assert 0.3 < hs["p50"] < 1.0 and hs["p99"] >= hs["p50"]
    # same object on re-lookup (get-or-create)
    assert reg.counter("faults/x").value == 3
    reg.reset("faults/")
    assert reg.counters("faults/") == {}
    assert reg.snapshot()["gauges"]["loss"] == 0.25  # other prefixes survive


@pytest.mark.fast
def test_registry_counters_thread_safe():
    reg = tracing.registry()

    def worker():
        for _ in range(500):
            reg.counter("faults/threads").inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("faults/threads").value == 4000


@pytest.mark.fast
def test_prometheus_text_renders_and_parses():
    from dcr_tpu.core.metrics import LatencyTracker

    R.bump_counter("kv_gc_errors", 2)
    tracing.registry().gauge("serve/queue_depth").set(3)
    lt = LatencyTracker(name="serve/request_latency_s")
    lt.observe(0.5)
    text = tracing.registry().prometheus_text()
    # minimal exposition-format parse: every non-comment line is
    # `name{labels}? value` with a float-parseable value
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            parts = line.split()
            assert parts[1] in ("TYPE", "HELP")
            if parts[1] == "TYPE":
                assert parts[3] in ("counter", "gauge", "summary")
            continue
        name, value = line.rsplit(" ", 1)
        samples[name] = float(value)
    assert samples["dcr_faults_kv_gc_errors"] == 2.0
    assert samples["dcr_faults_total"] == 2.0
    assert samples["dcr_serve_queue_depth"] == 3.0
    assert samples['dcr_serve_request_latency_s{quantile="0.50"}'] == 0.5
    assert samples["dcr_serve_request_latency_s_count"] == 1.0


@pytest.mark.fast
def test_prometheus_faults_total_present_on_clean_process():
    """Scrapes must be able to alert on faults-rate before any fault exists."""
    text = tracing.registry().prometheus_text()
    assert "dcr_faults_total 0" in text


@pytest.mark.fast
def test_update_gauges_flattens_nested_and_bools():
    tracing.update_gauges({"a": 1, "nested": {"b": 2.5}, "flag": True,
                           "skip": "strings"}, prefix="s/")
    g = tracing.registry().snapshot()["gauges"]
    assert g["s/a"] == 1.0 and g["s/nested/b"] == 2.5 and g["s/flag"] == 1.0
    assert "s/skip" not in g


@pytest.mark.fast
def test_merge_counter_rows_sums_sparse_hosts():
    assert tracing.merge_counter_rows([
        {"bad_samples": 2}, {"bad_samples": 1, "kv_gc_errors": 3}, {},
    ]) == {"bad_samples": 3, "kv_gc_errors": 3}


# ---------------------------------------------------------------------------
# resilience integration: counters + log levels
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_bump_counter_rides_registry():
    R.bump_counter("decode_fallbacks")
    R.bump_counter("decode_fallbacks", 2)
    assert R.counters() == {"decode_fallbacks": 3}
    # visible to Prometheus under the faults/ prefix
    assert tracing.registry().counters("faults/") == {
        "faults/decode_fallbacks": 3}
    R.reset_counters()
    assert R.counters() == {}


@pytest.mark.fast
def test_log_event_levels_and_prefixes(caplog):
    with caplog.at_level(logging.INFO, logger="dcr_tpu"):
        R.log_event("something_failed", step=3)
        R.log_trace("stage_begin", name="eval")
    fault = [r for r in caplog.records if "something_failed" in r.getMessage()]
    trace = [r for r in caplog.records if "stage_begin" in r.getMessage()]
    assert fault[0].levelno == logging.WARNING
    assert fault[0].getMessage().startswith("[fault] ")
    assert trace[0].levelno == logging.INFO
    assert trace[0].getMessage().startswith("[trace] ")


@pytest.mark.fast
def test_log_event_lands_in_flight_recorder_as_fault_event():
    R.log_event("bad_thing", step=7)
    recs = tracing.flight_records()
    fault_events = [r for r in recs if r["name"] == "fault/bad_thing"]
    assert fault_events and fault_events[0]["args"]["step"] == 7


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_span_nesting_parents_via_contextvars(tmp_path):
    path = tracing.configure(tmp_path, rank=0)
    assert path == tmp_path / "trace.jsonl"
    with tracing.span("outer") as outer:
        assert tracing.current_span_id() == outer.id
        with tracing.span("inner", detail=1) as inner:
            pass
        tracing.event("mark")
    assert tracing.current_span_id() is None
    recs = {r["name"]: r for r in tracing.flight_records()}
    assert recs["inner"]["parent"] == outer.id
    assert recs["mark"]["parent"] == outer.id
    assert recs["outer"]["parent"] is None
    assert recs["inner"]["args"] == {"detail": 1}
    # inner closed first, so it appears first; durations nest
    assert recs["outer"]["dur"] >= recs["inner"]["dur"]
    # file got the same records, schema-valid
    schema = trace_report.load_schema()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 3
    for rec in lines:
        assert trace_report.validate_record(rec, schema) == []


@pytest.mark.fast
def test_span_records_error_and_reraises(tmp_path):
    tracing.configure(tmp_path, rank=0)
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("nope")
    [rec] = tracing.flight_records()
    assert rec["name"] == "boom" and "ValueError" in rec["args"]["error"]


@pytest.mark.fast
def test_span_threads_do_not_share_parents(tmp_path):
    tracing.configure(tmp_path, rank=0)
    seen = {}

    def worker():
        with tracing.span("thread_root") as h:
            seen["parent"] = h.parent

    with tracing.span("main_root"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # a fresh thread starts a fresh context: no accidental cross-thread parent
    assert seen["parent"] is None


@pytest.mark.fast
def test_begin_end_handle_idempotent_and_complete_span(tmp_path):
    tracing.configure(tmp_path, rank=0)
    h = tracing.begin_span("serve/request", request_id=5)
    h.end(outcome="ok")
    h.end(outcome="double")                      # future callbacks can race
    tracing.complete_span("serve/queue_wait", start_wall=time.time() - 1.0,
                          dur_s=1.0, parent=h.id, request_id=5)
    recs = tracing.flight_records()
    assert [r["name"] for r in recs] == ["serve/request", "serve/queue_wait"]
    assert recs[0]["args"] == {"request_id": 5, "outcome": "ok"}
    assert recs[1]["parent"] == h.id
    assert recs[1]["dur"] == pytest.approx(1e6, rel=0.01)


@pytest.mark.fast
def test_ring_buffer_is_bounded():
    maxlen = tracing._state.ring.maxlen
    for i in range(maxlen + 50):
        tracing.event("e", i=i)
    recs = tracing.flight_records()
    assert len(recs) == maxlen
    assert recs[-1]["args"]["i"] == maxlen + 49   # newest kept, oldest dropped
    assert recs[0]["args"]["i"] == 50


@pytest.mark.fast
def test_trace_disabled_by_env_keeps_ring(tmp_path, monkeypatch):
    monkeypatch.setenv("DCR_TRACE", "0")
    assert tracing.configure(tmp_path, rank=0) is None
    with tracing.span("still_recorded"):
        pass
    assert not (tmp_path / "trace.jsonl").exists()
    assert [r["name"] for r in tracing.flight_records()] == ["still_recorded"]
    # flight recorder still anchored to the configured dir
    assert tracing.dump_flight_recorder("test") == tmp_path / "flightrec_0.json"


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_flight_recorder_dump_contents(tmp_path):
    tracing.configure(tmp_path, rank=0)
    with tracing.span("train/step", step=9):
        pass
    R.bump_counter("rollbacks")
    path = tracing.dump_flight_recorder("nan_abort: step 9 loss nan")
    doc = json.loads(path.read_text())
    assert doc["reason"].startswith("nan_abort")
    assert doc["rank"] == 0
    assert [r["name"] for r in doc["records"]] == ["train/step"]
    assert doc["registry"]["counters"]["faults/rollbacks"] == 1


@pytest.mark.fast
def test_flight_recorder_first_dump_wins(tmp_path):
    tracing.configure(tmp_path, rank=0)
    first = tracing.dump_flight_recorder("nan_abort")
    second = tracing.dump_flight_recorder("unhandled_exception: later")
    assert first == second
    assert json.loads(first.read_text())["reason"] == "nan_abort"


@pytest.mark.fast
def test_flight_recorder_unconfigured_is_noop(monkeypatch):
    monkeypatch.delenv("DCR_FLIGHTREC_DIR", raising=False)
    assert tracing.dump_flight_recorder("nowhere to go") is None


@pytest.mark.fast
def test_flight_recorder_env_dir_fallback(tmp_path, monkeypatch):
    monkeypatch.setenv("DCR_FLIGHTREC_DIR", str(tmp_path))
    tracing.event("before_death")
    path = tracing.dump_flight_recorder("env fallback")
    assert path is not None and path.parent == tmp_path


# ---------------------------------------------------------------------------
# trace_report
# ---------------------------------------------------------------------------

def _write_synthetic_trace(tmp_path: Path) -> Path:
    tracing.configure(tmp_path, rank=0)
    for step in range(3):
        with tracing.span("train/data_wait", step=step):
            pass
        with tracing.span("train/step", step=step):
            pass
    with tracing.span("ckpt/save", step=2):
        pass
    tracing.complete_span("serve/queue_wait", start_wall=time.time(),
                          dur_s=0.02, request_id=1)
    tracing.event("serve/compile", bucket="(16, 2)")
    tracing.event("serve/compile", bucket="(16, 2)")
    R.log_event("nan_rollback", at_step=3)
    tracing.reset_for_tests()        # close the file handle before reading
    return tmp_path


@pytest.mark.fast
def test_trace_report_summary_and_text(tmp_path, capsys):
    run_dir = _write_synthetic_trace(tmp_path)
    rc = trace_report.main([str(run_dir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "stage-time breakdown" in out
    assert "train/step" in out and "ckpt/save" in out
    assert "serve queue wait" in out
    assert "2x (16, 2)" in out                      # recompile count per bucket
    assert "fault/nan_rollback" in out              # fault timeline

    schema = trace_report.load_schema()
    records, errors = trace_report.load_trace(run_dir, schema)
    assert not errors
    summary = trace_report.summarize(records)
    assert summary["categories"]["step"]["count"] == 3
    assert summary["categories"]["data"]["count"] == 3
    assert summary["categories"]["ckpt"]["count"] == 1
    assert summary["serve_queue_wait"]["p50_ms"] == pytest.approx(20.0, rel=0.05)
    assert summary["serve_recompiles_per_bucket"] == {"(16, 2)": 2}
    assert [f["name"] for f in summary["fault_timeline"]] == ["fault/nan_rollback"]


@pytest.mark.fast
def test_trace_report_chrome_export_loads(tmp_path, capsys):
    run_dir = _write_synthetic_trace(tmp_path)
    chrome = tmp_path / "chrome.json"
    assert trace_report.main([str(run_dir), "--chrome", str(chrome)]) == 0
    capsys.readouterr()
    doc = json.loads(chrome.read_text())
    events = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)
    xs = [e for e in events if e["ph"] == "X"]
    assert xs and all(isinstance(e["dur"], int) and isinstance(e["ts"], int)
                      and isinstance(e["pid"], int) for e in xs)
    names = {e["name"] for e in xs}
    assert {"train/step", "ckpt/save"} <= names


@pytest.mark.fast
def test_trace_report_exit_codes(tmp_path, capsys):
    assert trace_report.main([str(tmp_path)]) == 1          # empty dir
    (tmp_path / "trace.jsonl").write_text('{"ph": "X", "name": 3}\n')
    assert trace_report.main([str(tmp_path)]) == 2          # schema violation
    capsys.readouterr()


@pytest.mark.fast
def test_validate_record_catches_field_drift():
    schema = trace_report.load_schema()
    good = {"ph": "i", "name": "e", "id": 1, "ts": 1.0, "pid": 0, "tid": 1,
            "tname": "t", "args": {}}
    assert trace_report.validate_record(good, schema) == []
    assert trace_report.validate_record({**good, "ph": "Z"}, schema)
    assert trace_report.validate_record({**good, "name": 7}, schema)
    span = {**good, "ph": "X"}
    assert trace_report.validate_record(span, schema)        # missing dur
    assert trace_report.validate_record({**span, "dur": 5}, schema) == []


# ---------------------------------------------------------------------------
# subprocess e2e: train + hang + NaN + serve (slow; CI `observability` job)
# ---------------------------------------------------------------------------

def _tiny_train_cfg(tmp_path: Path):
    from PIL import Image

    from dcr_tpu.core.config import (DataConfig, ModelConfig, OptimConfig,
                                     TrainConfig)

    rng = np.random.default_rng(0)
    for cls in ["c0", "c1"]:
        d = tmp_path / "data" / cls
        d.mkdir(parents=True, exist_ok=True)
        for i in range(8):
            Image.fromarray(rng.integers(0, 255, (20, 20, 3), np.uint8)).save(
                d / f"{i}.png")
    return TrainConfig(
        output_dir=str(tmp_path / "run"),
        seed=0, train_batch_size=2, max_train_steps=4, num_train_epochs=20,
        mixed_precision="no", save_steps=1000, modelsavesteps=2, log_every=1,
        model=ModelConfig.tiny(),
        data=DataConfig(train_data_dir=str(tmp_path / "data"), resolution=16,
                        class_prompt="nolevel", num_workers=2, seed=0),
        optim=OptimConfig(learning_rate=1e-4, lr_scheduler="constant",
                          lr_warmup_steps=0),
    )


def _subprocess_env(extra=None):
    import os

    repo = Path(__file__).parent.parent
    env = dict(os.environ)
    env.pop("DCR_FAULTS", None)
    env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(repo) + os.pathsep + env.get("PYTHONPATH", ""),
        JAX_THREEFRY_PARTITIONABLE="1",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="1.0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    env.update(extra or {})
    return env, repo


def _run_train_cli(cfg, cfg_path, *, extra_env=None, timeout=540):
    import subprocess
    import sys

    from dcr_tpu.core.config import save_config

    save_config(cfg, cfg_path)
    env, repo = _subprocess_env(extra_env)
    proc = subprocess.run(
        [sys.executable, "-m", "dcr_tpu.cli.train", f"--config={cfg_path}"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=timeout)
    return proc, proc.stdout + proc.stderr


def _assert_valid_trace(run_dir: Path, required_names: set) -> dict:
    """trace.jsonl exists, every record passes the checked-in schema, the
    required span names are present; returns the trace_report summary."""
    schema = trace_report.load_schema()
    records, errors = trace_report.load_trace(run_dir, schema)
    assert not errors, errors[:5]
    assert records, f"no trace records under {run_dir}"
    names = {r["name"] for r in records}
    assert required_names <= names, names
    return trace_report.summarize(records)


@pytest.mark.slow
def test_train_run_produces_trace_and_report(tmp_path):
    """Acceptance: a tiny CPU train run produces a trace.jsonl that
    trace_report renders into a stage-time breakdown, and whose Chrome
    export is valid JSON."""
    import subprocess
    import sys

    cfg = _tiny_train_cfg(tmp_path)
    proc, out = _run_train_cli(cfg, tmp_path / "cfg.json")
    assert proc.returncode == 0, out[-3000:]

    run_dir = Path(cfg.output_dir)
    assert (run_dir / "trace.jsonl").exists()
    summary = _assert_valid_trace(
        run_dir, {"train/step", "train/data_wait", "data/batch", "ckpt/save"})
    assert summary["categories"]["step"]["count"] == 4      # one per micro-step
    assert summary["categories"]["ckpt"]["count"] >= 1
    assert summary["fault_timeline"] == []                  # clean run

    env, repo = _subprocess_env()
    chrome = tmp_path / "chrome.json"
    rep = subprocess.run(
        [sys.executable, "-m", "tools.trace_report", str(run_dir),
         "--chrome", str(chrome), "--json"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert json.loads(rep.stdout)["spans"] > 0              # --json parses
    doc = json.loads(chrome.read_text())                    # Perfetto-loadable
    assert any(e.get("name") == "train/step" for e in doc["traceEvents"])


@pytest.mark.slow
def test_injected_hang_dumps_flight_recorder_before_exit_89(tmp_path):
    """Acceptance: DCR_FAULTS hang -> watchdog exit 89, and flightrec_0.json
    holds the last spans before the wedge."""
    from dcr_tpu.core.coordination import EXIT_HANG

    cfg = _tiny_train_cfg(tmp_path)
    proc, out = _run_train_cli(
        cfg, tmp_path / "cfg.json",
        extra_env={"DCR_FAULTS": "hang@step=3", "DCR_HANG_TIMEOUT_S": "4"})
    assert proc.returncode == EXIT_HANG, (proc.returncode, out[-3000:])

    dump = Path(cfg.output_dir) / "flightrec_0.json"
    assert dump.exists(), out[-3000:]
    doc = json.loads(dump.read_text())
    assert doc["reason"].startswith("hang_abort")
    names = [r["name"] for r in doc["records"]]
    assert "train/step" in names            # the last working spans survive
    assert any(n == "fault/injected" for n in names)  # the injection itself
    # the post-mortem log folds the recorder in
    assert "last trace records" in out


@pytest.mark.slow
def test_nan_fail_fast_dumps_flight_recorder(tmp_path):
    """Acceptance: default-config NaN fail-fast writes the nan_abort dump
    (first dump wins over the excepthook's) and still raises as the seed."""
    cfg = _tiny_train_cfg(tmp_path)
    proc, out = _run_train_cli(cfg, tmp_path / "cfg.json",
                               extra_env={"DCR_FAULTS": "nan_loss@step=3"})
    assert proc.returncode != 0
    assert "FloatingPointError" in out
    doc = json.loads((Path(cfg.output_dir) / "flightrec_0.json").read_text())
    assert doc["reason"].startswith("nan_abort: step 3")
    assert any(r["name"] == "fault/injected" for r in doc["records"])


@pytest.mark.slow
def test_serve_session_trace_prometheus_and_drain_dump(tmp_path, cpu_devices):
    """Acceptance: a short serve session produces a schema-valid trace with
    one span tree per request id, /metrics?format=prometheus parses and
    includes the faults counters, trace_report exits 0 on the logdir, and
    SIGTERM drain leaves a flight-recorder dump next to it."""
    import signal
    import socket
    import subprocess
    import sys
    import urllib.request

    from dcr_tpu.core.coordination import EXIT_PREEMPTED

    from tests.test_serve import _export_tiny_ckpt

    ckpt = _export_tiny_ckpt(tmp_path)
    env, repo = _subprocess_env()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    logdir = tmp_path / "servelogs"
    proc = subprocess.Popen(
        [sys.executable, "-m", "dcr_tpu.cli.serve",
         f"--model_path={ckpt}", f"--port={port}", f"--logdir={logdir}",
         "--resolution=16", "--num_inference_steps=2", "--sampler=ddim",
         "--max_batch=2", "--max_wait_ms=50", "--request_timeout_s=300",
         "--seed=0"],
        env=env, cwd=repo, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        deadline = time.monotonic() + 240
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=2) as r:
                    assert json.loads(r.read())["status"] == "ok"
                break
            except (AssertionError, OSError):
                if proc.poll() is not None or time.monotonic() > deadline:
                    raise AssertionError(
                        f"server did not come up (rc={proc.poll()}): "
                        f"{proc.stdout.read()[-3000:]}")
                time.sleep(0.5)

        body = json.dumps({"prompt": "a red square", "seed": 1}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            assert resp.status == 200

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics?format=prometheus",
                timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        samples = {}
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)                  # parses as floats
        assert "dcr_faults_total" in samples              # faults/* section
        assert samples["dcr_serve_completed_total"] == 1.0
        assert "dcr_serve_request_latency_s_count" in samples

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == EXIT_PREEMPTED
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    assert (logdir / "trace.jsonl").exists()
    summary = _assert_valid_trace(
        logdir, {"serve/request", "serve/queue_wait", "serve/assemble",
                 "serve/device_step", "serve/respond", "stage/serve_load"})
    assert summary["serve_queue_wait"]["count"] >= 1
    assert summary["serve_recompiles_per_bucket"]         # one bucket compiled
    # span tree: children reference the request root
    schema = trace_report.load_schema()
    records, _ = trace_report.load_trace(logdir, schema)
    roots = {r["id"]: r for r in records if r["name"] == "serve/request"}
    waits = [r for r in records if r["name"] == "serve/queue_wait"]
    assert roots and all(w["parent"] in roots for w in waits)
    assert all(r["args"]["request_id"] in
               {w["args"]["request_id"] for w in waits} for r in roots.values())

    doc = json.loads((logdir / "flightrec_0.json").read_text())
    assert doc["reason"].startswith("preempted")

    import sys as _sys

    rep = subprocess.run(
        [_sys.executable, "-m", "tools.trace_report", str(logdir)],
        env=env, cwd=repo, capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "serve queue wait" in rep.stdout
