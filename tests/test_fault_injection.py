"""End-to-end fault-injection harness: prove the recovery paths work.

Scenarios (ISSUE acceptance criteria), all on the virtual-CPU platform:

(a) injected SIGTERM mid-train, then restart -> bit-exact final state vs an
    uninterrupted run;
(b) corrupt latest checkpoint -> restore falls back to the previous step with
    a logged quarantine, not an exception;
(c) injected decode failure under budget -> epoch completes with the bad
    index quarantined; over budget -> clear abort;
(d) injected NaN with rollback enabled -> restore, skip, continue (finite
    final loss); default config -> fail-fast exactly as the seed.

Plus unit coverage of the primitives: fault-spec parsing/firing,
retry/backoff, watchdog/stage deadlines, quarantine manifests, checkpoint
content manifests.
"""

import json
import threading
import time

import numpy as np
import pytest
from PIL import Image

from dcr_tpu.core import resilience as R
from dcr_tpu.core.config import (DataConfig, FaultToleranceConfig, ModelConfig,
                                 OptimConfig, TrainConfig)
from dcr_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("DCR_FAULTS", raising=False)
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# Unit: fault registry
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_parse_faults_syntax():
    specs = faults.parse_faults(
        "decode_error@step=3,ckpt_corrupt@step=200x2,nan_loss@step=5&epoch=1")
    assert [(s.kind, s.where, s.times) for s in specs] == [
        ("decode_error", {"step": 3}, 1),
        ("ckpt_corrupt", {"step": 200}, 2),
        ("nan_loss", {"step": 5, "epoch": 1}, 1),
    ]
    assert faults.parse_faults("") == []
    with pytest.raises(ValueError, match="malformed"):
        faults.parse_faults("decode_error")          # no coordinates
    with pytest.raises(ValueError, match="malformed"):
        faults.parse_faults("nan_loss@step=abc")     # non-integer


@pytest.mark.fast
def test_registry_fires_once_and_matches_coords():
    reg = faults.install("decode_error@step=3")
    assert not reg.fire("decode_error", step=2, slot=0)
    assert not reg.fire("nan_loss", step=3)
    assert reg.fire("decode_error", step=3, slot=7)   # extra coords ignored
    assert not reg.fire("decode_error", step=3, slot=8)  # single-shot
    assert reg.pending() == []


@pytest.mark.fast
def test_registry_respects_times_and_env(monkeypatch):
    reg = faults.install("nan_loss@step=1x3")
    assert sum(reg.fire("nan_loss", step=1) for _ in range(5)) == 3
    # module-level fire() reads DCR_FAULTS lazily after clear()
    faults.clear()
    monkeypatch.setenv("DCR_FAULTS", "sigterm@step=9")
    assert not faults.fire("sigterm", step=8)
    assert faults.fire("sigterm", step=9)


@pytest.mark.fast
def test_registry_fire_is_atomic_across_threads():
    reg = faults.install("decode_error@step=1x10")
    hits = []

    def worker():
        for _ in range(100):
            if reg.fire("decode_error", step=1):
                hits.append(1)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(hits) == 10


# ---------------------------------------------------------------------------
# Unit: retry / deadline / quarantine primitives
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_retry_call_backs_off_then_succeeds():
    delays = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert R.retry_call(flaky, attempts=4, base_delay=0.1, jitter=0.0,
                        sleep=delays.append) == "ok"
    assert len(calls) == 3
    assert delays == [0.1, 0.2]  # exponential, jitter disabled


@pytest.mark.fast
def test_retry_call_exhausts_and_reraises():
    def always():
        raise OSError("down")

    with pytest.raises(OSError, match="down"):
        R.retry_call(always, attempts=3, sleep=lambda s: None)


@pytest.mark.fast
def test_retry_give_up_on_wins_over_retry_on(tmp_path):
    calls = []

    def missing():
        calls.append(1)
        raise FileNotFoundError("gone")

    with pytest.raises(FileNotFoundError):
        R.retry_call(missing, attempts=5, retry_on=(OSError,),
                     give_up_on=R.NONTRANSIENT_IO, sleep=lambda s: None)
    assert len(calls) == 1  # not retried
    with pytest.raises(FileNotFoundError):
        R.read_bytes_with_retry(tmp_path / "nope.bin")


@pytest.mark.fast
def test_watchdog_fires_on_overrun_and_deadline_checks():
    fired = []
    with R.watchdog("slowpoke", 0.05, on_timeout=lambda: fired.append(1)) as dl:
        time.sleep(0.15)
        assert dl.expired()
        with pytest.raises(R.DeadlineExceeded):
            dl.check()
    assert fired == [1]
    # disabled watchdog never fires, never expires
    with R.watchdog("fast", 0.0) as dl:
        assert not dl.expired()
        dl.check()


@pytest.mark.fast
def test_stage_logs_failure_and_reraises(caplog):
    with caplog.at_level("WARNING", logger="dcr_tpu"):
        with pytest.raises(ValueError):
            with R.stage("explodes"):
                raise ValueError("boom")
    assert any("stage_failed" in r.message for r in caplog.records)


@pytest.mark.fast
def test_quarantine_manifest_records_and_counts(tmp_path):
    q = R.QuarantineManifest(tmp_path / "q.jsonl")
    q.record("bad_sample", index=3, path="x.jpg")
    q.record("bad_sample", index=9, path="y.jpg")
    q.record("bad_checkpoint", step=100)
    assert q.count("bad_sample") == 2 and q.count("bad_checkpoint") == 1
    entries = q.entries()
    assert [e["kind"] for e in entries] == ["bad_sample", "bad_sample",
                                           "bad_checkpoint"]
    assert entries[0]["index"] == 3
    # each line is standalone JSON (appendable, tail-able)
    for line in (tmp_path / "q.jsonl").read_text().splitlines():
        json.loads(line)


# ---------------------------------------------------------------------------
# Unit: checkpoint content manifests + fallback restore
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_state_manifest_detects_tampering():
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import state_manifest, verify_manifest

    state = {"params": {"w": jnp.arange(8.0)}, "step": jnp.asarray(4)}
    manifest = state_manifest(state)
    assert verify_manifest(manifest, state) == []
    tampered = {"params": {"w": jnp.arange(8.0).at[3].set(99.0)},
                "step": jnp.asarray(4)}
    problems = verify_manifest(manifest, tampered)
    assert problems and "checksum mismatch" in problems[0]
    missing = {"params": {}, "step": jnp.asarray(4)}
    assert any("missing" in p for p in verify_manifest(manifest, missing))


def test_checkpoint_fallback_restores_previous_step(tmp_path):
    """Acceptance (b), manager level: corrupting the latest checkpoint makes
    restore fall back to N-1 with a logged quarantine, not an exception."""
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import CheckpointManager, _corrupt_step_dir

    q = R.QuarantineManifest(tmp_path / "quarantine.jsonl")
    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False, quarantine=q)
    for step in (2, 4):
        mgr.save(step, {"w": jnp.full((16,), float(step)),
                        "step": jnp.asarray(step)})
    mgr.wait()
    _corrupt_step_dir(tmp_path / "ckpt" / "4")
    like = {"w": jnp.zeros(16), "step": jnp.asarray(0)}
    state, step, skipped = mgr.restore_latest_valid(like)
    assert step == 2
    assert [s for s, _ in skipped] == [4]
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full(16, 2.0))
    assert (tmp_path / "ckpt" / "quarantined" / "4").exists()
    assert q.count("bad_checkpoint") == 1
    assert mgr.all_steps() == [2]  # quarantined step no longer offered
    mgr.close()


def test_checkpoint_explicit_restore_rejects_checksum_mismatch(tmp_path):
    """Silent corruption (orbax restores without complaint, bytes differ) is
    caught by the content manifest on an explicitly-requested step."""
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import CheckpointCorrupt, CheckpointManager

    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False)
    mgr.save(1, {"w": jnp.arange(16.0)})
    mgr.wait()
    # simulate silent corruption: tamper the manifest's recorded checksum so
    # the restored bytes no longer match what save-time recorded
    mpath = tmp_path / "ckpt" / "manifests" / "1.json"
    manifest = json.loads(mpath.read_text())
    key = next(iter(manifest["leaves"]))
    manifest["leaves"][key]["crc32"] ^= 0xFFFF
    mpath.write_text(json.dumps(manifest))
    with pytest.raises(CheckpointCorrupt, match="checksum mismatch"):
        mgr.restore({"w": jnp.zeros(16)}, 1)
    mgr.close()


def test_all_checkpoints_corrupt_raises_not_silent_restart(tmp_path):
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import CheckpointManager, _corrupt_step_dir

    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False)
    mgr.save(1, {"w": jnp.arange(4.0)})
    mgr.wait()
    _corrupt_step_dir(tmp_path / "ckpt" / "1")
    with pytest.raises(FileNotFoundError, match="quarantined"):
        mgr.restore_latest_valid({"w": jnp.zeros(4)})
    mgr.close()


# ---------------------------------------------------------------------------
# Data path: quarantine + deterministic replacement (acceptance c)
# ---------------------------------------------------------------------------

@pytest.fixture()
def image_folder(tmp_path):
    rng = np.random.default_rng(0)
    for cls in ["c0", "c1"]:
        d = tmp_path / "data" / cls
        d.mkdir(parents=True)
        for i in range(6):
            arr = rng.integers(0, 255, (40, 52, 3), np.uint8)
            Image.fromarray(arr).save(d / f"{cls}_{i}.png")
    return tmp_path / "data"


def _dataset(root, **fault_kw):
    from dcr_tpu.data.dataset import ObjectAttributeDataset
    from dcr_tpu.data.tokenizer import HashTokenizer

    cfg = DataConfig(train_data_dir=str(root), resolution=32,
                     class_prompt="nolevel", num_workers=2, seed=7)
    ft = FaultToleranceConfig(retry_base_delay=0.0, retry_max_delay=0.0,
                              **fault_kw)
    return ObjectAttributeDataset(cfg, HashTokenizer(100, 16), fault=ft), ft


def _corrupt_image(ds, position: int) -> int:
    index = int(ds.active_indices[position])
    with open(ds.paths[index], "wb") as f:
        f.write(b"garbage, not an image")
    return index


@pytest.mark.fast
def test_bad_sample_under_budget_quarantined_and_replaced(tmp_path, image_folder):
    from dcr_tpu.data.loader import DataLoader

    ds, ft = _dataset(image_folder, max_bad_sample_frac=0.5)
    bad = _corrupt_image(ds, 4)
    q = R.QuarantineManifest(tmp_path / "q.jsonl")
    loader = DataLoader(ds, batch_size=2, num_workers=2, seed=1,
                        fault=ft, quarantine=q)
    batches = list(loader.epoch(0))
    assert len(batches) == loader.steps_per_epoch()  # epoch completed
    served = np.concatenate([b.index for b in batches])
    assert bad not in served  # the bad sample never reaches the model
    assert loader.bad_samples == 1
    entries = q.entries()
    assert len(entries) == 1 and entries[0]["kind"] == "bad_sample"
    assert entries[0]["index"] == bad
    assert entries[0]["replacement_index"] in served


@pytest.mark.fast
def test_bad_sample_replacement_is_deterministic(tmp_path, image_folder):
    from dcr_tpu.data.loader import DataLoader

    ds, ft = _dataset(image_folder, max_bad_sample_frac=0.5)
    _corrupt_image(ds, 4)
    runs = []
    for _ in range(2):
        loader = DataLoader(ds, batch_size=2, num_workers=2, seed=1, fault=ft)
        runs.append(list(loader.epoch(0)))
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a.pixel_values, b.pixel_values)
        np.testing.assert_array_equal(a.index, b.index)


@pytest.mark.fast
def test_bad_samples_over_budget_abort(image_folder):
    from dcr_tpu.data.loader import DataLoader, TooManyBadSamples

    ds, ft = _dataset(image_folder, max_bad_sample_frac=0.05)  # budget = 0
    _corrupt_image(ds, 0)
    loader = DataLoader(ds, batch_size=2, num_workers=2, seed=1, fault=ft)
    with pytest.raises(TooManyBadSamples, match="max_bad_sample_frac"):
        for _ in loader.epoch(0):
            pass


@pytest.mark.fast
def test_injected_decode_error_follows_quarantine_path(tmp_path, image_folder):
    """decode_error@step=1 drives the exact code path a real corrupt image
    takes — no file harmed."""
    from dcr_tpu.data.loader import DataLoader

    ds, ft = _dataset(image_folder, max_bad_sample_frac=0.5)
    q = R.QuarantineManifest(tmp_path / "q.jsonl")
    faults.install("decode_error@step=1")
    loader = DataLoader(ds, batch_size=2, num_workers=2, seed=1,
                        fault=ft, quarantine=q)
    batches = list(loader.epoch(0))
    assert len(batches) == loader.steps_per_epoch()
    entries = q.entries()
    assert len(entries) == 1
    assert entries[0]["step"] == 1
    assert "InjectedFault" in entries[0]["error"]


@pytest.mark.fast
def test_injected_decode_error_default_config_fails_fast(image_folder):
    from dcr_tpu.data.loader import DataLoader
    from dcr_tpu.utils.faults import InjectedFault

    ds, ft = _dataset(image_folder)  # max_bad_sample_frac=0 (seed behavior)
    faults.install("decode_error@step=0")
    loader = DataLoader(ds, batch_size=2, num_workers=2, seed=1, fault=ft)
    with pytest.raises(InjectedFault):
        for _ in loader.epoch(0):
            pass


# ---------------------------------------------------------------------------
# Trainer end-to-end scenarios (a), (b), (d) — marked slow (each leg is a
# fresh process paying interpreter+jax startup; ~7 subprocess runs total).
# CI runs them in a dedicated job (.github/workflows/ci.yml `fault-e2e`), so
# every PR still proves the recovery paths end to end.
#
# Every TRAINING leg runs as a subprocess through the real CLI
# (`python -m dcr_tpu.cli.train` + DCR_FAULTS env) — the faithful model of
# production runs (one process per run; a preempted process checkpoints and
# DIES), and a hard requirement in this environment: a real SIGTERM followed
# by further in-process jax/orbax work corrupts the heap inside the
# tensorstore/orbax thread stack (glibc 'corrupted size vs. prev_size'), and
# multiple Trainer instances inside one long-lived pytest process hit the
# same native flakiness. In-process we only inspect artifacts: quarantine
# manifests, metrics.jsonl, and orbax restores against an abstract state.
# ---------------------------------------------------------------------------

@pytest.fixture()
def train_setup(tmp_path):
    rng = np.random.default_rng(0)
    for cls in ["c0", "c1"]:
        d = tmp_path / "data" / cls
        d.mkdir(parents=True)
        for i in range(8):
            Image.fromarray(rng.integers(0, 255, (20, 20, 3), np.uint8)).save(
                d / f"{i}.png")
    cfg = TrainConfig(
        output_dir=str(tmp_path / "run"),
        seed=0,
        train_batch_size=2,
        max_train_steps=6,
        num_train_epochs=20,
        mixed_precision="no",
        save_steps=1000,
        modelsavesteps=2,
        log_every=1,
        model=ModelConfig.tiny(),
        data=DataConfig(train_data_dir=str(tmp_path / "data"), resolution=16,
                        class_prompt="nolevel", num_workers=2, seed=0),
        optim=OptimConfig(learning_rate=1e-4, lr_scheduler="constant",
                          lr_warmup_steps=0),
    )
    return cfg, tmp_path


def _run_cli(cfg, cfg_path, *, dcr_faults: str = "", timeout: int = 540):
    """One training run = one process, through the real CLI entry point."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    from dcr_tpu.core.config import save_config

    save_config(cfg, cfg_path)
    repo = Path(__file__).parent.parent
    env = dict(os.environ)
    env.pop("DCR_FAULTS", None)
    if dcr_faults:
        env["DCR_FAULTS"] = dcr_faults
    env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(repo) + os.pathsep + env.get("PYTHONPATH", ""),
        # match the conftest jax config so trajectories are bit-identical to
        # in-process runs and the persistent compile cache is shared
        JAX_THREEFRY_PARTITIONABLE="1",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="1.0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    # conftest already forced --xla_force_host_platform_device_count=8 into
    # XLA_FLAGS (inherited via os.environ), so subprocesses see 8 devices
    proc = subprocess.run(
        [sys.executable, "-m", "dcr_tpu.cli.train", f"--config={cfg_path}"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=timeout)
    return proc, proc.stdout + proc.stderr


def _restore_final(cfg, step: int):
    """Restore a run's checkpoint against an abstract (zero-memory) state and
    return its flat numpy leaves — verifies the content manifest on the way."""
    import jax
    from pathlib import Path

    from dcr_tpu.core.checkpoint import CheckpointManager
    from dcr_tpu.diffusion.trainer import abstract_train_state

    mgr = CheckpointManager(Path(cfg.output_dir) / "checkpoints", verify=True)
    state = mgr.restore(abstract_train_state(cfg), step)
    mgr.close()
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(
        {"unet": state.unet_params, "opt": state.opt_state,
         "step": state.step}))]


@pytest.mark.slow
def test_sigterm_midtrain_resume_is_bit_exact(train_setup):
    """Acceptance (a): injected SIGTERM mid-train -> checkpoint-and-stop;
    a fresh process resumes and reproduces the uninterrupted run's final
    checkpoint bit-exactly (params, optimizer state, step)."""
    import dataclasses

    cfg, base = train_setup
    ref_cfg = dataclasses.replace(cfg, output_dir=str(base / "run_ref"))
    proc, out = _run_cli(ref_cfg, base / "ref_cfg.json")
    assert proc.returncode == 0, out[-3000:]

    # interrupted leg: real SIGTERM at micro-step 3; process checkpoints and
    # dies with the distinct preempted code a restart wrapper branches on
    from dcr_tpu.core.coordination import EXIT_PREEMPTED

    proc, out = _run_cli(cfg, base / "cfg.json", dcr_faults="sigterm@step=3")
    assert proc.returncode == EXIT_PREEMPTED, (proc.returncode, out[-3000:])
    assert "fault injection ACTIVE" in out       # CLI announced the harness
    assert "preemption: checkpointing at step 3" in out
    assert (base / "run" / "checkpoints" / "3").exists()

    # restart: fresh process resumes from the preemption checkpoint
    proc, out = _run_cli(cfg, base / "cfg.json")
    assert proc.returncode == 0, out[-3000:]
    assert "resumed from checkpoint step 3" in out

    ref_leaves = _restore_final(ref_cfg, 6)
    got_leaves = _restore_final(cfg, 6)
    assert len(got_leaves) == len(ref_leaves)
    for got, want in zip(got_leaves, ref_leaves):
        np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_corrupt_latest_checkpoint_falls_back_on_resume(train_setup):
    """Acceptance (b), full-stack: ckpt_corrupt@step=4 tears the latest save
    post-commit; the restarted process falls back to step 2 with a logged
    quarantine (no exception) and finishes the run."""
    import dataclasses

    cfg, base = train_setup
    cfg = dataclasses.replace(cfg, max_train_steps=4,
                              output_dir=str(base / "run_ckpt"))
    proc, out = _run_cli(cfg, base / "ckpt_cfg.json",
                         dcr_faults="ckpt_corrupt@step=4")
    assert proc.returncode == 0, out[-3000:]

    proc, out = _run_cli(cfg, base / "ckpt_cfg.json")
    assert proc.returncode == 0, out[-3000:]
    assert "resume fell back past 1 corrupt checkpoint(s)" in out
    assert "resumed from checkpoint step 2" in out
    run = base / "run_ckpt"
    assert (run / "checkpoints" / "quarantined" / "4").exists()
    entries = [json.loads(l) for l in
               (run / "quarantine.jsonl").read_text().splitlines()]
    bad = [e for e in entries if e["kind"] == "bad_checkpoint"]
    assert bad and bad[0]["step"] == 4
    # the resumed run retrained through step 4 and the counter was reported
    lines = [json.loads(l) for l in
             (run / "logs" / "metrics.jsonl").read_text().splitlines()]
    assert any(l.get("faults/ckpt_fallbacks") == 1 for l in lines)
    assert _restore_final(cfg, 4)  # final checkpoint restores and verifies


@pytest.mark.slow
def test_nan_rollback_restores_skips_and_continues(train_setup):
    """Acceptance (d), opt-in half: nan_loss@step=3 with max_rollbacks=1 ->
    restore the step-2 checkpoint, fast-forward past the bad window, and
    converge to a finite final loss."""
    import dataclasses

    cfg, base = train_setup
    cfg = dataclasses.replace(
        cfg, max_train_steps=5, output_dir=str(base / "run_roll"),
        fault=FaultToleranceConfig(max_rollbacks=1))
    proc, out = _run_cli(cfg, base / "roll_cfg.json",
                         dcr_faults="nan_loss@step=3")
    assert proc.returncode == 0, out[-3000:]  # must NOT fail fast
    assert "quarantine_nan_rollback" in out   # structured [fault] line
    run = base / "run_roll"
    roll = [json.loads(l) for l in
            (run / "quarantine.jsonl").read_text().splitlines()
            if json.loads(l)["kind"] == "nan_rollback"]
    assert len(roll) == 1
    assert roll[0]["at_step"] == 3 and roll[0]["restored_step"] == 2
    lines = [json.loads(l) for l in
             (run / "logs" / "metrics.jsonl").read_text().splitlines()]
    assert any(l.get("faults/rollbacks") == 1 for l in lines)
    # converging loss curve: post-rollback losses observed and finite
    losses = [l["loss"] for l in lines if "loss" in l]
    assert losses and np.isfinite(losses[-1])
    assert _restore_final(cfg, 5)             # run reached its final step


@pytest.mark.slow
def test_nan_default_config_fails_fast_as_seed(train_setup):
    """Acceptance (d), default half: with max_rollbacks=0 an injected NaN
    fails fast exactly as the seed — FloatingPointError naming the last good
    checkpoint, which is left intact as the recovery point."""
    import dataclasses

    cfg, base = train_setup
    cfg = dataclasses.replace(cfg, output_dir=str(base / "run_nan"))
    proc, out = _run_cli(cfg, base / "nan_cfg.json",
                         dcr_faults="nan_loss@step=3")
    assert proc.returncode != 0
    assert "FloatingPointError" in out and "non-finite loss" in out
    assert "last good checkpoint" in out
    # step-2 checkpoint survived as the recovery point; the poisoned step
    # was never saved
    run = base / "run_nan"
    assert (run / "checkpoints" / "2").exists()
    assert not (run / "checkpoints" / "3").exists()
