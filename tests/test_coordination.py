"""Multi-host resilience: the fault-agreement protocol, coordinated
preemption, the collective-hang watchdog, and rendezvous hardening.

Unit layer (fast, no subprocesses): FaultWord encode/decode, the pure
reduce_fault_words precedence table, Coordinator exchange with an injected
allgather, timeout-wrapped barriers (BarrierTimeout), the HangWatchdog
heartbeat, the coordinated checkpoint-fallback agreement loop against a
scripted peer, rank-targeted fault-spec parsing, and the quarantine-merge
tool.

E2E layer (slow, ISSUE 2 acceptance): real 2-process localhost
``jax.distributed`` runs through the actual train CLI — per the Orbax
heap-corruption memory every training leg is its own subprocess:

- rank-targeted NaN at step 5 → BOTH ranks roll back to the same checkpoint
  and the final state is bit-exact vs the symmetric-injection run;
- SIGTERM on rank 0 → one synchronized final checkpoint, both ranks exit
  EXIT_PREEMPTED, and the restarted pod reproduces the uninterrupted run's
  final state bit-exactly;
- injected hang on rank 1 → the watchdog fires within its timeout on both
  ranks (stack dumps + last agreement word in the log), both exit EXIT_HANG
  — no test-level timeout kill.
"""

import json
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest
from PIL import Image

from dcr_tpu.core import coordination as C
from dcr_tpu.core import dist
from dcr_tpu.core.config import (DataConfig, FaultToleranceConfig, ModelConfig,
                                 OptimConfig, TrainConfig, save_config)
from dcr_tpu.utils import faults
from tests._multiproc import REPO, run_two_process, worker_base_env


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("DCR_FAULTS", raising=False)
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# Unit: rank-targeted fault specs
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_parse_rank_suffix_and_mixed_separators():
    specs = faults.parse_faults(
        "nan_loss@step=5@rank=1,sigterm@step=7@rank=0,"
        "decode_error@step=3&slot=2@rank=1,hang@step=9@rank=1x2")
    assert [(s.kind, s.where, s.times) for s in specs] == [
        ("nan_loss", {"step": 5, "rank": 1}, 1),
        ("sigterm", {"step": 7, "rank": 0}, 1),
        ("decode_error", {"step": 3, "slot": 2, "rank": 1}, 1),
        ("hang", {"step": 9, "rank": 1}, 2),
    ]
    with pytest.raises(ValueError, match="malformed"):
        faults.parse_faults("nan_loss@step=5@rank=")


@pytest.mark.fast
def test_rank_coordinate_matches_explicit_and_implicit(monkeypatch):
    reg = faults.install("nan_loss@step=5@rank=1")
    # explicit rank coordinate from a hook point wins
    assert not reg.fire("nan_loss", step=5, rank=0)
    assert reg.fire("nan_loss", step=5, rank=1)
    # implicit: the registry fills rank from the process index
    reg = faults.install("sigterm@step=7@rank=1")
    monkeypatch.setattr(faults, "_current_rank", lambda: 0)
    assert not reg.fire("sigterm", step=7)
    monkeypatch.setattr(faults, "_current_rank", lambda: 1)
    assert reg.fire("sigterm", step=7)


@pytest.mark.fast
def test_rankless_specs_ignore_process_rank(monkeypatch):
    # no spec names a rank -> the implicit coordinate is never injected and
    # every process matches (the historical single-host behavior)
    reg = faults.install("nan_loss@step=5")
    monkeypatch.setattr(faults, "_current_rank",
                        lambda: pytest.fail("rank must not be resolved"))
    assert reg.fire("nan_loss", step=5)


# ---------------------------------------------------------------------------
# Unit: agreement word + reduce (pure, no collectives)
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_fault_word_encode_decode_roundtrip():
    w = C.FaultWord(nan_step=17, rollback_ok=True, preempt=True, bad_samples=9)
    assert C.FaultWord.decode(w.encode()) == w
    assert C.FaultWord.decode(C.FaultWord().encode()) == C.FaultWord()
    with pytest.raises(ValueError, match="fields"):
        C.FaultWord.decode(np.zeros(3, np.int64))


@pytest.mark.fast
def test_reduce_precedence_table():
    W = C.FaultWord
    # all quiet -> continue (bad totals still summed for telemetry)
    d = C.reduce_fault_words([W(bad_samples=2), W(bad_samples=3)])
    assert d.action is C.Action.CONTINUE and d.bad_total == 5
    # any nan + all nan-hosts can roll back -> ROLLBACK to the EARLIEST step
    d = C.reduce_fault_words([W(nan_step=9, rollback_ok=True),
                              W(nan_step=5, rollback_ok=True)])
    assert d.action is C.Action.ROLLBACK and d.nan_step == 5
    assert d.nan_ranks == (0, 1)
    # a nan host that cannot roll back -> the whole pod fails together
    d = C.reduce_fault_words([W(), W(nan_step=5, rollback_ok=False)])
    assert d.action is C.Action.FAIL and d.nan_ranks == (1,)
    # nan outranks preemption: never checkpoint poisoned params
    d = C.reduce_fault_words([W(preempt=True),
                              W(nan_step=5, rollback_ok=True)])
    assert d.action is C.Action.ROLLBACK and d.preempt_ranks == (0,)
    # preemption -> checkpoint-and-exit, even past the bad-sample budget
    d = C.reduce_fault_words([W(preempt=True, bad_samples=50), W()],
                             bad_budget=10)
    assert d.action is C.Action.CHECKPOINT_AND_EXIT and d.preempt_ranks == (0,)
    # per-host counts under the line, pod total over it -> global abort
    d = C.reduce_fault_words([W(bad_samples=6), W(bad_samples=6)],
                             bad_budget=10)
    assert d.action is C.Action.ABORT_BAD_SAMPLES and d.bad_total == 12
    # no budget configured -> counts are telemetry only
    d = C.reduce_fault_words([W(bad_samples=100)], bad_budget=None)
    assert d.action is C.Action.CONTINUE


@pytest.mark.fast
def test_coordinator_single_host_is_pure_and_one_shot():
    coord = C.Coordinator(process_index=0, process_count=1,
                          allgather=lambda v: pytest.fail("no collectives on one host"))
    assert coord.exchange(1).action is C.Action.CONTINUE
    coord.note_nan(3, rollback_ok=True)
    d = coord.exchange(3, tag="loss")
    assert d.action is C.Action.ROLLBACK and d.nan_step == 3
    # nan is one-shot: consumed by the exchange
    assert coord.exchange(4).action is C.Action.CONTINUE
    # preemption is sticky until the process exits
    coord.note_preempt()
    assert coord.exchange(5).action is C.Action.CHECKPOINT_AND_EXIT
    assert coord.exchange(6).action is C.Action.CHECKPOINT_AND_EXIT
    assert coord.last_agreement["action"] == "checkpoint_and_exit"


@pytest.mark.fast
def test_coordinator_peer_fault_reaches_local_decision():
    """A fault observed ONLY on the peer must still decide locally — the
    heart of the agreement protocol."""
    peer = C.FaultWord(nan_step=7, rollback_ok=True)

    def fake_allgather(vec):
        return np.stack([vec, peer.encode()])

    coord = C.Coordinator(process_index=0, process_count=2,
                          allgather=fake_allgather)
    d = coord.exchange(7, tag="loss")
    assert d.action is C.Action.ROLLBACK
    assert d.nan_step == 7 and d.nan_ranks == (1,)
    assert coord.last_agreement["nan_step"] == 7


@pytest.mark.fast
def test_coordinator_assert_same_raises_on_divergence():
    coord = C.Coordinator(process_index=0, process_count=2,
                          allgather=lambda v: np.stack([v, v + 2]))
    with pytest.raises(C.CoordinationError, match="resume_step"):
        coord.assert_same("resume_step", 4)


# ---------------------------------------------------------------------------
# Unit: timeout-wrapped sync points + hang watchdog
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_run_with_timeout_and_barrier_timeout_typed():
    assert dist.run_with_timeout(lambda: 42, 0.0) == 42       # inline path
    assert dist.run_with_timeout(lambda: 42, 5.0) == 42       # threaded path
    t0 = time.monotonic()
    with pytest.raises(dist.BarrierTimeout, match="slowpoke"):
        dist.run_with_timeout(lambda: time.sleep(5), 0.1, name="slowpoke")
    assert time.monotonic() - t0 < 2.0                        # did not wait 5s

    def boom():
        raise ValueError("inner")

    with pytest.raises(ValueError, match="inner"):            # errors surface
        dist.run_with_timeout(boom, 1.0)
    # single-host barrier returns immediately regardless of timeout
    dist.barrier("unit", timeout_s=0.01)


@pytest.mark.fast
def test_hang_watchdog_arms_on_first_beat_and_fires():
    fired = []
    wd = C.HangWatchdog(0.15, poll_s=0.02, abort=fired.append)
    wd.start()
    time.sleep(0.3)
    assert not fired            # never beat: not armed (long first compile)
    wd.beat(5)
    time.sleep(0.4)
    assert fired and "last step 5" in fired[0]
    wd.stop()


@pytest.mark.fast
def test_hang_watchdog_quiet_while_beating_and_disabled_noop():
    fired = []
    wd = C.HangWatchdog(0.2, poll_s=0.02, abort=fired.append)
    wd.start()
    for _ in range(10):
        wd.beat()
        time.sleep(0.03)
    wd.stop()
    assert not fired
    off = C.HangWatchdog(0.0)   # disabled: all no-ops
    off.start()
    off.beat()
    off.stop()
    assert off._thread is None


@pytest.mark.fast
def test_dump_stacks_includes_this_frame():
    text = C.dump_stacks()
    assert "--- thread" in text
    assert "test_dump_stacks_includes_this_frame" in text


@pytest.mark.fast
def test_hang_abort_logs_word_and_exits(monkeypatch, caplog):
    codes = []
    monkeypatch.setattr(C, "_exit_fn", codes.append)
    coord = C.Coordinator(process_index=0, process_count=1,
                          allgather=lambda v: v)
    coord.exchange(11)
    with caplog.at_level("WARNING", logger="dcr_tpu"):
        C.hang_abort("unit", coordinator=coord, detail="test detail")
    assert codes == [C.EXIT_HANG]
    joined = " ".join(r.getMessage() for r in caplog.records)
    assert "hang_abort" in joined and "thread stacks" in joined


# ---------------------------------------------------------------------------
# Unit: coordinated checkpoint-fallback agreement (scripted peer)
# ---------------------------------------------------------------------------

class ScriptedCoordinator:
    """agree_int plays back preset per-call responses (value -> row)."""

    process_count = 2
    timeout_s = 0.0

    def __init__(self, responses):
        self.responses = list(responses)
        self.calls = []

    def agree_int(self, value, name):
        self.calls.append((name, int(value)))
        return self.responses.pop(0)(int(value))


def _mk_ckpts(tmp_path, steps):
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False)
    for step in steps:
        mgr.save(step, {"w": jnp.full((8,), float(step))})
    mgr.wait()
    mgr.close()


@pytest.mark.fast
def test_coordinated_restore_takes_pod_minimum(tmp_path):
    """Local host has steps 2 and 4; the peer only proposes 2 (its 4 is torn
    or missing) -> the pod agrees on 2 even though 4 is locally fine."""
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import CheckpointManager

    _mk_ckpts(tmp_path, [2, 4])
    coord = ScriptedCoordinator([
        lambda v: [v, 2],    # proposals: local 4, peer 2 -> agreed 2
        lambda v: [v, 1],    # validation of step 2: both ok
    ])
    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False,
                            coordinator=coord)
    state, step, skipped = mgr.restore_latest_valid({"w": jnp.zeros(8)})
    assert step == 2 and skipped == []
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full(8, 2.0))
    assert [name for name, _ in coord.calls] == ["ckpt_candidate", "ckpt_valid"]
    mgr.close()


@pytest.mark.fast
def test_coordinated_restore_quarantines_peer_rejected_step(tmp_path):
    """Both propose 4; the peer fails validating it -> 4 is quarantined
    pod-wide and the next round lands on 2."""
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import CheckpointManager

    _mk_ckpts(tmp_path, [2, 4])
    coord = ScriptedCoordinator([
        lambda v: [v, 4],    # round 1 proposals -> agreed 4
        lambda v: [v, 0],    # round 1 validation: peer says no
        lambda v: [v, 2],    # round 2 proposals -> agreed 2
        lambda v: [v, 1],    # round 2 validation: both ok
    ])
    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False,
                            coordinator=coord)
    state, step, skipped = mgr.restore_latest_valid({"w": jnp.zeros(8)})
    assert step == 2
    assert [s for s, _ in skipped] == [4]
    assert "peer host" in skipped[0][1]
    assert (tmp_path / "ckpt" / "quarantined" / "4").exists()
    mgr.close()


@pytest.mark.fast
def test_coordinated_restore_raises_when_any_host_is_empty(tmp_path):
    import jax.numpy as jnp

    from dcr_tpu.core.checkpoint import CheckpointManager

    _mk_ckpts(tmp_path, [2])
    coord = ScriptedCoordinator([lambda v: [v, -1]])  # peer has nothing
    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False,
                            coordinator=coord)
    with pytest.raises(FileNotFoundError, match="every host"):
        mgr.restore_latest_valid({"w": jnp.zeros(8)})
    mgr.close()


# ---------------------------------------------------------------------------
# Unit: quarantine-manifest merge tool
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_merge_quarantine_reports_per_kind_and_rank(tmp_path):
    run = tmp_path / "run"
    run.mkdir()
    (run / "quarantine.jsonl").write_text(
        json.dumps({"kind": "bad_sample", "time": 3.0, "index": 7}) + "\n"
        + json.dumps({"kind": "nan_rollback", "time": 5.0, "at_step": 9}) + "\n")
    (run / "quarantine.p1.jsonl").write_text(
        json.dumps({"kind": "bad_sample", "time": 4.0, "index": 8}) + "\n")
    out = tmp_path / "report.json"
    merged = tmp_path / "merged.jsonl"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "merge_quarantine.py"),
         str(run), "--out", str(out), "--merged", str(merged)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(out.read_text())
    assert report["total"] == 3
    assert report["processes"] == [0, 1]
    assert report["by_kind"] == {"bad_sample": 2, "nan_rollback": 1}
    assert report["by_rank"] == {"0": 2, "1": 1}
    assert report["by_kind_rank"] == {"bad_sample@rank0": 1,
                                      "bad_sample@rank1": 1,
                                      "nan_rollback@rank0": 1}
    recs = [json.loads(l) for l in merged.read_text().splitlines()]
    assert [r["rank"] for r in recs] == [0, 1, 0]       # time-sorted
    # empty dir is distinguishable from a clean run
    empty = tmp_path / "empty"
    empty.mkdir()
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "merge_quarantine.py"), str(empty)],
        capture_output=True, text=True)
    assert proc.returncode == 2


# ---------------------------------------------------------------------------
# E2E: 2-process coordinated recovery through the real CLI (slow)
# ---------------------------------------------------------------------------

_FP_RE = re.compile(r"state fingerprint at step (\d+): ([0-9a-f]{8})")


def _fingerprint(out: str) -> str:
    m = _FP_RE.search(out)
    assert m, f"no state fingerprint in output:\n{out[-3000:]}"
    return m.group(2)


def _make_data(base: Path) -> Path:
    rng = np.random.default_rng(0)
    for cls in ["c0", "c1"]:
        d = base / "data" / cls
        d.mkdir(parents=True)
        for i in range(8):
            Image.fromarray(rng.integers(0, 255, (20, 20, 3), np.uint8)).save(
                d / f"{i}.png")
    return base / "data"


def _pod_cfg(base: Path, out_name: str, **overrides) -> TrainConfig:
    defaults = dict(
        output_dir=str(base / out_name),
        seed=0,
        train_batch_size=2,
        max_train_steps=6,
        num_train_epochs=20,
        mixed_precision="no",
        save_steps=1000,
        modelsavesteps=2,
        log_every=1,
        model=ModelConfig.tiny(),
        data=DataConfig(train_data_dir=str(base / "data"), resolution=16,
                        class_prompt="nolevel", num_workers=2, seed=0),
        optim=OptimConfig(learning_rate=1e-4, lr_scheduler="constant",
                          lr_warmup_steps=0),
    )
    defaults.update(overrides)
    return TrainConfig(**defaults)


def _run_pod(cfg, cfg_path: Path, *, dcr_faults: str = "",
             extra_env: dict | None = None, timeout: int = 600):
    """One 2-process training leg = two fresh CLI processes, 1 CPU device
    each (mesh data axis spans the DCN boundary)."""
    import os

    save_config(cfg, cfg_path)
    env = worker_base_env(local_devices=1, inherit=True)
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_THREEFRY_PARTITIONABLE="1",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="1.0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    if dcr_faults:
        env["DCR_FAULTS"] = dcr_faults
    if extra_env:
        env.update(extra_env)
    return run_two_process(
        [sys.executable, "-m", "dcr_tpu.cli.train", f"--config={cfg_path}"],
        env=env, timeout=timeout)


def _final_state_arrays(cfg, step: int) -> dict:
    with np.load(Path(cfg.output_dir) / "checkpoints" / str(step)
                 / "state.npz") as z:
        return {k: z[k].copy() for k in z.files}


def _rollback_records(run_dir: Path) -> dict[int, list[dict]]:
    out = {}
    for rank, name in ((0, "quarantine.jsonl"), (1, "quarantine.p1.jsonl")):
        path = run_dir / name
        entries = ([json.loads(l) for l in path.read_text().splitlines()]
                   if path.exists() else [])
        out[rank] = [e for e in entries if e["kind"] == "nan_rollback"]
    return out


@pytest.mark.slow
def test_two_process_rank_targeted_nan_rolls_back_both_ranks(tmp_path):
    """Acceptance: nan on rank 1 only -> the agreement makes BOTH ranks roll
    back to the same checkpoint at the same step, and the final state is
    bit-exact vs the symmetric-injection run (identical recovery action,
    identical trajectory)."""
    _make_data(tmp_path)
    ft = FaultToleranceConfig(max_rollbacks=1)

    ref_cfg = _pod_cfg(tmp_path, "run_nan_sym", fault=ft)
    ref = _run_pod(ref_cfg, tmp_path / "nan_sym.json",
                   dcr_faults="nan_loss@step=5")
    for rank, (rc, out) in enumerate(ref):
        assert rc == 0, f"sym rank {rank}:\n{out[-3000:]}"

    tgt_cfg = _pod_cfg(tmp_path, "run_nan_tgt", fault=ft)
    tgt = _run_pod(tgt_cfg, tmp_path / "nan_tgt.json",
                   dcr_faults="nan_loss@step=5@rank=1")
    for rank, (rc, out) in enumerate(tgt):
        assert rc == 0, f"tgt rank {rank}:\n{out[-3000:]}"

    # rank 0 saw a finite local loss yet took the agreed rollback action
    assert "agreement" in tgt[0][1] and '"action": "rollback"' in tgt[0][1]
    # both ranks recorded the identical rollback: at step 5, restored from 4
    for run_dir in (Path(ref_cfg.output_dir), Path(tgt_cfg.output_dir)):
        recs = _rollback_records(run_dir)
        for rank in (0, 1):
            assert len(recs[rank]) == 1, (run_dir, rank, recs)
            assert recs[rank][0]["at_step"] == 5
            assert recs[rank][0]["restored_step"] == 4
    # bit-exact: every rank of both runs ends at the same fingerprint...
    fps = {_fingerprint(out) for _, out in ref + tgt}
    assert len(fps) == 1, f"divergent final states: {fps}"
    # ...and the final checkpoints match array-for-array
    ref_arrays = _final_state_arrays(ref_cfg, 6)
    tgt_arrays = _final_state_arrays(tgt_cfg, 6)
    assert set(ref_arrays) == set(tgt_arrays)
    for key in ref_arrays:
        np.testing.assert_array_equal(ref_arrays[key], tgt_arrays[key])


@pytest.mark.slow
def test_two_process_sigterm_synchronized_checkpoint_and_exit(tmp_path):
    """Acceptance: SIGTERM on rank 0 -> one synchronized final checkpoint,
    both ranks exit EXIT_PREEMPTED, and the restarted pod reproduces the
    uninterrupted run bit-exactly."""
    _make_data(tmp_path)

    ref_cfg = _pod_cfg(tmp_path, "run_pre_ref")
    ref = _run_pod(ref_cfg, tmp_path / "pre_ref.json")
    for rank, (rc, out) in enumerate(ref):
        assert rc == 0, f"ref rank {rank}:\n{out[-3000:]}"
    ref_fp = {_fingerprint(out) for _, out in ref}
    assert len(ref_fp) == 1

    cfg = _pod_cfg(tmp_path, "run_pre")
    res = _run_pod(cfg, tmp_path / "pre.json",
                   dcr_faults="sigterm@step=3@rank=0")
    for rank, (rc, out) in enumerate(res):
        assert rc == C.EXIT_PREEMPTED, \
            f"rank {rank} exit {rc} != EXIT_PREEMPTED:\n{out[-3000:]}"
        # both ranks acknowledged the SAME stop point, attributed to rank 0
        assert "preemption: checkpointing at step 3" in out
        assert "signaled on ranks [0]" in out
        assert "exiting with code 83" in out
    assert (Path(cfg.output_dir) / "checkpoints" / "3").exists()

    resumed = _run_pod(cfg, tmp_path / "pre.json")
    for rank, (rc, out) in enumerate(resumed):
        assert rc == 0, f"resume rank {rank}:\n{out[-3000:]}"
        assert "resumed from checkpoint step 3" in out
    assert {_fingerprint(out) for _, out in resumed} == ref_fp
    ref_arrays = _final_state_arrays(ref_cfg, 6)
    got_arrays = _final_state_arrays(cfg, 6)
    for key in ref_arrays:
        np.testing.assert_array_equal(got_arrays[key], ref_arrays[key])


@pytest.mark.slow
def test_two_process_injected_hang_trips_watchdog_on_both_ranks(tmp_path):
    """Acceptance: rank 1 wedges at step 5 -> its heartbeat watchdog fires
    within the timeout; rank 0's agreement allgather times out the same way;
    both dump stacks + the last agreement word and exit EXIT_HANG. The
    processes end themselves — the launcher's timeout is never the thing
    that kills them."""
    _make_data(tmp_path)
    cfg = _pod_cfg(tmp_path, "run_hang")
    t0 = time.monotonic()
    res = _run_pod(cfg, tmp_path / "hang.json",
                   dcr_faults="hang@step=5@rank=1",
                   extra_env={"DCR_HANG_TIMEOUT_S": "45"}, timeout=900)
    elapsed = time.monotonic() - t0
    (rc0, out0), (rc1, out1) = res
    assert rc1 == C.EXIT_HANG, f"rank1 exit {rc1}:\n{out1[-3000:]}"
    assert rc0 == C.EXIT_HANG, f"rank0 exit {rc0}:\n{out0[-3000:]}"
    assert "injected_hang" in out1                  # the fault fired on rank 1
    for rank, out in ((0, out0), (1, out1)):
        assert "hang_abort" in out, f"rank {rank} missing hang_abort"
        assert "--- thread" in out, f"rank {rank} missing stack dump"
        assert "last_agreement" in out, f"rank {rank} missing agreement word"
    # watchdog-bounded exit, not a scheduler/test kill: well under launcher
    # timeout and roughly startup + 5 steps + the 45s watchdog window
    assert elapsed < 880, f"workers took {elapsed:.0f}s"
