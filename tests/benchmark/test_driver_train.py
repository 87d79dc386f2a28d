"""The `train_step` driver end to end at the `tiny` preset on the CPU (8
virtual devices, per-device batch 1), and `correct` coming out false once for
each fault the cell can have, planted under the harness in the timed path:
a step that returns its state unchanged, and half of the batch left out."""
import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import harness
from tests.benchmark.conftest import last_line

CELL = "sd21-train-256"


def run(capsys, seed: int, trace: int = 0):
    assert harness.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "1", "--trace", str(trace)]) == 0
    return last_line(capsys)


def test_runs_end_to_end_and_follows_the_reference(tiny, capsys):
    result, before = run(capsys, 2**31 + 3, trace=1)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    # the span's share is read; the shares of a peak are not, off the chip
    assert result["rehearsal"] == ["data_wait_share"]
    assert set(result["checks"]) == {"loss_worst_step", "grad_norm_worst_leaf",
                                     "change_norm_worst_leaf"}
    first = next(x for x in before if x["bench"] == "first_steps")
    assert len(first["losses"]) == 3
    window = next(x for x in before if x["bench"] == "window")
    assert window["compilations_in_window"] == 0 and window["units"] >= 2
    compared = next(x for x in before if x["bench"] == "compared")
    assert compared["n_idle"] == 0          # every leaf has a gradient
    assert not (tiny / "benchmark" / ".work" / CELL).exists()


def _broken_step(monkeypatch, breaker):
    from dcr_tpu.diffusion import train as T

    real = T.make_train_step

    def make(cfg, models, mesh):
        inner = real(cfg, models, mesh).__wrapped__
        return jax.jit(lambda state, batch, key: breaker(inner, state, batch, key))

    monkeypatch.setattr(T, "make_train_step", make)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tiny, capsys, monkeypatch):
    _broken_step(monkeypatch, lambda step, s, b, k: (s, step(s, b, k)[1]))
    result, _ = run(capsys, 11)
    assert result["correct"] is False
    # by the measure of the worst leaf an unmoved state reads 1
    assert result["checks"]["change_norm_worst_leaf"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert result["checks"]["grad_norm_worst_leaf"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(tiny, capsys, monkeypatch):
    def half(step, state, batch, key):
        n = batch["pixel_values"].shape[0] // 2
        # the second half's rows never reach the step: the first half stands
        # in their place, so the mean is taken over the first half's data
        batch = {k: jnp.concatenate([v[:n], v[:n]]) for k, v in batch.items()}
        return step(state, batch, key)

    _broken_step(monkeypatch, half)
    result, _ = run(capsys, 12)
    assert result["correct"] is False
    failed = [n for n, c in result["checks"].items() if not c["value"] <= c["limit"]]
    assert failed, result["checks"]


def test_the_control_in_fp8_and_the_half_batch_fault_fail_the_limits(tiny):
    """The reference put in the program's place, computed from fp8 operands
    (the step below the bf16 the cell states), and the reference over half of
    the batch: each has to fail one of the cell's numbers, at the limits the
    tiny cell runs under."""
    import numpy as np

    from benchmark.lib import sd_stack
    from benchmark.reference import finetune, sd21
    from dcr_tpu.core.config import TrainConfig, parse_cli

    cell = harness.load_cell(CELL)
    driver = harness.load_module("drivers", "train_step", tiny)
    tc = parse_cli(TrainConfig, sd_stack.model_argv(cell.config, 16))
    shapes = sd_stack.weight_shapes(tc)
    gen = np.random.default_rng(0)
    batches = [{"pixel_values": gen.uniform(-1, 1, (8, 16, 16, 3)).astype(np.float32),
                "input_ids": sd_stack.prompt_ids(k, 8, 16, 1000)} for k in range(3)]
    hyper = dict(learning_rate=1e-3, adam_beta1=0.9, adam_beta2=0.999,
                 adam_epsilon=1e-8, adam_weight_decay=1e-2, max_grad_norm=1.0)
    key = sd21.stream(jax.random.key(5), "train")
    fresh = lambda: sd_stack.make_weights(shapes, 5)                # noqa: E731

    def steps(**kw):
        with jax.default_matmul_precision("highest"):
            return finetune.reference_steps(cell.config, fresh, batches, key,
                                            hyper, row_block=4, **kw)

    exact = steps()
    quiet = lambda *a, **k: None                                    # noqa: E731
    same = driver.compare(steps(), exact, cell.traffic["limits"], quiet)
    assert harness.checks_pass(same) and max(c["value"] for c in same) == 0.0
    for other in (steps(ops=sd21.Ops(quant="fp8")), steps(rows=slice(0, 4))):
        checks = driver.compare(other, exact, cell.traffic["limits"], quiet)
        assert not harness.checks_pass(checks), checks
