import numpy as np
import pytest

pytestmark = pytest.mark.fast

from dcr_tpu.eval import retrieval_metrics as RM
from dcr_tpu.utils import profiling, provenance


def test_retrieval_metrics_perfect_ranking():
    sim = np.array([[0.9, 0.1, 0.5], [0.2, 0.8, 0.3]])
    rel = np.array([[True, False, False], [False, True, True]])
    rep = RM.retrieval_report(sim, rel, ks=(1, 2))
    # q1: relevant at rank 1 -> AP 1; q2: relevant at ranks 1,2 -> AP 1
    assert rep["mAP"] == pytest.approx(1.0)
    assert rep["MRR"] == 1.0
    assert rep["precision@1"] == 1.0
    assert rep["recall@2"] == pytest.approx(1.0)
    # non-trivial case: q with rel at ranks 1 and 3 of 3
    sim2 = np.array([[0.9, 0.5, 0.1]])
    rel2 = np.array([[True, False, True]])
    assert RM.mean_average_precision(sim2, rel2) == pytest.approx((1 + 2 / 3) / 2)
    assert RM.recall_at_k(sim2, rel2, 2) == pytest.approx(0.5)


def test_average_precision_edge_cases():
    assert np.isnan(RM.average_precision([False, False], 0))
    assert RM.average_precision([False, False], 2) == 0.0
    assert RM.average_precision([True, True], 2) == 1.0


def test_step_timer_and_mfu():
    t = profiling.StepTimer(flops_per_step=1e9)
    for _ in range(3):
        t.tick(items=4)
    rep = t.report()
    assert rep["steps_per_sec"] > 0
    assert rep["items_per_sec"] > 0
    assert rep["tflops_per_sec"] >= 0
    assert "mfu" not in rep     # the CPU has no peak on record


def test_step_timer_mfu_formula_is_per_device(monkeypatch):
    """Pin the MFU formula: flops_per_step is the PER-DEVICE share
    (flops_of_jitted is post-GSPMD cost analysis), so
    mfu = (flops_per_step * steps / dt) / (peak * 1e12) with NO device_count
    in the denominator — a run achieving exactly per-chip peak reports
    mfu == 1.0 whatever the device count (the old formula divided by
    device_count and under-reported by that factor). The CPU has no peak on
    record (and reports no mfu), so the test stands in a v5e's."""
    import jax

    n_dev = jax.device_count()
    assert n_dev > 1  # conftest forces 8 virtual devices; the regression
    #                   is only observable with more than one
    peak_tflops = profiling.PEAK_TFLOPS["tpu v5 lite"]
    monkeypatch.setattr(profiling, "chip_peak_tflops", lambda: peak_tflops)
    t = profiling.StepTimer(flops_per_step=peak_tflops * 1e12)  # peak/step/chip
    t._t0 -= 1.0                      # pretend exactly 1s elapsed
    monkeypatch.setattr(profiling.time, "perf_counter", lambda: t._t0 + 1.0)
    t.tick(items=1)
    rep = t.report()
    assert rep["mfu"] == pytest.approx(1.0, rel=1e-6)
    assert rep["tflops_per_sec"] == pytest.approx(peak_tflops, rel=1e-6)
    assert rep["tflops_per_sec_total"] == pytest.approx(peak_tflops * n_dev,
                                                        rel=1e-6)


def test_compiled_flops_returns_positive():
    import jax.numpy as jnp

    flops = profiling.compiled_flops(lambda a, b: a @ b,
                                     jnp.zeros((64, 64)), jnp.zeros((64, 64)))
    if flops is not None:
        assert flops >= 2 * 64 ** 3 * 0.9


def test_provenance_stamp(tmp_path):
    p = provenance.stamp(tmp_path)
    import json

    d = json.loads(p.read_text())
    assert {"sha", "branch", "dirty", "python", "time"} <= set(d)
    assert len(d["sha"]) >= 7
