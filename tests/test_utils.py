import numpy as np
import pytest

pytestmark = pytest.mark.fast

from dcr_tpu.eval import retrieval_metrics as RM
from dcr_tpu.utils import provenance


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


def test_provenance_stamp(tmp_path):
    p = provenance.stamp(tmp_path)
    import json

    d = json.loads(p.read_text())
    assert {"sha", "branch", "dirty", "python", "time"} <= set(d)
    assert len(d["sha"]) >= 7
