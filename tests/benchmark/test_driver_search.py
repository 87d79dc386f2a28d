"""The `store_search` driver end to end at a 4,096-row store on the CPU,
through run.py's own code; the contract of the last line; a CPU run un-shrunk;
the planted fault; and the control (the reference at Precision.HIGH in the
program's place is the same arithmetic on the CPU, so here the control is an
int8 rounding of the operands, which has to fail)."""
import numpy as np
import pytest

from benchmark.lib import harness
from tests.benchmark.conftest import last_line

CELL = "sscd-laion12m-share-search"
ARGV = ["--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "0.3"]


@pytest.mark.parametrize("trace", [0, 1])
def test_runs_end_to_end_and_the_last_line_is_the_contracts(tiny, capsys, trace):
    assert harness.main(ARGV + ["--trace", str(trace)]) == 0
    result, before = last_line(capsys)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # a rehearsal on the CPU carries no device metric, only the names read
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    want = ({"search_step_mfu", "topk_roofline", "device_idle_share.search"}
            if trace else {"search_queries_per_s", "search_batch_ms_p95", "setup_s"})
    # off the chip the shares of a peak are not read at all
    assert set(result["rehearsal"]) == (set() if trace else want)
    assert set(result["checks"]) == {"bad_keys", "best_score_gap", "score_error"}
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]
    phases = [line["bench"] for line in before]
    assert phases[0] == "start" and {"setup", "window", "verify"} <= set(phases)
    window = next(x for x in before if x["bench"] == "window")
    assert window["compilations_in_window"] == 0
    assert not (tiny / "benchmark" / ".work" / CELL).exists()
    assert (tiny / "benchmark" / ".cache" / "stores").is_dir()   # built once


def test_a_cpu_run_unshrunk_exits_nonzero_with_no_result(capsys):
    """No TPU: the run fails before it builds anything and prints no line on
    standard output."""
    assert harness.main(ARGV + ["--trace", "0"]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "measures on 'tpu' only" in captured.err


def test_an_unknown_workload_fails(tiny, capsys):
    assert harness.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_an_answer_altered_where_it_is_produced_is_not_correct(tiny, capsys, monkeypatch):
    from dcr_tpu.search import shardindex

    real = shardindex.ShardedTopK.query

    def altered(self, q):
        scores, keys = real(self, q)
        keys = keys.copy()
        keys[0, 0] = str((int(keys[0, 0]) + 1) % self.total)   # another row
        return scores, keys

    monkeypatch.setattr(shardindex.ShardedTopK, "query", altered)
    assert harness.main(ARGV + ["--trace", "0"]) == 0
    result, _ = last_line(capsys)
    assert result["correct"] is False
    gap = result["checks"]["best_score_gap"]
    assert gap["value"] > 100 * gap["limit"]


def test_the_control_in_a_lower_precision_fails(tiny):
    """The reference, put in the program's place and computed from operands
    rounded to int8, reports scores that are off by far more than the
    limit."""
    import jax.numpy as jnp

    from benchmark.lib import rng
    from benchmark.reference import topk

    cell = harness.load_cell(CELL)
    rows, dim = cell.config["rows"], cell.config["embed_dim"]
    seed = cell.config["corpus_seed"]
    q = np.asarray(rng.unit_rows(7, np.arange(64), dim))
    rows_of = lambda ids: rng.unit_rows(seed, ids, dim)           # noqa: E731

    def rounded(x):
        x = jnp.asarray(x)
        scale = jnp.max(jnp.abs(x)) / 127.0
        return jnp.round(x / scale) * scale

    best, _ = topk.best_rows(q, rows_of, rows, row_block=1024, query_block=64)
    score, idx = topk.best_rows(np.asarray(rounded(q)),
                                lambda ids: rounded(rows_of(ids)), rows,
                                row_block=1024, query_block=64)
    true = topk.scores_of(q, rows_of(idx))
    # it need not name another row; the score it reports for its row is off
    assert float(np.max(best - true)) >= 0.0
    error = float(np.max(np.abs(score - true)))
    assert error > 100 * cell.traffic["limits"]["score_error"]
    # and the reference agrees with plain numpy over the same rows
    sims = q @ np.asarray(rows_of(np.arange(rows))).T
    np.testing.assert_allclose(best, sims.max(axis=1), atol=1e-6)
