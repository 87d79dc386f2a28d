"""The harness's own arithmetic, with no driver of the repo under it: which
metrics a cell reports, the order of a traced run, and the two measures of a
leaf's gap."""
import pytest

from benchmark.lib import harness
from benchmark.reference import finetune

BENCH = {
    "end_to_end": [
        {"name": "a_per_s", "workloads": ["one", "two"]},
        {"name": "b_per_s", "workloads": ["three"]},
        {"name": "setup_s"}],
    "per_layer": [
        {"name": "listed", "moves": "a_per_s", "workloads": ["one"]},
        {"name": "unlisted", "moves": "a_per_s"},
        {"name": "idle.b", "moves": "b_per_s", "workloads": ["three"]}],
}


@pytest.mark.parametrize("cell, e2e, per", [
    ("one", ["a_per_s", "setup_s"], ["listed", "unlisted"]),
    # a later PR's cell: an entry without `workloads` follows the end-to-end
    # metric it moves, as the contract reads it
    ("two", ["a_per_s", "setup_s"], ["unlisted"]),
    ("three", ["b_per_s", "setup_s"], ["idle.b"]),
])
def test_which_metrics_a_cell_reports(cell, e2e, per):
    got_e2e, got_per = harness.metrics_of(BENCH, cell)
    assert [m["name"] for m in got_e2e] == e2e
    assert [m["name"] for m in got_per] == per


class FakeDriver:
    def __init__(self, events):
        self.events = events

    def unit(self):
        self.events.append("unit")

    def drain(self):
        pass


@pytest.mark.parametrize("trace", [False, True])
def test_the_window_closes_before_the_profiler_opens(trace, tmp_path, monkeypatch):
    """A profiler session changes the host path for the rest of the process,
    so a traced run measures its window first, in the state a plain run
    measures it in: the same lead-in, then the window, then the session."""
    import jax

    events = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path: events.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: events.append("stop"))
    cell = harness.Cell("c", 1, "cfg", {}, {"traced_units": 3}, [], [], tmp_path)

    class Meter:
        def snapshot(self):
            events.append("meter")
            return {"compilations": 0}

    bench = harness.Bench(cell, 1, 0.0, trace, jax.devices()[:1], Meter())
    window, tr = harness.run_window(bench, FakeDriver(events))
    lead, measured = ["unit"] * 3, ["meter", "unit", "meter"]
    traced = ["start"] + ["unit"] * 6 + ["stop"]
    assert events == lead + measured + (traced if trace else [])
    assert window.units == 1 and tr is None
    assert window.traced_units == (9 if trace else 3)


def test_a_small_leaf_by_both_measures():
    """Against its own norm a small leaf's gap reads whole; against the
    median leaf's it shrinks by the ratio of the two norms."""
    ref = {"big": 10.0, "mid": 1.0, "small": 0.01}
    got = {"big": 10.0, "mid": 1.01, "small": 0.015}
    gap, leaf = finetune.worst_leaf_gap(got, ref)
    assert leaf == "mid" and gap == pytest.approx(0.01)
    gap, leaf = finetune.worst_leaf_gap(got, ref, against_median=False)
    assert leaf == "small" and gap == pytest.approx(0.5)
    gap, leaf = finetune.worst_leaf_gap(got, ref, skip={"small"},
                                        against_median=False)
    assert leaf == "mid" and gap == pytest.approx(0.01)
