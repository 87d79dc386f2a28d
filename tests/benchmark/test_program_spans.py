"""The per-layer metrics of source `program_span` (PR 26) on a hand-made
timeline with made-up peaks: what each reads, `None` where its span never
fired in the window, `None` off the chip (`peaks` `None`), and `None` against
a program that keeps no timeline (the parent commit)."""
import json
import statistics

import pytest

from benchmark.lib import harness, program_spans as ps

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}      # made up
T0, SECONDS = 100.0, 10.0
MS = 1e-3

NEW = ["search_phase_ms.put", "search_phase_ms.dispatch",
       "search_phase_ms.device_wait", "search_phase_ms.fetch",
       "search_phase_ms.merge", "search_host_share", "loader_wait_share.fill",
       "loader_wait_share.steady", "loader_busy_share", "sample_d2h_share"]


def hand_made() -> dict:
    """Three `search/query` calls in the window (one before it, one after),
    two epochs of a loader with two workers, three fetches."""
    calls = [(90.0, 20 * MS)] + [(T0 + 1 + i, (17 + i) * MS) for i in range(3)] \
        + [(T0 + SECONDS + 1, 20 * MS)]
    line = {"search/query": calls}
    phases = {"put": 1.0, "dispatch": 0.5, "device_wait": 12.0, "fetch": 0.25,
              "merge": 2.0}
    for name in phases:
        line["search/" + name] = []
    for i, (start, _) in enumerate(calls):
        at = start                 # one after the other, 0.25 ms between
        for name, ms in phases.items():
            # the calls of the window grow by 1 ms each: device_wait takes it
            ms += i - 1 if name == "device_wait" and 0 < i < 4 else 0
            line["search/" + name].append((at, ms * MS))
            at += (ms + 0.25) * MS
    line["data/fill"] = [(T0 - 1.0, 0.4), (T0 + 4.0, 0.3), (T0 + 9.9, 0.4)]
    line["data/wait"] = [(T0 + 2.0, 0.05), (T0 + 6.0, 0.05), (T0 + 20.0, 9.0)]
    line["data/batch"] = [(T0 + k, 0.5) for k in range(-2, 12)]
    line["xfer/d2h"] = [(T0 + 1.0, 0.02), (T0 + 3.0, 0.02), (T0 + 5.0, 0.06)]
    return line


def a_run(name: str, peaks=PEAKS):
    base, _, group = name.partition(".")
    window = harness.Window(seconds=SECONDS, units=3, t0=T0)
    cell = harness.Cell("c", 1, "cfg", {}, {}, [], [], harness.ROOT)
    return harness.load_module("metrics", base), harness.Run(
        cell, peaks, window, {}, {}, None, group)


@pytest.fixture()
def program(monkeypatch):
    """A program whose timeline and gauge are the hand-made ones."""
    from dcr_tpu.core import tracing

    line = hand_made()
    monkeypatch.setattr(tracing, "timeline",
                        lambda name: list(reversed(line.get(name, []))))
    tracing.registry().gauge("data/workers").set(2)
    yield line
    tracing.registry().remove("data/workers")


WANT = {
    # the median call of the window's three, phase by phase
    "search_phase_ms.put": 1.0, "search_phase_ms.dispatch": 0.5,
    "search_phase_ms.device_wait": 13.0, "search_phase_ms.fetch": 0.25,
    "search_phase_ms.merge": 2.0,
    # 1 - (12 + 13 + 14) / (17 + 18 + 19)
    "search_host_share": 100.0 * (1.0 - 39.0 / 54.0),
    # the fills that straddle an end count with the part inside
    "loader_wait_share.fill": 100.0 * (0.0 + 0.3 + 0.1) / SECONDS,
    "loader_wait_share.steady": 100.0 * 0.1 / SECONDS,
    # ten batches wholly inside, over 10 s x 2 workers
    "loader_busy_share": 100.0 * 10 * 0.5 / (SECONDS * 2),
    "sample_d2h_share": 100.0 * 0.1 / SECONDS,
}


def check_the_ten(bench: dict) -> None:
    """PR 26's ten stand in `per_layer` in their order, whatever later PRs
    append after them."""
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [n for n in entries if n in NEW] == NEW      # appended, in order
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in NEW:
        m = entries[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["workloads"] and set(m["workloads"]) <= set(
            e2e[m["moves"]]["workloads"])
    assert set(WANT) == set(NEW)


def test_the_new_entries_are_the_ten_of_the_table():
    check_the_ten(json.loads((harness.ROOT / "BENCHMARK.json").read_text()))


@pytest.mark.parametrize("name", NEW)
def test_a_reader_on_the_hand_made_timeline(program, name):
    reader, run = a_run(name)
    assert reader.read(run) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_off_the_chip(program, name):
    reader, run = a_run(name, peaks=None)
    assert reader.read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_on_an_empty_timeline(monkeypatch, name):
    from dcr_tpu.core import tracing

    monkeypatch.setattr(tracing, "timeline", lambda name: [])
    reader, run = a_run(name)
    assert reader.read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_from_a_program_without_a_timeline(monkeypatch, name):
    """The parent of PR 26: `tracing` has no `timeline`; the reader returns
    nothing and does not raise."""
    from dcr_tpu.core import tracing

    monkeypatch.delattr(tracing, "timeline")
    reader, run = a_run(name)
    assert reader.read(run) is None


def test_the_five_phases_sum_to_the_median_call(program):
    phases = sum(a_run(n)[0].read(a_run(n)[1]) for n in NEW[:5])
    calls = ps.started_in(a_run(NEW[0])[1], "search/query")
    assert [d for _, d in calls] == pytest.approx([17 * MS, 18 * MS, 19 * MS])
    # 1.25 ms of every call lie between the spans
    assert phases == pytest.approx(1e3 * statistics.median(d for _, d in calls) - 1.25)


def test_spans_outside_the_window_are_left_out(program):
    _, run = a_run("sample_d2h_share")
    assert ps.started_in(run, "data/wait") == [(T0 + 2.0, 0.05), (T0 + 6.0, 0.05)]
    assert ps.seconds_in(run, "data/wait") == pytest.approx(0.1)
    assert ps.seconds_in(run, "never/fired") is None
    assert ps.seconds_inside([(0.0, 1.0), (2.0, 1.0)],
                             [(0.5, 0.1), (0.9, 0.5), (1.5, 9.0), (2.0, 0.2)]) \
        == pytest.approx([0.6, 0.2])


def test_the_readers_read_the_programs_own_spans():
    """No monkeypatch: real spans of `dcr_tpu.core.tracing`, a window taken
    round them on the same clock."""
    import time

    from dcr_tpu.core import tracing

    tracing.reset_for_tests()
    try:
        t0 = time.perf_counter()
        for _ in range(3):
            with tracing.span("xfer/d2h"):
                time.sleep(0.002)
        seconds = time.perf_counter() - t0
        reader, run = a_run("sample_d2h_share")
        run.window.t0, run.window.seconds = t0, seconds
        share = reader.read(run)
        assert 100.0 * 0.006 / seconds <= share <= 100.0
        run.window.t0 = t0 + 3600.0                     # a window elsewhere
        assert reader.read(run) is None
    finally:
        tracing.reset_for_tests()
