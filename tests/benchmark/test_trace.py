"""The trace reduction (benchmark/lib/trace.py) on a hand-made timeline and on
a small trace recorded on a v5e (tests/benchmark/data/probe.xplane.pb, made by
benchmark/tools/record_trace.py, my chip run, PR 25: three `jit_mm` calls,
each fetched, then three Pallas flash-attention forward calls at
(2, 4096, 5, 64) bf16, with `bench/dispatch`, `bench/fetch` and
`bench/host_merge` annotations round the host's calls)."""
from pathlib import Path

import pytest

from benchmark.lib import harness, trace as T

PROBE = Path(__file__).parent / "data" / "probe.xplane.pb"
MS = 1e6      # nanoseconds


def hand_made() -> T.Trace:
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 0 * MS, 2 * MS),
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1 * MS, 2 * MS),
           ("%while.3 = (s32[]) while((s32[]) %t), body=%b", 5 * MS, 3 * MS),
           ('%k.1 = bf16[2,8,4]{2,1,0} custom-call(bf16[2,8,4]{2,1,0} %q), '
            'custom_call_target="tpu_custom_call"', 5 * MS, 1 * MS),
           ("%copy.9 = f32[8]{0} copy(f32[8]{0} %x)", 9.5 * MS, 0.5 * MS)]
    host = [("bench/traced", 0.0, 10 * MS), ("bench/fetch", 3 * MS, 1.5 * MS),
            ("bench/data_wait", 4.5 * MS, 0.5 * MS),
            ("bench/dispatch", 8 * MS, 1.4 * MS)]
    return T.Trace(ops={0: ops}, modules={0: []}, host=host)


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    tr = hand_made()
    assert T.union(tr.ops[0], 0, 10 * MS) == [
        (0, 3 * MS), (5 * MS, 8 * MS), (9.5 * MS, 10 * MS)]
    assert T.busy_seconds(tr.ops[0], 0, 10 * MS) == pytest.approx(6.5e-3)
    assert T.busy_seconds(tr.ops[0], 2 * MS, 6 * MS) == pytest.approx(2e-3)


def test_idle_share_is_one_minus_busy_over_the_traced_window():
    tr = hand_made()
    assert T.busy_and_window(tr) == (pytest.approx(6.5e-3), pytest.approx(10e-3))
    assert T.idle_share(tr) == pytest.approx(35.0)
    # nothing ran on a device: no share at all, never 100 or 0
    assert T.idle_share(T.Trace(host=tr.host)) is None


def test_kernel_time_by_name_and_containers_left_out_of_the_breakdown():
    tr = hand_made()
    seconds, count, _ = T.seconds_by(tr.ops[0], "tpu_custom_call")
    assert (seconds, count) == (pytest.approx(1e-3), 1)
    top = dict(T.top_ops(tr))
    assert top["fusion:kLoop"] == pytest.approx(4e-3)
    assert top["k:custom-call:tpu_custom_call"] == pytest.approx(1e-3)
    assert not any(label.startswith("while") for label in top)


def test_gaps_go_to_the_host_span_that_overlaps_them_most():
    tr = hand_made()
    assert T.gaps(tr.ops[0], 0, 10 * MS) == [(3 * MS, 5 * MS), (8 * MS, 9.5 * MS)]
    assert T.attribute_gaps(tr) == [["fetch", pytest.approx(2e-3)],
                                    ["dispatch", pytest.approx(1.5e-3)]]
    tr.host = [e for e in tr.host if e[0] != "bench/dispatch"]
    assert dict(T.attribute_gaps(tr))["unattributed"] == pytest.approx(1.5e-3)


def test_shapes_are_read_out_of_an_instructions_text():
    text = ("%flash.1 = (bf16[10,4096,64]{2,1,0:T(8,128)(2,1)S(1)}, "
            "f32[10,4096,128]{2,1,0:T(8,128)}) custom-call(bf16[10,4096,64]{2,1,0} %a)")
    assert T.shapes_in(text)[:3] == [("bf16", (10, 4096, 64)),
                                     ("f32", (10, 4096, 128)),
                                     ("bf16", (10, 4096, 64))]
    assert T.op_kind(text) == ("flash", "custom-call")


def test_recorded_v5e_trace_planes_lines_and_annotations():
    tr = T.read(PROBE)
    assert list(tr.ops) == [0] and list(tr.modules) == [0]
    names = [n for n, _, _ in tr.modules[0]]
    assert sum(n.startswith("jit_mm(") for n in names) == 3
    assert sum(n.startswith("jit_flash(") for n in names) == 3
    assert {n for n, _, _ in tr.host} == {
        "bench/dispatch", "bench/fetch", "bench/host_merge"}
    busy, window = T.busy_and_window(tr)
    # the device ran 2.8 ms of a 23 ms stretch whose host work was fetches
    assert busy == pytest.approx(2.796e-3, rel=1e-3)
    assert 0 < busy < window
    assert T.idle_share(tr) == pytest.approx(100 * (1 - busy / window))
    gaps = dict(T.attribute_gaps(tr))
    assert max(gaps, key=gaps.get) == "fetch"


def test_flash_forward_roofline_from_the_recorded_trace():
    """The kernel's events are told from their instruction text; its share of
    the roofline follows from the shapes in that text: 4*10*4096*4096*64 FLOPs
    a call against the 0.685 ms the chip took, 31.8% of 197 TFLOP/s."""
    reader = harness.load_module("metrics", "flash_fwd_roofline")
    tr = T.read(PROBE)
    seconds, count, hits = T.seconds_by(tr.ops[0], reader.PATTERN)
    assert count == 3 and seconds == pytest.approx(2.056e-3, rel=1e-3)
    flops, nbytes = reader.kernel_work(hits[0][0])
    assert flops == 4 * 10 * 4096 * 4096 * 64
    assert nbytes == 4 * (10 * 4096 * 64 * 2) + 10 * 4096 * 128 * 4
    run = harness.Run(cell=None, peaks=harness.load_peaks()["tpu v5 lite"],
                      window=None, spans={}, counters={}, trace=tr)
    assert reader.read(run) == pytest.approx(31.81, abs=0.05)
    # a backward kernel's text (one result) is not counted as a forward call
    assert reader.kernel_work(
        '%dq = bf16[10,4096,64]{2,1,0} custom-call(bf16[10,4096,64]{2,1,0} %q), '
        'custom_call_target="tpu_custom_call"') is None
    # off the chip there are no peaks, and the reader returns nothing
    run.peaks = None
    assert reader.read(run) is None
