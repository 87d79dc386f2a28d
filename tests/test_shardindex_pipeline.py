"""The search call's host exchanges overlap the scan (PR 29): the answer's
host copies are queued at dispatch, and inside one placed segment the loop
keeps one chunk ahead where ``build()`` saw room for a second execution.

What is pinned here, on the CPU at a tiny size:

- a call of several chunks over several segments (a padded last one),
  resident and host-streamed, ``query`` and ``query_rows``, serial and a
  chunk ahead, answers bit for bit what the plain serial loop answers (the
  loop kept below as the reference), and what ``search_folders`` answers;
- the decision is a pure function of two byte counts, and ``build()`` takes
  it from what the compiled program and the device report (a CPU device
  reports no memory, so a built engine is serial here; the cases that run
  ahead set the built object's private decision);
- the counters say what ran: two host copies queued a chunk a segment, and
  chunks - 1 dispatches ahead a segment (none when serial);
- four threads on one engine get their own answers.
"""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from dcr_tpu.core import tracing
from dcr_tpu.search import embed as E
from dcr_tpu.search import search as S
from dcr_tpu.search.shardindex import (ShardedTopK, merge_topk, open_engine,
                                       room_for_two)
from dcr_tpu.search.store import (EmbeddingStoreReader, EmbeddingStoreWriter,
                                  ingest_dumps)

DIM = 16
QUERY_BATCH = 4
N_QUERIES = 5 * QUERY_BATCH + 3             # six chunks, the last padded
SEGMENT_ROWS = 8
FOLDER_ROWS = (9, 7, 5)                     # 21 rows: segments of 8, 8, 5 + 3 pad


@pytest.fixture()
def corpus(tmp_path, rng_np):
    folders = []
    for i, n in enumerate(FOLDER_ROWS):
        folder = tmp_path / f"laion{i}"
        folder.mkdir()
        E.save_embeddings(
            folder / "embedding.npz",
            rng_np.standard_normal((n, DIM)).astype(np.float32),
            [f"laion{i}_img{j}" for j in range(n)])
        folders.append(folder)
    writer = EmbeddingStoreWriter.create(tmp_path / "store", shard_rows=8)
    ingest_dumps(writer, folders)
    q = rng_np.standard_normal((N_QUERIES, DIM)).astype(np.float32)
    return folders, tmp_path / "store", q


def _engine(store, top_k: int, resident: bool, ahead: int) -> ShardedTopK:
    engine = ShardedTopK(EmbeddingStoreReader(store), top_k=top_k,
                         query_batch=QUERY_BATCH, segment_rows=SEGMENT_ROWS,
                         max_resident_rows=1 << 20 if resident else 1).build()
    assert engine.resident == resident and engine.num_segments == 3
    assert engine._ahead == 0               # XLA:CPU reports no memory figures
    engine._ahead = ahead
    return engine


def _serial_loop(engine: ShardedTopK, q: np.ndarray, placed_segments):
    """The loop as it was before the pipeline: dispatch, wait, fetch and
    merge chunk n before chunk n+1 is dispatched. The reference."""
    scores = np.full((len(q), engine.top_k), -np.inf, np.float32)
    keys = np.full((len(q), engine.top_k), "", dtype=object)
    for feats, valid, seg_keys, _ in placed_segments:
        for start, m, chunk_dev in engine._chunked_queries(q):
            s, idx = engine._fn(feats, valid, chunk_dev)
            s.block_until_ready()
            s, idx = np.asarray(s)[:m], np.asarray(idx)[:m]
            hit = np.where(np.isneginf(s), "", seg_keys[idx])
            sl = slice(start, start + m)
            scores[sl], keys[sl] = merge_topk(scores[sl], keys[sl], s, hit)
    return scores, keys


def _counters() -> dict:
    return tracing.registry().counters("search/")


@pytest.mark.parametrize("ahead", [0, 1], ids=["serial", "ahead"])
@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "streamed"])
@pytest.mark.parametrize("top_k", [1, 5])
def test_query_equals_the_serial_loop_and_the_brute_force(
        corpus, cpu_devices, top_k, resident, ahead):
    folders, store, q = corpus
    engine = _engine(store, top_k, resident, ahead)
    placed = (engine._dev_segments if resident
              else [engine._put_segment(s) for s in engine._segments])
    want_scores, want_keys = _serial_loop(engine, q, placed)

    tracing.reset_for_tests()
    scores, keys = engine.query(q)

    np.testing.assert_array_equal(want_scores, scores)      # bit for bit
    assert (want_keys == keys).all()
    brute = S.search_folders(q, [f"g{i}" for i in range(len(q))], folders,
                             top_k=top_k, num_chunks=2)
    # the brute force is another program (one matmul a folder): the same
    # float32 dots in the reduction order XLA:CPU picks for its shapes
    np.testing.assert_array_max_ulp(brute["scores"], scores, maxulp=4)
    assert (brute["keys"] == keys).all()

    chunks, segments = 6, 3
    counters = _counters()
    assert counters["search/segments_scanned_total"] == chunks * segments
    assert counters["search/host_copy_queued_total"] == 2 * chunks * segments
    assert counters.get("search/dispatch_ahead_total", 0) == (
        ahead * (chunks - 1) * segments)
    # a dispatch a chunk a segment, whatever the order
    assert len(tracing.timeline("search/dispatch")) == chunks * segments


@pytest.mark.parametrize("ahead", [0, 1], ids=["serial", "ahead"])
@pytest.mark.parametrize("top_k", [1, 5])
def test_query_rows_equals_the_serial_loop(corpus, cpu_devices, rng_np,
                                           top_k, ahead):
    _, store, q = corpus
    engine = _engine(store, top_k, True, ahead)
    # a WAL tail of two segments and a bit: 8 + 8 + 3 rows (5 pad)
    tail = rng_np.standard_normal((19, DIM)).astype(np.float32)
    tail_keys = np.asarray([f"tail{j}" for j in range(19)], dtype=object)
    placed = [engine._put_segment(engine._pad_segment(
        tail[s:s + SEGMENT_ROWS], tail_keys[s:s + SEGMENT_ROWS], DIM))
        for s in range(0, 19, SEGMENT_ROWS)]
    want_scores, want_keys = _serial_loop(engine, q, placed)

    tracing.reset_for_tests()
    scores, keys = engine.query_rows(q, tail, tail_keys)

    np.testing.assert_array_equal(want_scores, scores)
    assert (want_keys == keys).all()
    assert set(keys[:, 0]) <= set(tail_keys)
    counters = _counters()
    assert counters["search/host_copy_queued_total"] == 2 * 6 * 3
    assert counters.get("search/dispatch_ahead_total", 0) == ahead * 5 * 3


MB = 1 << 20


@pytest.mark.parametrize("execution, free, ahead", [
    (805 * MB, 9_000 * MB, True),       # the search cell: room to spare
    (805 * MB, 1_610 * MB, True),       # exactly two
    (805 * MB, 1_610 * MB - 1, False),  # one byte short of two
    (805 * MB, 900 * MB, False),        # one fits, two do not
    (805 * MB, -100 * MB, False),       # the reserve already overdrawn
    (0, 0, True),                       # nothing to hold
    (None, 9_000 * MB, False),          # no memory_analysis()
    (805 * MB, None, False),            # no memory_stats() (XLA:CPU)
    (None, None, False),
])
def test_the_gate_is_a_pure_function_of_two_byte_counts(execution, free,
                                                        ahead):
    assert room_for_two(execution, free) is ahead


@pytest.mark.parametrize("free, ahead", [(None, 0), (1 << 40, 1), (64, 0)],
                         ids=["unreadable", "roomy", "too-small"])
def test_build_decides_from_the_program_and_the_device(
        corpus, cpu_devices, monkeypatch, free, ahead):
    """``build()`` sets the compiled program's own bytes against the free
    figure and publishes the decision; with the free figure too small (or
    none) the loop is serial and nothing is dispatched ahead."""
    _, store, q = corpus
    if free is not None:        # a CPU device reports none: see the first id
        monkeypatch.setattr(ShardedTopK, "_free_bytes", lambda self: free)
    tracing.reset_for_tests()
    engine = open_engine(store, top_k=1, query_batch=QUERY_BATCH,
                         segment_rows=SEGMENT_ROWS)
    assert engine._ahead == ahead
    assert tracing.registry().gauge("search/dispatch_ahead").value == ahead
    engine.query(q)
    assert _counters().get("search/dispatch_ahead_total", 0) == ahead * 5 * 3
    text = tracing.registry().prometheus_text()
    assert f"dcr_search_dispatch_ahead {float(ahead)}" in text
    assert f"dcr_search_dispatch_ahead_total {ahead * 5 * 3}" in text
    assert f"dcr_search_host_copy_queued_total {2 * 6 * 3}" in text


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


class _Mesh:
    def __init__(self, stats_of_local, n_devices):
        self.local_devices = [_Device(s) for s in stats_of_local]
        self.devices = np.empty((n_devices,), dtype=object)


@pytest.mark.parametrize("stats, n_devices, want", [
    # one chip: limit - in use - one more segment (8 rows x (16 x 4 + 1) B)
    ([{"bytes_limit": 10_000, "bytes_in_use": 4_000}], 1, 6_000 - 520),
    # a mesh: the fullest device decides, a segment's share is rows / devices
    ([{"bytes_limit": 10_000, "bytes_in_use": 4_000},
      {"bytes_limit": 10_000, "bytes_in_use": 7_000}], 2, 3_000 - 260),
    ([None], 1, None),                                  # XLA:CPU
    ([{"bytes_in_use": 4_000}], 1, None),               # usage but no limit
    ([{"bytes_limit": 10_000, "bytes_in_use": 1}, {}], 2, None),
])
def test_free_bytes_reads_the_mesh_devices(corpus, cpu_devices, stats,
                                           n_devices, want):
    _, store, _ = corpus
    engine = ShardedTopK(EmbeddingStoreReader(store), query_batch=QUERY_BATCH,
                         segment_rows=SEGMENT_ROWS)
    engine.mesh = _Mesh(stats, n_devices)
    assert engine._free_bytes() == want


def test_four_threads_on_one_engine_get_their_own_answers(corpus, cpu_devices,
                                                          rng_np):
    _, store, _ = corpus
    engine = _engine(store, 5, True, 1)
    queries = [rng_np.standard_normal((N_QUERIES - i, DIM)).astype(np.float32)
               for i in range(4)]
    want = [_serial_loop(engine, q, engine._dev_segments) for q in queries]
    got: list = [None] * 4
    errors: list = []

    def caller(i: int) -> None:
        try:
            for _ in range(8):
                got[i] = engine.query(queries[i])
                np.testing.assert_array_equal(want[i][0], got[i][0])
                assert (want[i][1] == got[i][1]).all()
        except BaseException as e:      # reported on the test's own thread
            errors.append((i, e))
            raise

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert all(g is not None for g in got)
