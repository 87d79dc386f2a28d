"""dcr-ann acceptance: IVF + int8 approximate search tier (ISSUE 19).

The correctness matrix for search/ann.py + search/annindex.py:

1. training determinism — same seed + same shards produce BIT-IDENTICAL
   centroids and assignment (the one-hot-matmul Lloyd step, no scatter);
2. incremental folds — append-then-fold rewrites ONLY the affected lists
   (untouched manifest entries keep their exact file + sha256), and
   compaction drives the same fold through the live tier;
3. fault drills — ``ivf_list_corrupt@load=N`` lands quarantine + counter
   + rebuild-from-store; ``kmeans_nan@iter=N`` lands the bounded
   seed-shifted restart (and the typed failure when exhausted);
4. the query contract — shortlist re-rank scores are EXACT f32 dots,
   recall vs the exact oracle, ann-off bit-identity (the exact engine
   must not notice an ann tier on disk), and 8-way mesh == 1-device;
5. the operator surface — train-ivf/stats/query --ann CLI, the three-tier
   stats payload, trace schema + report, and the banked BENCH_ANN gate.
"""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from dcr_tpu.core import tracing
from dcr_tpu.search import ann
from dcr_tpu.search.annindex import (AnnEngine, open_ann_engine,
                                     spot_check_recall)
from dcr_tpu.search.livestore import LiveStore
from dcr_tpu.search.shardindex import open_engine
from dcr_tpu.search.store import EmbeddingStoreReader, EmbeddingStoreWriter
from dcr_tpu.utils import faults

DIM = 16


def _counter(name: str) -> int:
    return tracing.registry().counters("ann/").get(name, 0)


def _clustered(rng, rows, clusters=8, dim=DIM, noise=0.1):
    centers = rng.standard_normal((clusters, dim)).astype(np.float32) * 4.0
    assign = rng.integers(0, clusters, rows)
    return (centers[assign]
            + rng.standard_normal((rows, dim)).astype(np.float32) * noise)


def _store(path, feats, *, shard_rows=64, normalize=False, prefix="r"):
    w = EmbeddingStoreWriter(path, embed_dim=feats.shape[1],
                             shard_rows=shard_rows, normalize=normalize)
    w.add(feats, [f"{prefix}{i}" for i in range(feats.shape[0])])
    w.finalize()
    return path


# ---------------------------------------------------------------------------
# 1. training determinism + storage discipline
# ---------------------------------------------------------------------------

def test_kmeans_training_is_bit_deterministic(tmp_path, rng_np):
    feats = _clustered(rng_np, 200)
    a = _store(tmp_path / "a", feats)
    b = _store(tmp_path / "b", feats)
    ra = ann.train_ivf(a, n_lists=8, iters=6, seed=7)
    rb = ann.train_ivf(b, n_lists=8, iters=6, seed=7)
    assert ra["rows"] == rb["rows"] == 200
    ca = ann.AnnIndexReader(a).load_centroids()
    cb = ann.AnnIndexReader(b).load_centroids()
    np.testing.assert_array_equal(ca, cb)          # bit-identical centroids
    np.testing.assert_array_equal(ann.assign_rows(feats, ca),
                                  ann.assign_rows(feats, cb))


@pytest.mark.fast
def test_int8_codes_roundtrip_within_scale(rng_np):
    feats = rng_np.standard_normal((50, DIM)).astype(np.float32) * 3
    codes, scale, zero = ann.quantize_list(feats)
    assert codes.dtype == np.int8
    assert np.abs(codes).max() <= 127
    back = ann.dequantize(codes, scale, zero)
    assert np.abs(back - feats).max() <= scale * 0.5 + 1e-6


def test_train_commits_current_flip_and_stats(tmp_path, rng_np):
    store = _store(tmp_path / "s", _clustered(rng_np, 120))
    assert not ann.has_ann_index(store)
    assert ann.ann_stats(store) is None
    report = ann.train_ivf(store, n_lists=4, iters=3, seed=0)
    adir = store / "ann"
    assert (adir / "CURRENT").read_text().strip() == "ann_manifest.v1.json"
    assert (adir / "ann_manifest.v1.json").exists()
    assert ann.has_ann_index(store) and ann.ann_snapshot_version(store) == 1
    stats = ann.ann_stats(store)
    assert stats["rows"] == 120 and stats["n_lists"] == 4
    assert stats["snapshot"] == 1 and stats["seed"] == 0
    assert report["nonempty_lists"] == stats["nonempty_lists"]
    # every nonempty list sha256-verifies clean
    assert ann.AnnIndexReader(store).verify()["corrupt"] == 0


def test_fold_rewrites_only_affected_lists(tmp_path, rng_np):
    """The drift pin: appending rows near ONE centroid must rewrite only
    that centroid's list — every other manifest entry keeps its exact
    file name and sha256 (and therefore its bytes on disk)."""
    store = _store(tmp_path / "s", _clustered(rng_np, 160))
    ann.train_ivf(store, n_lists=8, iters=4, seed=1)
    before = {int(e["list"]): (e["file"], e["sha256"])
              for e in ann.read_ann_manifest(store)["lists"]}
    centroids = ann.AnnIndexReader(store).load_centroids()
    new = (centroids[[3, 3, 3]]
           + rng_np.standard_normal((3, DIM)).astype(np.float32) * 1e-3)
    target = ann.assign_rows(new, centroids)
    assert (target == target[0]).all()             # all land in one list
    rep = ann.fold_rows(store, new.astype(np.float32), ["n0", "n1", "n2"])
    assert rep["lists_rewritten"] == 1 and rep["snapshot"] == 2
    after = {int(e["list"]): (e["file"], e["sha256"])
             for e in ann.read_ann_manifest(store)["lists"]}
    moved = int(target[0])
    for lid, entry in before.items():
        if lid == moved:
            assert after[lid] != entry             # rewritten under v2
            assert after[lid][0].endswith("_v2.npz")
        else:
            assert after[lid] == entry             # byte-identical entry
    assert ann.AnnIndexReader(store).total == 163


# ---------------------------------------------------------------------------
# 2. fault drills
# ---------------------------------------------------------------------------

def test_ivf_list_corrupt_quarantines_counts_and_rebuilds(tmp_path, rng_np):
    store = _store(tmp_path / "s", _clustered(rng_np, 100))
    ann.train_ivf(store, n_lists=4, iters=3, seed=0)
    reader = ann.AnnIndexReader(store)
    entry = next(e for e in reader.manifest["lists"] if e["count"])
    before = _counter("ann/ivf_list_corrupt")
    faults.install(f"ivf_list_corrupt@load=0")
    try:
        assert reader.load_list(entry) is None
    finally:
        faults.clear()
    assert _counter("ann/ivf_list_corrupt") == before + 1
    assert int(entry["list"]) in reader.failed_lists
    quarantined = list((store / "ann").glob("*.quarantine*"))
    assert quarantined, "damaged list must be quarantine-renamed"
    # rebuild-from-store re-derives the same rows under a new snapshot
    rep = ann.rebuild_list(store, int(entry["list"]))
    assert rep["rows"] == int(entry["count"])
    fresh = ann.AnnIndexReader(store)
    assert fresh.verify()["corrupt"] == 0
    assert fresh.total == 100


def test_kmeans_nan_fault_restarts_bounded(tmp_path, rng_np):
    store = _store(tmp_path / "s", _clustered(rng_np, 80))
    faults.install("kmeans_nan@iter=1")
    try:
        report = ann.train_ivf(store, n_lists=4, iters=3, seed=0)
    finally:
        faults.clear()
    assert report["restarts"] == 1                 # poisoned once, recovered
    assert ann.AnnIndexReader(store).verify()["corrupt"] == 0
    # exhausting every restart raises the typed error, commits nothing
    store2 = _store(tmp_path / "s2", _clustered(rng_np, 80))
    faults.install(f"kmeans_nan@iter=0x{ann.MAX_KMEANS_RESTARTS + 1}")
    try:
        with pytest.raises(ann.AnnError, match="non-finite"):
            ann.train_ivf(store2, n_lists=4, iters=3, seed=0)
    finally:
        faults.clear()
    assert not ann.has_ann_index(store2)


def test_engine_rebuilds_corrupt_list_on_build(tmp_path, rng_np):
    """A list damaged on disk degrades to a rebuild at engine build time —
    queries still see every committed row."""
    feats = _clustered(rng_np, 90)
    store = _store(tmp_path / "s", feats)
    ann.train_ivf(store, n_lists=4, iters=3, seed=0)
    entry = next(e for e in ann.read_ann_manifest(store)["lists"]
                 if e["count"])
    path = store / "ann" / entry["file"]
    path.write_bytes(b"rotten" + path.read_bytes()[6:])
    engine = open_ann_engine(store, top_k=1, nprobe=4, query_batch=8)
    assert engine.total == 90
    scores, keys = engine.query(feats[:4])
    exact = feats @ feats[:4].T
    for i in range(4):
        assert keys[i][0] == f"r{int(exact[:, i].argmax())}"


# ---------------------------------------------------------------------------
# 3. the query contract
# ---------------------------------------------------------------------------

def test_rerank_scores_are_exact_dots_and_recall_high(tmp_path, rng_np):
    feats = _clustered(rng_np, 300)
    store = _store(tmp_path / "s", feats)
    ann.train_ivf(store, n_lists=8, iters=5, seed=0)
    engine = open_ann_engine(store, top_k=5, nprobe=4, query_batch=16)
    q = (feats[:20] + 0.01).astype(np.float32)
    scores, keys = engine.query(q)
    # re-rank is exact f32: every returned score IS the true dot product
    for i in range(q.shape[0]):
        for j in range(5):
            row = int(str(keys[i][j])[1:])
            np.testing.assert_allclose(
                scores[i][j], np.float32(q[i] @ feats[row]), rtol=1e-6)
    exact = open_engine(store, top_k=10, query_batch=16)
    recall = spot_check_recall(engine, exact, q, k=5)
    assert recall >= 0.95


def test_ann_off_is_bit_identical_with_ann_tier_on_disk(tmp_path, rng_np):
    """The exact path must not notice <store>/ann/ existing: scores AND
    keys bit-equal before and after training the IVF tier."""
    feats = _clustered(rng_np, 150)
    store = _store(tmp_path / "s", feats)
    q = (feats[:10] + 0.02).astype(np.float32)
    e1 = open_engine(store, top_k=3, query_batch=8)
    s1, k1 = e1.query(q)
    ann.train_ivf(store, n_lists=4, iters=3, seed=0)
    e2 = open_engine(store, top_k=3, query_batch=8)
    s2, k2 = e2.query(q)
    np.testing.assert_array_equal(s1, s2)
    assert (k1 == k2).all()


def test_mesh_sharded_ann_equals_single_device(tmp_path, rng_np,
                                               cpu_devices):
    from dcr_tpu.core.config import MeshConfig
    from dcr_tpu.parallel import mesh as pmesh

    feats = _clustered(rng_np, 200)
    store = _store(tmp_path / "s", feats)
    ann.train_ivf(store, n_lists=8, iters=4, seed=0)
    q = (feats[:12] + 0.01).astype(np.float32)
    one = open_ann_engine(store, top_k=4, nprobe=4, query_batch=8)
    s1, k1 = one.query(q)
    mesh8 = pmesh.make_mesh(MeshConfig(data=8))
    eight = open_ann_engine(store, mesh=mesh8, top_k=4, nprobe=4,
                            query_batch=8)
    s8, k8 = eight.query(q)
    # 8-way row sharding never splits the contraction axis, but this XLA:CPU
    # picks a reduction order per shard shape: within 4 ulps, keys equal
    np.testing.assert_array_max_ulp(s1, s8, maxulp=4)
    assert (k1 == k8).all()
    assert eight.segment_rows % 8 == 0


def test_query_rows_tail_scan_is_exact(tmp_path, rng_np):
    """The live-tail path: tail rows (in no inverted list) scan exactly
    through the re-rank program."""
    feats = _clustered(rng_np, 120)
    store = _store(tmp_path / "s", feats)
    ann.train_ivf(store, n_lists=4, iters=3, seed=0)
    engine = open_ann_engine(store, top_k=2, nprobe=2, query_batch=4)
    tail = rng_np.standard_normal((7, DIM)).astype(np.float32)
    q = tail[:3] + 0.001
    scores, keys = engine.query_rows(q, tail, [f"t{i}" for i in range(7)])
    exact = q @ tail.T
    for i in range(3):
        assert keys[i][0] == f"t{int(exact[i].argmax())}"
        np.testing.assert_allclose(scores[i][0], exact[i].max(), rtol=1e-6)


def test_engine_refuses_width_mismatch_and_raw_rows_for_cosine(
        tmp_path, rng_np):
    feats = _clustered(rng_np, 60)
    store = _store(tmp_path / "s", feats)
    ann.train_ivf(store, n_lists=4, iters=2, seed=0)
    with pytest.raises(ann.AnnError, match="ivf_normalize"):
        AnnEngine(store, require_normalized_rows=True)
    # a normalized-trained index satisfies the cosine consumer
    store2 = _store(tmp_path / "s2", _clustered(rng_np, 60), normalize=True)
    ann.train_ivf(store2, n_lists=4, iters=2, seed=0, normalize=True)
    AnnEngine(store2, require_normalized_rows=True)


# ---------------------------------------------------------------------------
# 4. live-tier integration: compaction folds into lists
# ---------------------------------------------------------------------------

def test_compaction_folds_wal_rows_into_lists(tmp_path, rng_np):
    feats = _clustered(rng_np, 100)
    store = _store(tmp_path / "s", feats, shard_rows=32)
    ann.train_ivf(store, n_lists=4, iters=3, seed=0)
    before = {int(e["list"]): (e["file"], e["sha256"])
              for e in ann.read_ann_manifest(store)["lists"]}
    centroids = ann.AnnIndexReader(store).load_centroids()
    new = (centroids[[1, 1]]
           + rng_np.standard_normal((2, DIM)).astype(np.float32) * 1e-3)
    with LiveStore.open(store) as live:
        live.append(new.astype(np.float32), ["w0", "w1"])
        rep = live.compact()
    assert rep["ann_lists_folded"] == 1
    after = {int(e["list"]): (e["file"], e["sha256"])
             for e in ann.read_ann_manifest(store)["lists"]}
    assert sum(1 for lid in before if after[lid] != before[lid]) == 1
    assert ann.AnnIndexReader(store).total == 102
    # the folded rows are servable through the ann path: top-1 matches a
    # brute-force oracle over committed + folded rows (dot-product metric,
    # so the oracle is argmax, not "the appended row itself")
    engine = open_ann_engine(store, top_k=1, nprobe=4, query_batch=4)
    allf = np.concatenate([feats, new.astype(np.float32)])
    allk = [f"r{i}" for i in range(100)] + ["w0", "w1"]
    q = new.astype(np.float32)
    _, keys = engine.query(q)
    want = (q @ allf.T).argmax(axis=1)
    assert [str(keys[i][0]) for i in range(2)] == [allk[j] for j in want]


def test_compact_without_ann_tier_reports_zero_folds(tmp_path, rng_np):
    with LiveStore.open(tmp_path / "s", embed_dim=DIM) as live:
        live.append(rng_np.standard_normal((3, DIM)).astype(np.float32),
                    ["a", "b", "c"])
        rep = live.compact()
    assert rep["ann_lists_folded"] == 0
    assert not ann.has_ann_index(tmp_path / "s")


# ---------------------------------------------------------------------------
# 5. operator surface: CLI, stats, schema, banked bench
# ---------------------------------------------------------------------------

def test_cli_train_ivf_stats_and_query_ann(tmp_path, rng_np, capsys):
    from dcr_tpu.cli.search import main as cli_main, store_stats
    from dcr_tpu.search.embed import save_embeddings

    feats = _clustered(rng_np, 120)
    store = _store(tmp_path / "s", feats)
    st = store_stats(store)
    assert st["ann"] is None and st["committed"]["rows"] == 120
    cli_main(["train-ivf", f"--store_dir={store}",
              "--n_lists=4", "--ivf_iters=3"])
    out = json.loads(capsys.readouterr().out)
    assert out["snapshot"] == 1 and out["rows"] == 120
    cli_main(["stats", f"--store_dir={store}"])
    text = capsys.readouterr().out
    assert "committed  120 rows" in text
    assert "ann        120 rows in 4/4 lists" in text
    cli_main(["stats", f"--store_dir={store}", "--json_out=true"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["ann"]["rows"] == 120 and doc["live"]["tail_rows"] == 0
    # query --ann end to end, against the exact path on the same gen set
    gen_dir = tmp_path / "gen"
    gen_dir.mkdir()
    q = (feats[:6] + 0.01).astype(np.float32)
    save_embeddings(gen_dir / "embedding.npz", q,
                    [f"g{i}" for i in range(6)])
    cli_main(["query", f"--store_dir={store}", f"--gen_folder={gen_dir}",
              f"--out_path={tmp_path / 'exact.npz'}", "--top_k=3"])
    cli_main(["query", f"--store_dir={store}", f"--gen_folder={gen_dir}",
              f"--out_path={tmp_path / 'ann.npz'}", "--top_k=3",
              "--ann=true", "--nprobe=4"])
    capsys.readouterr()
    with np.load(tmp_path / "exact.npz", allow_pickle=True) as ze, \
            np.load(tmp_path / "ann.npz", allow_pickle=True) as za:
        assert (ze["keys"][:, 0] == za["keys"][:, 0]).all()


@pytest.mark.fast
def test_ann_fault_kinds_are_documented():
    doc = faults.__doc__
    for kind in ("ivf_list_corrupt", "kmeans_nan"):
        assert f"``{kind}``" in doc, f"{kind} missing from faults registry"


@pytest.mark.fast
def test_trace_schema_and_report_know_ann():
    from tools import trace_report

    schema = json.loads(
        (Path(__file__).parent.parent / "tools" /
         "trace_schema.json").read_text())
    for name in ("search/kmeans", "search/ivf_scan", "search/ivf_rerank",
                 "search/ivf_merge"):
        assert name in schema["known_names"]["spans"]
    assert "ann/*" in schema["known_names"]["events"]
    records = [
        {"ph": "X", "name": "search/ivf_scan", "id": 1, "ts": 1e6,
         "dur": 800.0, "pid": 1, "tid": 1, "tname": "t",
         "args": {"segment": 0, "batch": 8, "nprobe": 4, "lists": 3,
                  "rows": 512, "index_size": 4096}},
        {"ph": "X", "name": "search/ivf_rerank", "id": 2, "ts": 2e6,
         "dur": 300.0, "pid": 1, "tid": 1, "tname": "t",
         "args": {"candidates": 40, "batch": 8}},
        {"ph": "X", "name": "search/kmeans", "id": 3, "ts": 3e6,
         "dur": 1500.0, "pid": 1, "tid": 1, "tname": "t",
         "args": {"iter": 0, "restart": 0}},
        {"ph": "i", "name": "ann/query_funnel", "id": 4, "ts": 4e6,
         "pid": 1, "tid": 1, "tname": "t",
         "args": {"batch": 8, "nprobe": 4, "lists_probed": 6,
                  "segments_scanned": 2, "segments_skipped": 6,
                  "shortlist": 64, "top_k": 5}},
        {"ph": "i", "name": "ann/recall_spot_check", "id": 5, "ts": 5e6,
         "pid": 1, "tid": 1, "tname": "t",
         "args": {"k": 10, "queries": 8, "recall": 0.98, "nprobe": 4}},
    ]
    summary = trace_report.ann_summary(records)
    assert summary["scan"]["segment_scans"] == 1
    assert summary["scan"]["nprobe_distribution"] == {"4": 1}
    assert summary["funnel"]["segment_skip_pct"] == 75.0
    assert summary["rerank"]["candidates"] == 40
    assert summary["train"]["lloyd_iters"] == 1
    assert summary["recall_spot_checks"]["mean_recall"] == 0.98
    text = trace_report.render_text(
        trace_report.summarize(records), [Path(".")])
    assert "ANN (IVF approximate search)" in text
    assert "nprobe distribution" in text and "recall spot-check" in text


@pytest.mark.fast
def test_ann_metrics_resolve_to_prometheus_names():
    for name, want in (
            ("ann/ivf_list_corrupt", "dcr_ann_ivf_list_corrupt"),
            ("ann/kmeans_restart", "dcr_ann_kmeans_restart"),
            ("ann/lists_scanned_total", "dcr_ann_lists_scanned_total"),
            ("ann/recall_spot_pct", "dcr_ann_recall_spot_pct")):
        assert tracing.sanitize_metric_name(name) == want


def test_banked_bench_ann_schema():
    from tools.bench_ann import validate_result

    banked = Path(__file__).parent.parent / "BENCH_ANN.json"
    assert banked.exists(), "BENCH_ANN.json must be committed"
    doc = json.loads(banked.read_text())
    assert validate_result(doc) == []
    assert doc["equality"] == {"exact_scores_equal": True,
                               "exact_keys_equal": True}
    assert doc["gate"]["enforced"] is True
    assert doc["gate"]["passed"] is True
    assert doc["gate"]["recall"] >= doc["gate"]["min_recall"]
    assert doc["gate"]["speedup"] >= doc["gate"]["min_speedup"]


@pytest.mark.fast
def test_risk_config_validates_ann_knobs():
    from dcr_tpu.core.config import RiskConfig, validate_risk_config

    with pytest.raises(ValueError, match="risk.ann"):
        validate_risk_config(RiskConfig(ann=True))
    with pytest.raises(ValueError, match="nprobe"):
        validate_risk_config(RiskConfig(ann=True, store_dir="/x", nprobe=0))
    validate_risk_config(RiskConfig(ann=True, store_dir="/x", nprobe=8))
