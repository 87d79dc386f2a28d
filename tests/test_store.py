"""dcr-store acceptance: sharded embedding store + mesh-sharded top-k.

Layers:

1. store build/append/verify roundtrip + writer validation (fast);
2. damage discipline: on-disk shard corruption, the deterministic
   ``store_shard_corrupt`` / ``search_dump_corrupt`` fault kinds, the
   sha256+rows dump sidecar, the search-folder quarantine/keep contract;
3. the exact-equality pins: store-backed top-k vs the brute force on the
   same dump (scores AND keys), single-device and 8-way mesh-sharded,
   padded-query invariance, host-streamed vs device-resident;
4. CLI subcommands + trace_report "Search" section + bench schema;
5. slow legs: serve ``/check`` answered from a store-backed index (HTTP
   e2e) and a warm-restarted ``dcr-search query`` answering with ZERO XLA
   compiles (``trace_report --max-compiles 0``).
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from dcr_tpu.core import tracing
from dcr_tpu.core.config import RiskConfig, SearchConfig
from dcr_tpu.search import embed as E
from dcr_tpu.search import search as S
from dcr_tpu.search.embed import EmbeddingDumpError
from dcr_tpu.search.store import (EmbeddingStoreReader, EmbeddingStoreWriter,
                                  MANIFEST_NAME, StoreError, ingest_dumps)
from dcr_tpu.utils import faults


def _counter(name: str) -> int:
    return tracing.registry().counters("search/").get(name, 0)


def _dump_folders(tmp_path, rng, sizes, dim=16, prefix="laion"):
    folders = []
    for i, n in enumerate(sizes):
        folder = tmp_path / f"{prefix}{i}"
        folder.mkdir()
        feats = rng.standard_normal((n, dim)).astype(np.float32)
        E.save_embeddings(folder / "embedding.npz", feats,
                          [f"{prefix}{i}_img{j}" for j in range(n)])
        folders.append(folder)
    return folders


def _build_store(tmp_path, folders, name="store", **writer_kw):
    writer = EmbeddingStoreWriter.create(tmp_path / name, **writer_kw)
    report = ingest_dumps(writer, folders)
    return tmp_path / name, report


# ---------------------------------------------------------------------------
# 1. store build/append/verify roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_store_build_append_verify_roundtrip(tmp_path, rng_np):
    folders = _dump_folders(tmp_path, rng_np, [10, 7, 13])
    store, report = _build_store(tmp_path, folders, shard_rows=8)
    assert report == {**report, "rows": 30, "dumps": 3, "skipped": 0}
    reader = EmbeddingStoreReader(store)
    assert reader.total == 30 and reader.embed_dim == 16
    # committed shards are fixed-capacity except the tail
    counts = [s["count"] for s in reader.shards]
    assert counts == [8, 8, 8, 6]
    feats, keys = reader.load_all()
    want = np.concatenate([E.load_embeddings(f / "embedding.npz")[0]
                           for f in folders])
    np.testing.assert_array_equal(feats, want)   # ingest preserves bytes
    assert keys[:2] == ["laion0_img0", "laion0_img1"]
    assert reader.verify() == {"shards": 4, "ok": 4, "corrupt": 0,
                               "rows_ok": 30, "total": 30}

    # append-only growth: committed shards untouched, manifest re-commits
    extra = _dump_folders(tmp_path, rng_np, [5], prefix="extra")
    before = {s["file"]: s["sha256"] for s in reader.shards}
    report2 = ingest_dumps(EmbeddingStoreWriter.append(store), extra)
    assert report2["rows"] == 5 and report2["total"] == 35
    reader2 = EmbeddingStoreReader(store)
    assert reader2.total == 35
    for s in reader2.shards:
        if s["file"] in before:
            assert s["sha256"] == before[s["file"]]
    feats2, keys2 = reader2.load_all()
    assert len(keys2) == 35 and keys2[-1] == "extra0_img4"
    np.testing.assert_array_equal(feats2[:30], want)


@pytest.mark.fast
def test_store_writer_validation_and_clobber_refusal(tmp_path, rng_np):
    w = EmbeddingStoreWriter.create(tmp_path / "s", shard_rows=4)
    w.add(rng_np.standard_normal((3, 8)).astype(np.float32), ["a", "b", "c"])
    with pytest.raises(StoreError, match="width"):
        w.add(np.zeros((2, 9), np.float32), ["d", "e"])
    with pytest.raises(StoreError, match="torn"):
        w.add(np.zeros((2, 8), np.float32), ["d"])
    with pytest.raises(StoreError, match="non-finite"):
        w.add(np.full((1, 8), np.nan, np.float32), ["d"])
    with pytest.raises(StoreError, match="N, D"):
        w.add(np.zeros((4,), np.float32), list("abcd"))
    w.finalize()
    with pytest.raises(StoreError, match="committed store"):
        EmbeddingStoreWriter.create(tmp_path / "s")
    # append on a directory that is not a store is typed
    with pytest.raises(StoreError, match="not an embedding store"):
        EmbeddingStoreWriter.append(tmp_path / "nowhere")


@pytest.mark.fast
def test_store_normalize_at_ingest(tmp_path, rng_np):
    folders = _dump_folders(tmp_path, rng_np, [6])
    store, _ = _build_store(tmp_path, folders, shard_rows=4, normalize=True)
    reader = EmbeddingStoreReader(store)
    assert reader.normalized is True
    feats, _ = reader.load_all()
    np.testing.assert_allclose(np.linalg.norm(feats, axis=1), 1.0, atol=1e-6)


# ---------------------------------------------------------------------------
# 2. damage discipline
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_shard_corruption_quarantined_and_survivors_serve(tmp_path, rng_np):
    folders = _dump_folders(tmp_path, rng_np, [16])
    store, _ = _build_store(tmp_path, folders, shard_rows=4)
    shard1 = store / "shard_00001.npz"
    blob = shard1.read_bytes()
    shard1.write_bytes(blob[:len(blob) // 2] + b"\xff" + blob[len(blob) // 2:])
    before = _counter("search/store_shard_corrupt")
    reader = EmbeddingStoreReader(store)
    feats, keys = reader.load_all()
    # 3 of 4 shards survive; the damaged one is renamed out of the key space
    assert len(keys) == 12
    assert "laion0_img4" not in keys          # rows 4..7 lived in shard 1
    assert _counter("search/store_shard_corrupt") == before + 1
    assert not shard1.exists()
    assert list(store.glob("shard_00001.npz.quarantined.*"))


@pytest.mark.fast
def test_store_verify_readonly_leaves_damage_in_place(tmp_path, rng_np):
    folders = _dump_folders(tmp_path, rng_np, [8])
    store, _ = _build_store(tmp_path, folders, shard_rows=4)
    shard0 = store / "shard_00000.npz"
    shard0.write_bytes(b"garbage")
    reader = EmbeddingStoreReader(store, quarantine=False)
    report = reader.verify()
    assert report["shards"] == 2 and report["ok"] == 1
    assert report["corrupt"] == 1 and report["rows_ok"] == 4
    assert shard0.exists()                    # read-only: nothing renamed


@pytest.mark.fast
def test_store_shard_corrupt_fault_kind(tmp_path, rng_np):
    folders = _dump_folders(tmp_path, rng_np, [12])
    store, _ = _build_store(tmp_path, folders, shard_rows=4)
    faults.install("store_shard_corrupt@load=1")
    try:
        before = _counter("search/store_shard_corrupt")
        feats, keys = EmbeddingStoreReader(store).load_all()
        assert len(keys) == 8                  # shard 1 (reads 0,1,2) poisoned
        assert _counter("search/store_shard_corrupt") == before + 1
        assert list(store.glob("shard_00001.npz.quarantined.*"))
    finally:
        faults.clear()


@pytest.mark.fast
def test_store_zero_survivors_and_corrupt_manifest(tmp_path, rng_np):
    folders = _dump_folders(tmp_path, rng_np, [4])
    store, _ = _build_store(tmp_path, folders, shard_rows=4)
    (store / "shard_00000.npz").write_bytes(b"x")
    with pytest.raises(StoreError, match="no shard survived"):
        EmbeddingStoreReader(store).load_all()

    store2, _ = _build_store(tmp_path, folders, name="store2", shard_rows=4)
    (store2 / MANIFEST_NAME).write_text("{not json")
    # read-only inspection first: typed error, nothing renamed
    with pytest.raises(StoreError, match="manifest corrupt"):
        EmbeddingStoreReader(store2, quarantine=False)
    assert (store2 / MANIFEST_NAME).exists()
    with pytest.raises(StoreError, match="manifest corrupt"):
        EmbeddingStoreReader(store2)
    assert list(store2.glob(f"{MANIFEST_NAME}.quarantined.*"))


@pytest.mark.fast
def test_zero_row_ingest_refuses_to_commit(tmp_path, rng_np):
    # every source dump corrupt: no manifest may commit — a committed
    # empty store would defer the failure to the first query AND block the
    # corrected rebuild behind the clobber refusal
    bad = tmp_path / "badchunk"
    bad.mkdir()
    (bad / "embedding.npz").write_bytes(b"garbage")
    with pytest.raises(StoreError, match="0 rows"):
        ingest_dumps(EmbeddingStoreWriter.create(tmp_path / "s"), [bad])
    assert not (tmp_path / "s" / MANIFEST_NAME).exists()
    # ...so the corrected rebuild works in place
    good = _dump_folders(tmp_path, rng_np, [3], dim=8)
    report = ingest_dumps(EmbeddingStoreWriter.create(tmp_path / "s"), good)
    assert report["rows"] == 3


@pytest.mark.fast
def test_save_embeddings_appends_npz_suffix(tmp_path, rng_np):
    # np.savez semantics preserved: a non-.npz name gets the suffix, so
    # load_embeddings' suffix dispatch can never misparse npz bytes as
    # pickle
    feats = rng_np.standard_normal((2, 4)).astype(np.float32)
    out = E.save_embeddings(tmp_path / "gen_embs", feats, ["a", "b"])
    assert out.name == "gen_embs.npz" and out.exists()
    f2, k2 = E.load_embeddings(out)
    np.testing.assert_array_equal(f2, feats)
    assert k2 == ["a", "b"]


@pytest.mark.fast
def test_dump_sidecar_detects_torn_dump(tmp_path, rng_np):
    feats = rng_np.standard_normal((5, 8)).astype(np.float32)
    path = tmp_path / "embedding.npz"
    E.save_embeddings(path, feats, [f"k{i}" for i in range(5)])
    side = Path(str(path) + ".sha256")
    assert side.exists()
    doc = json.loads(side.read_text())
    assert doc["rows"] == 5
    f2, k2 = E.load_embeddings(path)           # verified load round-trips
    np.testing.assert_array_equal(f2, feats)

    # torn write: truncate the dump — detected at load, typed
    blob = path.read_bytes()
    path.write_bytes(blob[:-20])
    before = _counter("search/dump_corrupt")
    with pytest.raises(EmbeddingDumpError, match="sha256"):
        E.load_embeddings(path)
    assert _counter("search/dump_corrupt") == before + 1

    # row-count mismatch: sidecar promises different rows
    path.write_bytes(blob)
    side.write_text(json.dumps({**doc, "rows": 7,
                                "sha256": doc["sha256"]}))
    with pytest.raises(EmbeddingDumpError, match="rows"):
        E.load_embeddings(path)

    # a corrupt SIDECAR degrades to an unverified load, loudly — never
    # takes down a possibly-fine dump
    side.write_text("{broken")
    before = tracing.registry().counters("search/").get(
        "search/dump_sidecar_unreadable", 0)
    f3, _ = E.load_embeddings(path)
    np.testing.assert_array_equal(f3, feats)
    assert tracing.registry().counters("search/")[
        "search/dump_sidecar_unreadable"] == before + 1


@pytest.mark.fast
def test_search_dump_corrupt_fault_kind(tmp_path, rng_np):
    path = tmp_path / "embedding.npz"
    E.save_embeddings(path, rng_np.standard_normal((3, 8)).astype(np.float32),
                      ["a", "b", "c"])
    E.reset_dump_load_seq()
    faults.install("search_dump_corrupt@load=0")
    try:
        with pytest.raises(EmbeddingDumpError, match="sha256"):
            E.load_embeddings(path)
        # the fault fired once; the next load is clean
        feats, keys = E.load_embeddings(path)
        assert keys == ["a", "b", "c"]
    finally:
        faults.clear()


@pytest.mark.fast
def test_search_folders_quarantines_unreadable_keeps_invalid(
        tmp_path, rng_np, cpu_devices):
    d = 8
    gen = rng_np.standard_normal((2, d)).astype(np.float32)
    good = _dump_folders(tmp_path, rng_np, [5], dim=d, prefix="good")[0]

    # UNREADABLE dump: quarantine-renamed + counted
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "embedding.npz").write_bytes(b"garbage")
    # readable-but-INVALID dump (features/keys row mismatch): left in place
    invalid = tmp_path / "invalid"
    invalid.mkdir()
    np.savez(invalid / "embedding.npz",
             features=np.zeros((4, d), np.float32),
             indexes=np.asarray(["only", "two"]))

    c_before = _counter("search/folder_corrupt")
    i_before = _counter("search/folder_invalid")
    result = S.search_folders(gen, ["g0", "g1"], [good, bad, invalid],
                              top_k=1)
    assert _counter("search/folder_corrupt") == c_before + 1
    assert _counter("search/folder_invalid") == i_before + 1
    assert not (bad / "embedding.npz").exists()
    assert list(bad.glob("embedding.npz.quarantined.*"))
    assert (invalid / "embedding.npz").exists()   # valid-looking artifact
    assert all(k.startswith("good0_") for k in result["keys"].ravel())

    # a sidecar-verified dump that fails its sha is quarantined WITH its
    # sidecar — a stale sidecar left behind would condemn any restored
    # replacement dump to a false-mismatch loop
    torn = tmp_path / "torn"
    torn.mkdir()
    dump = E.save_embeddings(torn / "embedding.npz",
                             rng_np.standard_normal((3, d)).astype(np.float32),
                             ["a", "b", "c"])
    dump.write_bytes(dump.read_bytes()[:-10])
    S.search_folders(gen, ["g0", "g1"], [torn], top_k=1)
    assert not dump.exists()
    assert not Path(str(dump) + ".sha256").exists()
    assert list(torn.glob("embedding.npz.sha256.quarantined.*"))
    # ...so a restored good dump (fresh write = fresh sidecar) serves again
    E.save_embeddings(torn / "embedding.npz",
                      rng_np.standard_normal((3, d)).astype(np.float32),
                      ["x", "y", "z"])
    res2 = S.search_folders(gen, ["g0", "g1"], [torn], top_k=1)
    assert all(k in ("x", "y", "z") for k in res2["keys"].ravel())


# ---------------------------------------------------------------------------
# 3. exact-equality pins
# ---------------------------------------------------------------------------

def _assert_scores_equal(want, got):
    # same float32 dots, but this XLA:CPU picks a reduction order per batch
    # and shard shape: equal within 4 ulps, not bit for bit
    np.testing.assert_array_max_ulp(want, got, maxulp=4)


def _equality_fixture(tmp_path, rng_np, dim=16, sizes=(10, 7, 13), n_gen=5):
    folders = _dump_folders(tmp_path, rng_np, list(sizes), dim=dim)
    gen = rng_np.standard_normal((n_gen, dim)).astype(np.float32)
    gen_keys = [f"g{i}" for i in range(n_gen)]
    store, _ = _build_store(tmp_path, folders, shard_rows=8)
    return folders, store, gen, gen_keys


def test_store_backed_equals_brute_force_single_device(
        tmp_path, rng_np, cpu_devices):
    folders, store, gen, gen_keys = _equality_fixture(tmp_path, rng_np)
    brute = S.search_folders(gen, gen_keys, folders, top_k=3, num_chunks=2)
    res = S.search_store(gen, gen_keys, store, top_k=3, query_batch=4)
    _assert_scores_equal(brute["scores"], res["scores"])
    assert (brute["keys"] == res["keys"]).all()
    assert list(res["gen_images"]) == gen_keys

    # and through the full run_search stage: the banked .npz files match
    gdir = tmp_path / "gens"
    gdir.mkdir()
    E.save_embeddings(gdir / "embedding.npz", gen, gen_keys)
    cfg_brute = SearchConfig(gen_folder=str(gdir), top_k=3,
                             out_path=str(tmp_path / "brute.npz"))
    cfg_store = SearchConfig(gen_folder=str(gdir), top_k=3,
                             store_dir=str(store), query_batch=4,
                             out_path=str(tmp_path / "store.npz"))
    S.run_search(cfg_brute, laion_folders=folders)
    S.run_search(cfg_store)
    with np.load(tmp_path / "brute.npz") as zb, \
            np.load(tmp_path / "store.npz") as zs:
        _assert_scores_equal(zb["scores"], zs["scores"])
        assert (zb["keys"] == zs["keys"]).all()
        assert (zb["gen_images"] == zs["gen_images"]).all()


def test_mesh_sharded_equals_single_device(tmp_path, rng_np, cpu_devices):
    from dcr_tpu.core.config import MeshConfig
    from dcr_tpu.parallel import mesh as pmesh
    from dcr_tpu.search.shardindex import open_engine

    folders, store, gen, gen_keys = _equality_fixture(
        tmp_path, rng_np, sizes=(20, 11), n_gen=6)
    brute = S.search_folders(gen, gen_keys, folders, top_k=4)
    mesh8 = pmesh.make_mesh(MeshConfig(data=8))
    engine = open_engine(store, mesh=mesh8, top_k=4, query_batch=3)
    scores, keys = engine.query(gen)
    # 8-way row sharding: same dots, same merge — key-equal
    _assert_scores_equal(brute["scores"], scores)
    assert (brute["keys"] == keys).all()
    # segment padded to the row-shard multiple
    assert engine.segment_rows % 8 == 0


def test_padded_query_invariance_and_chunking(tmp_path, rng_np, cpu_devices):
    from dcr_tpu.search.shardindex import open_engine

    _, store, gen, _ = _equality_fixture(tmp_path, rng_np, n_gen=10)
    engine = open_engine(store, top_k=2, query_batch=4)
    # 10 queries through the fixed batch-4 program (3 chunks, last padded)
    scores, keys = engine.query(gen)
    for i in range(len(gen)):
        s1, k1 = engine.query(gen[i:i + 1])    # padded 1-of-4
        np.testing.assert_array_equal(s1[0], scores[i])
        assert (k1[0] == keys[i]).all()


def test_streamed_segments_match_resident(tmp_path, rng_np, cpu_devices):
    from dcr_tpu.search.shardindex import ShardedTopK

    _, store, gen, _ = _equality_fixture(tmp_path, rng_np, sizes=(9, 9, 9))
    resident = ShardedTopK(EmbeddingStoreReader(store), top_k=3,
                           query_batch=4, segment_rows=8).build()
    streamed = ShardedTopK(EmbeddingStoreReader(store), top_k=3,
                           query_batch=4, segment_rows=8,
                           max_resident_rows=1).build()
    assert resident.resident and not streamed.resident
    assert resident.num_segments == 4          # 27 rows / 8-row segments
    assert resident._segments == []            # host copies dropped
    assert len(streamed._segments) == 4        # streamed keeps host copies
    s_r, k_r = resident.query(gen)
    s_s, k_s = streamed.query(gen)
    np.testing.assert_array_equal(s_r, s_s)
    assert (k_r == k_s).all()


def test_store_smaller_than_topk_pads_like_brute(tmp_path, rng_np,
                                                 cpu_devices):
    folders = _dump_folders(tmp_path, rng_np, [2], dim=8)
    store, _ = _build_store(tmp_path, folders, shard_rows=4)
    gen = rng_np.standard_normal((2, 8)).astype(np.float32)
    brute = S.search_folders(gen, ["g0", "g1"], folders, top_k=5)
    res = S.search_store(gen, ["g0", "g1"], store, top_k=5, query_batch=2)
    _assert_scores_equal(brute["scores"], res["scores"])
    assert (brute["keys"] == res["keys"]).all()
    assert np.isneginf(res["scores"][:, 2:]).all()
    assert (res["keys"][:, 2:] == "").all()


# ---------------------------------------------------------------------------
# 4. CLI + telemetry + bench schema
# ---------------------------------------------------------------------------

def test_cli_build_append_verify_query(tmp_path, rng_np, cpu_devices,
                                       capsys):
    from dcr_tpu.cli import search as cli

    folders_root = tmp_path / "corpus"
    folders_root.mkdir()
    _dump_folders(folders_root, rng_np, [6, 5], dim=8, prefix="chunk")
    store = tmp_path / "store"
    cli.main(["build", f"--store_dir={store}",
              f"--laion_folder={folders_root}", "--shard_rows=4"])
    report = json.loads(capsys.readouterr().out)
    assert report["rows"] == 11 and report["skipped"] == 0

    extra_root = tmp_path / "more"
    extra_root.mkdir()
    _dump_folders(extra_root, rng_np, [3], dim=8, prefix="late")
    cli.main(["append", f"--store_dir={store}",
              f"--laion_folder={extra_root}"])
    assert json.loads(capsys.readouterr().out)["total"] == 14

    cli.main(["verify", f"--store_dir={store}"])
    assert json.loads(capsys.readouterr().out)["corrupt"] == 0

    gen = rng_np.standard_normal((3, 8)).astype(np.float32)
    gdir = tmp_path / "gens"
    gdir.mkdir()
    E.save_embeddings(gdir / "embedding.npz", gen, ["g0", "g1", "g2"])
    out = tmp_path / "res.npz"
    cli.main(["query", f"--store_dir={store}", f"--gen_folder={gdir}",
              f"--out_path={out}", "--top_k=2", "--query_batch=2"])
    with np.load(out) as z:
        assert z["scores"].shape == (3, 2)
        assert list(z["gen_images"]) == ["g0", "g1", "g2"]

    # verify on a damaged store: exit 1, read-only (nothing renamed)
    shard = store / "shard_00000.npz"
    shard.write_bytes(b"junk")
    with pytest.raises(SystemExit) as exc:
        cli.main(["verify", f"--store_dir={store}"])
    assert exc.value.code == 1
    assert shard.exists()


def test_trace_report_search_section(tmp_path, rng_np, cpu_devices):
    from tools import trace_report

    tracing.configure(tmp_path / "trace")
    folders = _dump_folders(tmp_path, rng_np, [12], dim=8)
    store, _ = _build_store(tmp_path, folders, shard_rows=4)
    gen = rng_np.standard_normal((2, 8)).astype(np.float32)
    S.search_store(gen, ["g0", "g1"], store, top_k=1, query_batch=2)

    records, errors, meta = trace_report.load_fleet(
        [tmp_path / "trace"], trace_report.load_schema())
    assert errors == []
    summary = trace_report.summarize(records, meta)
    search = summary["search"]
    assert search["ingest"]["shards"] == 3 and search["ingest"]["rows"] == 12
    topk = search["store_topk"]
    assert topk["segment_scans"] >= 1 and topk["rows_scanned"] >= 12
    assert topk["rows_per_s"] > 0
    text = trace_report.render_text(summary, tmp_path / "trace")
    assert "store top-k" in text and "ingest" in text


@pytest.mark.fast
def test_bench_search_schema_validation():
    from tools.bench_search import validate_result

    good = {
        "version": 1,
        "config": {"corpus_rows": 8, "folders": 1, "queries": 2, "top_k": 1,
                   "embed_dim": 4, "query_batch": 2, "repeats": 1,
                   "ingested_rows": 8},
        "brute": {"seconds": 0.1, "rows_per_s": 160},
        "store": {"seconds": 0.01, "rows_per_s": 1600, "build_seconds": 0.1,
                  "ready_seconds": 0.1, "segments": 1, "resident": True},
        "equality": {"scores_equal": True, "keys_equal": True},
        "gate": {"min_speedup": 1.5, "speedup": 10.0, "enforced": True,
                 "passed": True},
    }
    assert validate_result(good) == []
    bad = json.loads(json.dumps(good))
    del bad["equality"]["keys_equal"]
    bad["gate"]["speedup"] = "fast"
    problems = validate_result(bad)
    assert any("keys_equal" in p for p in problems)
    assert any("speedup" in p for p in problems)


@pytest.mark.fast
def test_banked_bench_search_passes_schema_and_gate():
    from tools.bench_search import validate_result

    path = Path(__file__).resolve().parents[1] / "BENCH_SEARCH.json"
    doc = json.loads(path.read_text())
    assert validate_result(doc) == []
    assert doc["equality"] == {"scores_equal": True, "keys_equal": True}
    # the banked run is the enforced full-mode gate
    assert doc["gate"]["enforced"] is True and doc["gate"]["passed"] is True
    assert doc["gate"]["speedup"] >= doc["gate"]["min_speedup"] >= 1.5


# ---------------------------------------------------------------------------
# 5. slow legs: store-backed /check + warm-restart zero compiles
# ---------------------------------------------------------------------------

def _embed_train_images(tmp_path, images, image_size=32):
    from tests.test_risk import _build_index_from_images

    return _build_index_from_images(tmp_path, images, image_size=image_size)


@pytest.mark.slow
def test_check_served_from_store_backed_index(tmp_path, cpu_devices):
    """The acceptance e2e: serve answers /check (and per-response
    copy_risk) from a STORE-BACKED index — a corpus scored through the
    mesh-sharded search/topk engine instead of one resident matmul."""
    from tests.test_risk import _png_b64, _risk_service, _tiny_stack
    from dcr_tpu.obs.copyrisk import CopyRiskIndex

    stack = _tiny_stack()
    plain = _risk_service(stack)
    img_train = plain.submit("a red square", seed=1).future.result(timeout=300)
    img_clean = plain.submit("a blue circle", seed=2).future.result(
        timeout=300)
    plain.stop(timeout=60)

    dump = _embed_train_images(tmp_path, [img_train])
    store = tmp_path / "riskstore"
    writer = EmbeddingStoreWriter.create(store, shard_rows=4)
    writer.add_dump(dump)
    writer.finalize()

    # threshold from a store-backed probe (margins measured, not assumed)
    probe = CopyRiskIndex.load(
        RiskConfig(store_dir=str(store), image_size=32), batch=4)
    assert len(probe) == 1
    sim_hit = probe.score_batch(img_train[None])[0].max_sim
    sim_miss = probe.score_batch(img_clean[None])[0].max_sim
    assert sim_hit > sim_miss + 0.005, (sim_hit, sim_miss)
    threshold = (sim_hit + sim_miss) / 2

    risk = RiskConfig(store_dir=str(store), image_size=32,
                      threshold=threshold)
    svc = _risk_service(stack, risk=risk)
    try:
        assert svc.wait_risk_ready(timeout=300) and svc.risk_status() == "ok"
        req_hit = svc.submit("a red square", seed=1)
        req_miss = svc.submit("a blue circle", seed=2)
        out_hit = req_hit.future.result(timeout=300)
        req_miss.future.result(timeout=300)
        assert req_hit.risk["flagged"] is True
        assert req_hit.risk["top_key"].endswith("gen_0.png")
        assert req_miss.risk["flagged"] is False
        # scoring never perturbs generation, store-backed included
        assert np.array_equal(out_hit, img_train)
        # /check through the service front-end path
        check = svc.check({"image_png_b64": _png_b64(img_train)})
        assert check["flagged"] is True and check["index_size"] == 1
        assert svc.health_doc()["risk"] == "ok"
    finally:
        svc.stop(timeout=60)


@pytest.mark.slow
def test_serve_http_check_answers_from_store(tmp_path, cpu_devices):
    """HTTP leg: a dcr-serve subprocess configured with --risk.store_dir
    (no index_path at all) reaches risk=ok and answers POST /check."""
    import signal

    from tests.test_risk import _png_b64, _risk_service, _tiny_stack
    from tests.test_serve import (_export_tiny_ckpt, _free_port, _get,
                                  _serve_env)

    stack = _tiny_stack()
    plain = _risk_service(stack, max_batch=2)
    img_train = plain.submit("a red square", seed=1).future.result(timeout=300)
    plain.stop(timeout=60)
    dump = _embed_train_images(tmp_path, [img_train])
    store = tmp_path / "riskstore"
    writer = EmbeddingStoreWriter.create(store, shard_rows=4)
    writer.add_dump(dump)
    writer.finalize()

    ckpt = _export_tiny_ckpt(tmp_path)
    env, repo = _serve_env()
    port = _free_port()
    argv = [sys.executable, "-m", "dcr_tpu.cli.serve",
            f"--model_path={ckpt}", f"--port={port}",
            "--resolution=16", "--num_inference_steps=2", "--sampler=ddim",
            "--max_batch=2", "--max_wait_ms=100", "--queue_depth=16",
            "--request_timeout_s=300", "--seed=0",
            f"--logdir={tmp_path / 'log'}",
            f"--risk.store_dir={store}", "--risk.image_size=32",
            "--risk.threshold=0.999"]
    proc = subprocess.Popen(argv, env=env, cwd=repo, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.monotonic() + 300
        while True:
            try:
                _, health = _get(port, "/healthz", timeout=2)
                if health["status"] == "ok" and health["risk"] == "ok":
                    break
            except OSError:
                pass
            if proc.poll() is not None or time.monotonic() > deadline:
                out = proc.stdout.read() if proc.stdout else ""
                raise AssertionError(
                    f"server not risk-ready (rc={proc.poll()}): {out[-3000:]}")
            time.sleep(0.5)
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/check",
            data=json.dumps({"image_png_b64": _png_b64(img_train)}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            doc = json.loads(resp.read())
        assert resp.status == 200
        assert doc["flagged"] is True and doc["index_size"] == 1
        assert doc["max_sim"] >= 0.999
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=120) == 83    # EXIT_PREEMPTED drain


@pytest.mark.slow
def test_query_warm_restart_zero_compiles(tmp_path, rng_np, cpu_devices):
    """A second `dcr-search query` incarnation against the same warm cache
    answers with ZERO XLA compiles (trace_report --max-compiles 0) and
    bit-identical results."""
    from tests.test_serve import _serve_env
    from tools import trace_report

    folders_root = tmp_path / "corpus"
    folders_root.mkdir()
    _dump_folders(folders_root, rng_np, [24, 17], dim=8, prefix="chunk")
    store = tmp_path / "store"
    ingest_dumps(EmbeddingStoreWriter.create(store, shard_rows=8),
                 [folders_root])
    gen = rng_np.standard_normal((5, 8)).astype(np.float32)
    gdir = tmp_path / "gens"
    gdir.mkdir()
    E.save_embeddings(gdir / "embedding.npz", gen,
                      [f"g{i}" for i in range(5)])

    env, repo = _serve_env()
    # no XLA persistent cache in the subprocesses: with it active this
    # jaxlib emits unserializable executables, every warm entry degrades
    # to the export tier, and incarnation 2's compile-on-load would
    # (correctly) fail the --max-compiles 0 gate (same discipline as the
    # test_risk / test_warmcache restart e2e)
    for k in list(env):
        if k.startswith("JAX_COMPILATION") or k.startswith("JAX_PERSISTENT"):
            env.pop(k)
    # ... and the CLIs' setup_compile_cache() would otherwise turn it back on
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    warm = tmp_path / "warm"

    def run_query(logdir, out):
        argv = [sys.executable, "-m", "dcr_tpu.cli.search", "query",
                f"--store_dir={store}", f"--gen_folder={gdir}",
                f"--out_path={out}", "--top_k=2", "--query_batch=4",
                f"--warm_dir={warm}", f"--logdir={logdir}"]
        proc = subprocess.run(argv, env=env, cwd=repo, capture_output=True,
                              text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    run_query(tmp_path / "log1", tmp_path / "res1.npz")
    run_query(tmp_path / "log2", tmp_path / "res2.npz")
    with np.load(tmp_path / "res1.npz") as z1, \
            np.load(tmp_path / "res2.npz") as z2:
        np.testing.assert_array_equal(z1["scores"], z2["scores"])
        assert (z1["keys"] == z2["keys"]).all()
    # incarnation 1 compiled (and populated the cache); incarnation 2 warm
    records, _, _ = trace_report.load_fleet(
        [tmp_path / "log1"], trace_report.load_schema())
    assert any(r["name"] == "warmcache/compile" for r in records)
    assert trace_report.main([str(tmp_path / "log2"),
                              "--max-compiles", "0"]) == 0
