"""dcr-watch tests: live copy-risk observability.

Fast tier (pure logic + tiny jit only): embedding-dump loading (.npz and
the reference toolchain's pickle format, torn/non-finite/corrupt dumps
quarantined), the top-k cosine scorer, the exact-transform property of
prepare_images, bounded evidence dumps, the flagged-pair gallery,
trace_report's "Copy risk" section and tools/risk_report, lease/health
risk-state plumbing and supervisor /check routing (stub HTTP worker).

Slow tier (real tiny compiled stack): a request seeded to reproduce a
train image is flagged while a normal request is not, generated images are
bit-identical with scoring on vs off, the trainer-hook gauges land in
MetricWriter, and the HTTP e2e — /generate copy_risk + /check + Prometheus
counters + evidence dump, then a warm-cache restart whose second
incarnation scores with ZERO XLA compiles (trace_report --max-compiles 0).
"""

import base64
import io
import json
import pickle
import threading
import time

import numpy as np
import pytest

from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing
from dcr_tpu.core.config import RiskConfig
from dcr_tpu.obs.copyrisk import (EMBED_DIM, CopyRiskIndex, EvidenceRecorder,
                                  RiskIndexError, decode_image_b64,
                                  load_risk_dump, prepare_images,
                                  verify_risk_dump)


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


def _features(n: int, dim: int = EMBED_DIM) -> np.ndarray:
    """Deterministic, non-degenerate [n, dim] float32 features."""
    base = np.arange(n * dim, dtype=np.float32).reshape(n, dim)
    return np.cos(base * 0.37) + 0.01 * base / (n * dim)


def _keys(n: int) -> list:
    return [f"train/img_{i:04d}.png" for i in range(n)]


def _png_b64(image: np.ndarray) -> str:
    from PIL import Image

    buf = io.BytesIO()
    arr = (np.clip(image, 0, 1) * 255).round().astype(np.uint8)
    Image.fromarray(arr).save(buf, format="PNG")
    return base64.b64encode(buf.getvalue()).decode()


def _grad_image(i: int, size: int = 16) -> np.ndarray:
    x = np.linspace(0, 1, size * size * 3, dtype=np.float32)
    return np.roll(x, i * 97).reshape(size, size, 3) * ((i % 3 + 1) / 3.0)


# ---------------------------------------------------------------------------
# dump loading: both formats, verify-before-load, quarantine
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_dump_roundtrip_npz_and_reference_pickle(tmp_path):
    from dcr_tpu.search.embed import save_embeddings

    feats, keys = _features(5), _keys(5)
    save_embeddings(tmp_path / "embedding.npz", feats, keys)
    with open(tmp_path / "embedding.pkl", "wb") as f:
        pickle.dump({"features": feats, "indexes": keys}, f)

    for name in ("embedding.npz", "embedding.pkl"):
        got_feats, got_keys = load_risk_dump(tmp_path / name)
        assert got_keys == keys, name
        np.testing.assert_allclose(got_feats, feats, rtol=1e-6)


@pytest.mark.fast
def test_corrupt_dump_quarantined_and_counted(tmp_path):
    path = tmp_path / "embedding.npz"
    path.write_bytes(b"this is not a zip archive at all")
    with pytest.raises(RiskIndexError):
        load_risk_dump(path)
    assert not path.exists(), "corrupt dump must be quarantined away"
    assert list(tmp_path.glob("embedding.npz.quarantined.*"))
    assert R.counters().get("copy_risk/index_corrupt_total", 0) == 1


@pytest.mark.fast
def test_torn_and_nonfinite_dumps_rejected(tmp_path):
    from dcr_tpu.search.embed import save_embeddings

    # torn: features/indexes disagree. A READABLE dump that fails
    # verification is a typed error but stays IN PLACE — it may be a valid
    # artifact of the wrong kind / shared by a fleet; only unparseable
    # files get the destructive quarantine rename.
    np.savez(tmp_path / "torn.npz", features=_features(4),
             indexes=np.asarray(_keys(3)))
    with pytest.raises(RiskIndexError, match="torn"):
        load_risk_dump(tmp_path / "torn.npz")
    assert (tmp_path / "torn.npz").exists()
    assert not list(tmp_path.glob("torn.npz.quarantined.*"))
    assert R.counters().get("copy_risk/index_invalid_total", 0) == 1

    # non-finite features
    bad = _features(4)
    bad[2, 7] = np.nan
    save_embeddings(tmp_path / "nan.npz", bad, _keys(4))
    with pytest.raises(RiskIndexError, match="non-finite"):
        load_risk_dump(tmp_path / "nan.npz")
    assert (tmp_path / "nan.npz").exists()

    # wrong width (verify_risk_dump directly: no file involved)
    with pytest.raises(RiskIndexError, match="width"):
        verify_risk_dump(np.zeros((3, 64), np.float32), _keys(3))
    with pytest.raises(RiskIndexError, match="non-empty"):
        verify_risk_dump(np.zeros((0, EMBED_DIM), np.float32), [])

    # absent path: typed, NOT quarantined (nothing to rename)
    with pytest.raises(RiskIndexError, match="no embedding dump"):
        load_risk_dump(tmp_path / "missing.npz")


# ---------------------------------------------------------------------------
# scorer + transform
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_risk_scorer_topk_is_cosine_and_sorted(cpu_devices):
    from dcr_tpu.obs.copyrisk import make_risk_scorer

    feats = _features(16)
    feats = feats / np.linalg.norm(feats, axis=-1, keepdims=True)
    # queries deliberately NOT normalized: the scorer must normalize
    q = np.stack([feats[3] * 7.5, feats[11] * 0.2])
    sims, idx = make_risk_scorer(3)(feats, q.astype(np.float32))
    sims, idx = np.asarray(sims), np.asarray(idx)
    assert idx[0, 0] == 3 and idx[1, 0] == 11
    np.testing.assert_allclose(sims[:, 0], [1.0, 1.0], atol=1e-5)
    assert (np.diff(sims, axis=1) <= 1e-6).all(), "top-k must sort desc"
    expected = feats @ feats[3]
    np.testing.assert_allclose(sims[0], np.sort(expected)[::-1][:3],
                               atol=1e-5)


@pytest.mark.fast
def test_prepare_images_matches_embed_pipeline_transform(tmp_path):
    """An index embedded from saved PNGs must score a live float image of
    the same pixels at ~1.0 — which requires prepare_images to be the
    embed pipeline's folder transform exactly, uint8 round-trip included."""
    from PIL import Image

    from dcr_tpu.eval.features import (IMAGENET_NORM, EvalImageFolder,
                                       reference_resize_for)

    img = _grad_image(1, size=24)
    Image.fromarray((img * 255).round().astype(np.uint8)).save(
        tmp_path / "gen_0.png")
    folder = EvalImageFolder(tmp_path, 16,
                             resize_to=reference_resize_for(16),
                             normalize=IMAGENET_NORM)
    via_disk = folder.load(0)
    via_live = prepare_images(img[None], 16)[0]
    np.testing.assert_allclose(via_live, via_disk, atol=1e-6)


@pytest.mark.fast
def test_decode_image_b64(cpu_devices):
    img = _grad_image(2)
    arr = decode_image_b64({"image_png_b64": _png_b64(img)})
    assert arr.shape == (16, 16, 3) and 0.0 <= arr.min() <= arr.max() <= 1.0
    with pytest.raises(ValueError, match="image_png_b64"):
        decode_image_b64({})
    with pytest.raises(ValueError, match="undecodable"):
        decode_image_b64({"image_png_b64": "bm90IGFuIGltYWdl"})


@pytest.mark.fast
def test_risk_config_validation():
    from dcr_tpu.core.config import (ServeConfig, TrainConfig,
                                     validate_serve_config,
                                     validate_train_config)

    cfg = ServeConfig()
    cfg.risk.top_k = 0
    with pytest.raises(ValueError, match="top_k"):
        validate_serve_config(cfg)
    cfg.risk.top_k = 1
    cfg.risk.image_size = 8
    with pytest.raises(ValueError, match="image_size"):
        validate_serve_config(cfg)
    cfg.risk.image_size = 224
    cfg.risk.max_evidence = -1
    with pytest.raises(ValueError, match="max_evidence"):
        validate_serve_config(cfg)
    # the trainer path validates the same block: a bad --risk.* must fail
    # at config time, not as a per-interval score_failed counter
    tcfg = TrainConfig()
    tcfg.risk.top_k = 0
    with pytest.raises(ValueError, match="top_k"):
        validate_train_config(tcfg)


# ---------------------------------------------------------------------------
# evidence recorder + gallery
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_evidence_recorder_bounded(tmp_path):
    from dcr_tpu.obs.copyrisk import RiskScore

    rec = EvidenceRecorder(tmp_path / "ev", max_evidence=2)
    score = RiskScore(max_sim=0.99, top_key="train/x.png",
                      topk=[("train/x.png", 0.99)])
    img = _grad_image(0)
    first = rec.record(img, score, 0.5, request_id=1, prompt="p", seed=7)
    second = rec.record(img, score, 0.5, request_id=2, prompt="p", seed=8)
    third = rec.record(img, score, 0.5, request_id=3, prompt="p", seed=9)
    assert first is not None and second is not None and third is None
    docs = sorted((tmp_path / "ev").glob("flagged_*.json"))
    pngs = sorted((tmp_path / "ev").glob("flagged_*.png"))
    assert len(docs) == 2 and len(pngs) == 2
    doc = json.loads(docs[0].read_text())
    assert doc["top_key"] == "train/x.png" and doc["request_id"] == 1
    assert (tmp_path / "ev" / doc["image"]).exists()
    counters = tracing.registry().counters("copy_risk/")
    assert counters["copy_risk/evidence_dumped_total"] == 2
    assert counters["copy_risk/evidence_dropped_total"] == 1
    # disabled recorder: no dir, no writes, no exceptions
    assert EvidenceRecorder(None, 8).record(img, score, 0.5) is None


@pytest.mark.fast
def test_evidence_write_failure_refunds_budget(tmp_path):
    """A transient write failure must not consume the bounded evidence
    budget: once writes succeed again, the recorder still keeps evidence."""
    from dcr_tpu.obs.copyrisk import RiskScore

    blocker = tmp_path / "ev"
    blocker.write_text("a file where the evidence dir should be")
    rec = EvidenceRecorder(blocker, max_evidence=1)
    score = RiskScore(max_sim=0.99, top_key="train/x.png",
                      topk=[("train/x.png", 0.99)])
    img = _grad_image(0)
    assert rec.record(img, score, 0.5, request_id=1) is None   # mkdir fails
    assert R.counters().get("copy_risk/evidence_write_failed", 0) == 1
    blocker.unlink()                                           # disk "frees"
    assert rec.record(img, score, 0.5, request_id=2) is not None
    assert len(list(blocker.glob("flagged_*.json"))) == 1


@pytest.mark.fast
def test_flagged_pair_gallery(tmp_path):
    from PIL import Image

    from dcr_tpu.eval.gallery import flagged_pair_gallery

    flags, matches = [], []
    for i in range(3):
        f, m = tmp_path / f"flag_{i}.png", tmp_path / f"match_{i}.png"
        Image.fromarray((_grad_image(i) * 255).astype(np.uint8)).save(f)
        Image.fromarray((_grad_image(i + 5) * 255).astype(np.uint8)).save(m)
        flags.append(f)
        matches.append(m)
    pages = flagged_pair_gallery(flags, matches, [0.7, 0.9, 0.8],
                                 tmp_path / "gallery", thumb=16)
    assert len(pages) == 1 and pages[0].exists()
    assert pages[0].name == "gallery_rank0_2.png"   # ranked_galleries paging
    from PIL import Image as I

    with I.open(pages[0]) as page:
        assert page.width == 2 * 16 + 2      # [flagged | match] + pad
        assert page.height == 3 * 16 + 2 * 2
    with pytest.raises(ValueError, match="aligned"):
        flagged_pair_gallery(flags, matches[:2], [0.1, 0.2, 0.3],
                             tmp_path / "bad")
    with pytest.raises(ValueError, match="no flagged"):
        flagged_pair_gallery([], [], [], tmp_path / "empty")


# ---------------------------------------------------------------------------
# report plumbing: trace_report "Copy risk" section + tools/risk_report
# ---------------------------------------------------------------------------

def _risk_trace_records(flag_key="train/img_0001.png"):
    """Schema-valid synthetic trace: two scored serve batches + one
    training risk/score span + one flagged event."""
    base = {"pid": 0, "tid": 1, "tname": "serve-worker"}
    recs = [
        {"ph": "X", "name": "serve/risk_score", "id": 1, "ts": 1e6,
         "dur": 1500.0, "parent": None,
         "args": {"batch": 2, "sims": [0.99, 0.42],
                  "prompts": ["dup prompt", "clean prompt"],
                  "flagged": 1}, **base},
        {"ph": "X", "name": "serve/risk_score", "id": 2, "ts": 2e6,
         "dur": 1500.0, "parent": None,
         "args": {"batch": 1, "sims": [0.41], "prompts": ["clean prompt"],
                  "flagged": 0}, **base},
        {"ph": "X", "name": "risk/score", "id": 3, "ts": 3e6, "dur": 900.0,
         "parent": None, "args": {"step": 500, "sims": [0.5, 0.6]}, **base},
        {"ph": "i", "name": "risk/flagged", "id": 4, "ts": int(1.1e6),
         "parent": None,
         "args": {"request_id": 12, "max_sim": 0.99, "top_key": flag_key,
                  "prompt": "dup prompt", "seed": 7, "threshold": 0.9},
         **base},
    ]
    return recs


@pytest.mark.fast
def test_trace_report_copy_risk_section(tmp_path, capsys):
    from tools import trace_report

    trace = tmp_path / "trace.jsonl"
    trace.write_text("".join(json.dumps(r) + "\n"
                             for r in _risk_trace_records()))
    assert trace_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "copy risk: 5 generation(s) scored, 1 flagged" in out
    assert "train/img_0001.png" in out

    records, errors, meta = trace_report.load_fleet(
        [tmp_path], trace_report.load_schema())
    assert not errors
    summary = trace_report.summarize(records, meta)
    risk = summary["copy_risk"]
    assert risk["scored"] == 5 and risk["flagged"] == 1
    assert risk["sim_max"] == 0.99
    assert risk["flagged_train_keys"] == {"train/img_0001.png": 1}
    # risk spans categorize as "risk", not "serve"
    assert summary["categories"]["risk"]["count"] == 3


@pytest.mark.fast
def test_risk_report_per_prompt_timeline_and_gallery(tmp_path, capsys):
    from PIL import Image

    from tools import risk_report

    train_key = tmp_path / "train_img.png"
    Image.fromarray((_grad_image(4) * 255).astype(np.uint8)).save(train_key)
    trace_dir = tmp_path / "logs"
    trace_dir.mkdir()
    (trace_dir / "trace.jsonl").write_text(
        "".join(json.dumps(r) + "\n"
                for r in _risk_trace_records(flag_key=str(train_key))))
    ev = trace_dir / "risk_evidence"
    ev.mkdir()
    Image.fromarray((_grad_image(0) * 255).astype(np.uint8)).save(
        ev / "flagged_0001_12.png")
    (ev / "flagged_0001_12.json").write_text(json.dumps({
        "max_sim": 0.99, "top_key": str(train_key),
        "topk": [[str(train_key), 0.99]], "threshold": 0.9,
        "image": "flagged_0001_12.png", "request_id": 12,
        "prompt": "dup prompt", "seed": 7, "time": time.time()}))

    gallery = tmp_path / "gallery"
    assert risk_report.main([str(trace_dir),
                             "--gallery", str(gallery)]) == 0
    out = capsys.readouterr().out
    assert "dup prompt" in out and "FLAGGED" in out
    assert "5 generation(s) scored, 1 flagged" in out
    assert list(gallery.glob("gallery_rank*.png"))

    # per-prompt arithmetic: the dup prompt carries the flagged max
    records, _, _ = risk_report.TR.load_fleet(
        [trace_dir], risk_report.TR.load_schema())
    per = risk_report.per_prompt_breakdown(records)
    assert per["dup prompt"] == {"count": 1, "mean_sim": 0.99,
                                 "max_sim": 0.99, "flagged": 1}
    assert per["clean prompt"]["count"] == 2
    assert per["<train sample grid>"]["count"] == 2


@pytest.mark.fast
def test_risk_report_empty_trace(tmp_path, capsys):
    from tools import risk_report

    trace = tmp_path / "trace.jsonl"
    trace.write_text(json.dumps({
        "ph": "X", "name": "serve/request", "id": 1, "ts": 1e6, "dur": 10.0,
        "parent": None, "pid": 0, "tid": 1, "tname": "t", "args": {}}) + "\n")
    assert risk_report.main([str(tmp_path)]) == 0
    assert "nothing scored" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# fleet plumbing: lease field, supervisor health + /check routing
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_worker_lease_risk_roundtrip(tmp_path):
    from dcr_tpu.serve.fleet import (WorkerLease, fleet_paths, read_lease,
                                     write_lease)

    paths = fleet_paths(tmp_path).ensure()
    lease = WorkerLease(index=0, pid=123, port=8001, vae_scale=8,
                        lease_s=5.0, risk="ok")
    write_lease(paths, lease)
    assert read_lease(paths, 0).risk == "ok"
    # a pre-dcr-watch lease (no risk field) still parses, as "absent"
    doc = json.loads(paths.lease_file(0).read_text())
    del doc["risk"]
    paths.lease_file(0).write_text(json.dumps(doc))
    assert read_lease(paths, 0).risk == "absent"


def _stub_check_server(doc, status=200):
    """Minimal HTTP worker answering POST /check (stdlib, one thread)."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            length = int(self.headers.get("Content-Length", "0"))
            self.rfile.read(length)
            body = json.dumps(doc).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):
            pass

    httpd = HTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    return httpd, httpd.server_address[1]


def _stub_supervisor(tmp_path, index_path="some/embedding.npz"):
    from dcr_tpu.core.config import FleetConfig, ServeConfig
    from dcr_tpu.serve.supervisor import FleetSupervisor

    cfg = ServeConfig(
        fleet=FleetConfig(workers=1, dir=str(tmp_path / "fleet")),
        risk=RiskConfig(index_path=index_path))
    return FleetSupervisor(cfg)     # not .start()ed: no real spawns


@pytest.mark.fast
def test_supervisor_risk_health_transitions(tmp_path):
    from dcr_tpu.serve.fleet import WorkerLease
    from dcr_tpu.serve.supervisor import ALIVE

    sup = _stub_supervisor(tmp_path, index_path="")
    assert sup.risk_health() == "absent"      # nothing configured

    sup = _stub_supervisor(tmp_path / "b")
    assert sup.risk_health() == "loading"     # configured, no lease yet
    slot = sup._slots[0]
    slot.state = ALIVE
    slot.lease = WorkerLease(index=0, pid=1, port=1, vae_scale=8,
                             lease_s=5.0, risk="loading")
    assert sup.risk_health() == "loading"
    slot.lease.risk = "failed"
    assert sup.risk_health() == "failed"      # every reporter failed: visible
    slot.lease.risk = "ok"
    assert sup.risk_health() == "ok"
    assert sup.health_doc()["risk"] == "ok"
    assert sup.status()["workers"][0]["risk"] == "ok"
    sup.journal.close()


@pytest.mark.fast
def test_supervisor_check_routes_to_risk_ok_worker(tmp_path):
    from dcr_tpu.obs.copyrisk import RiskUnavailableError
    from dcr_tpu.serve.fleet import WorkerLease
    from dcr_tpu.serve.supervisor import ALIVE

    sup = _stub_supervisor(tmp_path)
    with pytest.raises(RiskUnavailableError) as exc:
        sup.check({"image_png_b64": "ignored"})
    assert exc.value.status == "loading"

    doc = {"max_sim": 0.97, "top_key": "train/x.png", "flagged": True,
           "topk": [["train/x.png", 0.97]], "threshold": 0.5}
    httpd, port = _stub_check_server(doc)
    try:
        slot = sup._slots[0]
        slot.state = ALIVE
        slot.lease = WorkerLease(index=0, pid=1, port=port, vae_scale=8,
                                 lease_s=5.0, risk="ok")
        got = sup.check({"image_png_b64": "ignored"})
        assert got == {**doc, "worker": 0}
        # a worker whose index failed must NOT be routed to
        slot.lease.risk = "failed"
        with pytest.raises(RiskUnavailableError) as exc:
            sup.check({"image_png_b64": "ignored"})
        assert exc.value.status == "failed"
    finally:
        httpd.shutdown()
        sup.journal.close()


@pytest.mark.fast
def test_supervisor_check_fails_over_dead_worker(tmp_path):
    """The crash race the fleet exists for: the first risk-ready worker
    dies between the lease read and the POST — /check must fail over to
    the next ready lease, not 500."""
    import socket

    from dcr_tpu.obs.copyrisk import RiskUnavailableError
    from dcr_tpu.serve.fleet import WorkerLease
    from dcr_tpu.serve.supervisor import ALIVE, _WorkerSlot

    def dead_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]     # closed: connections refused

    sup = _stub_supervisor(tmp_path)
    sup._slots.append(_WorkerSlot(1))
    doc = {"max_sim": 0.4, "top_key": "train/y.png", "flagged": False,
           "topk": [["train/y.png", 0.4]], "threshold": 0.5}
    httpd, live_port = _stub_check_server(doc)
    try:
        for slot, port in zip(sup._slots, (dead_port(), live_port)):
            slot.state = ALIVE
            slot.lease = WorkerLease(index=slot.index, pid=1, port=port,
                                     vae_scale=8, lease_s=5.0, risk="ok")
        got = sup.check({"image_png_b64": "ignored"})
        assert got == {**doc, "worker": 1}      # served by the survivor
        assert R.counters()["fleet_check_transport_errors"] == 1
        # both dead: typed 503, never an unhandled transport error
        sup._slots[1].lease.port = dead_port()
        httpd.shutdown()
        with pytest.raises(RiskUnavailableError):
            sup.check({"image_png_b64": "ignored"})
    finally:
        sup.journal.close()


# ---------------------------------------------------------------------------
# slow tier: real tiny stack
# ---------------------------------------------------------------------------

def _tiny_stack():
    from tests.test_serve import _tiny_stack as build

    return build()


def _risk_service(stack, risk=None, **cfg_kw):
    from dcr_tpu.core.config import ServeConfig
    from dcr_tpu.serve.worker import GenerationService

    kw = dict(resolution=16, num_inference_steps=2, sampler="ddim",
              max_batch=4, max_wait_ms=30.0, queue_depth=32, seed=0)
    kw.update(cfg_kw)
    cfg = ServeConfig(**kw)
    if risk is not None:
        cfg.risk = risk
    svc = GenerationService(cfg, stack)
    svc.start()
    return svc


def _build_index_from_images(tmp_path, images, image_size=32):
    """Save images as the 'train set', embed with the real pipeline."""
    from PIL import Image

    from dcr_tpu.core.config import SearchConfig
    from dcr_tpu.search.embed import embed_images

    train = tmp_path / "train"
    train.mkdir(exist_ok=True)
    for i, img in enumerate(images):
        Image.fromarray((np.clip(img, 0, 1) * 255).round().astype(
            np.uint8)).save(train / f"gen_{i}.png")
    return embed_images(SearchConfig(image_size=image_size, batch_size=4),
                        source=train)


@pytest.mark.slow
def test_serve_flags_reproduced_train_image_and_stays_bit_identical(
        tmp_path, cpu_devices):
    """The acceptance core, in-process: a request seeded to reproduce a
    train image is flagged (copy_risk.max_sim >= threshold, flagged counter
    bumps, evidence dump written) while a normal request is not, and images
    are bit-identical with scoring on vs off."""
    stack = _tiny_stack()
    plain = _risk_service(stack)
    img_train = plain.submit("a red square", seed=1).future.result(timeout=300)
    img_clean = plain.submit("a blue circle", seed=2).future.result(timeout=300)
    plain.stop(timeout=60)

    index_path = _build_index_from_images(tmp_path, [img_train])

    # threshold strictly between the reproduced image's ~1.0 and the
    # unrelated image's background similarity (random-init SSCD backgrounds
    # run high, so the margin is measured, not assumed)
    probe = CopyRiskIndex.load(
        RiskConfig(index_path=str(index_path), image_size=32), batch=4)
    sim_hit = probe.score_batch(img_train[None])[0].max_sim
    sim_miss = probe.score_batch(img_clean[None])[0].max_sim
    assert sim_hit > sim_miss + 0.005, (sim_hit, sim_miss)
    threshold = (sim_hit + sim_miss) / 2

    risk = RiskConfig(index_path=str(index_path), image_size=32,
                      threshold=threshold,
                      evidence_dir=str(tmp_path / "ev"), max_evidence=4)
    svc = _risk_service(stack, risk=risk)
    assert svc.wait_risk_ready(timeout=300) and svc.risk_status() == "ok"

    req_hit = svc.submit("a red square", seed=1)
    req_miss = svc.submit("a blue circle", seed=2)
    out_hit = req_hit.future.result(timeout=300)
    out_miss = req_miss.future.result(timeout=300)

    assert req_hit.risk["flagged"] is True
    assert req_hit.risk["max_sim"] >= threshold
    assert req_hit.risk["top_key"].endswith("gen_0.png")
    assert req_miss.risk["flagged"] is False
    # bit-identical with scoring on vs off
    assert np.array_equal(out_hit, img_train)
    assert np.array_equal(out_miss, img_clean)
    # telemetry: flagged counter, sim histogram, evidence dump
    counters = tracing.registry().counters("copy_risk/")
    assert counters["copy_risk/flagged_total"] == 1
    assert counters["copy_risk/scored_total"] >= 2
    evidence = sorted((tmp_path / "ev").glob("flagged_*.json"))
    assert len(evidence) == 1
    doc = json.loads(evidence[0].read_text())
    assert doc["request_id"] == req_hit.id and doc["prompt"] == "a red square"
    # /check: the train image itself is flagged; garbage body is a 400-class
    check = svc.check({"image_png_b64": _png_b64(img_train)})
    assert check["flagged"] is True and check["index_size"] == 1
    with pytest.raises(ValueError):
        svc.check({"image_png_b64": "!!!"})
    assert svc.health_doc()["risk"] == "ok"
    svc.stop(timeout=60)


@pytest.mark.slow
def test_failed_index_load_degrades_to_unscored_serving(tmp_path,
                                                        cpu_devices):
    """A bad index file must produce risk=failed + a counter — and a worker
    that still answers /generate (unscored), with /check a typed 503."""
    from dcr_tpu.obs.copyrisk import RiskUnavailableError

    bad = tmp_path / "embedding.npz"
    bad.write_bytes(b"garbage")
    stack = _tiny_stack()
    svc = _risk_service(stack, risk=RiskConfig(index_path=str(bad),
                                               image_size=32))
    assert svc.wait_risk_ready(timeout=120)
    assert svc.risk_status() == "failed"
    assert svc.health_doc()["risk"] == "failed"
    assert R.counters().get("copy_risk/index_load_failed", 0) == 1
    req = svc.submit("still serving", seed=3)
    assert req.future.result(timeout=300) is not None
    assert req.risk is None
    with pytest.raises(RiskUnavailableError) as exc:
        svc.check({"image_png_b64": "x"})
    assert exc.value.status == "failed"
    svc.stop(timeout=60)


@pytest.mark.slow
def test_trainer_sample_hook_emits_risk_gauges(tmp_path, cpu_devices):
    """score_sample_grid with a stub trainer: risk/* gauges through
    MetricWriter (jsonl + registry), risk/score span recorded."""
    from dcr_tpu.core.config import TrainConfig
    from dcr_tpu.core.metrics import MetricWriter
    from dcr_tpu.diffusion.sample_hook import score_sample_grid

    imgs = [np.clip(_grad_image(i), 0, 1) for i in range(2)]
    index_path = _build_index_from_images(tmp_path, [imgs[0]])

    cfg = TrainConfig(output_dir=str(tmp_path / "run"))
    cfg.risk = RiskConfig(index_path=str(index_path), image_size=32,
                          threshold=0.999)

    class StubTrainer:
        pass

    trainer = StubTrainer()
    trainer.cfg = cfg
    trainer.writer = MetricWriter(tmp_path / "logs", use_tensorboard=False)
    state = {}
    tracing.configure(tmp_path / "trace")
    score_sample_grid(trainer, state, 500, np.stack(imgs))
    # the index memoizes in hook state; a second call reuses it
    first_index = state["risk_index"]
    score_sample_grid(trainer, state, 1000, np.stack(imgs))
    assert state["risk_index"] is first_index is not None
    trainer.writer.close()

    metrics = [json.loads(l) for l in
               (tmp_path / "logs" / "metrics.jsonl").read_text().splitlines()]
    assert [row["step"] for row in metrics] == [500, 1000]
    row = metrics[0]
    assert row["risk/scored"] == 2 and row["risk/flagged"] == 1
    assert row["risk/max_sim"] >= 0.999
    # gauges mirrored into the registry (the /metrics surface)
    assert tracing.registry().snapshot()["gauges"]["risk/max_sim"] >= 0.999
    # spans: risk/score recorded with sims
    trace = (tmp_path / "trace" / "trace.jsonl").read_text()
    assert '"risk/score"' in trace


@pytest.mark.slow
def test_serve_http_e2e_risk_and_warm_restart_zero_compiles(tmp_path,
                                                            cpu_devices):
    """Full HTTP acceptance: a dcr-serve subprocess with a risk index flags
    the reproduced request over /generate, answers POST /check, exports
    dcr_copy_risk_* Prometheus series, dumps evidence — then a SECOND
    incarnation against the same warm cache reaches risk=ok and serves a
    scored request with ZERO XLA compiles (trace_report --max-compiles 0):
    scoring does not trip the recompile budget."""
    import signal
    import subprocess
    import sys

    from tests.test_serve import _export_tiny_ckpt, _free_port, _get, _serve_env
    from tools import trace_report

    ckpt = _export_tiny_ckpt(tmp_path)
    env, repo = _serve_env()
    # no XLA persistent cache in the subprocesses: with it active this
    # jaxlib emits unserializable executables, every warm entry degrades to
    # the export tier, and incarnation 2's compile-on-load would
    # (correctly) fail the --max-compiles 0 gate (same discipline as the
    # test_warmcache restart e2e)
    for k in list(env):
        if k.startswith("JAX_COMPILATION") or k.startswith("JAX_PERSISTENT"):
            env.pop(k)
    # ... and the CLIs' setup_compile_cache() would otherwise turn it back on
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"

    # train image + threshold from an offline probe of the same stack
    stack = _tiny_stack()
    plain = _risk_service(stack, max_batch=2)
    img_train = plain.submit("a red square", seed=1).future.result(timeout=300)
    img_clean = plain.submit("a blue circle", seed=2).future.result(timeout=300)
    plain.stop(timeout=60)
    index_path = _build_index_from_images(tmp_path, [img_train])
    probe = CopyRiskIndex.load(
        RiskConfig(index_path=str(index_path), image_size=32), batch=2)
    sim_hit = probe.score_batch(img_train[None])[0].max_sim
    sim_miss = probe.score_batch(img_clean[None])[0].max_sim
    threshold = (sim_hit + sim_miss) / 2

    warm_dir = tmp_path / "warmcache"

    def spawn(logdir):
        port = _free_port()
        argv = [sys.executable, "-m", "dcr_tpu.cli.serve",
                f"--model_path={ckpt}", f"--port={port}",
                "--resolution=16", "--num_inference_steps=2",
                "--sampler=ddim", "--max_batch=2", "--max_wait_ms=100",
                "--queue_depth=16", "--request_timeout_s=300", "--seed=0",
                f"--logdir={logdir}", f"--warm.dir={warm_dir}",
                f"--risk.index_path={index_path}", "--risk.image_size=32",
                f"--risk.threshold={threshold}"]
        proc = subprocess.Popen(argv, env=env, cwd=repo,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        deadline = time.monotonic() + 300
        while True:
            try:
                status, health = _get(port, "/healthz", timeout=2)
                if health["status"] == "ok" and health["risk"] == "ok":
                    break
            except OSError:
                pass
            if proc.poll() is not None or time.monotonic() > deadline:
                out = proc.stdout.read() if proc.stdout else ""
                raise AssertionError(
                    f"server not ready (rc={proc.poll()}): {out[-3000:]}")
            time.sleep(0.5)
        return proc, port

    def post(port, path, payload, timeout=300):
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def drain(proc):
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 83      # EXIT_PREEMPTED

    log1 = tmp_path / "log1"
    proc, port = spawn(log1)
    try:
        status, doc_hit = post(port, "/generate",
                               {"prompt": "a red square", "seed": 1})
        assert status == 200
        assert doc_hit["copy_risk"]["flagged"] is True
        assert doc_hit["copy_risk"]["max_sim"] >= threshold
        status, doc_miss = post(port, "/generate",
                                {"prompt": "a blue circle", "seed": 2})
        assert status == 200 and doc_miss["copy_risk"]["flagged"] is False
        # bit-identical to the risk-off in-process generation
        from PIL import Image

        with Image.open(io.BytesIO(
                base64.b64decode(doc_hit["image_png_b64"]))) as im:
            served = np.asarray(im, np.uint8)
        expected = (np.clip(img_train, 0, 1) * 255).round().astype(np.uint8)
        assert np.array_equal(served, expected)
        # /check over HTTP
        status, check = post(port, "/check",
                             {"image_png_b64": _png_b64(img_train)})
        assert status == 200 and check["flagged"] is True
        # prometheus export carries the dcr_copy_risk_* family
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics?format=prometheus",
                timeout=10) as resp:
            prom = resp.read().decode()
        assert "dcr_copy_risk_flagged_total 1" in prom
        assert "dcr_copy_risk_sim" in prom
        # evidence dump landed under the logdir
        assert list((log1 / "risk_evidence").glob("flagged_*.json"))
    finally:
        if proc.poll() is None:
            drain(proc)

    # incarnation 2: same warm dir, fresh logdir — risk-ready with ZERO
    # compiles, and a scored request still flags
    log2 = tmp_path / "log2"
    proc, port = spawn(log2)
    try:
        status, doc = post(port, "/generate",
                           {"prompt": "a red square", "seed": 1})
        assert status == 200 and doc["copy_risk"]["flagged"] is True
    finally:
        if proc.poll() is None:
            drain(proc)
    assert trace_report.main([str(log2), "--max-compiles", "0"]) == 0
