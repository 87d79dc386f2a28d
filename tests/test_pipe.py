"""dcr-pipe tests: the fused→producer/denoiser split, the prefetch ring,
the persistent latent cache (verify/quarantine/recompute), the trainer
integration, and the trace_report Pipeline section."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from dcr_tpu.core.config import (DataConfig, MeshConfig, ModelConfig,
                                 OptimConfig, PipeConfig, TrainConfig,
                                 validate_train_config)

REPO = Path(__file__).resolve().parent.parent


def _cfg(**kw):
    cfg = TrainConfig(**kw)
    cfg.model = ModelConfig.tiny()
    cfg.mixed_precision = "no"
    cfg.optim.learning_rate = 1e-3
    cfg.optim.lr_scheduler = "constant"
    cfg.optim.lr_warmup_steps = 0
    return cfg


def _batch(key, cfg, bsz=8):
    import jax
    import jax.numpy as jnp

    px = 8 * 2 ** (len(cfg.model.vae_block_out_channels) - 1)
    return {
        "pixel_values": np.asarray(
            jax.random.uniform(key, (bsz, px, px, 3)) * 2 - 1),
        "input_ids": np.asarray(jax.random.randint(
            jax.random.fold_in(key, 1), (bsz, cfg.model.text_max_length), 0,
            cfg.model.text_vocab_size)),
        "index": np.arange(bsz, dtype=np.int64),
    }


@pytest.fixture(scope="module")
def setup():
    import jax

    from dcr_tpu.diffusion.trainer import build_models

    cfg = _cfg()
    models, params = build_models(cfg, jax.random.key(0))
    return cfg, models, params


def _make_state(cfg, models, params, mesh):
    import jax
    import jax.numpy as jnp

    from dcr_tpu.diffusion import train as T

    params = jax.tree.map(lambda x: jnp.array(np.asarray(x)), params)
    state = T.init_train_state(cfg, models, unet_params=params["unet"],
                               text_params=params["text"],
                               vae_params=params["vae"])
    return T.shard_train_state(state, mesh)


# ---------------------------------------------------------------------------
# stream ownership + state views
# ---------------------------------------------------------------------------

def test_rng_stream_ownership_partitions_the_fused_streams():
    """Every RNG stream the fused step draws has exactly one pipelined
    owner — a new stream must be assigned before it can ship."""
    from dcr_tpu.diffusion import encode_stage as E

    fused_streams = {"vae_sample", "noise", "timesteps", "emb_noise",
                     "mixup_beta", "mixup_perm"}
    producer = set(E.PRODUCER_STREAMS)
    denoiser = set(E.DENOISER_STREAMS)
    assert producer | denoiser == fused_streams
    assert not (producer & denoiser)


def test_split_merge_roundtrip():
    import jax

    from dcr_tpu.diffusion import encode_stage as E
    from dcr_tpu.diffusion.trainer import abstract_train_state

    for tte in (False, True):
        cfg = _cfg(train_text_encoder=tte)
        state = abstract_train_state(cfg)
        hot, frozen = E.split_state(state, tte)
        if tte:
            assert hot.text_params is not None and frozen["text"] is None
        else:
            assert hot.text_params is None and frozen["text"] is not None
        merged = E.merge_state(hot, frozen, tte)
        assert jax.tree.structure(merged) == jax.tree.structure(state)


# ---------------------------------------------------------------------------
# the split's numerics
# ---------------------------------------------------------------------------

def test_pipelined_matches_fused_loss_and_params(setup, cpu_devices):
    """encode∘denoise == fused within float-fusion tolerance, with the SAME
    q-sample draws (keys derive from the same streams at the same step)."""
    import jax

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import encode_stage as E
    from dcr_tpu.diffusion import train as T
    from dcr_tpu.parallel import mesh as pmesh

    cfg, models, params = setup
    mesh = pmesh.make_mesh(MeshConfig())
    key = rngmod.root_key(0)
    raw = _batch(jax.random.key(1), cfg)

    fused = T.make_train_step(cfg, models, mesh)
    s1 = _make_state(cfg, models, params, mesh)
    fused_losses = []
    for _ in range(3):
        s1, m = fused(s1, pmesh.shard_batch(mesh, dict(raw)), key)
        fused_losses.append(float(m["loss"]))

    encode_fn = E.make_encode_stage(cfg, models, mesh)
    denoise_fn = E.make_denoise_step(cfg, models, mesh)
    s2 = _make_state(cfg, models, params, mesh)
    hot, frozen = E.split_state(s2, cfg.train_text_encoder)
    pipe_losses = []
    for i in range(3):
        enc = encode_fn(frozen, pmesh.shard_batch(mesh, dict(raw)), key,
                        np.uint32(i))
        hot, m = denoise_fn(hot, enc, key)
        pipe_losses.append(float(m["loss"]))

    np.testing.assert_allclose(pipe_losses, fused_losses, rtol=1e-4)
    merged = E.merge_state(hot, frozen, cfg.train_text_encoder)
    # adam's grad normalization turns float-fusion noise into O(lr)-scale
    # update flips on near-zero-grad elements, so relative tolerance is the
    # wrong gate post-optimizer — bound the ABSOLUTE drift instead (3 steps
    # at lr 1e-3 bounds honest drift well under 1e-4; observed ~2e-5)
    for a, b in zip(jax.tree.leaves(jax.device_get(s1.unet_params)),
                    jax.tree.leaves(jax.device_get(merged.unet_params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-4)


def test_pipelined_with_mitigations_and_trained_text_encoder(setup,
                                                             cpu_devices):
    """Embedding mitigations (denoiser-owned streams) and the
    train_text_encoder passthrough both reproduce the fused numerics."""
    import jax

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import encode_stage as E
    from dcr_tpu.diffusion import train as T
    from dcr_tpu.parallel import mesh as pmesh

    _, models, params = setup
    key = rngmod.root_key(0)
    for kw in ({"rand_noise_lam": 0.5}, {"mixup_noise_lam": 0.3},
               {"train_text_encoder": True}):
        cfg = _cfg(**kw)
        cfg.model = ModelConfig.tiny()
        mesh = pmesh.make_mesh(MeshConfig())
        raw = _batch(jax.random.key(1), cfg)
        s1 = _make_state(cfg, models, params, mesh)
        _, m1 = T.make_train_step(cfg, models, mesh)(
            s1, pmesh.shard_batch(mesh, dict(raw)), key)
        s2 = _make_state(cfg, models, params, mesh)
        hot, frozen = E.split_state(s2, cfg.train_text_encoder)
        enc = E.make_encode_stage(cfg, models, mesh)(
            frozen, pmesh.shard_batch(mesh, dict(raw)), key, np.uint32(0))
        if cfg.train_text_encoder:
            assert "input_ids" in enc and "ctx" not in enc
        _, m2 = E.make_denoise_step(cfg, models, mesh)(hot, enc, key)
        np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]),
                                   rtol=1e-4)


def test_cache_stage_reconstructs_live_latents(setup, cpu_devices):
    """moments + vae_sample draw == the live encode's posterior sample
    (same stream, same step key) — one cache serves any step/epoch."""
    import jax

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import encode_stage as E
    from dcr_tpu.parallel import mesh as pmesh

    cfg, models, params = setup
    mesh = pmesh.make_mesh(MeshConfig())
    key = rngmod.root_key(0)
    raw = _batch(jax.random.key(1), cfg)
    state = _make_state(cfg, models, params, mesh)
    _, frozen = E.split_state(state, cfg.train_text_encoder)
    live = E.make_encode_stage(cfg, models, mesh)
    mom = E.make_encode_stage(cfg, models, mesh, emit="moments")(
        frozen, pmesh.shard_batch(mesh, dict(raw)), key, np.uint32(0))
    cache_fn = E.make_cache_stage(cfg, models, mesh)
    for step in (0, 7):
        got = cache_fn({"mean": mom["mean"], "std": mom["std"],
                        "ctx": mom["ctx"], "index": mom["index"]},
                       key, np.uint32(step))
        want = live(frozen, pmesh.shard_batch(mesh, dict(raw)), key,
                    np.uint32(step))
        np.testing.assert_allclose(np.asarray(got["latents"]),
                                   np.asarray(want["latents"]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(got["ctx"]),
                                   np.asarray(want["ctx"]), atol=1e-6)


# ---------------------------------------------------------------------------
# the producer ring (no jax needed — stub encode)
# ---------------------------------------------------------------------------

def _ring(batches, encode, depth=2, start=0):
    from dcr_tpu.diffusion.encode_stage import EncodeProducer

    return EncodeProducer(iter(batches), encode, depth=depth,
                          start_step=start)


def test_producer_ring_orders_and_terminates():
    seen = []

    def encode(batch, step):
        seen.append(step)
        return {"v": batch, "step": step}

    p = _ring(list(range(5)), encode, depth=2, start=3)
    try:
        for i in range(5):
            enc = p.get(3 + i)
            assert enc == {"v": i, "step": 3 + i}
        assert p.get(8) is None          # end-of-epoch sentinel
        assert seen == [3, 4, 5, 6, 7]
    finally:
        p.stop()


def test_producer_ring_bounded_depth():
    """The producer may run at most `depth` batches ahead of the consumer
    (plus the one blocked in put) — the ring is a real backpressure bound."""
    encoded = []

    def encode(batch, step):
        encoded.append(step)
        return step

    p = _ring(list(range(32)), encode, depth=2)
    try:
        time.sleep(0.5)                  # let the producer run ahead
        assert len(encoded) <= 3         # depth 2 in ring + 1 blocked in put
        for i in range(32):
            assert p.get(i) == i
    finally:
        p.stop()


def test_producer_ring_propagates_errors():
    def encode(batch, step):
        if step == 2:
            raise RuntimeError("encoder exploded")
        return step

    p = _ring(list(range(5)), encode)
    try:
        assert p.get(0) == 0
        assert p.get(1) == 1
        with pytest.raises(RuntimeError, match="encoder exploded"):
            p.get(2)
    finally:
        p.stop()


def test_producer_ring_stop_mid_stream_and_gauge():
    from dcr_tpu.core import tracing

    p = _ring(list(range(100)), lambda b, s: s, depth=3)
    assert p.get(0) == 0
    p.stop()
    p.stop()                             # idempotent
    assert not p._thread.is_alive()
    # the gauge exists and holds a small ring occupancy
    g = tracing.registry().gauge("data/queue_depth")
    assert 0 <= g.value <= 3


# ---------------------------------------------------------------------------
# the latent cache
# ---------------------------------------------------------------------------

def _write_cache(tmp_path, n=10, shard_size=4, fp=None):
    from dcr_tpu.data import latent_cache as LC

    fp = fp or {"version": 1, "test": "roundtrip"}
    w = LC.LatentCacheWriter(tmp_path, fp, shard_size=shard_size)
    rng = np.random.default_rng(0)
    mean = rng.standard_normal((n, 2, 2, 4)).astype(np.float32)
    std = np.abs(rng.standard_normal((n, 2, 2, 4))).astype(np.float32)
    ctx = rng.standard_normal((n, 3, 8)).astype(np.float32)
    idx = np.arange(100, 100 + n, dtype=np.int64)
    w.add(idx, mean, std, ctx)
    w.finalize()
    return fp, idx, mean, std, ctx


def test_latent_cache_roundtrip_multi_shard(tmp_path):
    from dcr_tpu.data import latent_cache as LC

    fp, idx, mean, std, ctx = _write_cache(tmp_path, n=10, shard_size=4)
    assert len(list(tmp_path.glob("shard_*.npz"))) == 3  # 4+4+2
    r = LC.LatentCacheReader(tmp_path, fp)
    assert r.coverage() == (10, 10)
    got = r.lookup(np.asarray([103, 100, 109]))
    assert got is not None
    np.testing.assert_array_equal(got[0], mean[[3, 0, 9]])
    np.testing.assert_array_equal(got[1], std[[3, 0, 9]])
    np.testing.assert_array_equal(got[2], ctx[[3, 0, 9]])
    assert r.lookup(np.asarray([100, 555])) is None  # any miss -> None


def test_latent_cache_fingerprint_mismatch(tmp_path):
    from dcr_tpu.data import latent_cache as LC

    fp, *_ = _write_cache(tmp_path)
    with pytest.raises(LC.LatentCacheError, match="different"):
        LC.LatentCacheReader(tmp_path, dict(fp, test="other"))


def test_latent_cache_corrupt_shard_quarantined(tmp_path):
    from dcr_tpu.data import latent_cache as LC

    fp, idx, mean, *_ = _write_cache(tmp_path, n=10, shard_size=4)
    shard = tmp_path / "shard_00001.npz"       # rows 4..7
    blob = bytearray(shard.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    shard.write_bytes(bytes(blob))
    r = LC.LatentCacheReader(tmp_path, fp)
    # the damaged shard is out of the key space, its indices are misses
    assert not shard.exists()
    assert any("quarantined" in p.name for p in tmp_path.iterdir())
    assert r.lookup(np.asarray([104])) is None
    got = r.lookup(np.asarray([100, 109]))     # other shards still serve
    assert got is not None
    np.testing.assert_array_equal(got[0], mean[[0, 9]])
    assert r.coverage()[0] == 6


def test_latent_cache_corrupt_fault_kind(tmp_path):
    """latent_cache_corrupt@load=N drives the verify/quarantine/recompute
    path deterministically, mirroring warmcache's cache_corrupt."""
    from dcr_tpu.core import resilience as R
    from dcr_tpu.data import latent_cache as LC
    from dcr_tpu.utils import faults

    fp, *_ = _write_cache(tmp_path, n=10, shard_size=4)
    before = R.counters().get("latentcache/shard_corrupt", 0)
    faults.install("latent_cache_corrupt@load=0")
    try:
        r = LC.LatentCacheReader(tmp_path, fp)
    finally:
        faults.clear()
    # the first shard load was poisoned in memory -> quarantined on disk
    assert not (tmp_path / "shard_00000.npz").exists()
    assert r.lookup(np.asarray([100])) is None
    assert r.coverage()[0] == 6
    after = R.counters().get("latentcache/shard_corrupt", 0)
    assert after == before + 1


def test_latent_cache_manifest_corrupt(tmp_path):
    from dcr_tpu.data import latent_cache as LC

    _write_cache(tmp_path)
    (tmp_path / "manifest.json").write_text("{not json")
    with pytest.raises(LC.LatentCacheError, match="corrupt"):
        LC.LatentCacheReader(tmp_path)
    assert any("quarantined" in p.name for p in tmp_path.iterdir())


def test_latent_cache_missing_manifest(tmp_path):
    from dcr_tpu.data import latent_cache as LC

    with pytest.raises(LC.LatentCacheError, match="precompute"):
        LC.LatentCacheReader(tmp_path / "nope")


def test_cached_encode_falls_back_on_miss(tmp_path, monkeypatch):
    """The recompute path: any uncached index re-encodes the batch live."""
    from dcr_tpu.core import resilience as R
    from dcr_tpu.data import latent_cache as LC
    from dcr_tpu.diffusion import encode_stage as E
    from dcr_tpu.parallel import mesh as pmesh_mod

    fp, *_ = _write_cache(tmp_path, n=4, shard_size=4)
    r = LC.LatentCacheReader(tmp_path, fp)
    calls = {"cache": 0, "live": 0}

    def cache_fn(moments, key, step):
        calls["cache"] += 1
        return {"from": "cache"}

    def fallback(batch, step):
        calls["live"] += 1
        return {"from": "live"}

    monkeypatch.setattr(pmesh_mod, "shard_batch", lambda mesh, d: d)
    enc = E.cached_encode(cache_fn, r, None, None, fallback)
    before = R.counters().get("latentcache/batch_recompute", 0)
    out = enc({"index": np.asarray([100, 101])}, 0)
    assert out == {"from": "cache"}
    out = enc({"index": np.asarray([100, 999])}, 1)
    assert out == {"from": "live"}
    after = R.counters().get("latentcache/batch_recompute", 0)
    assert after == before + 1
    assert calls == {"cache": 1, "live": 1}


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_pipe_config_validation():
    cfg = _cfg()
    cfg.pipe = PipeConfig(depth=0)
    with pytest.raises(ValueError, match="depth"):
        validate_train_config(cfg)
    cfg = _cfg(train_text_encoder=True)
    cfg.pipe = PipeConfig(latent_cache="/tmp/x")
    with pytest.raises(ValueError, match="train_text_encoder"):
        validate_train_config(cfg)
    cfg = _cfg()
    cfg.pipe = PipeConfig(latent_cache="/tmp/x")
    cfg.data.trainspecial = "allcaps"
    cfg.data.class_prompt = "instancelevel_blip"
    with pytest.raises(ValueError, match="trainspecial"):
        validate_train_config(cfg)
    # per-occurrence realizations the frozen cache cannot serve
    cfg = _cfg()
    cfg.pipe = PipeConfig(latent_cache="/tmp/x")
    cfg.data.random_flip = False
    cfg.data.duplication = "dup_image"
    with pytest.raises(ValueError, match="dup_image"):
        validate_train_config(cfg)
    cfg = _cfg()
    cfg.pipe = PipeConfig(latent_cache="/tmp/x")
    assert cfg.data.random_flip            # the default
    with pytest.raises(ValueError, match="random_flip"):
        validate_train_config(cfg)
    cfg = _cfg()
    cfg.pipe = PipeConfig(latent_cache="/tmp/x")
    cfg.data.random_flip = False
    cfg.data.center_crop = False
    with pytest.raises(ValueError, match="center_crop"):
        validate_train_config(cfg)
    cfg = _cfg()
    cfg.pipe = PipeConfig(latent_cache="/tmp/x")
    cfg.data.random_flip = False
    validate_train_config(cfg)           # valid cache config
    cfg = _cfg()
    cfg.pipe = PipeConfig(enabled=True, depth=3)
    validate_train_config(cfg)           # valid (live producer: any regime)


# ---------------------------------------------------------------------------
# trace_report Pipeline section
# ---------------------------------------------------------------------------

def _rec(name, ts, dur, ph="X", **args):
    rec = {"ph": ph, "name": name, "id": 1, "ts": float(ts), "pid": 0,
           "tid": 1, "tname": "t", "args": args, "_proc": 0, "_plabel": "p"}
    if ph == "X":
        rec["dur"] = float(dur)
        rec["parent"] = None
    return rec


def test_trace_report_pipeline_section():
    import tools.trace_report as tr

    # encoder spans overlap half of each denoise span; two 1 ms waits
    records = [
        _rec("train/encode", 0, 1000),
        _rec("train/encode", 2000, 1000),
        _rec("train/step", 500, 1000),
        _rec("train/step", 2500, 1000),
        _rec("train/encode_wait", 400, 1000),
        _rec("train/encode_wait", 2400, 1000),
        _rec("train/data_wait", 0, 500),
    ]
    pipe = tr.pipeline_summary(records)
    assert pipe["encoded_batches"] == 2
    assert pipe["encode_total_ms"] == 2.0
    assert pipe["denoise_total_ms"] == 2.0
    assert pipe["encode_wait_total_ms"] == 2.0
    assert pipe["bubble_pct"] == 50.0
    assert pipe["overlap_ms"] == 1.0     # half of each encode span
    assert pipe["overlap_pct"] == 50.0
    assert pipe["data_wait_total_ms"] == 0.5
    # fused-only traces keep their old shape
    assert tr.pipeline_summary([_rec("train/step", 0, 1000)]) is None
    # and the text renderer mentions the section
    summary = tr.summarize(records, {})
    text = tr.render_text(summary, [Path(".")])
    assert "pipeline:" in text and "bubble 50.0%" in text


def test_bench_pipe_schema():
    import tools.bench_pipe as bp

    doc = {
        "cores": 1, "steps": 10, "min_speedup": 1.25, "batch_sizes": [4],
        "legs": {"bs4": {
            "fused": {"steps_per_sec": 5.0, "step_ms": 200.0,
                      "hbm_peak_bytes": None},
            "pipelined": {"steps_per_sec": 5.5, "step_ms": 182.0,
                          "speedup": 1.1, "hbm_peak_bytes": 123456},
            "latent_cache": {"steps_per_sec": 7.0, "step_ms": 143.0,
                             "speedup": 1.4, "hbm_peak_bytes": None},
        }},
        "gate": {"batch_size": 4, "speedup": 1.4, "mode": "latent_cache",
                 "passed": True},
    }
    assert bp.validate_result(doc) == []
    bad = json.loads(json.dumps(doc))
    del bad["gate"]["passed"]
    bad["legs"]["bs4"]["pipelined"].pop("speedup")
    assert len(bp.validate_result(bad)) == 2
    # dcr-hbm: hbm_peak_bytes must be present (null on stats-less backends)
    # and integral where present
    missing = json.loads(json.dumps(doc))
    missing["legs"]["bs4"]["fused"].pop("hbm_peak_bytes")
    wrong = json.loads(json.dumps(doc))
    wrong["legs"]["bs4"]["fused"]["hbm_peak_bytes"] = "big"
    assert any("hbm_peak_bytes" in p for p in bp.validate_result(missing))
    assert any("hbm_peak_bytes" in p for p in bp.validate_result(wrong))


def test_banked_bench_pipe_artifact_is_valid_and_gated():
    """The checked-in BENCH_PIPE.json must parse, validate, and pass its
    own gate — a regressed re-bank cannot merge silently."""
    import tools.bench_pipe as bp

    path = REPO / "BENCH_PIPE.json"
    doc = json.loads(path.read_text())
    assert bp.validate_result(doc) == []
    assert doc["gate"]["passed"] is True
    assert doc["gate"]["speedup"] >= doc["min_speedup"] >= 1.25


# ---------------------------------------------------------------------------
# trainer integration (slow: real epochs through the Trainer)
# ---------------------------------------------------------------------------

@pytest.fixture()
def train_setup(tmp_path):
    from PIL import Image

    rng = np.random.default_rng(0)
    for cls in ["c0", "c1"]:
        d = tmp_path / "data" / cls
        d.mkdir(parents=True)
        for i in range(8):
            Image.fromarray(
                rng.integers(0, 255, (20, 20, 3), np.uint8)).save(
                    d / f"{i}.png")

    def make(out, **pipe):
        return TrainConfig(
            output_dir=str(tmp_path / out), seed=0, train_batch_size=2,
            max_train_steps=6, num_train_epochs=10, mixed_precision="no",
            save_steps=1000, modelsavesteps=4, log_every=2,
            model=ModelConfig.tiny(),
            data=DataConfig(train_data_dir=str(tmp_path / "data"),
                            resolution=16, class_prompt="nolevel",
                            num_workers=2, seed=0, random_flip=False),
            optim=OptimConfig(learning_rate=1e-4, lr_scheduler="constant",
                              lr_warmup_steps=0),
            pipe=PipeConfig(**pipe),
        )

    return make, tmp_path


@pytest.mark.slow
def test_trainer_pipelined_end_to_end(train_setup):
    """Pipelined Trainer run: same loss curve as fused within tolerance,
    checkpoints + resume + pipeline spans all work."""
    import jax

    from dcr_tpu.diffusion.trainer import Trainer

    make, tmp_path = train_setup
    m_fused = Trainer(make("run_fused")).train()
    t = Trainer(make("run_pipe", enabled=True, depth=2))
    assert t.pipelined
    m_pipe = t.train()
    assert abs(m_pipe["loss"] - m_fused["loss"]) <= \
        1e-3 * max(abs(m_fused["loss"]), 1e-9)
    assert t.ckpt.all_steps() == [4, 6]
    # the trace carries the pipeline spans
    names = {json.loads(l)["name"] for l in
             (tmp_path / "run_pipe" / "trace.jsonl").read_text().splitlines()}
    assert {"train/encode", "train/encode_wait", "train/step"} <= names
    # resume continues pipelined
    cfg2 = make("run_pipe", enabled=True)
    cfg2.max_train_steps = 8
    t2 = Trainer(cfg2)
    assert t2.maybe_resume() == 6
    t2.train()
    assert 8 in t2.ckpt.all_steps()
    assert int(jax.device_get(t2.state.step)) == 8


@pytest.mark.slow
def test_pipelined_nan_rollback(train_setup):
    """NaN rollback under the producer/consumer split: restore the last
    checkpoint, keep the ORIGINAL frozen buffers (the producer pins them),
    fast-forward past the bad window, and finish the run."""
    from dcr_tpu.diffusion.trainer import Trainer
    from dcr_tpu.utils import faults

    make, tmp_path = train_setup
    cfg = make("run_nanpipe", enabled=True)
    cfg.log_every = 1
    cfg.modelsavesteps = 2
    cfg.fault.max_rollbacks = 1
    faults.install("nan_loss@step=3")
    try:
        t = Trainer(cfg)
        frozen_before = t.state.vae_params
        m = t.train()
    finally:
        faults.clear()
    assert np.isfinite(m["loss"])
    assert t._rollbacks == 1
    assert "nan_rollback" in \
        (tmp_path / "run_nanpipe" / "quarantine.jsonl").read_text()
    # the run finished all 6 micro-steps despite the rollback
    import jax

    assert int(jax.device_get(t.state.step)) == 6
    # the frozen view still references the ORIGINAL buffers — the restore's
    # duplicate frozen copy was dropped, not kept alive alongside
    assert t._frozen["vae"] is frozen_before


@pytest.mark.parametrize("case", ["named_ahead", "another_batch_asked",
                                  "write_fails"])
def test_precompute_job_keeps_the_device_ahead(train_setup, case):
    """`PrecomputeJob.encode_batch(number, then)`: the batch named as next is
    on the device before this one is waited for and the rows go to the disk
    on the writer's thread, and the cache is byte for byte what the calls one
    after the other write; a caller that asks for another batch than it
    named gets that batch; a write that fails is raised by the next call."""
    from dcr_tpu.cli.precompute import PrecomputeJob

    make, tmp_path = train_setup

    def job(name):
        cfg = make(name)
        cfg.pipe.latent_cache = str(tmp_path / name)
        cfg.pipe.cache_shard_size = 4
        cfg.train_batch_size = 1            # a row a (virtual) device
        return PrecomputeJob(cfg)

    ahead = job("ahead")
    batches = len(ahead)
    assert batches == 2 and ahead.batch_size == 8
    if case == "named_ahead":
        for n in range(batches):
            ahead.encode_batch(n, n + 1 if n + 1 < batches else None)
            assert (ahead._ahead or [None])[0] == (n + 1 if n + 1 < batches else None)
        ahead.drain()
        assert len(list((tmp_path / "ahead").glob("shard_*.npz"))) == 4
        serial = job("serial")
        for n in range(batches):
            serial.encode_batch(n)
        shards = lambda j: json.loads(j.finalize().read_text())["shards"]  # noqa: E731
        assert shards(ahead) == shards(serial)
    elif case == "another_batch_asked":
        ahead.encode_batch(0, 1)
        got = ahead.encode_batch(0)
        want = job("serial").encode_batch(0)
        assert got["index"].tolist() == want["index"].tolist()
        for name in ("mean", "std", "ctx"):
            np.testing.assert_array_equal(got[name], want[name])
        ahead.close()
    else:
        def full(*args):
            raise OSError("no space left on device")

        ahead.writer.add = full
        ahead.encode_batch(0, 1)
        with pytest.raises(OSError, match="no space"):
            ahead.encode_batch(1)


@pytest.mark.slow
def test_precompute_and_cache_fed_training(train_setup):
    """dcr-precompute-latents -> Trainer(pipe.latent_cache): encoders never
    run in the hot path, loss matches fused within tolerance, and a corrupt
    shard degrades to live recompute instead of failing the run."""
    from dcr_tpu.cli.precompute import precompute
    from dcr_tpu.diffusion.trainer import Trainer

    make, tmp_path = train_setup
    cache = tmp_path / "lcache"
    cfgp = make("run_pre")
    cfgp.pipe.latent_cache = str(cache)
    # small shards so corrupting ONE leaves others serving (losing every
    # shard is correctly a typed error, not a silent recompute-everything)
    cfgp.pipe.cache_shard_size = 4
    summary = precompute(cfgp)
    assert len(list(cache.glob("shard_*.npz"))) == 4
    assert summary["indices"] == 16
    m_fused = Trainer(make("run_fused2")).train()
    t = Trainer(make("run_cache", latent_cache=str(cache)))
    assert t.pipelined
    m_cache = t.train()
    assert abs(m_cache["loss"] - m_fused["loss"]) <= \
        1e-3 * max(abs(m_fused["loss"]), 1e-9)
    # fingerprint mismatch is a loud typed failure, not silent retraining
    from dcr_tpu.data.latent_cache import LatentCacheError

    bad = make("run_badcache", latent_cache=str(cache))
    bad.seed = 1                          # different frozen params
    with pytest.raises(LatentCacheError, match="different"):
        Trainer(bad).train()
    # corrupt one shard: training still completes (recompute path)
    shard = next(cache.glob("shard_*.npz"))
    blob = bytearray(shard.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    shard.write_bytes(bytes(blob))
    t3 = Trainer(make("run_cache2", latent_cache=str(cache)))
    m3 = t3.train()
    assert np.isfinite(m3["loss"])
    assert any("quarantined" in p.name for p in cache.iterdir())
