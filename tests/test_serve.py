"""dcr-serve subsystem tests.

Fast tier: pure-logic units for the batching policy, admission queue, LRU
embedding cache, latency tracker, tokenizer fingerprints, and the modelstyle
fallback warning — no models, no compiles.

Slow tier: the properties that need a real (tiny) compiled stack —
batch-composition independence of per-request PRNG keys, cache semantics
through the worker — plus the HTTP end-to-end: a real `dcr-serve` subprocess
answering concurrent requests from dynamically formed batches, then SIGTERM
draining in-flight work and exiting with EXIT_PREEMPTED (83).
"""

import json
import threading
import time

import numpy as np
import pytest

from dcr_tpu.serve.batcher import Batcher, should_flush
from dcr_tpu.serve.cache import EmbeddingCache, embedding_key, mitigation_tag
from dcr_tpu.serve.queue import (DrainingError, GenBucket, QueueFullError,
                                 Request, RequestQueue)


def _bucket(**kw) -> GenBucket:
    d = dict(resolution=16, steps=2, guidance=7.5, sampler="ddim",
             rand_noise_lam=0.0)
    d.update(kw)
    return GenBucket(**d)


def _req(prompt="p", seed=0, **bucket_kw) -> Request:
    return Request(prompt=prompt, seed=seed, bucket=_bucket(**bucket_kw))


# ---------------------------------------------------------------------------
# batching policy
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_should_flush_policy():
    # full group flushes regardless of age
    assert should_flush(4, 4, 0.0, 1.0)
    assert should_flush(5, 4, 0.0, 1.0)
    # partial group holds until the deadline...
    assert not should_flush(2, 4, 0.01, 1.0)
    # ...then flushes
    assert should_flush(2, 4, 1.0, 1.0)
    # empty never flushes, even during drain
    assert not should_flush(0, 4, 99.0, 1.0, draining=True)
    # drain flushes partials immediately
    assert should_flush(1, 4, 0.0, 1.0, draining=True)


@pytest.mark.fast
def test_batcher_flushes_full_batch_immediately():
    q = RequestQueue(maxsize=16)
    for i in range(4):
        q.submit(_req(seed=i))
    b = Batcher(max_batch=4, max_wait_s=60.0)     # deadline far away
    t0 = time.monotonic()
    batch = b.next_batch(q, stop=threading.Event())
    assert len(batch) == 4
    assert time.monotonic() - t0 < 5.0            # did not wait for the deadline
    assert q.empty()


@pytest.mark.fast
def test_batcher_max_wait_flushes_partial_batch():
    q = RequestQueue(maxsize=16)
    q.submit(_req(seed=1))
    q.submit(_req(seed=2))
    b = Batcher(max_batch=8, max_wait_s=0.08)
    t0 = time.monotonic()
    batch = b.next_batch(q, stop=threading.Event())
    elapsed = time.monotonic() - t0
    assert [r.seed for r in batch] == [1, 2]      # FIFO, partial
    assert elapsed >= 0.05                        # held for (about) the deadline


@pytest.mark.fast
def test_batcher_groups_by_bucket():
    """Requests from different buckets never share a batch; the leftover
    bucket group is preserved in FIFO order for the next pop."""
    q = RequestQueue(maxsize=16)
    q.submit(_req(seed=1, steps=2))
    q.submit(_req(seed=2, steps=4))               # different compiled program
    q.submit(_req(seed=3, steps=2))
    b = Batcher(max_batch=8, max_wait_s=0.02)
    first = b.next_batch(q, stop=threading.Event())
    assert [r.seed for r in first] == [1, 3]      # head bucket group only
    second = b.next_batch(q, stop=threading.Event())
    assert [r.seed for r in second] == [2]
    assert q.empty()


@pytest.mark.fast
def test_batcher_drain_flushes_without_deadline():
    q = RequestQueue(maxsize=16)
    q.submit(_req(seed=1))
    stop = threading.Event()
    stop.set()                                    # draining
    b = Batcher(max_batch=8, max_wait_s=60.0)
    t0 = time.monotonic()
    batch = b.next_batch(q, stop=stop)
    assert len(batch) == 1
    assert time.monotonic() - t0 < 5.0
    # queue empty + stop set -> the loop's termination signal
    assert b.next_batch(q, stop=stop) is None


# ---------------------------------------------------------------------------
# admission queue
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_queue_overload_typed_reject():
    q = RequestQueue(maxsize=2)
    q.submit(_req(seed=1))
    q.submit(_req(seed=2))
    with pytest.raises(QueueFullError):
        q.submit(_req(seed=3))
    assert q.depth() == 2                         # rejected request not queued


@pytest.mark.fast
def test_queue_draining_typed_reject():
    q = RequestQueue(maxsize=4)
    q.submit(_req(seed=1))
    q.close()
    with pytest.raises(DrainingError):
        q.submit(_req(seed=2))
    # pops continue after close — that is the drain contract
    assert [r.seed for r in q.take_group(4)] == [1]


# ---------------------------------------------------------------------------
# request validation (client-controlled params must never reach jit)
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_validate_bucket_rejects_bad_params():
    from dcr_tpu.serve.queue import InvalidRequestError
    from dcr_tpu.serve.worker import validate_bucket

    ok = _bucket()
    validate_bucket(ok, vae_scale=2)                  # tiny model: scale 2
    for bad in [_bucket(sampler="foo"),
                _bucket(steps=0), _bucket(steps=10_001),
                _bucket(resolution=0), _bucket(resolution=17),  # % 2 != 0
                _bucket(resolution=1 << 20),
                _bucket(guidance=-1.0), _bucket(guidance=1e6),
                _bucket(rand_noise_lam=-0.1)]:
        with pytest.raises(InvalidRequestError):
            validate_bucket(bad, vae_scale=2)
    # SD-scale: resolution must be a multiple of the VAE factor
    with pytest.raises(InvalidRequestError):
        validate_bucket(_bucket(resolution=260), vae_scale=8)
    validate_bucket(_bucket(resolution=256), vae_scale=8)


# ---------------------------------------------------------------------------
# embedding cache
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_cache_lru_eviction_and_recency():
    c = EmbeddingCache(capacity=2)
    k1, k2, k3 = (("fp", f"p{i}", "lam=0") for i in range(3))
    c.put(k1, np.ones(3)); c.put(k2, np.ones(3) * 2)
    assert c.get(k1) is not None                  # refreshes k1's recency
    c.put(k3, np.ones(3) * 3)                     # evicts k2 (LRU), not k1
    assert k2 not in c and k1 in c and k3 in c
    assert len(c) == 2


@pytest.mark.fast
def test_cache_key_binds_mitigation_and_tokenizer():
    b0 = _bucket(rand_noise_lam=0.0)
    b1 = _bucket(rand_noise_lam=0.1)
    assert mitigation_tag(b0) != mitigation_tag(b1)
    k_clean = embedding_key("fp", "a dog", mitigation_tag(b0))
    k_mit = embedding_key("fp", "a dog", mitigation_tag(b1))
    k_other_tok = embedding_key("fp2", "a dog", mitigation_tag(b0))
    assert len({k_clean, k_mit, k_other_tok}) == 3
    c = EmbeddingCache(capacity=8)
    c.put(k_clean, np.zeros(2))
    assert c.get(k_mit) is None                   # mitigation params miss
    assert c.get(k_other_tok) is None             # tokenizer swap misses
    assert c.stats() == {"hits": 0, "misses": 2, "size": 1, "capacity": 8,
                         "hit_rate": 0.0}


@pytest.mark.fast
def test_cache_capacity_zero_disables():
    c = EmbeddingCache(capacity=0)
    c.put(("a",), np.zeros(1))
    assert c.get(("a",)) is None and len(c) == 0


@pytest.mark.fast
def test_tokenizer_fingerprint():
    from dcr_tpu.data.tokenizer import HashTokenizer

    a = HashTokenizer(vocab_size=100, model_max_length=16)
    b = HashTokenizer(vocab_size=100, model_max_length=16)
    c = HashTokenizer(vocab_size=200, model_max_length=16)
    assert a.fingerprint() == b.fingerprint()     # same mapping, same id
    assert a.fingerprint() != c.fingerprint()     # vocab change changes id


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_latency_tracker_percentiles():
    from dcr_tpu.core.metrics import LatencyTracker

    t = LatencyTracker(window=100)
    assert t.percentiles() == {"p50": 0.0, "p99": 0.0}
    for v in range(1, 101):
        t.observe(v / 1000.0)
    p = t.percentiles((50, 99))
    assert 0.045 <= p["p50"] <= 0.055
    assert p["p99"] >= 0.09
    # window bounds memory: old observations fall out
    for _ in range(200):
        t.observe(1.0)
    assert t.percentiles()["p50"] == 1.0


@pytest.mark.fast
def test_serve_metrics_occupancy():
    from dcr_tpu.serve.worker import ServeMetrics

    m = ServeMetrics()
    m.note_batch(4, 4, ok=True)
    m.note_batch(1, 4, ok=True)
    s = m.snapshot()
    assert s["batch_occupancy_max"] == 1.0
    assert s["batch_occupancy_last"] == 0.25
    assert s["batch_occupancy_avg"] == pytest.approx(0.625)
    assert s["completed_total"] == 5


# ---------------------------------------------------------------------------
# modelstyle fallback warning (satellite: DCR006 no-silent-swallow)
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_infer_modelstyle_warns_on_missing_key(tmp_path, caplog):
    from dcr_tpu.cli.sample import infer_modelstyle

    (tmp_path / "config.json").write_text(json.dumps({"data": {}}))
    with caplog.at_level("WARNING", logger="dcr_tpu"):
        style = infer_modelstyle(str(tmp_path))
    assert style == "nolevel"
    [rec] = [r for r in caplog.records if "modelstyle_fallback" in r.getMessage()]
    msg = rec.getMessage()
    assert str(tmp_path / "config.json") in msg   # names the path
    assert "data.class_prompt" in msg             # names the missing key


@pytest.mark.fast
def test_infer_modelstyle_no_warning_when_key_present(tmp_path, caplog):
    from dcr_tpu.cli.sample import infer_modelstyle

    (tmp_path / "config.json").write_text(
        json.dumps({"data": {"class_prompt": "classlevel"}}))
    with caplog.at_level("WARNING", logger="dcr_tpu"):
        assert infer_modelstyle(str(tmp_path)) == "classlevel"
    assert not [r for r in caplog.records
                if "modelstyle_fallback" in r.getMessage()]


# ---------------------------------------------------------------------------
# compiled-stack properties (slow: build + compile tiny models)
# ---------------------------------------------------------------------------

def _tiny_stack():
    import jax

    from dcr_tpu.core.config import MeshConfig, ModelConfig, TrainConfig
    from dcr_tpu.data.tokenizer import HashTokenizer
    from dcr_tpu.diffusion.trainer import build_models
    from dcr_tpu.parallel import mesh as pmesh
    from dcr_tpu.sampling.pipeline import GenerationStack

    tiny = ModelConfig.tiny()
    tcfg = TrainConfig(mixed_precision="no")
    tcfg.model = tiny
    models, params = build_models(tcfg, jax.random.key(0))
    tok = HashTokenizer(vocab_size=tiny.text_vocab_size,
                        model_max_length=tiny.text_max_length)
    return GenerationStack(models, params, tiny,
                           tok, pmesh.make_mesh(MeshConfig()))


def _service(stack, **cfg_kw):
    from dcr_tpu.core.config import ServeConfig
    from dcr_tpu.serve.worker import GenerationService

    kw = dict(resolution=16, num_inference_steps=2, sampler="ddim",
              max_batch=2, max_wait_ms=30.0, queue_depth=16, seed=0)
    kw.update(cfg_kw)
    return GenerationService(ServeConfig(**kw), stack)


@pytest.mark.slow
def test_per_request_keys_independent_of_batch(cpu_devices):
    """The tentpole determinism contract: the same request produces the
    bit-identical image whether it runs alone (padded batch) or alongside
    other requests — per-request fold_in keys + one fixed compiled shape.
    rand_noise_lam > 0 so the vmapped per-request mitigation noise is
    exercised too (ddpm then covers per-step ancestral noise)."""
    stack = _tiny_stack()
    svc = _service(stack, rand_noise_lam=0.1)
    b = svc.default_bucket()

    alone = svc.execute([Request(prompt="a red square", seed=7, bucket=b)])
    mixed = svc.execute([Request(prompt="a red square", seed=7, bucket=b),
                         Request(prompt="a blue circle", seed=9, bucket=b)])
    assert np.array_equal(alone[0], mixed[0])
    # and the neighbors really are different images (keys independent)
    assert not np.array_equal(mixed[0], mixed[1])
    # same prompt, different seed -> different image
    reseeded = svc.execute([Request(prompt="a red square", seed=8, bucket=b)])
    assert not np.array_equal(alone[0], reseeded[0])


@pytest.mark.slow
def test_ddpm_per_request_ancestral_noise_independent(cpu_devices):
    """The stochastic sampler's per-step noise is also per-request (vmapped
    fold_in), so ancestral sampling keeps batch-composition independence."""
    stack = _tiny_stack()
    svc = _service(stack, sampler="ddpm")
    b = svc.default_bucket()
    alone = svc.execute([Request(prompt="x", seed=3, bucket=b)])
    mixed = svc.execute([Request(prompt="x", seed=3, bucket=b),
                         Request(prompt="y", seed=4, bucket=b)])
    assert np.array_equal(alone[0], mixed[0])


@pytest.mark.slow
def test_worker_cache_and_batching_end_to_end(cpu_devices):
    """Through the real worker thread: repeated prompts hit the embedding
    cache, batches form dynamically, metrics/status report it all."""
    stack = _tiny_stack()
    svc = _service(stack, max_batch=4, max_wait_ms=150.0)
    svc.start()
    try:
        reqs = [svc.submit("a red square", seed=i) for i in range(4)]
        imgs = [r.future.result(timeout=300) for r in reqs]
        assert all(i.shape == (16, 16, 3) for i in imgs)
        # 4 identical prompts: one text-tower run, three cache hits
        assert svc.cache.stats()["hits"] >= 3
        assert svc.cache.stats()["misses"] <= 2   # prompt + possible uncond
        status = svc.status()
        assert status["batch_occupancy_max"] > 0.25   # requests shared batches
        assert status["completed_total"] == 4
        assert status["latency_ms"]["p99"] > 0
        # per-request keys: same prompt+seed later reproduces bit-exactly,
        # now entirely from cache
        again = svc.submit("a red square", seed=2).future.result(timeout=300)
        assert np.array_equal(again, imgs[2])
        # resident-program budget: a second distinct bucket is rejected with
        # a typed error BEFORE any compile (max_compiled_buckets=1 here)
        from dcr_tpu.serve.queue import BucketLimitError, InvalidRequestError

        svc.cfg.max_compiled_buckets = 1
        other = svc.default_bucket()._replace(steps=3)
        with pytest.raises(BucketLimitError):
            svc.submit("x", bucket=other)
        # invalid bucket params are typed client errors, not compile crashes
        with pytest.raises(InvalidRequestError):
            svc.submit("x", bucket=svc.default_bucket()._replace(sampler="foo"))
        assert svc.status()["rejected_bucket_limit"] == 1
        assert svc.status()["rejected_invalid"] == 1
    finally:
        assert svc.stop(timeout=60)


# ---------------------------------------------------------------------------
# HTTP end-to-end: real dcr-serve subprocess (slow; own CI job)
# ---------------------------------------------------------------------------

def _export_tiny_ckpt(tmp_path):
    import jax

    from dcr_tpu.core.checkpoint import export_hf_layout
    from dcr_tpu.core.config import (DataConfig, ModelConfig, TrainConfig,
                                     to_dict)
    from dcr_tpu.diffusion.trainer import build_models

    cfg = TrainConfig()
    cfg.model = ModelConfig.tiny()
    cfg.data = DataConfig(class_prompt="nolevel")
    models, params = build_models(cfg, jax.random.key(0))
    export_hf_layout(
        tmp_path / "checkpoint", unet=params["unet"], vae=params["vae"],
        text_encoder=params["text"],
        scheduler_config={"num_train_timesteps": 1000,
                          "beta_schedule": "scaled_linear",
                          "beta_start": 0.00085, "beta_end": 0.012,
                          "prediction_type": "epsilon"},
        model_config=to_dict(cfg.model))
    return tmp_path / "checkpoint"


def _serve_env():
    import os
    from pathlib import Path

    repo = Path(__file__).parent.parent
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(repo) + os.pathsep + env.get("PYTHONPATH", ""),
        JAX_THREEFRY_PARTITIONABLE="1",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="1.0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    return env, repo


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post_generate(port, prompt, seed, timeout=300):
    import urllib.request

    body = json.dumps({"prompt": prompt, "seed": seed}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path, timeout=10):
    import urllib.request

    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


@pytest.mark.slow
def test_serve_e2e_http_batching_cache_and_sigterm_drain(tmp_path, cpu_devices):
    """Acceptance e2e: concurrent HTTP requests are answered from dynamically
    formed batches (occupancy > 1 request), repeated prompts hit the embedding
    cache, and SIGTERM drains in-flight work then exits EXIT_PREEMPTED."""
    import base64
    import io
    import signal
    import subprocess
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from PIL import Image

    from dcr_tpu.core.coordination import EXIT_PREEMPTED

    ckpt = _export_tiny_ckpt(tmp_path)
    env, repo = _serve_env()
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "dcr_tpu.cli.serve",
         f"--model_path={ckpt}", f"--port={port}",
         "--resolution=16", "--num_inference_steps=2", "--sampler=ddim",
         "--max_batch=4", "--max_wait_ms=300", "--queue_depth=32",
         "--request_timeout_s=300", "--seed=0"],
        env=env, cwd=repo, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        # wait for the port (jax import + stack load; no compile needed yet)
        deadline = time.monotonic() + 240
        while True:
            try:
                status, health = _get(port, "/healthz", timeout=2)
                assert status == 200 and health["status"] == "ok"
                break
            except (AssertionError, OSError):
                if proc.poll() is not None or time.monotonic() > deadline:
                    out = proc.stdout.read() if proc.stdout else ""
                    raise AssertionError(
                        f"server did not come up (rc={proc.poll()}): {out[-3000:]}")
                time.sleep(0.5)

        # wave 1: 8 concurrent requests, 2 unique prompts -> batches + cache
        prompts = ["a red square", "a blue circle"] * 4
        with ThreadPoolExecutor(max_workers=8) as ex:
            results = list(ex.map(
                lambda a: _post_generate(port, a[1], seed=a[0]),
                enumerate(prompts)))
        assert all(status == 200 for status, _ in results)
        png = base64.b64decode(results[0][1]["image_png_b64"])
        img = Image.open(io.BytesIO(png))
        assert img.size == (16, 16)

        _, metrics = _get(port, "/metrics")
        # dynamic batching proof: some batch held more than one request
        assert metrics["batch_occupancy_max"] * 4 > 1, metrics
        assert metrics["cache"]["hits"] >= 1, metrics
        assert metrics["completed_total"] == 8
        assert metrics["latency_ms"]["p99"] > 0

        # invalid bucket params over HTTP: typed 400, no compile, port alive
        import urllib.error
        import urllib.request

        body = json.dumps({"prompt": "x", "sampler": "bogus"}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400

        # wave 2: requests in flight when SIGTERM lands must still complete
        with ThreadPoolExecutor(max_workers=4) as ex:
            futs = [ex.submit(_post_generate, port, "a green dot", 100 + i)
                    for i in range(4)]
            time.sleep(0.4)                       # let them reach the queue
            proc.send_signal(signal.SIGTERM)
            drained = [f.result(timeout=300) for f in futs]
        assert all(status == 200 for status, _ in drained)

        rc = proc.wait(timeout=120)
        assert rc == EXIT_PREEMPTED, (rc, proc.stdout.read()[-3000:])
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
