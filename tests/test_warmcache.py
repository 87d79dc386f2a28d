"""dcr-warm tests: persistent executable cache + warm-start readiness.

Fast tier — cache-poisoning robustness on trivial programs (no model
compiles): truncated entries, bit-flipped payloads, wrong-fingerprint
entries, same-key garbage payloads, the deterministic ``cache_corrupt``
fault kind, concurrent writers racing on one cache directory, the
``jax.export`` fallback tier, and the warm-start manifest. Every poisoning
case must recompile successfully, bump a ``warmcache/*`` counter, and
quarantine the bad entry — no crash, no wrong program.

Slow tier — the crash-to-ready acceptance paths: a trainer-shaped train
step (donated state + PRNG key + loader-batch pytree) round-trips the cache
bit-identically; a real ``dcr-serve`` subprocess restarts against a
populated cache with /healthz readiness gating and ZERO compiles
(trace_report-verified); a fleet worker SIGKILLed with a populated cache
respawns to ready with zero recompile spans and bit-identical responses.
"""

import json
import os
import signal
import struct
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing, warmcache
from dcr_tpu.utils import faults


def _toy_fn():
    return jax.jit(lambda x, y: x * 2.0 + y)


def _toy_args():
    return (jnp.ones((4,), jnp.float32), jnp.full((4,), 3.0, jnp.float32))


def _aot(cache, k=1, surface="test/toy"):
    return warmcache.aot_compile(surface, _toy_fn(), _toy_args(),
                                 static_config={"k": k}, cache=cache)


def _counters():
    return {k: v for k, v in R.counters().items() if k.startswith("warmcache")}


def _parse_entry(blob: bytes):
    head = len(warmcache.MAGIC) + warmcache._LEN.size
    (mlen,) = warmcache._LEN.unpack(blob[len(warmcache.MAGIC):head])
    meta = json.loads(blob[head:head + mlen].decode())
    return meta, blob[head + mlen:]


def _build_entry(meta: dict, payload: bytes) -> bytes:
    mb = json.dumps(meta, sort_keys=True).encode()
    return warmcache.MAGIC + warmcache._LEN.pack(len(mb)) + mb + payload


# ---------------------------------------------------------------------------
# round-trip + keying
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_roundtrip_store_then_hit(tmp_path):
    cache = warmcache.WarmCache(tmp_path)
    r1 = _aot(cache)
    assert r1.source == "compiled" and r1.entry is not None and r1.entry.exists()
    out1 = np.asarray(r1.fn(*_toy_args()))
    # a fresh cache instance (= a new process incarnation) warm-loads
    r2 = _aot(warmcache.WarmCache(tmp_path))
    assert r2.source == "cache" and r2.key == r1.key
    assert np.array_equal(out1, np.asarray(r2.fn(*_toy_args())))


@pytest.mark.fast
def test_static_config_and_topology_change_the_key(tmp_path):
    cache = warmcache.WarmCache(tmp_path)
    r1 = _aot(cache, k=1)
    r2 = _aot(cache, k=2)
    assert r2.source == "compiled" and r2.key != r1.key
    # a version/topology-skewed fingerprint is a DIFFERENT key: a skewed
    # entry can never be found under the current program's key, so skew
    # degrades to a plain miss + recompile by construction
    fn = _toy_fn()
    lowered = fn.lower(*warmcache.abstract_args(_toy_args()))
    fp = warmcache.program_fingerprint("test/toy", lowered,
                                       warmcache.abstract_args(_toy_args()),
                                       static_config={"k": 1})
    skewed = dict(fp, topology=dict(fp["topology"], jaxlib="0.0.1"))
    assert warmcache.entry_key(skewed) != warmcache.entry_key(fp)


@pytest.mark.fast
def test_aot_without_cache_still_compiles(tmp_path):
    r = warmcache.aot_compile("test/toy", _toy_fn(), _toy_args(),
                              static_config={}, cache=None)
    assert r.source == "compiled" and r.entry is None
    assert np.array_equal(np.asarray(r.fn(*_toy_args())),
                          np.asarray(_toy_fn()(*_toy_args())))


# ---------------------------------------------------------------------------
# cache poisoning: every case recompiles, counts, quarantines
# ---------------------------------------------------------------------------

def _assert_poison_recovery(tmp_path, damage, kind):
    """Write a valid entry, apply ``damage(path)``, reload: recompile OK,
    ``warmcache/<kind>`` bumped, entry quarantined out of the key space."""
    cache = warmcache.WarmCache(tmp_path)
    r1 = _aot(cache)
    expected = np.asarray(r1.fn(*_toy_args()))
    damage(r1.entry)
    before = _counters().get(f"warmcache/{kind}", 0)
    r2 = _aot(warmcache.WarmCache(tmp_path))
    assert r2.source == "compiled", f"poisoned entry must recompile ({kind})"
    assert np.array_equal(expected, np.asarray(r2.fn(*_toy_args())))
    assert _counters().get(f"warmcache/{kind}", 0) == before + 1
    quarantined = list(tmp_path.glob("*.quarantined.*"))
    assert quarantined, "bad entry not quarantined"
    # self-healing: the recompile re-stored a GOOD entry at the key, so the
    # next incarnation warm-loads — and what it loads is the fresh bytes,
    # not the damaged ones (those live under the quarantine name)
    r3 = _aot(warmcache.WarmCache(tmp_path))
    assert r3.source == "cache"
    assert np.array_equal(expected, np.asarray(r3.fn(*_toy_args())))


@pytest.mark.fast
def test_truncated_entry_recovers(tmp_path):
    _assert_poison_recovery(
        tmp_path, lambda p: p.write_bytes(p.read_bytes()[:23]),
        "cache_truncated")


@pytest.mark.fast
def test_truncated_payload_recovers(tmp_path):
    def damage(p):
        blob = p.read_bytes()
        p.write_bytes(blob[:-64])      # header intact, payload short
    _assert_poison_recovery(tmp_path, damage, "cache_truncated")


@pytest.mark.fast
def test_bitflipped_payload_recovers(tmp_path):
    def damage(p):
        blob = bytearray(p.read_bytes())
        blob[-10] ^= 0xFF
        p.write_bytes(bytes(blob))
    _assert_poison_recovery(tmp_path, damage, "cache_corrupt")


@pytest.mark.fast
def test_bad_magic_recovers(tmp_path):
    def damage(p):
        blob = bytearray(p.read_bytes())
        blob[0] ^= 0xFF
        p.write_bytes(bytes(blob))
    _assert_poison_recovery(tmp_path, damage, "cache_corrupt")


@pytest.mark.fast
def test_wrong_fingerprint_entry_recovers(tmp_path):
    cache = warmcache.WarmCache(tmp_path)
    r1 = _aot(cache, k=1)
    r2 = _aot(cache, k=2)

    def damage(path):
        # an entry that is internally VALID (magic, sha, lengths all pass)
        # but is a different program: only the fingerprint check stands
        # between it and executing the wrong executable
        path.write_bytes(r2.entry.read_bytes())
    _assert_poison_recovery(tmp_path, damage, "fingerprint_mismatch")


@pytest.mark.fast
def test_same_key_garbage_payload_recovers(tmp_path):
    def damage(path):
        # meta fully consistent (sha/len recomputed for the garbage), so
        # every integrity check passes and deserialization itself must fail
        # safely — the version-skew-inside-a-same-key-entry case
        meta, _ = _parse_entry(path.read_bytes())
        garbage = b"\x80\x05not a pickled executable"
        meta["payload_len"] = len(garbage)
        meta["payload_sha256"] = warmcache._sha(garbage)
        path.write_bytes(_build_entry(meta, garbage))
    _assert_poison_recovery(tmp_path, damage, "load_error")


@pytest.mark.fast
def test_cache_corrupt_fault_kind_is_deterministic(tmp_path):
    """The DCR_FAULTS hook drives the full corrupt path in CI: damage is
    injected at a deterministic load index, and recovery is the REAL
    quarantine + recompile machinery, not a simulation."""
    cache = warmcache.WarmCache(tmp_path)
    r1 = _aot(cache)
    expected = np.asarray(r1.fn(*_toy_args()))
    fresh = warmcache.WarmCache(tmp_path)
    faults.install("cache_corrupt@load=0")
    try:
        before = _counters().get("warmcache/cache_corrupt", 0)
        r2 = _aot(fresh)
        assert r2.source == "compiled"
        assert np.array_equal(expected, np.asarray(r2.fn(*_toy_args())))
        assert _counters().get("warmcache/cache_corrupt", 0) == before + 1
        # the spec fired once; the re-stored entry loads clean afterwards
        r3 = _aot(fresh)
        assert r3.source == "cache"
    finally:
        faults.clear()


@pytest.mark.fast
def test_thread_race_on_one_cache_dir(tmp_path):
    """Two writers racing the same key: both must succeed (atomic replace,
    last writer wins) and the surviving entry must verify and load."""
    barrier = threading.Barrier(2)
    results = [None, None]

    def run(i):
        cache = warmcache.WarmCache(tmp_path)
        barrier.wait()
        r = _aot(cache)
        results[i] = np.asarray(r.fn(*_toy_args()))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(r is not None for r in results)
    assert np.array_equal(results[0], results[1])
    r = _aot(warmcache.WarmCache(tmp_path))
    assert r.source == "cache"
    assert np.array_equal(results[0], np.asarray(r.fn(*_toy_args())))


_RACE_SCRIPT = """
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from dcr_tpu.core import warmcache

cache = warmcache.WarmCache(sys.argv[1])
fn = jax.jit(lambda x: x * 3.0 + 1.0)
res = warmcache.aot_compile("race/toy", fn, (jnp.ones((8,), jnp.float32),),
                            static_config={}, cache=cache)
out = np.asarray(res.fn(np.ones((8,), np.float32)))
print(json.dumps({"source": res.source, "sum": float(out.sum())}))
"""


def test_two_processes_racing_one_cache_dir(tmp_path):
    """The real fleet shape: two separate PROCESSES compile/store the same
    surface into one shared cache dir concurrently. Both must produce the
    correct result and leave a loadable entry."""
    repo = Path(__file__).parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(repo) + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, "-c", _RACE_SCRIPT,
                               str(tmp_path)],
                              env=env, cwd=repo, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(2)]
    docs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"racer failed: {err[-2000:]}"
        docs.append(json.loads(out.strip().splitlines()[-1]))
    assert all(d["sum"] == 32.0 for d in docs), docs
    # whoever lost the race, the surviving entry must be valid: a third
    # incarnation loads it
    out = subprocess.run([sys.executable, "-c", _RACE_SCRIPT, str(tmp_path)],
                         env=env, cwd=repo, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc == {"source": "cache", "sum": 32.0}


@pytest.mark.fast
def test_export_tier_roundtrip(tmp_path, monkeypatch):
    """The lowered-StableHLO fallback tier (jax.export + compile-on-load)
    stores and loads correctly when forced — the path jaxlibs with fragile
    executable deserialization take."""
    monkeypatch.setenv("DCR_WARMCACHE_TIER", warmcache.TIER_EXPORT)
    cache = warmcache.WarmCache(tmp_path)
    r1 = _aot(cache)
    assert r1.source == "compiled"
    meta, _ = _parse_entry(r1.entry.read_bytes())
    assert meta["tier"] == warmcache.TIER_EXPORT
    out1 = np.asarray(r1.fn(*_toy_args()))
    r2 = _aot(warmcache.WarmCache(tmp_path))
    assert r2.source == "cache"
    assert np.array_equal(out1, np.asarray(r2.fn(*_toy_args())))
    # the tier lives in entry META, not the key: an executable-tier process
    # loads an export-tier entry transparently (this is what makes the
    # per-entry store degrade — build_payload validation failure — findable)
    monkeypatch.setenv("DCR_WARMCACHE_TIER", warmcache.TIER_EXECUTABLE)
    r3 = _aot(warmcache.WarmCache(tmp_path))
    assert r3.source == "cache" and r3.key == r1.key
    assert np.array_equal(out1, np.asarray(r3.fn(*_toy_args())))


@pytest.mark.fast
def test_guarded_one_way_fallback():
    calls = []

    def fast(*a):
        calls.append("fast")
        raise TypeError("aval mismatch")

    def slow(*a):
        calls.append("slow")
        return 42

    fn = warmcache.guarded(fast, slow, "test/guard")
    assert fn() == 42
    assert fn() == 42
    # one-way: the failing executable is tried exactly once
    assert calls == ["fast", "slow", "slow"]


# ---------------------------------------------------------------------------
# warm-start manifest
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_warm_manifest_is_lru_and_budget_capped(tmp_path):
    """The manifest keeps the most-recently-compiled entries (re-recording
    moves an entry to the tail) and max_entries trims the oldest — so a
    long-lived shared cache dir can never fill every future incarnation's
    resident-program budget with stale history."""
    buckets = [[16, s, 7.5, "ddim", 0.0] for s in range(1, 6)]
    for b in buckets:
        warmcache.update_warm_manifest(tmp_path, [b], max_entries=3)
    assert warmcache.read_warm_manifest(tmp_path) == buckets[2:]
    # re-recording an existing entry refreshes it to the tail
    warmcache.update_warm_manifest(tmp_path, [buckets[2]], max_entries=3)
    assert warmcache.read_warm_manifest(tmp_path) == [
        buckets[3], buckets[4], buckets[2]]


@pytest.mark.fast
def test_non_json_native_static_config_roundtrips(tmp_path):
    """A tuple (JSON-lossy: round-trips as a list) in static_config must not
    defeat the cache — the fingerprint is canonicalized once, so the second
    incarnation HITS instead of quarantining the entry it just wrote."""
    cache = warmcache.WarmCache(tmp_path)
    static = {"shape": (16, 2), "mode": "x"}
    r1 = warmcache.aot_compile("test/toy", _toy_fn(), _toy_args(),
                               static_config=static, cache=cache)
    assert r1.source == "compiled" and r1.entry is not None
    r2 = warmcache.aot_compile("test/toy", _toy_fn(), _toy_args(),
                               static_config=static,
                               cache=warmcache.WarmCache(tmp_path))
    assert r2.source == "cache"
    assert not list(tmp_path.glob("*.quarantined.*"))


@pytest.mark.fast
def test_warm_manifest_union_and_corrupt_quarantine(tmp_path):
    b1 = [16, 2, 7.5, "ddim", 0.0]
    b2 = [32, 4, 5.0, "ddpm", 0.1]
    warmcache.update_warm_manifest(tmp_path, [b1])
    warmcache.update_warm_manifest(tmp_path, [b1, b2])   # dedup + union
    assert warmcache.read_warm_manifest(tmp_path) == [b1, b2]
    # corrupt manifest: quarantined, read degrades to empty, counter bumped
    path = tmp_path / warmcache.MANIFEST_NAME
    path.write_text("{not json")
    before = _counters().get("warmcache/manifest_corrupt", 0)
    assert warmcache.read_warm_manifest(tmp_path) == []
    assert _counters().get("warmcache/manifest_corrupt", 0) == before + 1
    assert list(tmp_path.glob(f"{warmcache.MANIFEST_NAME}.quarantined.*"))
    # and the NEXT update starts a fresh manifest cleanly
    warmcache.update_warm_manifest(tmp_path, [b2])
    assert warmcache.read_warm_manifest(tmp_path) == [b2]


@pytest.mark.fast
def test_trace_report_recompile_budget(tmp_path):
    """--max-compiles counts per (stream, os_pid) incarnation — a cold boot
    and a warm respawn sharing one trace file are budgeted separately — and
    never double-bills a bucket compile's serve/compile event against its
    warmcache/compile span."""
    from tools import trace_report as TR

    recs = [
        {"ph": "i", "name": "serve/compile", "id": 1, "parent": None,
         "ts": 1000, "pid": 0, "tid": 1, "tname": "t",
         "args": {"bucket": "(16, 2)", "os_pid": 100}},
        {"ph": "X", "name": "warmcache/compile", "id": 2, "parent": None,
         "ts": 1000, "dur": 5, "pid": 0, "tid": 1, "tname": "t",
         "args": {"surface": "serve/batch_sampler", "os_pid": 100}},
        {"ph": "X", "name": "warmcache/compile", "id": 3, "parent": None,
         "ts": 2000, "dur": 5, "pid": 0, "tid": 1, "tname": "t",
         "args": {"surface": "serve/encode", "os_pid": 100}},
        {"ph": "X", "name": "warmcache/load", "id": 4, "parent": None,
         "ts": 3000, "dur": 5, "pid": 0, "tid": 1, "tname": "t",
         "args": {"surface": "serve/batch_sampler", "os_pid": 200}},
        # an export-tier entry's compile-on-load is a REAL XLA compile and
        # must count — else a broken executable tier passes --max-compiles 0
        {"ph": "X", "name": "warmcache/load_compile", "id": 5, "parent": None,
         "ts": 4000, "dur": 5, "pid": 0, "tid": 1, "tname": "t",
         "args": {"surface": "serve/encode", "os_pid": 300}},
    ]
    (tmp_path / "trace.jsonl").write_text(
        "\n".join(json.dumps(r) for r in recs) + "\n")
    records, errors, _ = TR.load_fleet([tmp_path], TR.load_schema())
    assert not errors
    counts = TR.compiles_per_incarnation(records)
    # event+span for the same compile counts once; pid 200 only loaded;
    # pid 300's export-tier compile-on-load is billed
    assert counts == {"trace.jsonl@pid100": 2, "trace.jsonl@pid300": 1}
    assert TR.main([str(tmp_path), "--max-compiles", "2"]) == 0
    assert TR.main([str(tmp_path), "--max-compiles", "1"]) == 3
    assert TR.main([str(tmp_path), "--max-compiles", "0"]) == 3


@pytest.mark.fast
def test_fingerprint_fields_cover_the_key_surface():
    fn = _toy_fn()
    avals = warmcache.abstract_args(_toy_args())
    lowered = fn.lower(*avals)
    fp = warmcache.program_fingerprint("test/toy", lowered, avals,
                                       static_config={"k": 1})
    assert fp["surface"] == "test/toy"
    assert fp["static_config"] == {"k": 1}
    assert fp["in_avals"] and fp["out_avals"] and fp["lowered_sha256"]
    topo = fp["topology"]
    assert topo["platform"] and topo["jax"] and topo["jaxlib"]
    assert topo["device_count"] >= 1 and topo["process_count"] >= 1


# ---------------------------------------------------------------------------
# slow: trainer-shaped program round-trip (donation + PRNG key + pytrees)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_step_warm_roundtrip_bit_identical(tmp_path, cpu_devices):
    """The train step — donated TrainState, loader-batch dict (incl. the
    jit-unused index leaf), typed PRNG key — survives the cache with
    bit-identical metrics and parameters, using avals constructed exactly
    like Trainer._warm_start does."""
    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.core.config import MeshConfig, ModelConfig, TrainConfig
    from dcr_tpu.diffusion import train as T
    from dcr_tpu.diffusion.trainer import build_models
    from dcr_tpu.parallel import mesh as pmesh

    cfg = TrainConfig(train_batch_size=2, mixed_precision="no")
    cfg.model = ModelConfig.tiny()
    cfg.data.resolution = 16
    models, params = build_models(cfg, jax.random.key(0))
    mesh = pmesh.make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])

    def mkstate():
        p = jax.tree.map(lambda x: jnp.array(np.asarray(x)), params)
        s = T.init_train_state(cfg, models, unet_params=p["unet"],
                               text_params=p["text"], vae_params=p["vae"])
        return T.shard_train_state(s, mesh)

    step = T.make_train_step(cfg, models, mesh)
    key = rngmod.root_key(0)
    rng = np.random.default_rng(0)
    raw = {"pixel_values": rng.standard_normal((2, 16, 16, 3)).astype(np.float32),
           "input_ids": rng.integers(0, 100, (2, 16)).astype(np.int32),
           "index": np.arange(2, dtype=np.int64)}

    ref_state, ref_metrics = step(mkstate(), pmesh.shard_batch(mesh, dict(raw)),
                                  key)

    bs = pmesh.batch_sharding(mesh)
    avals = {
        "pixel_values": jax.ShapeDtypeStruct((2, 16, 16, 3), jnp.float32,
                                             sharding=bs),
        "input_ids": jax.ShapeDtypeStruct((2, 16), jnp.int32, sharding=bs),
        "index": jax.ShapeDtypeStruct(
            (2,), jax.dtypes.canonicalize_dtype(jnp.int64), sharding=bs),
    }
    r1 = warmcache.aot_compile("train/step", step, (mkstate(), avals, key),
                               static_config={}, cache=warmcache.WarmCache(tmp_path))
    assert r1.source == "compiled"
    r2 = warmcache.aot_compile("train/step", step, (mkstate(), avals, key),
                               static_config={},
                               cache=warmcache.WarmCache(tmp_path))
    assert r2.source == "cache", "second incarnation must warm-load"
    warm_state, warm_metrics = r2.fn(mkstate(),
                                     pmesh.shard_batch(mesh, dict(raw)), key)
    assert float(warm_metrics["loss"]) == float(ref_metrics["loss"])
    ref_leaves = jax.tree.leaves(ref_state)
    warm_leaves = jax.tree.leaves(warm_state)
    assert all(bool(jnp.array_equal(a, b))
               for a, b in zip(ref_leaves, warm_leaves)), \
        "warm-loaded step diverged from the jit path"


# ---------------------------------------------------------------------------
# slow: serve worker restart against a populated cache (real subprocess)
# ---------------------------------------------------------------------------

def _wait_health(get, port, want, deadline_s, proc):
    seen = []
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            status, doc = get(port, "/healthz", timeout=2)
            assert status == 200
            seen.append(doc["status"])
            if doc["status"] == want:
                return doc, seen
        except (AssertionError, OSError):
            pass
        if proc.poll() is not None:
            out = proc.stdout.read() if proc.stdout else ""
            raise AssertionError(
                f"server died (rc={proc.poll()}): {out[-3000:]}")
        time.sleep(0.2)
    raise AssertionError(f"no {want!r} within {deadline_s}s (saw {seen[-5:]})")


@pytest.mark.slow
def test_serve_warm_restart_readiness_and_zero_compiles(tmp_path, cpu_devices):
    """Crash-to-ready acceptance, single worker: incarnation 1 boots cold
    (populating the cache; /healthz holds at "warming" until the warm plan
    is compiled), incarnation 2 boots against the populated cache, reaches
    ready with ZERO XLA compiles (trace_report --max-compiles 0), and
    answers the same request bit-identically."""
    from tests.test_serve import (_export_tiny_ckpt, _free_port, _get,
                                  _post_generate, _serve_env)
    from dcr_tpu.core.coordination import EXIT_PREEMPTED

    ckpt = _export_tiny_ckpt(tmp_path)
    env, repo = _serve_env()
    # drop JAX's OWN persistent compile cache: with it, this jaxlib's CPU
    # backend returns executables whose raw serialization is broken
    # ("Symbols not found"), every entry degrades to the export tier, and an
    # export-tier load performs a counted compile-on-load — the zero-compile
    # assertion below would be vacuous. Without it, the executable tier is
    # genuinely exercised end to end (and a regression that breaks it now
    # FAILS the --max-compiles 0 gate instead of hiding behind XLA's cache).
    for k in list(env):
        if k.startswith("JAX_COMPILATION") or k.startswith("JAX_PERSISTENT"):
            env.pop(k)
    # ... and the CLIs' setup_compile_cache() would otherwise turn it back on
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    warm_dir = tmp_path / "warm"

    def start(logdir):
        port = _free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m", "dcr_tpu.cli.serve",
             f"--model_path={ckpt}", f"--port={port}",
             "--resolution=16", "--num_inference_steps=2", "--sampler=ddim",
             "--max_batch=2", "--max_wait_ms=50", "--queue_depth=16",
             "--request_timeout_s=300", "--seed=0",
             f"--warm.dir={warm_dir}", f"--logdir={logdir}"],
            env=env, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        return proc, port

    log1, log2 = tmp_path / "run1", tmp_path / "run2"
    proc, port = start(log1)
    try:
        doc, seen = _wait_health(_get, port, "ok", 300, proc)
        # the readiness phase was observable: never "ok" before the warm
        # plan compiled (cold compile leaves a wide "warming" window)
        assert "warming" in seen, f"cold boot never reported warming: {seen}"
        assert doc["buckets_warm"] >= 1 and doc["buckets_total"] >= 1
        status, resp1 = _post_generate(port, "a red square", seed=7)
        assert status == 200
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == EXIT_PREEMPTED
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    proc, port = start(log2)
    try:
        doc, _ = _wait_health(_get, port, "ok", 300, proc)
        assert doc["buckets_warm"] >= 1
        status, resp2 = _post_generate(port, "a red square", seed=7)
        assert status == 200
        assert resp1["image_png_b64"] == resp2["image_png_b64"], \
            "warm-loaded sampler is not bit-identical to the cold one"
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == EXIT_PREEMPTED
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    from tools import trace_report as TR

    # incarnation 2 served entirely from the cache: zero-compile budget holds
    assert TR.main([str(log2), "--max-compiles", "0"]) == 0
    # and the counter is not vacuous: the cold boot exceeds the same budget
    assert TR.main([str(log1), "--max-compiles", "0"]) == 3


# ---------------------------------------------------------------------------
# slow: fleet worker SIGKILL -> warm respawn, zero recompiles (acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_kill_warm_respawn_zero_recompiles(tmp_path, cpu_devices):
    """Kill a fleet worker whose cache is populated: the respawned worker
    reaches ready (lease-carried readiness; supervisor holds dispatch until
    then) with zero recompile spans, and responses stay bit-identical."""
    from tests.test_serve import _export_tiny_ckpt, _serve_env
    from dcr_tpu.core.config import (FleetConfig, ServeConfig,
                                     WarmCacheConfig)
    from dcr_tpu.serve.fleet import read_lease
    from dcr_tpu.serve.supervisor import FleetSupervisor

    _serve_env()   # ensures the subprocess env contract is importable
    ckpt = _export_tiny_ckpt(tmp_path)
    cfg = ServeConfig(
        model_path=str(ckpt), resolution=16, num_inference_steps=2,
        sampler="ddim", max_batch=2, max_wait_ms=30.0, queue_depth=64,
        request_timeout_s=300.0, seed=0,
        warm=WarmCacheConfig(dir=str(tmp_path / "warm")),
        fleet=FleetConfig(workers=1, dir=str(tmp_path / "fleet"),
                          heartbeat_s=0.5, lease_s=3.0,
                          dispatch_timeout_s=300.0, spawn_timeout_s=300.0,
                          max_attempts=8, respawn_max=10,
                          respawn_base_delay_s=0.2, respawn_max_delay_s=1.0))
    sup = FleetSupervisor(cfg)
    sup.start()
    try:
        deadline = time.monotonic() + 300
        while sup.status()["workers_alive"] == 0:
            assert time.monotonic() < deadline, \
                f"fleet never came up: {sup.status()!r}"
            time.sleep(0.25)
        lease1 = read_lease(sup.paths, 0)
        assert lease1 is not None and lease1.ready
        assert lease1.buckets_warm >= 1 and lease1.buckets_total >= 1
        pid1 = lease1.pid
        doc = sup.health_doc()
        assert doc["workers_ready"] == 1 and doc["buckets_warm"] >= 1

        r1 = sup.submit("a red square", seed=7).future.result(timeout=300)

        t_kill = time.monotonic()
        os.kill(pid1, signal.SIGKILL)
        pid2 = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            lease = read_lease(sup.paths, 0)
            if lease is not None and lease.ready and lease.pid != pid1:
                pid2 = lease.pid
                break
            time.sleep(0.1)
        assert pid2 is not None, "respawned worker never reached ready"
        ttr = time.monotonic() - t_kill

        r2 = sup.submit("a red square", seed=7).future.result(timeout=300)
        assert r1["image_png_b64"] == r2["image_png_b64"], \
            "respawned worker's response is not bit-identical"
        print(f"warm respawn time-to-ready: {ttr:.2f}s")
    finally:
        sup.begin_drain()
        sup.join_drained(120)
        sup.shutdown()

    from tools import trace_report as TR

    records, errors, _ = TR.load_fleet([Path(cfg.fleet.dir)],
                                       TR.load_schema())
    assert not errors, errors[:5]
    compiles = TR.compiles_per_incarnation(records)
    cold = {k: n for k, n in compiles.items() if k.endswith(f"@pid{pid1}")}
    respawn = {k: n for k, n in compiles.items() if k.endswith(f"@pid{pid2}")}
    assert any(n >= 1 for n in cold.values()), \
        f"cold incarnation shows no compiles — counter broken? {compiles}"
    assert not any(n > 0 for n in respawn.values()), \
        f"warm respawn recompiled: {respawn}"
