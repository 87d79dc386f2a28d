"""Pipelined-training bench: fused vs pipelined vs latent-cache-fed.

The dcr-pipe speed gate (ISSUE 13). For each batch size it measures
steps/sec + MFU of three legs driving the SAME synthetic host batches:

- **fused**: the original one-program train step (the pipelined-OFF path);
- **pipelined**: the producer/consumer split — a real
  :class:`~dcr_tpu.diffusion.encode_stage.EncodeProducer` thread runs the
  live frozen-encoder stage ahead of the denoiser hot step. Its win is
  overlap: on a multi-core host the encoder hides behind the denoiser; on a
  single-core rig (this container) the two stages serialize and the leg
  measures ~the split's program-size effect only — the banked ``cores``
  field says which regime produced the number;
- **latent_cache**: the producer reads precomputed VAE posterior moments +
  text embeddings from a real on-disk latent cache
  (data/latent_cache.py — written and verify-loaded through the production
  reader), so the encoders never execute. This win is FLOPs removed, not
  overlap, and holds at any core count — it is the leg that carries the
  gate on the 1-core CPU smoke rig.

Gate: at the first (primary) batch size, the best pipelined-arc leg
(max of pipelined / latent_cache) must reach ``MIN_PIPE_SPEEDUP`` (1.25x)
steps/sec over fused, or exit 1. Results bank as BENCH_PIPE.json.

``--smoke`` (CI) additionally enforces:
- **disabled-path bit-identity**: two fused runs from identical init give
  bit-equal params (the pipelined-OFF path is deterministic), and the fused
  ``train/step@default`` entry regenerated via tools/check/surfaces.py has
  the SAME lowered-HLO sha as the checked-in compile_manifest.json — the
  dense program did not move;
- **pipelined-on loss curve**: per-step losses of the pipelined run stay
  within ``LOSS_RTOL`` of the fused reference (SMOKE_LOSSCURVE-style; the
  split is the same math, only XLA fusion boundaries differ);
- BENCH_PIPE.json schema validation.

Usage: python tools/bench_pipe.py [--smoke]
Env knobs: BENCH_PIPE_BS (default "4,8"), BENCH_PIPE_STEPS (default 30;
smoke 10), BENCH_PIPE_RES (default 64), BENCH_PIPE_MIN (gate, default
1.25), BENCH_PIPE_DEPTH (ring depth, default 2).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

OUT = Path(__file__).resolve().parent.parent / "BENCH_PIPE.json"
MANIFEST = Path(__file__).resolve().parent.parent / "compile_manifest.json"

#: ISSUE 13 acceptance floor: best pipelined-arc leg vs fused, steps/sec.
MIN_PIPE_SPEEDUP = 1.25
#: pipelined-vs-fused per-step loss tolerance (same math, different XLA
#: fusion boundaries; observed ~5e-7 on this rig — 1e-3 leaves margin).
LOSS_RTOL = 1e-3


def _env_list(name: str, default: str) -> list[int]:
    return [int(x) for x in (os.environ.get(name) or default).split(",") if x]


def _rig_cfg(batch_size: int, resolution: int):
    """The bench rig: a small stack whose frozen-encoder share of the fused
    step is realistic (VAE at pixel resolution, 3 blocks x 2 layers ≈ 40%
    of the step on CPU — SD-scale VAEs at 256-512px sit in the same range
    against a per-device UNet shard), so the split has something to win."""
    from dcr_tpu.core.config import ModelConfig, TrainConfig

    cfg = TrainConfig(train_batch_size=batch_size, mixed_precision="no")
    cfg.model = ModelConfig(
        sample_size=resolution // 4,
        block_out_channels=(32, 64), layers_per_block=1,
        attention_head_dim=8, cross_attention_dim=32, norm_num_groups=8,
        vae_block_out_channels=(32, 64, 64), vae_layers_per_block=2,
        text_vocab_size=1000, text_hidden_size=32, text_layers=2,
        text_heads=2, text_max_length=16, flash_attention=False)
    cfg.data.resolution = resolution
    cfg.optim.lr_warmup_steps = 0
    cfg.optim.lr_scheduler = "constant"
    return cfg


class _Rig:
    """Models/params/mesh + the synthetic host-batch set for one config."""

    def __init__(self, cfg, n_batches: int = 8):
        import jax
        import numpy as np

        from dcr_tpu.diffusion.trainer import build_models
        from dcr_tpu.parallel import mesh as pmesh

        self.cfg = cfg
        self.mesh = pmesh.make_mesh(cfg.mesh)
        self.models, self.params = build_models(cfg, jax.random.key(0),
                                                mesh=self.mesh)
        bsz = cfg.train_batch_size * jax.local_device_count()
        self.bsz = bsz
        rng = np.random.default_rng(0)
        res = cfg.data.resolution
        self.batches = [{
            "pixel_values": rng.standard_normal(
                (bsz, res, res, 3)).astype(np.float32),
            "input_ids": rng.integers(
                0, cfg.model.text_vocab_size,
                (bsz, cfg.model.text_max_length)).astype(np.int32),
            "index": np.arange(j * bsz, (j + 1) * bsz, dtype=np.int64),
        } for j in range(n_batches)]

    def state(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from dcr_tpu.diffusion import train as T

        p = jax.tree.map(lambda x: jnp.array(np.asarray(x)), self.params)
        s = T.init_train_state(self.cfg, self.models, unet_params=p["unet"],
                               text_params=p["text"], vae_params=p["vae"])
        return T.shard_train_state(s, self.mesh)

    def batch_iter(self, steps: int):
        for i in range(steps):
            yield self.batches[i % len(self.batches)]


def _flops(fn, *args) -> float:
    from dcr_tpu.utils.profiling import flops_of_jitted

    return flops_of_jitted(fn, *args)


def _leg_result(steps: int, dt: float, flops: float) -> dict:
    from dcr_tpu.obs.memwatch import peak_bytes
    from dcr_tpu.utils.profiling import chip_peak_tflops

    peak = chip_peak_tflops()       # None on the CPU: no mfu there
    per_step = dt / steps
    mfu = (flops / per_step) / (peak * 1e12) if flops and peak else None
    return {"steps_per_sec": round(steps / dt, 3),
            "step_ms": round(per_step * 1e3, 2),
            "gflops_per_step": round(flops / 1e9, 2) if flops else None,
            "mfu": round(mfu, 5) if mfu else None,
            # dcr-hbm: process high-water mark AS OF leg end (null on
            # backends without memory_stats — XLA:CPU, this CI rig).
            # Monotonic across the legs sharing this process: read the
            # step between consecutive legs, not each value as an
            # independent per-leg peak (XLA has no peak reset).
            "hbm_peak_bytes": peak_bytes()}


def run_fused(rig: _Rig, steps: int, losses: list | None = None) -> dict:
    import jax
    import numpy as np

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import train as T
    from dcr_tpu.parallel import mesh as pmesh

    fused = T.make_train_step(rig.cfg, rig.models, rig.mesh)
    key = rngmod.root_key(0)
    s = rig.state()
    s, m = fused(s, pmesh.shard_batch(rig.mesh, dict(rig.batches[0])), key)
    flops = _flops(fused, s, pmesh.shard_batch(rig.mesh,
                                               dict(rig.batches[0])), key)
    s = rig.state()
    t0 = time.perf_counter()
    for batch in rig.batch_iter(steps):
        s, m = fused(s, pmesh.shard_batch(rig.mesh, dict(batch)), key)
        if losses is not None:
            losses.append(float(jax.device_get(m["loss"])))
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    out = _leg_result(steps, dt, flops)
    out["final_params"] = s
    return out


def _run_producer_leg(rig: _Rig, steps: int, make_encode,
                      losses: list | None = None) -> dict:
    """Shared pipelined/cache-fed driver: a real EncodeProducer feeds the
    denoiser hot step; ``make_encode(frozen)`` returns the producer's
    encode callable."""
    import jax

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import encode_stage as E

    denoise = E.make_denoise_step(rig.cfg, rig.models, rig.mesh)
    key = rngmod.root_key(0)

    def one(n: int, record: list | None):
        s = rig.state()
        hot, frozen = E.split_state(s, rig.cfg.train_text_encoder)
        producer = E.EncodeProducer(
            rig.batch_iter(n), make_encode(frozen),
            depth=int(os.environ.get("BENCH_PIPE_DEPTH") or 2),
            start_step=0)
        try:
            t0 = time.perf_counter()
            m = None
            for i in range(n):
                enc = producer.get(i)
                hot, m = denoise(hot, enc, key)
                if record is not None:
                    record.append(float(jax.device_get(m["loss"])))
            jax.block_until_ready(m["loss"])
            return time.perf_counter() - t0, hot, frozen
        finally:
            producer.stop()

    one(2, None)                                   # compile both programs
    dt, hot, frozen = one(steps, losses)
    s2 = rig.state()
    hot2, _ = E.split_state(s2, rig.cfg.train_text_encoder)
    enc_avals_src = rig.batch_iter(1)
    flops = _denoise_flops(rig, denoise, hot2, make_encode, enc_avals_src)
    out = _leg_result(steps, dt, flops)
    out["final_params"] = E.merge_state(hot, frozen,
                                        rig.cfg.train_text_encoder)
    return out


def _denoise_flops(rig: _Rig, denoise, hot, make_encode, src) -> float:
    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import encode_stage as E

    _, frozen = E.split_state(rig.state(), rig.cfg.train_text_encoder)
    enc = make_encode(frozen)(next(iter(src)), 0)
    return _flops(denoise, hot, enc, rngmod.root_key(0))


def run_pipelined(rig: _Rig, steps: int, losses: list | None = None) -> dict:
    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import encode_stage as E

    encode_fn = E.make_encode_stage(rig.cfg, rig.models, rig.mesh)
    key = rngmod.root_key(0)

    def make_encode(frozen):
        return E.live_encode(encode_fn, frozen, rig.mesh, key)

    return _run_producer_leg(rig, steps, make_encode, losses)


def build_bench_cache(rig: _Rig, cache_dir: Path) -> dict:
    """Write a REAL latent cache (production writer, production format) from
    the rig's synthetic batch set; returns the fingerprint used."""
    import jax
    import numpy as np

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.data import latent_cache as LC
    from dcr_tpu.diffusion import encode_stage as E
    from dcr_tpu.parallel import mesh as pmesh

    enc_m = E.make_encode_stage(rig.cfg, rig.models, rig.mesh,
                                emit="moments")
    _, frozen = E.split_state(rig.state(), rig.cfg.train_text_encoder)
    fp = {"version": 1, "bench": "dcr-pipe",
          "resolution": rig.cfg.data.resolution, "bsz": rig.bsz}
    writer = LC.LatentCacheWriter(cache_dir, fp)
    key = rngmod.root_key(0)
    for batch in rig.batches:
        enc = enc_m(frozen, pmesh.shard_batch(rig.mesh, dict(batch)), key,
                    np.uint32(0))
        writer.add(batch["index"],
                   np.asarray(jax.device_get(enc["mean"])),
                   np.asarray(jax.device_get(enc["std"])),
                   np.asarray(jax.device_get(enc["ctx"])))
    writer.finalize()
    return fp


def run_latent_cache(rig: _Rig, steps: int, cache_dir: Path,
                     fp: dict) -> dict:
    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.data import latent_cache as LC
    from dcr_tpu.diffusion import encode_stage as E

    reader = LC.LatentCacheReader(cache_dir, fp)
    cache_fn = E.make_cache_stage(rig.cfg, rig.models, rig.mesh)
    encode_fn = E.make_encode_stage(rig.cfg, rig.models, rig.mesh)
    key = rngmod.root_key(0)

    def make_encode(frozen):
        live = E.live_encode(encode_fn, frozen, rig.mesh, key)
        return E.cached_encode(cache_fn, reader, rig.mesh, key, live)

    return _run_producer_leg(rig, steps, make_encode)


def check_disabled_bit_identity(rig: _Rig, steps: int) -> dict:
    """Two fused runs from identical init must end bit-equal, and the fused
    program's manifest digest must match the checked-in one."""
    import jax
    import numpy as np

    a = run_fused(rig, steps)
    b = run_fused(rig, steps)
    la = jax.tree.leaves(jax.device_get(a["final_params"].unet_params))
    lb = jax.tree.leaves(jax.device_get(b["final_params"].unet_params))
    bit_equal = all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(la, lb))

    from tools.check.manifest import fingerprint
    from tools.check.surfaces import SURFACES

    spec = next(s for s in SURFACES if s.key == "train/step@default")
    kwargs = spec.build()
    entry = fingerprint(spec.key, kwargs["fn"], kwargs["args"],
                        static_config=kwargs.get("static_config", {}),
                        donate_argnums=kwargs.get("donate_argnums", ()),
                        surface=spec.surface, variant=spec.variant)
    checked_in = json.loads(MANIFEST.read_text())["entries"].get(
        "train/step@default", {})
    digest_ok = (entry.get("lowered_sha256")
                 == checked_in.get("lowered_sha256") != None)
    return {"params_bit_equal": bool(bit_equal),
            "fused_manifest_digest_ok": bool(digest_ok),
            "steps": steps}


def validate_result(doc: dict) -> list[str]:
    """Schema problems with a BENCH_PIPE document ([] = valid) — enforced
    by the --smoke leg and tests/test_pipe.py."""
    problems: list[str] = []

    def need(obj, field, types, where):
        v = obj.get(field)
        if not isinstance(v, types) or isinstance(v, bool):
            problems.append(f"{where}.{field}: {type(v).__name__}")
        return v

    need(doc, "cores", int, "$")
    need(doc, "steps", int, "$")
    need(doc, "min_speedup", float, "$")
    bss = need(doc, "batch_sizes", list, "$") or []
    legs = need(doc, "legs", dict, "$") or {}
    for bs in bss:
        group = need(legs, f"bs{bs}", dict, "$.legs") or {}
        for leg in ("fused", "pipelined", "latent_cache"):
            row = need(group, leg, dict, f"$.legs.bs{bs}") or {}
            need(row, "steps_per_sec", (int, float), f"$.legs.bs{bs}.{leg}")
            need(row, "step_ms", (int, float), f"$.legs.bs{bs}.{leg}")
            # dcr-hbm: present on every leg, null where the backend has no
            # memory stats (int bytes where it does)
            if "hbm_peak_bytes" not in row:
                problems.append(f"$.legs.bs{bs}.{leg}.hbm_peak_bytes: "
                                "missing")
            elif not isinstance(row["hbm_peak_bytes"], (int, type(None))) \
                    or isinstance(row["hbm_peak_bytes"], bool):
                problems.append(
                    f"$.legs.bs{bs}.{leg}.hbm_peak_bytes: "
                    f"{type(row['hbm_peak_bytes']).__name__}")
            if leg != "fused":
                need(row, "speedup", (int, float), f"$.legs.bs{bs}.{leg}")
    gate = need(doc, "gate", dict, "$") or {}
    need(gate, "batch_size", int, "$.gate")
    need(gate, "speedup", (int, float), "$.gate")
    need(gate, "mode", str, "$.gate")
    if "passed" not in gate or not isinstance(gate["passed"], bool):
        problems.append("$.gate.passed: missing/not bool")
    return problems


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    smoke = "--smoke" in argv
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    batch_sizes = _env_list("BENCH_PIPE_BS", "4,8")
    steps = int(os.environ.get("BENCH_PIPE_STEPS")
                or (10 if smoke else 30))
    res = int(os.environ.get("BENCH_PIPE_RES") or 64)
    min_speedup = float(os.environ.get("BENCH_PIPE_MIN") or MIN_PIPE_SPEEDUP)
    print(f"bench_pipe{' --smoke' if smoke else ''}: bs={batch_sizes} "
          f"steps={steps} res={res} cores={os.cpu_count()}", flush=True)

    legs: dict = {}
    problems: list[str] = []
    smoke_doc: dict = {}
    reps = int(os.environ.get("BENCH_PIPE_REPS") or 2)
    for i, bs in enumerate(batch_sizes):
        rig = _Rig(_rig_cfg(bs, res))

        def best(run, *args):
            # best-of-reps: single-shot wall timing on this class of shared
            # box swings ±25%; the fastest rep is the least-perturbed one
            rows = [run(rig, steps, *args) for _ in range(reps)]
            return max(rows, key=lambda r: r["steps_per_sec"])

        fused = best(run_fused)
        pipe = best(run_pipelined)
        with tempfile.TemporaryDirectory() as td:
            fp = build_bench_cache(rig, Path(td))
            cache = best(run_latent_cache, Path(td), fp)
        for row in (fused, pipe, cache):
            row.pop("final_params", None)
        pipe["speedup"] = round(
            pipe["steps_per_sec"] / fused["steps_per_sec"], 3)
        cache["speedup"] = round(
            cache["steps_per_sec"] / fused["steps_per_sec"], 3)
        legs[f"bs{bs}"] = {"fused": fused, "pipelined": pipe,
                           "latent_cache": cache}
        print(f"  bs{bs}: fused {fused['steps_per_sec']}/s  "
              f"pipelined {pipe['steps_per_sec']}/s ({pipe['speedup']}x)  "
              f"latent_cache {cache['steps_per_sec']}/s "
              f"({cache['speedup']}x)", flush=True)
        if smoke and i == 0:
            # dedicated UNTIMED passes for the loss curve: the per-step
            # device_get sync they need would otherwise perturb the timed
            # legs (and serialize exactly the pipeline being measured)
            losses_fused: list = []
            losses_pipe: list = []
            run_fused(rig, min(steps, 8), losses_fused)
            run_pipelined(rig, min(steps, 8), losses_pipe)
            rel = [abs(a - b) / max(abs(a), 1e-9)
                   for a, b in zip(losses_fused, losses_pipe)]
            smoke_doc["losscurve"] = {
                "fused": [round(x, 6) for x in losses_fused],
                "pipelined": [round(x, 6) for x in losses_pipe],
                "max_rel_diff": max(rel) if rel else None,
                "tolerance": LOSS_RTOL,
                "within": bool(rel) and max(rel) <= LOSS_RTOL,
            }
            if not smoke_doc["losscurve"]["within"]:
                problems.append(
                    f"pipelined loss curve off the fused reference: "
                    f"max_rel_diff={max(rel) if rel else None} > {LOSS_RTOL}")
            ident = check_disabled_bit_identity(rig, min(steps, 6))
            smoke_doc["disabled_path"] = ident
            if not ident["params_bit_equal"]:
                problems.append("disabled path NOT bit-identical: fused "
                                "params diverged between identical runs")
            if not ident["fused_manifest_digest_ok"]:
                problems.append("fused train/step@default lowered sha != "
                                "checked-in compile_manifest.json — the "
                                "pipelined-OFF program moved")

    gate_bs = batch_sizes[0]
    g = legs[f"bs{gate_bs}"]
    best_mode = max(("pipelined", "latent_cache"),
                    key=lambda k: g[k]["speedup"])
    gate = {"batch_size": gate_bs, "min_speedup": min_speedup,
            "speedup": g[best_mode]["speedup"], "mode": best_mode,
            "passed": g[best_mode]["speedup"] >= min_speedup}
    if not gate["passed"]:
        problems.append(
            f"gate FAILED: best pipelined-arc speedup {gate['speedup']}x "
            f"({best_mode}) < required {min_speedup}x at bs{gate_bs}")

    result = {
        "bench": "dcr-pipe", "resolution": res, "steps": steps,
        "batch_sizes": batch_sizes, "cores": int(os.cpu_count() or 1),
        "min_speedup": float(min_speedup),
        "legs": legs, "gate": gate,
        "smoke": smoke_doc or None,
        "note": ("the pipelined leg's overlap win needs >1 core; on a "
                 "1-core rig the gate is carried by latent_cache, whose "
                 "win is encoder FLOPs removed, not overlap"),
    }
    schema_problems = validate_result(result)
    problems.extend(f"schema: {p}" for p in schema_problems)
    OUT.write_text(json.dumps(result, indent=1, sort_keys=True) + "\n")
    print(f"bench_pipe: wrote {OUT}", flush=True)
    if problems:
        for p in problems:
            print(f"bench_pipe: FAIL: {p}", flush=True)
        return 1
    print(f"bench_pipe: gate OK — {gate['speedup']}x ({gate['mode']}) >= "
          f"{min_speedup}x", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
