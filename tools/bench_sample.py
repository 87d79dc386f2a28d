"""Sampling-throughput bench on the local chip (BASELINE.json config 3).

Full SD-2.1 stack, 256px, 50-step DPM-Solver++(2M) with CFG (the reference's
diff_inference.py:93 recipe), whole trajectory one jitted lax.scan. Appends
per-phase JSON to BENCH_SAMPLE.jsonl (partial results survive kills).

Usage: python tools/bench_sample.py  [BS ladder via BENCH_SAMPLE_BS=4,8]
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

OUT = Path(__file__).resolve().parent.parent / "BENCH_SAMPLE.jsonl"


def emit(rec: dict) -> None:
    rec["t"] = time.strftime("%H:%M:%S")
    with OUT.open("a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 10.0)

    from dcr_tpu.core.config import MeshConfig, ModelConfig, SampleConfig, TrainConfig
    from dcr_tpu.diffusion.trainer import build_models
    from dcr_tpu.parallel import mesh as pmesh
    from dcr_tpu.sampling.sampler import make_sampler

    emit({"phase": "devices", "devices": [str(d) for d in jax.devices()]})
    n_dev = len(jax.devices())

    tcfg = TrainConfig(mixed_precision="bf16")
    tcfg.model = ModelConfig()
    mesh = pmesh.make_mesh(MeshConfig())
    models, params = build_models(tcfg, jax.random.key(0), mesh=mesh)
    params = {"unet": jax.tree.map(lambda x: x.astype(jnp.bfloat16), params["unet"]),
              "vae": jax.tree.map(lambda x: x.astype(jnp.bfloat16), params["vae"]),
              "text": jax.tree.map(lambda x: x.astype(jnp.bfloat16), params["text"])}
    emit({"phase": "models_built"})

    ladder = [int(b) for b in
              (os.environ.get("BENCH_SAMPLE_BS") or "4,8").split(",")]
    scfg = SampleConfig(resolution=256, num_inference_steps=50, sampler="dpm++")
    sample_fn = jax.jit(make_sampler(scfg, models, mesh))

    for bs in ladder:
        ids = jnp.ones((bs * n_dev, tcfg.model.text_max_length), jnp.int32)
        uncond = jnp.ones((bs * n_dev, tcfg.model.text_max_length), jnp.int32)

        def run(n: int) -> float:
            t0 = time.perf_counter()
            imgs = None
            for i in range(n):
                imgs = sample_fn(params, ids, uncond, jax.random.key(i))
            jax.block_until_ready(imgs)
            return time.perf_counter() - t0

        try:
            t0 = time.perf_counter()
            run(1)
            emit({"phase": "compiled", "bs": bs,
                  "compile_plus_first_s": round(time.perf_counter() - t0, 1)})
            per_call = min(run(3) for _ in range(2)) / 3
            emit({"phase": "rung_done", "bs": bs,
                  "samples_per_sec_per_chip": round(bs * n_dev / per_call / n_dev, 3),
                  "secs_per_image": round(per_call / (bs * n_dev), 3),
                  "call_s": round(per_call, 2)})
        except Exception as e:
            emit({"phase": "rung_failed", "bs": bs, "error": repr(e)[:300]})
            break
    emit({"phase": "done"})


if __name__ == "__main__":
    main()
