"""Fast-sampling fidelity + speed bench: the dcr-fast quality gate.

Sweeps steps × reuse-ratio (× extrapolation order) over the plan-based
score-reuse sampler (dcr_tpu/sampling/fastsample.py) at a FIXED (ckpt,
prompt set, seed, bucket): for every point it measures wall latency, the
static UNet-call count, the SSCD similarity of each fast image against the
dense reference image of the SAME (prompt, seed) — the papers' replication
metric turned on ourselves: "faster" must provably not be "different" —
and the FID between the fast and reference grids. The curve is banked as
BENCH_FASTSAMPLE.json.

The gate: the DEFAULT operating point (FastSampleConfig defaults —
reuse_ratio 0.5, order 2 — at the sweep's largest step count) must achieve
at least ``MIN_CALL_REDUCTION`` (1.8x) fewer denoiser calls AND hold SSCD
similarity within the declared budget (``SIM_BUDGET_MEAN``/``_MIN``), or
the process exits 1. For calibration the bench also banks the *background*
similarity of mismatched (different-prompt) pairs: with the deterministic
random-init SSCD used here unrelated images already score ~0.93-0.98, so
the budget is meaningful only because fast-vs-reference pairs sit well
above that background (the banked numbers show the margin).

``--smoke`` (CI): one sweep point, no FID, plus the disabled-path
end-to-end bit-identity check — a sampler built with fast disabled and one
built with ``enabled=True, reuse_ratio=0`` must produce byte-identical
images (the all-full plan IS the original program) — and schema validation
of the banked JSON. Exit 1 on any violation.

Usage: python tools/bench_fastsample.py [--smoke]
Env knobs: BENCH_FAST_STEPS (default "8,16,32"), BENCH_FAST_RATIOS
(default "0.25,0.5"), BENCH_FAST_ORDERS (default "1,2"), BENCH_FAST_RES
(default 16), BENCH_FAST_IMAGE_SIZE (SSCD crop, default 32),
BENCH_FAST_REPS (timing repetitions, default 3).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

OUT = Path(__file__).resolve().parent.parent / "BENCH_FASTSAMPLE.json"

#: Declared fidelity budget for the default operating point, on the bench's
#: deterministic random-init SSCD (background sim of UNRELATED images is
#: ~0.93-0.98 here — the banked `background_sim_mean` shows the margin).
#: Probed on this container: ratio 0.5 / order 2 holds mean >= 0.999 from
#: 16 steps up; the budget leaves headroom for box-to-box float drift while
#: still sitting far above background.
SIM_BUDGET_MEAN = 0.995
SIM_BUDGET_MIN = 0.99
#: The ISSUE 12 acceptance floor: the default point must save at least
#: this factor in denoiser calls.
MIN_CALL_REDUCTION = 1.8

_PROMPTS = ("a red square", "a blue circle", "a green triangle",
            "a yellow star", "a church", "a truck", "a dog", "a tree")


def validate_result(doc: dict) -> list[str]:
    """Schema problems with a BENCH_FASTSAMPLE document ([] = valid) — the
    contract tests and the --smoke leg both enforce."""
    problems = []

    def need(obj, field, types, where):
        v = obj.get(field)
        if not isinstance(v, types) or isinstance(v, bool):
            problems.append(f"{where}.{field}: {type(v).__name__}, "
                            f"want {types}")
        return v

    for field in ("model", "sampler"):
        need(doc, field, str, "$")
    for field in ("resolution", "prompts", "image_size"):
        need(doc, field, int, "$")
    for field in ("sim_budget_mean", "sim_budget_min", "min_call_reduction",
                  "background_sim_mean"):
        need(doc, field, (int, float), "$")
    if not isinstance(doc.get("pass"), bool):
        problems.append("$.pass: missing or not a bool")
    curve = doc.get("curve")
    if not isinstance(curve, list) or not curve:
        return problems + ["$.curve: missing or empty"]
    for i, row in enumerate(curve):
        where = f"$.curve[{i}]"
        if not isinstance(row, dict):
            problems.append(f"{where}: not an object")
            continue
        for field in ("steps", "unet_calls", "order"):
            need(row, field, int, where)
        for field in ("ratio", "call_reduction", "wall_s", "ref_wall_s",
                      "latency_speedup", "sscd_sim_mean", "sscd_sim_min"):
            need(row, field, (int, float), where)
        if row.get("fid") is not None:
            need(row, "fid", (int, float), where)
    dp = doc.get("default_point")
    if not isinstance(dp, dict):
        problems.append("$.default_point: missing")
    return problems


def _build():
    import jax

    from dcr_tpu.core.config import MeshConfig, ModelConfig, TrainConfig
    from dcr_tpu.data.tokenizer import HashTokenizer
    from dcr_tpu.diffusion.trainer import build_models
    from dcr_tpu.parallel import mesh as pmesh

    cfg = TrainConfig(mixed_precision="no")
    cfg.model = ModelConfig.tiny()
    models, params = build_models(cfg, jax.random.key(0))
    tok = HashTokenizer(cfg.model.text_vocab_size, cfg.model.text_max_length)
    mesh = pmesh.make_mesh(MeshConfig())
    return models, params, tok, mesh


def _embedder(image_size: int):
    """Deterministic random-init SSCD: (images [N,H,W,3] in [0,1]) ->
    L2-normalized [N, 512] features. Self-consistent — the same pixels give
    the same embedding — which is all fast-vs-reference similarity needs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dcr_tpu.models.resnet import SSCDModel
    from dcr_tpu.obs.copyrisk import prepare_images

    model = SSCDModel(embed_dim=512)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, image_size, image_size, 3)))
    apply = jax.jit(lambda x: model.apply(variables, x))

    def feats(images):
        f = np.asarray(apply(prepare_images(images, image_size)))
        return f / np.linalg.norm(f, axis=1, keepdims=True)

    return feats


def _make_runner(models, params, tok, mesh, *, res: int, reps: int):
    """(steps, ratio, order) -> (images, median wall seconds) at the fixed
    (ckpt, prompts, seed) workload — one compiled trajectory per point."""
    import numpy as np

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.core.config import FastSampleConfig, SampleConfig
    from dcr_tpu.sampling.sampler import make_sampler

    ids = tok(list(_PROMPTS))
    unc = np.broadcast_to(tok([""])[0], ids.shape).copy()
    p = {"unet": params["unet"], "vae": params["vae"], "text": params["text"]}
    key = rngmod.root_key(0)

    def run(steps: int, ratio: float, order: int = 2):
        cfg = SampleConfig(
            resolution=res, num_inference_steps=steps, guidance_scale=7.5,
            sampler="dpm++", im_batch=1, seed=0,
            fast=FastSampleConfig(enabled=ratio > 0, reuse_ratio=ratio,
                                  order=order))
        sampler = make_sampler(cfg, models, mesh)
        images = np.asarray(sampler(p, ids, unc, key))   # compile + warm
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(sampler(p, ids, unc, key))
            walls.append(time.perf_counter() - t0)
        return images, statistics.median(walls)

    return run


def _background_sim(feats_ref) -> float:
    """Mean similarity of MISMATCHED (different-prompt) reference pairs —
    the random-init SSCD's background level, banked so the budget's margin
    over it is visible."""
    import numpy as np

    n = len(feats_ref)
    sims = [float(feats_ref[i] @ feats_ref[(i + 1) % n]) for i in range(n)]
    return float(np.mean(sims))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv

    import jax

    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    import numpy as np

    from dcr_tpu.core.config import FastSampleConfig
    from dcr_tpu.eval.fid import fid_from_features
    from dcr_tpu.sampling import fastsample

    res = int(os.environ.get("BENCH_FAST_RES", "16"))
    image_size = int(os.environ.get("BENCH_FAST_IMAGE_SIZE", "32"))
    reps = int(os.environ.get("BENCH_FAST_REPS", "3"))
    # the gate is on THE default operating point the sample/serve configs
    # actually ship (FastSampleConfig defaults) — env sweep overrides add
    # curve points but can never redirect the gate to a weaker point
    default_ratio = FastSampleConfig().reuse_ratio
    default_order = FastSampleConfig().order
    if smoke:
        steps_list, ratios, orders = [16], [default_ratio], [default_order]
    else:
        steps_list = [int(s) for s in os.environ.get(
            "BENCH_FAST_STEPS", "8,16,32").split(",")]
        ratios = [float(r) for r in os.environ.get(
            "BENCH_FAST_RATIOS", "0.25,0.5").split(",")]
        orders = [int(o) for o in os.environ.get(
            "BENCH_FAST_ORDERS", "1,2").split(",")]
        if default_ratio not in ratios:
            ratios.append(default_ratio)
        if default_order not in orders:
            orders.append(default_order)

    print(f"bench_fastsample{' --smoke' if smoke else ''}: steps={steps_list}"
          f" ratios={ratios} orders={orders} res={res} prompts="
          f"{len(_PROMPTS)} image_size={image_size}", flush=True)

    models, params, tok, mesh = _build()
    run = _make_runner(models, params, tok, mesh, res=res, reps=reps)
    feats = _embedder(image_size)

    problems: list[str] = []
    if smoke:
        # disabled-path bit-identity, end to end: enabled with ratio 0 is
        # the all-full plan, which must be the ORIGINAL program — byte-equal
        # images, not merely close ones
        ref_images, _ = run(steps_list[0], 0.0)
        r0_images, _ = run(steps_list[0], 1e-9)  # enabled=True, plan dense
        if not np.array_equal(ref_images, r0_images):
            problems.append("fast enabled with reuse_ratio~0 is NOT "
                            "bit-identical to the disabled sampler")
        else:
            print("smoke: disabled-path bit-identity OK", flush=True)

    curve = []
    default_point = None
    background = 0.0
    for steps in steps_list:
        ref_images, ref_wall = run(steps, 0.0)
        ref_feats = feats(ref_images)
        background = _background_sim(ref_feats)
        for ratio in ratios:
            plan = fastsample.fast_plan(steps, ratio)
            calls = fastsample.unet_calls(plan)
            for order in orders:
                images, wall = run(steps, ratio, order)
                f = feats(images)
                sims = (ref_feats * f).sum(axis=1)
                row = {
                    "steps": steps, "ratio": ratio, "order": order,
                    "unet_calls": calls,
                    "call_reduction": round(steps / max(1, calls), 3),
                    "wall_s": round(wall, 4),
                    "ref_wall_s": round(ref_wall, 4),
                    "latency_speedup": round(ref_wall / wall, 3),
                    "sscd_sim_mean": round(float(sims.mean()), 6),
                    "sscd_sim_min": round(float(sims.min()), 6),
                    "fid": (None if smoke else
                            round(fid_from_features(ref_feats, f), 6)),
                }
                curve.append(row)
                print(json.dumps(row), flush=True)
                if (steps == max(steps_list) and ratio == default_ratio
                        and order == default_order):
                    default_point = row
    assert default_point is not None   # the sweep always includes it

    # the fidelity gate on the chosen default operating point
    if default_point["call_reduction"] < MIN_CALL_REDUCTION:
        problems.append(
            f"default point saves only {default_point['call_reduction']}x "
            f"denoiser calls < {MIN_CALL_REDUCTION}x")
    if default_point["sscd_sim_mean"] < SIM_BUDGET_MEAN:
        problems.append(
            f"default point SSCD sim mean {default_point['sscd_sim_mean']} "
            f"below budget {SIM_BUDGET_MEAN}")
    if default_point["sscd_sim_min"] < SIM_BUDGET_MIN:
        problems.append(
            f"default point SSCD sim min {default_point['sscd_sim_min']} "
            f"below budget {SIM_BUDGET_MIN}")

    result = {
        "model": "tiny", "sampler": "dpm++", "resolution": res,
        "guidance": 7.5, "seed": 0, "prompts": len(_PROMPTS),
        "image_size": image_size, "timing_reps": reps, "smoke": smoke,
        "sim_budget_mean": SIM_BUDGET_MEAN,
        "sim_budget_min": SIM_BUDGET_MIN,
        "min_call_reduction": MIN_CALL_REDUCTION,
        "background_sim_mean": round(background, 6),
        "curve": curve,
        "default_point": default_point,
        "pass": not problems,
    }
    schema_problems = validate_result(result)
    if schema_problems:
        problems.extend(f"schema: {p}" for p in schema_problems)
        result["pass"] = False
    if not smoke:
        # the smoke leg must never clobber the banked full curve
        OUT.write_text(json.dumps(result, indent=2) + "\n")
        print(f"wrote {OUT}", flush=True)
    else:
        print("smoke: schema OK" if not schema_problems else
              f"smoke: schema problems: {schema_problems}", flush=True)

    if problems:
        print("FASTSAMPLE FAIL: " + "; ".join(problems), flush=True)
        return 1
    print(f"FASTSAMPLE OK: default point {default_point['call_reduction']}x "
          f"fewer UNet calls at SSCD sim mean "
          f"{default_point['sscd_sim_mean']} (background "
          f"{result['background_sim_mean']})", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
