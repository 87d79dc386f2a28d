#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dcr-tpu still starts on the chip.

One process drives the paper's main path once on ONE TPU chip, at SD-2.1's
published widths (``ModelConfig()`` defaults, random weights from a seed),
through the CLIs' own ``main(argv)``:

    device  jax.devices(); anything but a TPU is a failure
    kernel  Pallas flash attention, forward and backward, against a plain
            float32 softmax attention
    train   three finetuning steps on a seeded image folder, one checkpoint,
            the final export                       (dcr_tpu.cli.train)
    sample  50-step DPM-Solver++ from that export at 256 px and at 512 px,
            the only place the kernel runs inside the UNet (dcr_tpu.cli.sample)
    search  SSCD embeddings of the samples and of the training folder, top-3
            search against a numpy reference       (dcr_tpu.cli.search)

Every phase prints one JSON line. A phase that fails, or cannot run, ends the
script at once with a non-zero exit code; nothing is skipped by the clock.
The last line of a good run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

    python chip_smoke.py                      # what the driver runs: all five
    python chip_smoke.py --only device,kernel # bring-up: a subset of phases
    python chip_smoke.py --chips 4            # three train steps on four
                                              # chips against the same three
                                              # on one, and nothing else
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import resource
import shutil
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / ".smoke_work"       # git-ignored; removed when the run ends
PHASES = ("device", "kernel", "train", "sample", "search")

# What the driver's run uses. tests/test_chip_smoke.py shrinks these (and
# nothing inside the program) to rehearse the control flow on the CPU.
SIZE = {
    "platform": "tpu",
    "kernel_shape": (2, 4096, 5, 64),     # SD-2.1's top UNet level at 512 px
    "kernel_interpret": False,
    "images": 64,
    "image_px": 320,                      # Imagenette-320-like JPEGs
    "train_px": 256,
    # per-device batch, the value in configs/imagenette_sd21_256.json: the
    # step compiled for a described v5e needs 15.78 GB + 0.28 GB of code, of
    # which 12.13 GB are the resident state, under the chip's 16.91 GB
    # (my v5e:2x2 compile, PR 24; batch 8 needs 15.46 GB, batch 4 15.20 GB)
    "train_batch": 16,
    "train_steps": 3,
    "model_argv": (),                     # extra --model.* overrides
    "samples": ((256, 4), (512, 1)),      # (resolution, images in the batch)
    "sample_steps": 50,
    "embed_px": 224,
    "embed_batch": 32,
}
KERNEL_REL_TOL = 2e-2      # bf16 operands (eps 3.9e-3), f32 accumulation
# float32 dots at precision=HIGHEST against numpy float32: 2.3e-7 of the
# largest score on a v5e (my chip run, PR 24); default precision gives 2e-3
SEARCH_REL_TOL = 1e-5
FOUR_CHIP_REL_TOL = 1e-2   # bf16 step, other reduction order across devices


class SmokeFailure(RuntimeError):
    """A phase's own check failed (as opposed to the program raising)."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


class CompileMeter:
    """Compile seconds and persistent-cache hits/misses, from jax.monitoring."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, seconds: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += seconds

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.compile_seconds, self.cache_hits, self.cache_misses


def memory(device) -> dict:
    stats = device.memory_stats() or {}       # the CPU reports none
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def host_peak_rss_bytes() -> int:
    """High-water mark of this process's resident memory (the TPU runtime
    alone maps 14 GB of a v5e host's 40 GiB at start-up)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def run_phase(name: str, fn, meter: CompileMeter) -> None:
    """Run one phase and print its JSON line; re-raises what the phase raised
    after printing an ``"ok": false`` line for it."""
    import jax

    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    try:
        result = fn()
    except BaseException as e:
        print(json.dumps({"phase": name, "ok": False,
                          "seconds": round(time.perf_counter() - t0, 2),
                          "error": repr(e)[:2000]}), flush=True)
        raise
    c1, h1, m1 = meter.snapshot()
    line = {"phase": name, "ok": True,
            "seconds": round(time.perf_counter() - t0, 2),
            "compile_seconds": round(c1 - c0, 2),
            "cache_hits": h1 - h0, "cache_misses": m1 - m0,
            "peak_bytes_in_use":
                memory(jax.devices()[0])["peak_bytes_in_use"],
            "host_peak_rss_bytes": host_peak_rss_bytes()}
    line.update(result)
    print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(cache_dir: str, want_count: int | None) -> dict:
    from importlib.metadata import version

    import jax

    devices = jax.devices()
    d0 = devices[0]
    check(d0.platform == SIZE["platform"],
          f"platform is {d0.platform!r}, this smoke needs "
          f"{SIZE['platform']!r}")
    if want_count is not None:
        check(len(devices) == want_count,
              f"{len(devices)} devices, --chips {want_count} needs "
              f"{want_count}")
    stats = d0.memory_stats() or {}
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "bytes_limit": stats.get("bytes_limit"),
            "versions": {p: version(p) for p in (
                "jax", "jaxlib", "libtpu", "flax", "optax",
                "orbax-checkpoint")},
            "compile_cache_dir": cache_dir}


def phase_kernel() -> dict:
    import jax
    import jax.numpy as jnp

    from dcr_tpu.ops import flash_attention as fa

    shape, interpret = SIZE["kernel_shape"], SIZE["kernel_interpret"]
    kq, kk, kv, kw = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    w = jax.random.normal(kw, shape, jnp.float32)     # the cotangent
    check(fa.supported(q, k, v), f"supported() refuses {shape}")

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, interpret)

    def reference(q, k, v):
        hi = jax.lax.Precision.HIGHEST
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi)
        p = jax.nn.softmax(logits / math.sqrt(q.shape[-1]), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hi)

    def out_and_grads(attention, *args):
        def loss(q, k, v):
            return jnp.sum(attention(q, k, v).astype(jnp.float32) * w)

        return jax.jit(attention)(*args), jax.jit(
            jax.grad(loss, argnums=(0, 1, 2)))(*args)

    out, grads = out_and_grads(flash, q, k, v)
    ref_out, ref_grads = out_and_grads(
        reference, *(x.astype(jnp.float32) for x in (q, k, v)))
    errors = {}
    for name, got, want in zip(("out", "dq", "dk", "dv"), (out, *grads),
                               (ref_out, *ref_grads)):
        got = jnp.asarray(got, jnp.float32)
        check(bool(jnp.isfinite(got).all()), f"{name} is not finite")
        errors[name] = float(jnp.max(jnp.abs(got - want))
                             / jnp.max(jnp.abs(want)))
        check(errors[name] <= KERNEL_REL_TOL,
              f"{name}: max error over max reference {errors[name]:.3g} "
              f"> {KERNEL_REL_TOL}")
    return {"shape": list(shape), "dtype": "bfloat16", "interpret": interpret,
            "max_err_over_max_ref": errors, "rel_tol": KERNEL_REL_TOL}


def seeded_image(rng, px: int):
    """A low-frequency colour pattern: images that differ, and compress."""
    import numpy as np
    from PIL import Image

    low = rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
    return Image.fromarray(low).resize((px, px), Image.BICUBIC)


def write_train_folder(root: Path) -> Path:
    """A seeded class-per-subdirectory folder of JPEGs and its caption table
    (``{path: [caption]}``, the instancelevel_blip format); returns the
    caption JSON."""
    import numpy as np

    rng = np.random.default_rng(0)
    captions = {}
    for i in range(SIZE["images"]):
        path = root / f"class{i % 4}" / f"{i:03d}.jpg"
        path.parent.mkdir(parents=True, exist_ok=True)
        seeded_image(rng, SIZE["image_px"]).save(path, quality=90)
        captions[str(path)] = [f"a seeded colour pattern, number {i}"]
    caption_json = root.parent / "captions.json"
    caption_json.write_text(json.dumps(captions))
    return caption_json


def train_argv(batch: int) -> list[str]:
    """The user's command line: the repo's SD-2.1 config plus what makes the
    run three steps long. A constant learning rate, so that all three steps
    move the weights (the config warms up from zero over 5000 steps)."""
    return [f"--config={REPO / 'configs' / 'imagenette_sd21_256.json'}",
            f"--output_dir={WORK / 'run'}",
            f"--train_batch_size={batch}",
            f"--max_train_steps={SIZE['train_steps']}",
            "--log_every=1",
            f"--data.train_data_dir={WORK / 'train'}",
            f"--data.caption_jsons={WORK / 'captions.json'}",
            f"--data.resolution={SIZE['train_px']}",
            "--data.num_workers=8",
            "--optim.lr_scheduler=constant", "--optim.lr_warmup_steps=0",
            *SIZE["model_argv"]]


def unet_parameter_count(cfg) -> int:
    import jax

    from dcr_tpu.models.unet2d import init_unet, unet_param_count

    return int(unet_param_count(jax.eval_shape(
        lambda key: init_unet(cfg.model, key)[1], jax.random.key(0))))


def phase_train() -> dict:
    import jax

    from dcr_tpu.cli import train as cli_train
    from dcr_tpu.core.config import TrainConfig, parse_cli
    from dcr_tpu.native import jpeg_decoder

    write_train_folder(WORK / "train")
    argv = train_argv(SIZE["train_batch"])
    cli_train.main(argv)

    run = WORK / "run"
    rows = [json.loads(line) for line in
            (run / "logs" / "metrics.jsonl").read_text().splitlines()]
    losses = [row["loss"] for row in rows if "loss" in row]
    check(len(losses) == SIZE["train_steps"],
          f"{len(losses)} losses logged, expected {SIZE['train_steps']}")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    ckpt = run / "checkpoints" / str(SIZE["train_steps"])
    check(ckpt.is_dir() and any(ckpt.iterdir()), f"no checkpoint at {ckpt}")
    exported = sorted(p.name for p in (run / "checkpoint").iterdir())
    check(exported == ["model_index.json", "scheduler", "text_encoder",
                       "unet", "vae"], f"export holds {exported}")
    cfg = parse_cli(TrainConfig, argv)
    return {"widths": list(cfg.model.block_out_channels),
            "unet_parameters": unet_parameter_count(cfg),
            "resolution": SIZE["train_px"], "batch": SIZE["train_batch"],
            "precision": cfg.mixed_precision, "losses": losses,
            "checkpoint": f"checkpoints/{ckpt.name}",
            "checkpoint_backend":
                "npz" if jax.default_backend() == "cpu" else "orbax",
            "exported": exported,
            "jpeg_decoder": "native" if jpeg_decoder.available() else "PIL"}


def random_init_export() -> None:
    """``--only sample`` without ``train``: export seeded random weights of
    the same widths in the run-directory layout dcr-train leaves, so that a
    fault in sampling does not pay for training again."""
    import jax

    from dcr_tpu.core.checkpoint import export_hf_layout
    from dcr_tpu.core.config import (TrainConfig, parse_cli, save_config,
                                     to_dict)
    from dcr_tpu.diffusion.trainer import build_models

    write_train_folder(WORK / "train")
    cfg = parse_cli(TrainConfig, train_argv(SIZE["train_batch"]))
    _, params = build_models(cfg, jax.random.key(0))
    run = WORK / "run"
    run.mkdir(parents=True, exist_ok=True)
    save_config(cfg, run / "config.json")
    export_hf_layout(
        run / "checkpoint", unet=jax.device_get(params["unet"]),
        vae=jax.device_get(params["vae"]),
        text_encoder=jax.device_get(params["text"]),
        scheduler_config={
            "num_train_timesteps": cfg.model.num_train_timesteps,
            "beta_schedule": cfg.model.beta_schedule,
            "beta_start": cfg.model.beta_start,
            "beta_end": cfg.model.beta_end,
            "prediction_type": cfg.model.prediction_type},
        model_config=to_dict(cfg.model))


def phase_sample() -> dict:
    import jax
    import numpy as np
    from PIL import Image

    from dcr_tpu.cli import sample as cli_sample

    gc.collect()      # the trainer went out of scope when its main returned
    device = jax.devices()[0]
    result = {"bytes_in_use_at_start": memory(device)["bytes_in_use"]}
    if not (WORK / "run" / "checkpoint").exists():
        random_init_export()
        result["checkpoint"] = ("seeded random-init export "
                                "(train is not among --only)")
    result.update(sampler="dpm++", steps=SIZE["sample_steps"], guidance=7.5,
                  runs=[])
    ir_dir = WORK / "ir"
    for px, batch in SIZE["samples"]:
        out = WORK / f"samples_{px}"
        last = (px, batch) == SIZE["samples"][-1]
        if last:      # keep the lowered text of the largest program
            jax.config.update("jax_dump_ir_to", str(ir_dir))
        t0 = time.perf_counter()
        try:
            cli_sample.main([
                f"--model_path={WORK / 'run'}", f"--savepath={out}",
                "--num_batches=1", f"--im_batch={batch}",
                f"--resolution={px}",
                f"--num_inference_steps={SIZE['sample_steps']}",
                "--guidance_scale=7.5", "--sampler=dpm++", "--seed=0",
                f"--caption_json={WORK / 'captions.json'}"])
        finally:
            if last:
                jax.config.update("jax_dump_ir_to", None)
        pngs = sorted((out / "generations").glob("*.png"))
        check(len(pngs) == batch, f"{len(pngs)} PNGs at {px} px, not {batch}")
        for png in pngs:
            arr = np.asarray(Image.open(png).convert("RGB"), np.float32)
            check(arr.shape == (px, px, 3), f"{png.name} is {arr.shape}")
            # a sampler that ended in NaN writes an all-zero PNG
            check(bool(np.isfinite(arr).all()) and float(arr.std()) > 0.0,
                  f"{png.name} at {px} px is constant ({arr.min()}.."
                  f"{arr.max()}): the sampler's output was not finite")
        result["runs"].append({"resolution": px, "pngs": len(pngs),
                               "seconds": round(time.perf_counter() - t0, 2)})
    # the sampler program as it was lowered for the run above: every
    # kernel-eligible attention site must be a Mosaic custom call — a kernel
    # in interpret mode lowers to plain HLO and leaves none
    texts = [p.read_text() for p in ir_dir.glob("*sample_fn*")]
    check(len(texts) > 0, f"no sampler program was dumped under {ir_dir}")
    calls = max(text.count("tpu_custom_call") for text in texts)
    expected = flash_sites(*SIZE["samples"][-1])
    check(calls == expected,
          f"{calls} tpu_custom_call in the {SIZE['samples'][-1][0]} px "
          f"sampler, expected {expected}")
    result.update(tpu_custom_calls_in_lowered_sampler=calls,
                  kernels_in_interpret_mode=0)
    return result


def flash_sites(px: int, images: int) -> int:
    """Self-attention sites of the exported UNet that the dispatcher hands the
    Pallas kernel in the sampler at `px`: float32, two rows an image (CFG).
    At SD-2.1 widths on the chip that is the top level's 2 down + 3 up
    transformer blocks (4,096 tokens at 512 px; 1,024 at 256 px from 3 images
    a batch on); none off the TPU."""
    import jax
    import jax.numpy as jnp

    from dcr_tpu.core.config import ModelConfig, from_dict
    from dcr_tpu.models.unet2d import self_attention_shapes
    from dcr_tpu.ops import attention

    index = json.loads(
        (WORK / "run" / "checkpoint" / "model_index.json").read_text())
    cfg = from_dict(ModelConfig, index["model_config"])
    sites = [jax.ShapeDtypeStruct(shape, jnp.float32)
             for shape in self_attention_shapes(cfg, 2 * images, px // 8)]
    return sum(attention.path_for(x, x, x, use_flash=cfg.flash_attention)
               == "flash" for x in sites)


def stand_in_samples() -> None:
    """``--only search`` without ``sample``: seeded PNGs where phase 4's
    would be."""
    import numpy as np

    rng = np.random.default_rng(1)
    for px, batch in SIZE["samples"]:
        out = WORK / f"samples_{px}" / "generations"
        out.mkdir(parents=True, exist_ok=True)
        for i in range(batch):
            seeded_image(rng, px).save(out / f"{i}.png")


def phase_search() -> dict:
    import numpy as np

    from dcr_tpu.cli import search as cli_search
    from dcr_tpu.search.embed import load_embeddings

    result = {"embedder": "SSCD resnet50_disc, RANDOM init (no weights are "
                          "in the image): the scores mean nothing, the "
                          "agreement of the two paths does"}
    if not (WORK / "train").exists():
        write_train_folder(WORK / "train")
    if not any((WORK / f"samples_{px}" / "generations").exists()
               for px, _ in SIZE["samples"]):
        stand_in_samples()
        result["queries_from"] = ("seeded stand-in PNGs (sample is not "
                                  "among --only)")
    queries = WORK / "queries"
    queries.mkdir(parents=True, exist_ok=True)
    for px, _ in SIZE["samples"]:
        for png in (WORK / f"samples_{px}" / "generations").glob("*.png"):
            shutil.copy(png, queries / f"{px}px_{png.name}")
    embed = [f"--image_size={SIZE['embed_px']}",
             f"--batch_size={SIZE['embed_batch']}"]
    corpus = WORK / "corpus"       # one "LAION chunk" folder: the train set
    (corpus / "chunk0").mkdir(parents=True, exist_ok=True)
    cli_search.main(["embed", f"--gen_folder={queries}", *embed])
    cli_search.main(["embed", f"--gen_folder={WORK / 'train'}",
                     f"--embedding_out={corpus / 'chunk0' / 'embedding.npz'}",
                     *embed])
    # both programs that promise float32 dots: the folder scan
    # (search/matmul) and the store-backed engine (search/topk)
    top_k = 3
    cli_search.main(["search", f"--gen_folder={queries}",
                     f"--laion_folder={corpus}", f"--top_k={top_k}",
                     f"--out_path={WORK / 'brute.npz'}"])
    cli_search.main(["build", f"--store_dir={WORK / 'store'}",
                     f"--laion_folder={corpus}"])
    cli_search.main(["search", f"--gen_folder={queries}",
                     f"--store_dir={WORK / 'store'}", f"--top_k={top_k}",
                     f"--out_path={WORK / 'store.npz'}"])

    q, _ = load_embeddings(queries / "embedding.npz")
    feats, keys = load_embeddings(corpus / "chunk0" / "embedding.npz")
    check(bool(np.isfinite(q).all() and np.isfinite(feats).all()),
          "embeddings are not finite")
    sims = np.asarray(q, np.float32) @ np.asarray(feats, np.float32).T
    order = np.argsort(-sims, axis=1)[:, :top_k]
    want_scores = np.take_along_axis(sims, order, axis=1)
    want_keys = np.asarray(keys)[order]
    tol = SEARCH_REL_TOL * float(np.abs(sims).max())
    result.update(queries=int(q.shape[0]), corpus_rows=int(feats.shape[0]),
                  embed_dim=int(q.shape[1]), top_k=top_k,
                  precision="HIGHEST", abs_tol=tol, max_abs_score_diff={})
    for name in ("brute", "store"):
        with np.load(WORK / f"{name}.npz") as z:
            check((z["keys"] == want_keys).all(),
                  f"{name}: keys differ from the numpy reference")
            diff = float(np.abs(z["scores"] - want_scores).max())
        result["max_abs_score_diff"][name] = diff
        check(diff <= tol, f"{name}: scores differ by {diff:.3g} > {tol:.3g}")
    result["keys_equal"] = True
    return result


def phase_four_chips() -> dict:
    """Three train steps on the default mesh over all four chips, then the
    same three on a one-device mesh: same seed, same global batch."""
    import concurrent.futures

    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.core.config import MeshConfig, TrainConfig, parse_cli
    from dcr_tpu.data.dataset import ObjectAttributeDataset
    from dcr_tpu.data.loader import DataLoader
    from dcr_tpu.data.tokenizer import load_tokenizer
    from dcr_tpu.diffusion import train as T
    from dcr_tpu.diffusion.trainer import build_models, build_modules
    from dcr_tpu.parallel import mesh as pmesh

    write_train_folder(WORK / "train")
    devices = jax.devices()
    global_batch = SIZE["train_batch"]
    check(global_batch % len(devices) == 0,
          f"batch {global_batch} does not divide over {len(devices)} devices")
    cfg = parse_cli(TrainConfig, train_argv(global_batch // len(devices)))
    tokenizer = load_tokenizer(None, vocab_size=cfg.model.text_vocab_size,
                               model_max_length=cfg.model.text_max_length)
    loader = DataLoader(ObjectAttributeDataset(cfg.data, tokenizer),
                        batch_size=global_batch,
                        num_workers=cfg.data.num_workers, seed=cfg.data.seed)
    batches = [dict(b) for _, b in zip(range(SIZE["train_steps"]),
                                       loader.epoch(0))]
    check(len(batches) == SIZE["train_steps"], "the folder is too small")
    root = rngmod.root_key(cfg.seed)
    key = rngmod.stream_key(root, "train")

    def make_state(mesh):
        models, params = build_models(cfg, rngmod.stream_key(root, "init"),
                                      mesh=mesh)
        return T.shard_train_state(T.init_train_state(
            cfg, models, unet_params=params["unet"],
            text_params=params["text"], vae_params=params["vae"]), mesh)

    def three_steps(step, state, mesh) -> tuple[list[float], dict]:
        losses = []
        for batch in batches:
            sharded = pmesh.shard_batch(mesh, batch)
            state, metrics = step(state, sharded, key)
            losses.append(float(metrics["loss"]))
        leaves = jax.tree.leaves(state.unet_params)
        placement = {
            "mesh": dict(mesh.shape),
            "devices_holding_parameters":
                sorted({len(x.sharding.device_set) for x in leaves}),
            "devices_holding_batch":
                len(sharded["pixel_values"].sharding.device_set),
            "batch_rows_per_device": sorted(
                s.data.shape[0]
                for s in sharded["pixel_values"].addressable_shards),
            "bytes_in_use": [memory(d)["bytes_in_use"] for d in devices],
        }
        return losses, placement

    mesh_four = pmesh.make_mesh(cfg.mesh)
    mesh_one = pmesh.make_mesh(MeshConfig(data=1), devices=devices[:1])
    state = make_state(mesh_four)
    # Each mesh's step program takes minutes to compile and four chips are
    # held meanwhile, so both compile at once: the four-device one from the
    # state that is there, the one-device one from its shapes moved onto the
    # one-device mesh (the two states cannot be on device 0 together).
    lowered = []
    for mesh, like in ((mesh_four, state), (mesh_one, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=NamedSharding(mesh_one, x.sharding.spec)), state))):
        step = T.make_train_step(cfg, build_modules(cfg, mesh=mesh), mesh)
        lowered.append(step.lower(
            like, pmesh.shard_batch(mesh, batches[0]), key))
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        step_four, step_one = pool.map(lambda low: low.compile(), lowered)
    four, placed_four = three_steps(step_four, state, mesh_four)
    del state
    gc.collect()
    one, placed_one = three_steps(step_one, make_state(mesh_one), mesh_one)
    n = len(devices)
    check(placed_four["devices_holding_parameters"] == [n]
          and placed_four["devices_holding_batch"] == n,
          f"state does not span {n} devices: {placed_four}")
    check(all(math.isfinite(x) for x in four + one), f"{four} {one}")
    worst = float(np.max(np.abs(np.subtract(four, one)) / np.abs(one)))
    check(worst <= FOUR_CHIP_REL_TOL,
          f"four-chip losses {four} differ from one-chip {one} by "
          f"{worst:.3g} > {FOUR_CHIP_REL_TOL}")
    return {"global_batch": global_batch, "losses_four_chips": four,
            "losses_one_chip": one, "max_rel_diff": worst,
            "rel_tol": FOUR_CHIP_REL_TOL, "four_chips": placed_four,
            "one_chip": placed_one}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--only", default="",
                        help="comma-separated subset of: " + ", ".join(PHASES))
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the four-chip train phase and the "
                             "one-chip steps it is compared with")
    args = parser.parse_args(argv)
    only = [p for p in args.only.split(",") if p]
    unknown = sorted(set(only) - set(PHASES))
    if unknown or (only and args.chips == 4):
        parser.error(f"unknown phase(s) {unknown}" if unknown
                     else "--only and --chips 4 exclude each other")

    import jax

    from dcr_tpu.cli import setup_compile_cache

    cache_dir = setup_compile_cache()
    meter = CompileMeter()
    if args.chips == 4:
        plan = [("device", lambda: phase_device(cache_dir, 4)),
                ("train_four_chips", phase_four_chips)]
    else:
        fns = {"device": lambda: phase_device(cache_dir, None),
               "kernel": phase_kernel, "train": phase_train,
               "sample": phase_sample, "search": phase_search}
        # the device check always runs: no phase may start on another platform
        plan = [(p, fns[p]) for p in PHASES
                if not only or p in only or p == "device"]

    shutil.rmtree(WORK, ignore_errors=True)
    try:
        for name, fn in plan:
            try:
                run_phase(name, fn, meter)
            except (Exception, SystemExit):     # a CLI may exit by itself
                traceback.print_exc()
                print(json.dumps({"ok": False, "failed_phase": name}),
                      flush=True)
                return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
