"""Bulk generation pipeline: checkpoint -> prompts -> sharded sampling -> PNGs.

Library equivalent of diff_inference.py:main (43-201) and sd_mitigation.py:main
(43-113): loads an HF-layout checkpoint dir (as written by Trainer.export_checkpoint,
matching the reference's save format), builds the prompt list for the model's
conditioning style, runs the jitted scan sampler over prompt batches, and writes
<savepath>/generations/{count}.png + prompts.txt — the exact directory contract
the eval stage consumes (diff_retrieval.py:125-126).

Instead of parsing config back out of path substrings (the reference's
filesystem-as-config pattern, diff_inference.py:44-81), the model's own
config.json is read from the checkpoint dir.
"""

from __future__ import annotations

import contextlib
import json
import logging
from pathlib import Path
from typing import Any, NamedTuple, Optional, Sequence

import jax
import numpy as np
from PIL import Image

from dcr_tpu.core import dist
from dcr_tpu.core import tracing
from dcr_tpu.core.checkpoint import import_hf_layout
from dcr_tpu.core.config import ModelConfig, SampleConfig, from_dict
from dcr_tpu.core import rng as rngmod
from dcr_tpu.data.tokenizer import TokenizerBase, load_tokenizer
from dcr_tpu.diffusion.train import DiffusionModels
from dcr_tpu.models import schedulers as S
from dcr_tpu.models.text_tower import build_text_tower
from dcr_tpu.models.unet2d import UNet2DCondition
from dcr_tpu.models.vae import AutoencoderKL
from dcr_tpu.parallel import mesh as pmesh
from dcr_tpu.parallel.sharding import params_sharding
from dcr_tpu.sampling import fastsample
from dcr_tpu.sampling.prompts import build_prompt_list, save_prompts
from dcr_tpu.sampling.sampler import make_sampler

log = logging.getLogger("dcr_tpu")


def load_checkpoint_models(ckpt_dir: str | Path, mesh=None):
    """(models, params) from an HF-layout dir written by Trainer.export_checkpoint.
    Model shapes come from model_index.json (our serialized ModelConfig).

    Passing a mesh with a seq axis >1 enables ring/Ulysses sequence-parallel
    attention inside the sampler's UNet (same mechanism as training) — the
    long-context inference path for 512px+ latents."""
    ckpt_dir = Path(ckpt_dir)
    index = json.loads((ckpt_dir / "model_index.json").read_text())
    if "model_config" in index:
        # round-2+ export: our native ModelConfig nested under "model_config"
        cfg_dict = index["model_config"]
    elif "block_out_channels" in index:
        # round-1 legacy flat dict, whose CLIPTextModel hardcoded quick_gelu —
        # preserve those numerics when the key predates the text_act field
        cfg_dict = {**index, "text_act": index.get("text_act", "quick_gelu")}
    else:
        # a GENUINE diffusers checkpoint directory (e.g. downloaded SD-2.1):
        # infer dims from the per-subfolder config.json files
        from dcr_tpu.core.checkpoint import model_config_from_diffusers

        cfg_dict = model_config_from_diffusers(ckpt_dir)
    model_cfg = from_dict(ModelConfig, cfg_dict)
    params = {
        "unet": import_hf_layout(ckpt_dir, "unet"),
        "vae": import_hf_layout(ckpt_dir, "vae"),
        "text": import_hf_layout(ckpt_dir, "text_encoder"),
    }
    models = DiffusionModels(
        unet=UNet2DCondition(model_cfg, mesh=mesh),
        vae=AutoencoderKL(model_cfg),
        text_encoder=build_text_tower(model_cfg),
        # model_cfg carries the schedule fields for every checkpoint flavor:
        # native exports round-trip them; the genuine-diffusers path fills
        # them from scheduler_config.json (model_config_from_diffusers)
        schedule=S.make_schedule(
            num_train_timesteps=model_cfg.num_train_timesteps,
            beta_schedule=model_cfg.beta_schedule,
            beta_start=model_cfg.beta_start, beta_end=model_cfg.beta_end,
            prediction_type=model_cfg.prediction_type),
    )
    _validate_loaded(models, model_cfg, params, ckpt_dir)
    return models, params, model_cfg


def _validate_loaded(models: "DiffusionModels", model_cfg: ModelConfig,
                     params: dict, ckpt_dir: Path) -> None:
    """Strict structural check of loaded trees against the architectures the
    config describes (shapes from jax.eval_shape — trace-only, no compute).
    Catches unsupported checkpoints (wrong dims, SDXL-family leftovers)
    loudly instead of sampling garbage from a partially-consumed state dict."""
    import jax.numpy as jnp

    from dcr_tpu.models.convert import check_converted

    key = jax.random.key(0)
    px = 2 ** (len(model_cfg.vae_block_out_channels) - 1) * model_cfg.sample_size
    expected = {
        "unet": jax.eval_shape(
            models.unet.init, key,
            jax.ShapeDtypeStruct((1, model_cfg.sample_size,
                                  model_cfg.sample_size,
                                  model_cfg.in_channels), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1, model_cfg.text_max_length,
                                  model_cfg.cross_attention_dim), jnp.float32),
        )["params"],
        "vae": jax.eval_shape(
            models.vae.init, key,
            jax.ShapeDtypeStruct((1, px, px, 3), jnp.float32), key)["params"],
        "text": jax.eval_shape(
            models.text_encoder.init, key,
            jax.ShapeDtypeStruct((1, model_cfg.text_max_length), jnp.int32),
        )["params"],
    }
    problems = [f"{comp}{p}" for comp in expected
                for p in check_converted(expected[comp], params[comp])]
    if problems:
        head = "; ".join(problems[:8])
        raise ValueError(
            f"checkpoint {ckpt_dir} does not match the architecture its "
            f"configs describe ({len(problems)} mismatches): {head}")


def resolve_checkpoint(cfg: SampleConfig) -> Path:
    """checkpoint_<iternum>/ or checkpoint/ under the run dir
    (reference convention, diff_inference.py:85-88)."""
    root = Path(cfg.model_path)
    if (root / "unet").exists():  # already a checkpoint dir
        return root
    if cfg.iternum and cfg.iternum > 0:
        cand = root / f"checkpoint_{cfg.iternum}"
        if not cand.exists():
            raise FileNotFoundError(f"no checkpoint_{cfg.iternum} under {root}")
        return cand
    cand = root / "checkpoint"
    if not cand.exists():
        raise FileNotFoundError(f"no exported checkpoint/ under {root} "
                                "(run Trainer.export_checkpoint or pass iternum)")
    return cand


class GenerationStack(NamedTuple):
    """Everything a generation path needs, loaded once: static modules, mesh-placed
    params, the model config, the tokenizer the checkpoint shipped with, and the
    device mesh. Shared by the bulk pipeline (:func:`generate`) and the online
    serving worker (dcr_tpu/serve/worker.py) so the two load paths cannot drift."""

    models: DiffusionModels
    params: dict
    model_cfg: ModelConfig
    tokenizer: TokenizerBase
    mesh: Any


def load_generation_stack(cfg: SampleConfig, *,
                          mesh=None,
                          tokenizer: Optional[TokenizerBase] = None,
                          models=None, params=None) -> GenerationStack:
    """checkpoint dir -> :class:`GenerationStack`, params placed on the mesh.

    ``models``/``params`` may be passed pre-built (tests, in-process benches);
    then only tokenizer resolution and mesh placement happen here. Placement
    rules match training: tensor-axis meshes shard the big matmul weights
    Megatron-style, fsdp axes shard by largest-divisible-dim, anything else
    replicates — so a model too big for one chip's HBM still loads without
    code changes.
    """
    mesh = mesh if mesh is not None else pmesh.make_mesh(cfg.mesh)
    if models is None:
        ckpt = resolve_checkpoint(cfg)
        models, params, model_cfg = load_checkpoint_models(ckpt, mesh=mesh)
    else:
        model_cfg = models.unet.config
    tokenizer = tokenizer or load_tokenizer(
        cfg.model_path or None,
        vocab_size=models.text_encoder.config.text_vocab_size,
        model_max_length=models.text_encoder.config.text_max_length)
    tensor_parallel = mesh.shape.get(pmesh.TENSOR_AXIS, 1) > 1
    params = jax.device_put(
        params, params_sharding(mesh, params, tensor_parallel=tensor_parallel))
    return GenerationStack(models=models, params=params, model_cfg=model_cfg,
                           tokenizer=tokenizer, mesh=mesh)


def generate(cfg: SampleConfig, *, modelstyle: str,
             tokenizer: Optional[TokenizerBase] = None,
             caption_json: Optional[str] = None,
             prompts: Optional[Sequence[str]] = None,
             models=None, params=None) -> Path:
    """Run bulk generation; returns the savepath containing generations/."""
    dist.initialize()
    stack = load_generation_stack(cfg, tokenizer=tokenizer,
                                  models=models, params=params)
    models, params = stack.models, stack.params
    tokenizer, mesh = stack.tokenizer, stack.mesh

    if prompts is None:
        prompts = build_prompt_list(
            modelstyle, cfg.num_batches, seed=cfg.seed, tokenizer=tokenizer,
            caption_json=caption_json,
            rand_augs=cfg.rand_augs if cfg.rand_augs != "none" else None,
            rand_aug_repeats=cfg.rand_aug_repeats)
    savepath = Path(cfg.savepath or "inferences/run")
    gen_dir = savepath / "generations"
    if dist.is_primary():
        gen_dir.mkdir(parents=True, exist_ok=True)
        save_prompts(prompts, savepath)

    sampler = make_sampler(cfg, models, mesh)
    uncond_ids = tokenizer([""])[0]
    key = rngmod.root_key(cfg.seed)
    # fast-sampling accounting (dcr-fast): static per config, so the
    # denoiser-call reduction is known without touching the device. The
    # canonical params fold every dense-degraded parameterization onto the
    # true dense identity (one executable-cache key per distinct program).
    fast_ratio, fast_order = fastsample.canonical_plan_params(
        cfg.num_inference_steps,
        cfg.fast.reuse_ratio if cfg.fast.enabled else 0.0, cfg.fast.order)
    plan = fastsample.fast_plan(cfg.num_inference_steps, fast_ratio)
    unet_calls = fastsample.unet_calls(plan)

    count = 0
    # fixed device batch (prompts_per_batch × im_batch, padded up to a multiple
    # of the data-parallel size) so every chunk hits the same compiled program
    dp = pmesh.data_parallel_size(mesh)
    prompts_per_batch = max(1, len(jax.devices()) // max(1, cfg.im_batch))
    device_batch = -(-prompts_per_batch * cfg.im_batch // dp) * dp
    if cfg.warm.dir and jax.process_count() == 1:
        # dcr-warm: the fixed-shape bulk sampler resolves through the
        # persistent executable cache — a re-run of the same (config,
        # topology) starts generating without an XLA compile. Any cache
        # problem degrades to the jit path (guarded).
        from dcr_tpu.core import warmcache

        ids_aval = jax.ShapeDtypeStruct(
            (device_batch, len(uncond_ids)), np.asarray(uncond_ids).dtype)
        res = warmcache.aot_compile(
            "sample/sampler", sampler,
            (params, ids_aval, ids_aval,
             rngmod.step_key(rngmod.stream_key(key, "sample"), 0)),
            static_config={
                "resolution": cfg.resolution,
                "num_inference_steps": cfg.num_inference_steps,
                "guidance_scale": cfg.guidance_scale,
                "sampler": cfg.sampler,
                "rand_noise_lam": cfg.rand_noise_lam,
                "im_batch": cfg.im_batch,
                "device_batch": device_batch,
                # the fast plan is baked into the program: a different plan
                # must be a different executable-cache key — and the
                # CANONICAL params above key every dense-degraded
                # parameterization the same as the true dense run (no
                # spurious warm-cache miss from an irrelevant knob)
                "fast_ratio": fast_ratio,
                "fast_order": fast_order,
            },
            cache=warmcache.WarmCache(cfg.warm.dir))
        log.info("bulk sampler %s via warm cache (%s) in %.2fs",
                 res.source, cfg.warm.dir, res.build_s)
        sampler = warmcache.guarded(res.fn, sampler, "sample/sampler")
    for start in range(0, len(prompts), prompts_per_batch):
        chunk = list(prompts[start:start + prompts_per_batch])
        ids = tokenizer(chunk)                              # [P, L]
        ids = np.repeat(ids, cfg.im_batch, axis=0)          # [P*im_batch, L]
        real = len(ids)
        if real < device_batch:                             # pad to fixed batch
            ids = np.concatenate(
                [ids, np.repeat(ids[-1:], device_batch - real, axis=0)])
        unc = np.broadcast_to(uncond_ids, ids.shape).copy()
        batch_key = rngmod.step_key(rngmod.stream_key(key, "sample"), start)
        # one sample/fast span per accelerated batch execution (args.batch
        # = trajectories in it) feeds trace_report's "Fast sampling"
        # section; dense runs keep their pre-fast trace shape
        fast_span = (tracing.span("sample/fast",
                                  steps=cfg.num_inference_steps,
                                  unet_calls=unet_calls, batch=real,
                                  fast_ratio=fast_ratio,
                                  fast_order=fast_order,
                                  sampler=cfg.sampler)
                     if unet_calls < cfg.num_inference_steps
                     else contextlib.nullcontext())
        with fast_span:
            images = pmesh.to_host(sampler(params, ids, unc, batch_key))[:real]
        if dist.is_primary():
            for img in images:
                arr = (img * 255).round().astype(np.uint8)
                im = Image.fromarray(arr)
                if im.size[0] > cfg.resolution:  # reference resize-down (195-198)
                    im = im.resize((cfg.resolution, cfg.resolution), Image.LANCZOS)
                im.save(gen_dir / f"{count}.png")
                count += 1
        else:
            count += len(images)
    log.info("wrote %d generations to %s", count, gen_dir)
    return savepath
