"""Driver `bulk_sample`: the bulk sampling job's own calls, bounded by seconds.

The entry is the jitted program `sampling/sampler.make_sampler(cfg, models,
mesh)` returns, called and fetched with `pmesh.to_host` as
`sampling/pipeline.generate()`'s loop does, with the models and the seeded
weights placed through `load_generation_stack(cfg, models=..., params=...)`.
One unit is one batch: one prompt's ids repeated `im_batch` times in, float
images on the host out. `generate()` itself is not the entry: its loop has no
bound in seconds, and the PNG encoding it does between batches is outside the
window (PERF.md, Open questions).

Traffic parameters (the workload's file): `resolution`, `im_batch`,
`num_inference_steps`, `guidance_scale`, `sampler`, `prompt_pool`,
`check_images`, `limits`.
"""
from __future__ import annotations

import gc

import numpy as np

from benchmark.lib import flops, harness, rng, sd_stack
from benchmark.reference import sd21


def image_rms_worst(served, reference) -> tuple[float, list[float]]:
    """The widest root-mean-square difference of a served image from the
    reference's, over the checked images (pixels in [0, 1]); NaN if any is."""
    rms = [float(np.sqrt(np.mean((np.asarray(s, np.float32) - r) ** 2)))
           for s, r in zip(served, reference)]
    return (max(rms) if all(np.isfinite(rms)) else float("nan")), rms


class Driver:
    def __init__(self, bench):
        self.bench = bench
        self.cfg = bench.cell.config
        self.traffic = bench.cell.traffic
        self.failed = 0
        self.sampler = self.params = None
        self.done: list = []            # (batch number, images [im_batch, H, W, 3])
        self._n = 0

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        import jax

        from dcr_tpu.core.config import SampleConfig, TrainConfig, parse_cli
        from dcr_tpu.diffusion.trainer import build_modules
        from dcr_tpu.parallel import mesh as pmesh
        from dcr_tpu.sampling.pipeline import load_generation_stack
        from dcr_tpu.sampling.sampler import make_sampler

        b, t = self.bench, self.traffic
        px = int(t["resolution"])
        model_cfg = parse_cli(TrainConfig, sd_stack.model_argv(self.cfg, px))
        self.shapes = sd_stack.weight_shapes(model_cfg, b.cache)
        cfg = SampleConfig(resolution=px, im_batch=int(t["im_batch"]),
                           num_inference_steps=int(t["num_inference_steps"]),
                           guidance_scale=float(t["guidance_scale"]),
                           sampler=t["sampler"], seed=harness.seed31(b.seed))
        weights = sd_stack.make_weights(self.shapes, b.seed)
        jax.block_until_ready(weights)
        b.log("weights_made")
        stack = load_generation_stack(cfg, models=build_modules(model_cfg),
                                      params=weights)
        del weights
        self.params = stack.params
        self.sampler = make_sampler(cfg, stack.models, stack.mesh)
        self.to_host = pmesh.to_host
        text = self.cfg["text_encoder"]
        self.prompts = sd_stack.prompt_ids(
            b.seed, int(t["prompt_pool"]), text["max_position_embeddings"],
            text["vocab_size"])
        self.uncond = np.asarray(stack.tokenizer([""])[0], np.int32)
        # generate()'s fixed device batch: prompts x im_batch, padded up to
        # a multiple of the data-parallel size (one prompt, im_batch rows, on
        # one chip)
        dp = pmesh.data_parallel_size(stack.mesh)
        self.prompts_per_batch = max(1, len(jax.devices()) // max(1, cfg.im_batch))
        self.images_per_batch = -(-self.prompts_per_batch * cfg.im_batch // dp) * dp
        self.im_batch = cfg.im_batch
        self.unit()                      # warm the one shape
        self.done.clear()
        self._n = 0
        b.log("sampler_warm", **b.meter.snapshot())

    def batch_inputs(self, n: int):
        """(ids, uncond ids, key) of batch number n: each prompt's ids
        im_batch times, padded with the last row, as generate() feeds its
        sampler."""
        pick = [(n * self.prompts_per_batch + k) % len(self.prompts)
                for k in range(self.prompts_per_batch)]
        ids = np.repeat(self.prompts[pick], self.im_batch, axis=0)
        if len(ids) < self.images_per_batch:
            ids = np.concatenate([ids, np.repeat(
                ids[-1:], self.images_per_batch - len(ids), axis=0)])
        unc = np.broadcast_to(self.uncond, ids.shape).copy()
        return ids, unc, rng.key_of(self.bench.seed, 1000 + n)

    # -- the window -----------------------------------------------------
    def unit(self) -> None:
        n = self._n
        self._n += 1
        ids, unc, key = self.batch_inputs(n)
        with self.bench.span("dispatch"):
            out = self.sampler(self.params, ids, unc, key)
        with self.bench.span("fetch"):
            images = self.to_host(out)
        self.done.append((n, images))

    def drain(self) -> None:
        pass                      # a unit ends with its images on the host

    def end_to_end(self, window) -> dict:
        return {"sample_images_per_s":
                    window.units * self.images_per_batch / window.seconds}

    def counters(self, window) -> dict:
        t = self.traffic
        return {"flops_per_unit": flops.sample_batch_flops(
            self.cfg, int(t["resolution"]), self.images_per_batch,
            int(t["num_inference_steps"]))}

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        self.sampler = self.params = None
        gc.collect()

    def verify(self, window) -> list:
        import jax
        import jax.numpy as jnp

        b, t = self.bench, self.traffic
        limits = t["limits"]
        # every image of the window: finite and not constant
        bad = sum(int(not np.isfinite(img).all() or float(img.std()) == 0.0)
                  for _, images in self.done for img in images)
        # a sample of them, drawn from the seed, against the reference
        gen = np.random.default_rng([int(b.seed), 17])
        pairs = [(i, j) for i in range(len(self.done))
                 for j in range(self.images_per_batch)]
        picks = [pairs[k] for k in gen.choice(
            len(pairs), size=min(int(t["check_images"]), len(pairs)),
            replace=False)]
        latent = int(t["resolution"]) // 2 ** (
            len(self.cfg["vae"]["block_out_channels"]) - 1)
        shape = (self.images_per_batch, latent, latent,
                 self.cfg["vae"]["latent_channels"])
        ids, noise, served = [], [], []
        for i, j in picks:
            n, images = self.done[i]
            batch_ids, _, key = self.batch_inputs(n)
            ids.append(batch_ids[j])
            # the program draws the whole batch's noise from its 'init' stream
            noise.append(jax.random.normal(sd21.stream(key, "init"), shape)[j])
            served.append(images[j])
        ids = np.stack(ids)
        unc = np.broadcast_to(self.uncond, ids.shape).copy()
        self.checked = (jnp.asarray(ids), jnp.asarray(unc), jnp.stack(noise))
        self.reference = ref = self.reference_images(sd21.EXACT)
        worst, rms = image_rms_worst(served, ref)
        b.log("compared", picks=picks, image_rms=rms,
              reference_std=[float(r.std()) for r in ref])
        return [harness.check("bad_images", bad, limits["bad_images"]),
                harness.check("image_rms_worst", worst,
                              limits["image_rms_worst"])]

    def reference_images(self, ops) -> np.ndarray:
        """The plain reference's images for the checked prompts and noise."""
        import jax

        t = self.traffic
        ids, unc, noise = self.checked
        weights = sd_stack.make_weights(self.shapes, self.bench.seed)
        with jax.default_matmul_precision("highest"):
            return np.asarray(sd21.sample_images(
                ops, weights, self.cfg, ids, unc, noise,
                steps=int(t["num_inference_steps"]),
                guidance=float(t["guidance_scale"])))

    def close(self) -> None:
        self.sampler = self.params = None
