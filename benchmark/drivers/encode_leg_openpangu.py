"""Driver `encode_leg_openpangu`: the finetune's frozen leg,
`dcr-precompute-latents`, bounded by seconds, on the openPangu-Ultra-MoE text
tower.

The job, the unit and the window are `encode_leg`'s to the letter (a
`PrecomputeJob` built as the CLI builds it, `encode_batch(number, then)` round
and round the dataset, back to back, one caller), and so is the comparison
that decides `correct` (`encode_leg.compare`); what is this tower's own is
brought here: how the configuration's file becomes `--model.openpangu.*`, the
seeded leaves' deviations, the FLOPs of a unit (`lib/pangu_stack.py`,
`lib/pangu_flops.py`), the plain reference
(`reference/openpangu_ultra_moe.py`), and a stack whose first layer has no
expert layer, so that the routing is read from the layers that have one.

Traffic parameters (the workload's file): `train_config`, `overrides`,
`images`, `image_px`, `caption_tokens`, `check_rows`, `reference.tie_eps`,
`limits`.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from benchmark.drivers import encode_leg
from benchmark.drivers.encode_leg import compare, moe_counters  # noqa: F401
from benchmark.lib import harness, lm_stack, pangu_flops, pangu_stack, sd_stack
from benchmark.reference import openpangu_ultra_moe as ref
from benchmark.reference import sd21

#: what this tower's program counts beside `encode_leg.MOE_COUNTERS`
NEW_COUNTERS = ("moe/tokens_unheld_total",)
NEW_GAUGES = ("moe/layers", "tower/layers")


def new_counters() -> dict:
    """The counters and gauges PR 32 added to the program, as the registry
    holds them; a program without them gives none."""
    from benchmark.lib import program_spans as ps
    from dcr_tpu.core import tracing

    have = tracing.registry().counters("moe/")
    out = {name: int(have[name]) for name in NEW_COUNTERS if name in have}
    for name in NEW_GAUGES:
        value = ps.gauge(name)
        if value is not None:
            out[name] = int(value)
    return out


def window_phases(window) -> dict:
    """Where a plain window's seconds went, for the reader of a log: of each
    of the program's four phases the seconds inside the measured window and
    the longest single span, and the median and the longest unit (a run that
    sits low shows here whether one stall or every unit did it)."""
    from benchmark.lib import program_spans as ps

    lo, hi = window.t0, window.t0 + window.seconds
    out = {}
    for phase in ("load", "encode", "fetch", "write"):
        spans = [d for t, d in ps.timeline(f"precompute/{phase}") if lo <= t < hi]
        out[f"{phase}_s"] = round(sum(spans), 4)
        out[f"{phase}_max_s"] = round(max(spans, default=0.0), 4)
    units = sorted(d for _, d in window.unit_times)
    if units:
        out.update(unit_median_s=round(units[len(units) // 2], 4),
                   unit_max_s=round(units[-1], 4),
                   units_over_twice_median=sum(
                       d > 2 * units[len(units) // 2] for d in units))
    return out


class Driver(encode_leg.Driver):
    # -- set-up ---------------------------------------------------------
    def job_argv(self) -> list[str]:
        b, t = self.bench, self.traffic
        config = b.work / "train_config.json"
        config.write_text(json.dumps(t["train_config"]))
        seed = harness.seed31(b.seed)
        px = int(t["train_config"]["data"]["resolution"])
        return [f"--config={config}", f"--output_dir={b.work / 'run'}",
                f"--seed={seed}", f"--data.seed={seed}",
                f"--data.train_data_dir={b.work / 'train'}",
                f"--data.caption_jsons={b.work / 'captions.json'}",
                f"--pipe.latent_cache={b.work / 'latent_cache'}",
                *t.get("overrides", []), *pangu_stack.model_argv(self.cfg, px)]

    def setup(self) -> None:
        import jax

        from dcr_tpu.cli.precompute import PrecomputeJob
        from dcr_tpu.core.config import TrainConfig, parse_cli

        b, t = self.bench, self.traffic
        captions = sd_stack.write_image_folder(
            b.work / "train", b.seed, int(t["images"]), int(t["image_px"]))
        lm_stack.write_captions(captions, b.seed, tuple(t["caption_tokens"]))
        b.log("images_written", images=int(t["images"]))
        self.train_cfg = cfg = parse_cli(TrainConfig, self.job_argv())
        self.shapes = lm_stack.weight_shapes(cfg)
        weights = {"vae": lm_stack.vae_weights(self.shapes, b.seed),
                   "text": pangu_stack.tower_leaves(self.shapes, self.weights_seed)}
        jax.block_until_ready(weights)
        b.log("weights_made", weights_seed=self.weights_seed, tower_parameters=sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(weights["text"])))
        self.job = job = PrecomputeJob(cfg, pretrained_params=weights)
        del weights
        self.batch = job.batch_size
        b.log("job_built", batches=len(job), **b.meter.snapshot())
        # the first unit: the window's own call, on the first batch; what the
        # reference will follow is kept (the inputs as the program placed
        # them, and what the program fetched for them)
        rows = int(t["check_rows"])
        sharded, _ = job.load_batch(0)
        self.first_ids = np.array(sharded["input_ids"])
        self.fed = {"pixel_values": np.array(sharded["pixel_values"][:rows]),
                    "input_ids": self.first_ids[:rows]}
        del sharded
        before = moe_counters()
        out = job.encode_batch(0, 1)
        self.first = {name: np.array(out[name][:rows])
                      for name in ("mean", "std", "ctx")}
        self.first_counts = {name: value - before[name]
                             for name, value in moe_counters().items()}
        self._next = 1
        real = (self.fed["input_ids"] != 0).sum(axis=1)
        b.log("first_unit", real_tokens=real.tolist(), **self.first_counts,
              **new_counters(), **b.meter.snapshot())

    def counters(self, window) -> dict:
        """FLOPs of a unit from shapes, the routed experts' term from the
        assignments the held experts really got (the program's counter, a
        unit's mean over everything driven), and the skew they saw."""
        t = self.traffic
        px = int(t["train_config"]["data"]["resolution"])
        seq = int(self.cfg["text_max_length"])
        counts = moe_counters()
        units = max(1, self._next)
        held = counts["moe/assignments_held_total"] / units
        experts = int(self.cfg["n_routed_experts"]) * len(
            pangu_stack.expert_layers(self.cfg))
        out = {"flops_per_unit": pangu_flops.encode_unit_flops(
                   self.cfg, px, self.batch, seq, held),
               "held_assignments_per_unit": held,
               "dropped_assignments": counts["moe/assignments_dropped_total"]}
        if self.load_max and held > 0:
            out["held_load_max_over_mean"] = float(
                np.mean(self.load_max) / (held / experts))
        self.bench.log("routing", units=units, **counts, **new_counters(), **{
            k: v for k, v in out.items() if k != "flops_per_unit"})
        self.bench.log("phases", **window_phases(window))
        return out

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        """Before the tower goes: the program's own router scores and choices
        on the first unit's captions, expert layer by expert layer (the
        `routing` collection of its module; the timed program keeps none of
        it). This second pass is held to the timed one by what the timed one
        did return: the held assignments it counted for that unit."""
        import jax

        if self.job is None:
            return
        job = self.job
        self.dropped = moe_counters()["moe/assignments_dropped_total"]
        tower = job.models.text_encoder
        _, kept = jax.jit(lambda p, i: tower.apply(
            {"params": p}, i, mutable=["routing"]))(job.frozen["text"],
                                                    self.first_ids)
        routing = [{name: np.asarray(kept["routing"][f"layers_{i}"]["moe"][name][0])
                    for name in ("scores", "chosen")}
                   for i in pangu_stack.expert_layers(self.cfg)]
        del kept
        first = int(self.cfg["share"]["held_experts_first"])
        held = range(first, first + int(self.cfg["n_routed_experts"]))
        again = sum(int(np.isin(r["chosen"], held).sum()) for r in routing)
        self.second_pass_gap = abs(
            again - self.first_counts["moe/assignments_held_total"]
        ) / self.first_counts["moe/assignments_total"]
        checked = int(self.traffic["check_rows"]) * self.first_ids.shape[1]
        self.program_routing = [{name: value[:checked] for name, value in r.items()}
                                for r in routing]
        self.close()
        gc.collect()

    def reference(self, *, ops=ref.EXACT, vae_ops=sd21.EXACT,
                  follow=None) -> dict:
        """The plain reference on the checked rows: VAE moments and the
        tower's states, float32 at HIGHEST, a layer's leaves alive at a time.
        `ops` is anything with the `dot`, `einsum` and `q` of the reference's
        `Ops` (`tools/readings_encode.py` hands in LongCat's)."""
        import jax
        import jax.numpy as jnp

        b, t = self.bench, self.traffic
        with jax.default_matmul_precision("highest"):
            vae = lm_stack.vae_weights(self.shapes, b.seed)
            mean, logvar = jax.jit(lambda p, x: sd21.vae_encode(
                vae_ops, p, self.cfg, x))(vae, jnp.asarray(self.fed["pixel_values"]))
            std = jnp.exp(0.5 * jnp.clip(logvar, -30.0, 20.0))
            out = {"mean": np.asarray(mean), "std": np.asarray(std)}
            del vae, mean, logvar, std
            tower = ref.forward(
                pangu_stack.reference_sizes(self.cfg), self.fed["input_ids"],
                lambda part: pangu_stack.tower_leaves(
                    self.shapes, self.weights_seed, part, "float32"),
                ops=ops, follow=follow,
                tie_eps=float(t["reference"]["tie_eps"]))
        out["ctx"] = np.asarray(tower["ctx"])
        out["routing"] = [{k: np.asarray(v) for k, v in layer.items()}
                          for layer in tower["routing"]]
        return out
