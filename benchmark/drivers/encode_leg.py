"""Driver `encode_leg`: the finetune's frozen leg, `dcr-precompute-latents`,
bounded by seconds.

Set-up builds a `dcr_tpu.cli.precompute.PrecomputeJob` exactly as the CLI
builds it (with the seeded weights handed in as `pretrained_params`); a unit
is the job's own per-batch body, `encode_batch`: decode and tokenize one batch,
run the encode program (VAE moments + the text tower's states), fetch, and
add the rows to the latent cache. The CLI's loop is `for number in range(...):
job.encode_batch(number, number + 1)`; the window drives the same call round
and round the dataset, back to back, one caller.

A tower is three modules, named by the class attributes `stack` (the
configuration's file as `--model.*` arguments, the reference's sizes, the
seeded leaves, the layers with an expert layer), `flops` (the FLOPs of a
unit) and `ref` (the plain reference): LongCat's here; another tower's
driver subclasses this one and names its own.

Traffic parameters (the workload's file): `train_config`, `overrides`,
`images`, `image_px`, `caption_tokens`, `check_rows`, `reference.tie_eps`,
`limits`.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from benchmark.lib import harness, lm_flops, lm_stack, sd_stack
from benchmark.reference import longcat_flash, sd21

MOE_COUNTERS = ("moe/assignments_total", "moe/assignments_held_total",
                "moe/assignments_zero_total", "moe/assignments_dropped_total")


def moe_counters() -> dict:
    from dcr_tpu.core import tracing

    have = tracing.registry().counters("moe/")
    return {name: int(have.get(name, 0)) for name in MOE_COUNTERS}


def rel_rms(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


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


class Driver:
    stack = lm_stack
    flops = lm_flops
    ref = longcat_flash

    def __init__(self, bench):
        self.bench = bench
        self.cfg = bench.cell.config
        self.traffic = bench.cell.traffic
        # the tower's weights are the deployment's (the configuration's
        # `weights_seed`); --seed draws images, captions and the VAE's weights
        self.weights_seed = int(self.cfg["weights_seed"])
        self.job = None
        self.failed = 0
        self.load_max: list[int] = []

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
                *t.get("overrides", []), *self.stack.model_argv(self.cfg, px)]

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
                   "text": self.stack.tower_leaves(self.shapes, self.weights_seed)}
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
              **self.tower_counts(), **b.meter.snapshot())

    def tower_counts(self) -> dict:
        """What a tower's program counts beside `MOE_COUNTERS`, for the log's
        `first_unit` and `routing` lines: nothing for LongCat."""
        return {}

    # -- the window -----------------------------------------------------
    def unit(self) -> None:
        from benchmark.lib import program_spans as ps

        batches = len(self.job)
        self.job.encode_batch(self._next % batches, (self._next + 1) % batches)
        self._next += 1
        self.load_max.append(ps.gauge("moe/held_expert_load_max") or 0)

    def drain(self) -> None:
        self.job.drain()        # the batch on the device ahead, and the writer

    def end_to_end(self, window) -> dict:
        return {"train_images_per_s": window.units * self.batch / window.seconds}

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
            self.stack.expert_layers(self.cfg))
        out = {"flops_per_unit": self.flops.encode_unit_flops(
                   self.cfg, px, self.batch, seq, held),
               "held_assignments_per_unit": held,
               "dropped_assignments": counts["moe/assignments_dropped_total"]}
        if self.load_max and held > 0:
            out["held_load_max_over_mean"] = float(
                np.mean(self.load_max) / (held / experts))
        self.bench.log("routing", units=units, **counts, **self.tower_counts(), **{
            k: v for k, v in out.items() if k != "flops_per_unit"})
        self.bench.log("phases", **window_phases(window))
        return out

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        """Before the tower goes: the program's own router scores and choices
        on the first unit's captions, expert layer by expert layer (the
        `routing` collection of its module; the timed program keeps none of
        it). This second pass is held to the timed one by what the timed one
        did return: the held and zero-compute assignments it counted for that
        unit (a tower without zero-compute experts counts none on either
        side)."""
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
                   for i in self.stack.expert_layers(self.cfg)]
        del kept
        first = int(self.cfg["share"]["held_experts_first"])
        held = range(first, first + int(self.cfg["n_routed_experts"]))
        again = {"moe/assignments_held_total": sum(
                     int(np.isin(r["chosen"], held).sum()) for r in routing),
                 "moe/assignments_zero_total": sum(
                     int((r["chosen"] >= self.stack.routed_total(self.cfg)).sum())
                     for r in routing)}
        self.second_pass_gap = sum(
            abs(again[name] - self.first_counts[name]) for name in again
        ) / self.first_counts["moe/assignments_total"]
        checked = int(self.traffic["check_rows"]) * self.first_ids.shape[1]
        self.program_routing = [{name: value[:checked] for name, value in r.items()}
                                for r in routing]
        self.close()
        gc.collect()

    def verify(self, window) -> list:
        b, t = self.bench, self.traffic
        program = {**self.first, "routing": self.program_routing,
                   "dropped": self.dropped,
                   "second_pass_gap": self.second_pass_gap}
        reference = self.reference(follow=[r["chosen"] for r in
                                           self.program_routing])
        return compare(program, reference, t["limits"], b.log)

    def reference(self, *, ops=None, vae_ops=sd21.EXACT, follow=None) -> dict:
        """The plain reference on the checked rows: VAE moments and the
        tower's states, float32 at HIGHEST, a layer's leaves alive at a time.
        `ops` is the reference's `Ops` (`self.ref.EXACT` where None; the
        control hands in `self.ref.Ops(quant="fp8")`)."""
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
            tower = self.ref.forward(
                self.stack.reference_sizes(self.cfg), self.fed["input_ids"],
                lambda part: self.stack.tower_leaves(
                    self.shapes, self.weights_seed, part, "float32"),
                ops=self.ref.EXACT if ops is None else ops, follow=follow,
                tie_eps=float(t["reference"]["tie_eps"]))
        out["ctx"] = np.asarray(tower["ctx"])
        out["routing"] = [{k: np.asarray(v) for k, v in layer.items()}
                          for layer in tower["routing"]]
        return out

    def close(self) -> None:
        job, self.job = self.job, None
        if job is None:
            return
        import jax

        job.close()
        for leaf in jax.tree.leaves(job.frozen):
            leaf.delete()
        job.frozen = None
        del job
        gc.collect()


#: tie widths at which the log's line says how the tokens would divide
EPS_TABLE = (0.01, 0.02, 0.03, 0.05)


def compare(program: dict, reference: dict, limits: dict, log) -> list:
    """The numbers compared, each beside its limit. `program` holds `ctx`,
    `mean`, `std` of the checked rows as the timed path fetched them, its
    `routing` (scores and choices a layer), `dropped` and `second_pass_gap`;
    `reference` the same from the plain reference, which was handed the
    program's choices (`reference/longcat_flash.route`): `near_tie` marks the
    tokens whose k-th and (k+1)-th score it holds no further than `tie_eps`
    apart, and `outside` those where the program chose experts that no
    scores within `tie_eps` of the reference's would choose, near tie or
    not: every choice that differs beyond a near tie, and every choice at a
    near tie that reaches below the tie."""
    rows = len(reference["ctx"])
    ctx = [rel_rms(program["ctx"][r], reference["ctx"][r]) for r in range(rows)]
    both = lambda d, r: np.concatenate([d["mean"][r].ravel(),      # noqa: E731
                                        d["std"][r].ravel()])
    moments = [rel_rms(both(program, r), both(reference, r)) for r in range(rows)]
    layers = list(zip(program["routing"], reference["routing"]))
    scores = [rel_rms(p["scores"], q["scores"]) for p, q in layers]
    near = [float(np.mean(q["near_tie"])) for _, q in layers]
    outside = [float(np.mean(q["outside"])) for _, q in layers]
    checks = [
        harness.check("ctx_rms_worst", max(ctx), limits["ctx_rms_worst"]),
        harness.check("moments_rms_worst", max(moments),
                      limits["moments_rms_worst"]),
        harness.check("router_score_error", max(scores),
                      limits["router_score_error"]),
        harness.check("near_tie_tokens_share", float(np.mean(near)),
                      limits["near_tie_tokens_share"]),
        harness.check("choice_outside_tie_share", max(outside),
                      limits["choice_outside_tie_share"]),
        harness.check("dropped_assignments", program["dropped"],
                      limits["dropped_assignments"]),
    ]
    margin = np.concatenate([q["margin"] for _, q in layers])
    slack = np.concatenate([q["slack"] for _, q in layers])
    log("compared", ctx_rms=ctx, moments_rms=moments, router_score_rms=scores,
        near_tie_share=near, choice_outside_tie_share=outside,
        second_pass_gap=program.get("second_pass_gap"),
        at_tie_eps={str(e): [float(np.mean(margin < e)),
                             float(np.mean(slack >= e))] for e in EPS_TABLE})
    return checks
