"""Driver `train_step`: the finetune loop's own calls, bounded by seconds.

Set-up builds a `Trainer` exactly as `dcr_tpu.cli.train` builds it (with the
seeded weights handed in as `pretrained_params`) and drives it through its
first `check_steps` steps; the window then drives THE SAME object, per step,
through what `Trainer._train_impl` calls: `next()` on `trainer.loader`'s epoch
iterator, `pmesh.shard_batch`, the trainer's compiled `train/step` on
`trainer.state`, and the fetch of the metrics at each log boundary.
`Trainer.train()` itself is not the entry: it has no bound in seconds and ends
in an export and a save.

Traffic parameters (the workload's file): `train_config` (a copy of the
repo's config), `overrides`, `images`, `image_px`, `check_steps`,
`reference.row_block`, `limits`.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from benchmark.lib import flops, harness, sd_stack
from benchmark.reference import finetune, sd21


def adam_mu(opt_state):
    """The first moment inside an optax state: the first node with `.mu`."""
    import jax

    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if not found:
        raise harness.BenchFailure("no Adam state in the optimizer's state")
    return found[0].mu


class Driver:
    def __init__(self, bench):
        self.bench = bench
        self.cfg = bench.cell.config
        self.traffic = bench.cell.traffic
        self.trainer = None
        self.failed = 0
        self.steps = 0
        self.metrics = None
        self._it = None
        self._epoch = 0

    # -- set-up ---------------------------------------------------------
    def train_argv(self) -> list[str]:
        b, t = self.bench, self.traffic
        config = b.work / "train_config.json"
        config.write_text(json.dumps(t["train_config"]))
        seed = harness.seed31(b.seed)
        px = int(t["train_config"]["data"]["resolution"])
        return [f"--config={config}", f"--output_dir={b.work / 'run'}",
                f"--seed={seed}", f"--data.seed={seed}",
                f"--data.train_data_dir={b.work / 'train'}",
                f"--data.caption_jsons={b.work / 'captions.json'}",
                *t.get("overrides", []), *sd_stack.model_argv(self.cfg, px)]

    def setup(self) -> None:
        import jax

        from dcr_tpu.core.config import TrainConfig, parse_cli
        from dcr_tpu.diffusion.sample_hook import make_sample_hook
        from dcr_tpu.diffusion.trainer import Trainer
        from dcr_tpu.parallel import mesh as pmesh

        b, t = self.bench, self.traffic
        sd_stack.write_image_folder(b.work / "train", b.seed, int(t["images"]),
                                    int(t["image_px"]))
        b.log("images_written", images=int(t["images"]))
        self.train_cfg = cfg = parse_cli(TrainConfig, self.train_argv())
        self.shapes = sd_stack.weight_shapes(cfg, b.cache)
        weights = sd_stack.make_weights(self.shapes, b.seed)
        jax.block_until_ready(weights)
        b.log("weights_made")
        self.trainer = trainer = Trainer(cfg, sample_hook=make_sample_hook(),
                                         pretrained_params=weights)
        del weights
        mesh = trainer.mesh
        self.shard = lambda batch: pmesh.shard_batch(mesh, dict(batch))
        self.global_batch = cfg.train_batch_size * jax.local_device_count()
        self.log_every = max(1, int(cfg.log_every))
        b.log("trainer_built", **b.meter.snapshot())
        # the first steps: through the window's own call and feed, on rows
        # that all differ; what the reference will follow is kept
        self.fed: list[dict] = []
        self.losses: list[float] = []
        self.grad_norms = self.change_norms = None
        for k in range(int(t["check_steps"])):
            batch = self._next_batch()
            self.fed.append({name: np.array(batch[name]) for name in
                             ("pixel_values", "input_ids")})
            sharded = self.shard(batch)
            if k == 0:
                # as the loop does before its first step: compile once, run
                # that executable
                trainer._step_flops(sharded)
                b.log("step_compiled", **b.meter.snapshot())
            trainer.state, metrics = trainer._step_call(
                trainer.state, sharded, trainer.train_key)
            self.losses.append(float(jax.device_get(metrics["loss"])))
            if k == 0:
                b1 = cfg.optim.adam_beta1
                mu = adam_mu(trainer.state.opt_state)["unet"]
                self.grad_norms = {name: v / (1.0 - b1) for name, v in
                                   sd_stack.leaf_norms(mu).items()}
        self.change_norms = sd_stack.change_norms(
            self.shapes, "unet", trainer.state.unet_params, b.seed)
        self.metrics = metrics
        b.log("first_steps", losses=self.losses)

    def _next_batch(self):
        while True:
            if self._it is None:
                self._it = self.trainer.loader.epoch(self._epoch)
            batch = next(self._it, None)
            if batch is not None:
                return batch
            self._it, self._epoch = None, self._epoch + 1

    # -- the window -----------------------------------------------------
    def unit(self) -> None:
        import jax

        trainer = self.trainer
        with self.bench.span("data_wait"):
            batch = self._next_batch()
        with self.bench.span("dispatch"):
            sharded = self.shard(batch)
            trainer.state, self.metrics = trainer._step_call(
                trainer.state, sharded, trainer.train_key)
        self.steps += 1
        if self.steps % self.log_every == 0:
            with self.bench.span("fetch"):      # the loop's log boundary
                jax.device_get(self.metrics)

    def drain(self) -> None:
        import jax

        jax.block_until_ready(self.metrics)

    def end_to_end(self, window) -> dict:
        return {"train_images_per_s":
                    window.units * self.global_batch / window.seconds}

    def counters(self, window) -> dict:
        px = int(self.traffic["train_config"]["data"]["resolution"])
        return {"flops_per_unit": flops.train_step_flops(
            self.cfg, px, self.global_batch)}

    # -- after the window -------------------------------------------------
    def release(self) -> None:
        import jax

        if self.trainer is not None:
            loss = float(jax.device_get(self.metrics["loss"]))
            self.bench.log("last_loss", loss=loss, steps=self.steps)
            if self._it is not None:
                self._it.close()
            self.close()
        self.metrics = None
        gc.collect()

    def verify(self, window) -> list:
        import jax

        b, t, tc = self.bench, self.traffic, self.train_cfg
        hyper = {k: getattr(tc.optim, k) for k in (
            "learning_rate", "adam_beta1", "adam_beta2", "adam_epsilon",
            "adam_weight_decay", "max_grad_norm")}
        seed = harness.seed31(b.seed)
        train_key = sd21.stream(jax.random.key(seed), "train")
        with jax.default_matmul_precision("highest"):
            ref = finetune.reference_steps(
                self.cfg, lambda: sd_stack.make_weights(self.shapes, b.seed),
                self.fed, train_key, hyper,
                row_block=int(t["reference"]["row_block"]),
                log=lambda what, **kw: b.log(
                    what, host_rss_bytes=harness.host_rss_bytes(), **kw))
        self.reference = ref
        return compare(self.program_numbers(), ref, t["limits"], b.log)

    def program_numbers(self) -> dict:
        """What the timed path itself produced in its first steps."""
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}

    def close(self) -> None:
        trainer, self.trainer = self.trainer, None
        if trainer is None:
            return
        from dcr_tpu.obs import memwatch

        import jax

        trainer.writer.close()
        memwatch.reset_for_tests()          # stops the sampler thread
        # the 12 GB of state go now, whoever else still holds the trainer:
        # the reference needs the room
        for leaf in jax.tree.leaves(trainer.state):
            leaf.delete()
        trainer.state = None
        del trainer
        gc.collect()


def compare(program: dict, ref: dict, limits: dict, log) -> list:
    """The numbers compared, each beside its limit: the steps' losses, the
    first gradient's norm as the optimizer got it (worst leaf), and the
    parameters' change over the steps (worst leaf among those whose gradient
    is not nought). `program` holds `losses`, `grad_norms`, `change_norms` of
    the timed path (or of a control put in its place)."""
    loss_gaps = [abs(got - want) / abs(want)
                 for got, want in zip(program["losses"], ref["losses"])]
    # the widest of the steps' gaps is the number compared: one step's gap
    # alone does not separate the program from the control (PERF.md)
    checks = [harness.check("loss_worst_step", max(loss_gaps),
                            limits["loss_worst_step"])]
    gap, leaf = finetune.worst_leaf_gap(program["grad_norms"], ref["grad_norms"])
    checks.append(harness.check("grad_norm_worst_leaf", gap,
                                limits["grad_norm_worst_leaf"]))
    idle = finetune.idle_leaves(ref["raw_grad_norms"])
    gap2, leaf2 = finetune.worst_leaf_gap(program["change_norms"],
                                          ref["change_norms"], skip=idle)
    checks.append(harness.check("change_norm_worst_leaf", gap2,
                                limits["change_norm_worst_leaf"]))
    log("compared", losses=program["losses"], reference_losses=ref["losses"],
        loss_gaps=loss_gaps,
        grad_worst_leaf=leaf, change_worst_leaf=leaf2,
        # every leaf against its own norm alone: read, held to no limit
        grad_plain_worst=finetune.worst_leaf_gap(
            program["grad_norms"], ref["grad_norms"], against_median=False),
        change_plain_worst=finetune.worst_leaf_gap(
            program["change_norms"], ref["change_norms"], skip=idle,
            against_median=False),
        idle_leaves=sorted(idle)[:8], n_idle=len(idle),
        grad_global_norm=ref["grad_global_norm"])
    return checks
