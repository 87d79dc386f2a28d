"""dcr-precompute-latents: build a persistent latent cache once, train every
regime against it (dcr-pipe, data/latent_cache.py).

    dcr-precompute-latents --pipe.latent_cache=<dir> \
        --data.train_data_dir=... --data.random_flip=false [--key=value ...]

Takes the SAME TrainConfig as dcr-train: the cache fingerprint hashes the
frozen VAE/text params (derived from ``seed``/``model`` exactly as the
Trainer derives them), the dataset path list, resolution/crop, the caption
regime, and the tokenizer — so ``dcr-train --pipe.latent_cache=<dir>`` with
a matching config verifies-and-loads, and anything else is a readable
fingerprint-mismatch error, never silent training on the wrong latents.

What is cached per active dataset index: the VAE posterior **moments**
(mean/std — the per-occurrence posterior *sample* stays a train-time draw on
the ``vae_sample`` RNG stream, so one cache serves every epoch and every
duplication regime) and the frozen text embedding of that index's caption
realization. Requires ``data.random_flip=false`` (a cached latent encodes
one pixel realization) and a frozen text encoder.
"""

from __future__ import annotations

import json
import logging
import time
from collections.abc import Sequence
from concurrent.futures import ThreadPoolExecutor

from dcr_tpu.core import tracing
from dcr_tpu.core.config import (TEXT_TOWERS, TrainConfig, parse_cli,
                                 validate_train_config)

log = logging.getLogger("dcr_tpu")


class PrecomputeJob:
    """The encode of one dataset into one latent cache: set-up once, then
    :meth:`encode_batch` per batch, then :meth:`finalize`. `precompute` below
    is the CLI's loop over it; a caller that must bound the work by seconds
    (the benchmark) drives the same per-batch body itself.

    The body keeps the device ahead of the host: a caller that names the
    batches it will ask for next (`then`) has them decoded and handed to the
    device BEFORE the current batch is waited for, and the current batch's
    rows are put on the disk by one writer thread while the device encodes
    the next. Decoded serially behind the device, the rate followed the
    host's fsync from run to run (PERF.md, PR 28); with one batch ahead, a
    load or a write that stalls longer than the host's slack in a unit still
    stops the device, so the CLI names `AHEAD` batches (PERF.md, PR 35).

    `pretrained_params` (``{"vae": ..., "text": ...}``, any subset) replaces
    the seeded initialisers' trees, as `Trainer(pretrained_params=...)` does.
    """

    #: batches the CLI's loop names as next: on the device behind the one
    #: waited for
    AHEAD = 2

    def __init__(self, cfg: TrainConfig, *,
                 pretrained_params: dict | None = None):
        import jax

        from dcr_tpu.core import rng as rngmod
        from dcr_tpu.data import latent_cache as LC
        from dcr_tpu.data.dataset import ObjectAttributeDataset
        from dcr_tpu.data.tokenizer import load_tokenizer
        from dcr_tpu.diffusion import encode_stage as E
        from dcr_tpu.diffusion.trainer import build_models
        from dcr_tpu.parallel import mesh as pmesh

        if not cfg.pipe.latent_cache:
            raise SystemExit("dcr-precompute-latents: set --pipe.latent_cache="
                             "<cache dir>")
        # validate_pipe_config (via validate_train_config) enforces the cache
        # compatibility rules — frozen text encoder, no caption-redrawing
        # regimes, random_flip=false, center_crop=true — with messages naming
        # the flag to flip; train with the SAME settings or the fingerprint
        # rejects the cache.
        validate_train_config(cfg)
        self.mesh = pmesh.make_mesh(cfg.mesh)
        tokenizer = load_tokenizer(cfg.pretrained_model or None,
                                   vocab_size=cfg.model.text_vocab_size,
                                   model_max_length=cfg.model.text_max_length)
        self.dataset = ObjectAttributeDataset(cfg.data, tokenizer)
        # the same param derivation as Trainer.__init__ — equal (seed, model)
        # config => equal frozen params => equal cache fingerprint
        root = rngmod.root_key(cfg.seed)
        self.models, self.frozen = build_models(
            cfg, rngmod.stream_key(root, "init"), mesh=self.mesh,
            pretrained=pretrained_params, parts=("vae", "text"))
        self.encode_fn = E.make_encode_stage(cfg, self.models, self.mesh,
                                             emit="moments")
        gauge_layers(cfg.model)
        fp = LC.cache_fingerprint(cfg, self.dataset, tokenizer,
                                  vae_params=self.frozen["vae"],
                                  text_params=self.frozen["text"])
        self.writer = LC.LatentCacheWriter(cfg.pipe.latent_cache, fp,
                                           shard_size=cfg.pipe.cache_shard_size)
        self.batch_size = cfg.train_batch_size * jax.local_device_count()
        self.key = rngmod.stream_key(root, "train")
        self.done = 0
        self._ahead = {}        # number -> (indices, result not waited for), `then`'s order
        self._writer = ThreadPoolExecutor(1, thread_name_prefix="precompute-write")
        self._writing = None    # the one shard's rows the writer may hold

    def __len__(self) -> int:
        """Batches in one pass over the dataset."""
        return -(-len(self.dataset) // self.batch_size)

    def load_batch(self, number: int):
        """Batch `number` of the pass, decoded, tokenized and placed; the
        tail is padded to the one compiled shape. -> (sharded batch, indices
        of its valid rows)."""
        import numpy as np

        from dcr_tpu.data.loader import Batch
        from dcr_tpu.parallel import mesh as pmesh

        lo, n, bsz = number * self.batch_size, len(self.dataset), self.batch_size
        positions = list(range(lo, min(lo + bsz, n)))
        valid = len(positions)
        # padded rows are encoded and discarded
        positions += [positions[-1]] * (bsz - valid)
        examples = [self.dataset.get(p) for p in positions]
        batch = Batch(
            pixel_values=np.stack([e.pixel_values for e in examples]),
            input_ids=np.stack([e.input_ids for e in examples]),
            index=np.asarray([e.index for e in examples], np.int64),
        )
        return (pmesh.shard_batch(self.mesh, dict(batch)),
                np.asarray(batch["index"][:valid]))

    def _start(self, number: int):
        """Load batch `number` and hand it to the device. -> (indices of its
        valid rows, the encode's result, not waited for)."""
        import numpy as np

        with tracing.span("precompute/load", batch=number):
            sharded, index = self.load_batch(number)
        return index, self.encode_fn(self.frozen, sharded, self.key,
                                     np.uint32(0))

    def _write(self, number: int, out: dict) -> None:
        with tracing.span("precompute/write", batch=number):
            self.writer.add(out["index"], out["mean"], out["std"], out["ctx"])

    def encode_batch(self, number: int,
                     then: int | Sequence[int] | None = None) -> dict:
        """The per-batch body: load, encode, fetch, write. `then` is the
        batch the caller will ask for next, or the batches it will ask for
        next in that order (None: none); a batch named before is not handed
        over again. Returns what was fetched (`mean`, `std`, `ctx` of the
        valid rows, and `index`); the rows are on the disk after the next
        call or :meth:`drain`."""
        import jax
        import numpy as np

        ahead, self._ahead = self._ahead, {}
        index, enc = ahead.pop(number) if number in ahead else self._start(number)
        for later in ((then,) if isinstance(then, int) else then or ()):
            # queued behind this batch: the device goes on without the host
            self._ahead[later] = (ahead.pop(later) if later in ahead
                                  else self._start(later))
        with tracing.span("precompute/encode", batch=number):
            # the wait alone: the fetch below then times the copy alone
            enc = jax.block_until_ready(enc)
        with tracing.span("precompute/fetch", batch=number):
            valid = len(index)
            out = {name: np.asarray(jax.device_get(enc[name]))[:valid]
                   for name in ("mean", "std", "ctx")}
            if "moe" in enc:
                count_routing(jax.device_get(enc["moe"]))
        out["index"] = index
        self._wait_for_writer()
        self._writing = self._writer.submit(self._write, number, out)
        self.done += valid
        return out

    def _wait_for_writer(self) -> None:
        writing, self._writing = self._writing, None
        if writing is not None:
            writing.result()        # a failed write is raised here

    def drain(self) -> None:
        """Wait for what is in flight: the batches handed to the device
        ahead of their calls, and the writer."""
        import jax

        jax.block_until_ready([enc for _, enc in self._ahead.values()])
        self._wait_for_writer()

    def close(self) -> None:
        """Wait for what is in flight and stop the writer thread."""
        self.drain()
        self._ahead = {}
        self._writer.shutdown()

    def finalize(self):
        """Flush the last shard and write the manifest; -> its path."""
        self.close()
        return self.writer.finalize()


def count_routing(stats: dict) -> None:
    """An expert tower's routing counts of one call into the moe/* counters
    (`stats`: the host copy of `TextTowerOutput.moe_stats`; a tower gives the
    counts it has: `unheld` is not every tower's, and a stack without an
    expert layer gives none)."""
    reg = tracing.registry()
    for counter, name in (("moe/assignments_total", "assignments"),
                          ("moe/assignments_held_total", "held"),
                          ("moe/assignments_zero_total", "zero"),
                          ("moe/assignments_dropped_total", "dropped"),
                          ("moe/tokens_unheld_total", "unheld")):
        reg.counter(counter).inc(int(stats.get(name, 0)))
    reg.gauge("moe/held_expert_load_max").set(int(stats.get("held_load_max", 0)))


def gauge_layers(model) -> None:
    """The depth of a language-model tower (`model`: the ModelConfig) into the
    gauges `tower/layers` and `moe/layers` (the layers whose counts
    `count_routing` adds up), so that a mean load an expert a layer can be had
    from the registry alone."""
    block = TEXT_TOWERS[model.text_tower].block
    if block is not None:
        layers, expert_layers = getattr(model, block).layer_counts()
        tracing.registry().gauge("tower/layers").set(layers)
        tracing.registry().gauge("moe/layers").set(expert_layers)


def precompute(cfg: TrainConfig) -> dict:
    """Encode the dataset's active indices into cfg.pipe.latent_cache.
    Returns a summary dict (also printed as the CLI's one JSON line)."""
    t0 = time.time()
    job = PrecomputeJob(cfg)
    n = len(job.dataset)
    batches = len(job)
    for number in range(batches):
        job.encode_batch(number, range(number + 1,
                                       min(number + 1 + job.AHEAD, batches)))
        if number % 20 == 0:
            log.info("precompute: %d/%d indices encoded", job.done, n)
    manifest = job.finalize()
    summary = {"cache": cfg.pipe.latent_cache, "indices": job.done,
               "shards": len(json.loads(manifest.read_text())["shards"]),
               "seconds": round(time.time() - t0, 1)}
    log.info("latent cache written: %s", summary)
    return summary


def main(argv=None) -> None:
    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s", force=True)
    cfg = parse_cli(TrainConfig, argv)
    print(json.dumps(precompute(cfg)))


if __name__ == "__main__":
    main()
