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

from dcr_tpu.core.config import TrainConfig, parse_cli, validate_train_config

log = logging.getLogger("dcr_tpu")


def precompute(cfg: TrainConfig) -> dict:
    """Encode the dataset's active indices into cfg.pipe.latent_cache.
    Returns a summary dict (also printed as the CLI's one JSON line)."""
    import jax
    import numpy as np

    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.data import latent_cache as LC
    from dcr_tpu.data.dataset import ObjectAttributeDataset
    from dcr_tpu.data.loader import Batch
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

    t0 = time.time()
    mesh = pmesh.make_mesh(cfg.mesh)
    tokenizer = load_tokenizer(cfg.pretrained_model or None,
                               vocab_size=cfg.model.text_vocab_size,
                               model_max_length=cfg.model.text_max_length)
    dataset = ObjectAttributeDataset(cfg.data, tokenizer)
    # the same param derivation as Trainer.__init__ — equal (seed, model)
    # config => equal frozen params => equal cache fingerprint
    root = rngmod.root_key(cfg.seed)
    models, params = build_models(cfg, rngmod.stream_key(root, "init"),
                                  mesh=mesh)
    frozen = {"vae": params["vae"], "text": params["text"]}
    encode_fn = E.make_encode_stage(cfg, models, mesh, emit="moments")
    fp = LC.cache_fingerprint(cfg, dataset, tokenizer,
                              vae_params=params["vae"],
                              text_params=params["text"])
    writer = LC.LatentCacheWriter(cfg.pipe.latent_cache, fp,
                                  shard_size=cfg.pipe.cache_shard_size)

    bsz = cfg.train_batch_size * jax.local_device_count()
    n = len(dataset)
    key = rngmod.stream_key(root, "train")
    done = 0
    for lo in range(0, n, bsz):
        positions = list(range(lo, min(lo + bsz, n)))
        valid = len(positions)
        # pad the tail to the one compiled batch shape; padded rows are
        # encoded and discarded
        while len(positions) < bsz:
            positions.append(positions[-1])
        examples = [dataset.get(p) for p in positions]
        batch = Batch(
            pixel_values=np.stack([e.pixel_values for e in examples]),
            input_ids=np.stack([e.input_ids for e in examples]),
            index=np.asarray([e.index for e in examples], np.int64),
        )
        sharded = pmesh.shard_batch(mesh, dict(batch))
        enc = encode_fn(frozen, sharded, key, np.uint32(0))
        writer.add(np.asarray(batch["index"][:valid]),
                   np.asarray(jax.device_get(enc["mean"]))[:valid],
                   np.asarray(jax.device_get(enc["std"]))[:valid],
                   np.asarray(jax.device_get(enc["ctx"]))[:valid])
        done += valid
        if (lo // bsz) % 20 == 0:
            log.info("precompute: %d/%d indices encoded", done, n)
    manifest = writer.finalize()
    summary = {"cache": cfg.pipe.latent_cache, "indices": done,
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
