"""A temporary copy of the benchmark at the `tiny` preset, for CPU rehearsals.

The copy holds BENCHMARK.json (cells, metrics and drivers as committed) and the
files under `benchmark/`, with every configuration and traffic file SHRUNK:
ModelConfig.tiny()'s sizes for `sd21`, a 4,096-row store, 16 px training,
3-step samplers. Nothing in `run.py` or the harness knows of it: the tests
point `harness.ROOT` at the copy and `harness.PLATFORM` at the CPU, as
tests/test_chip_smoke.py does with `chip_smoke.SIZE`.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_SD21 = {
    "unet": {"attention_head_dim": [4, 8], "block_out_channels": [32, 64],
             "cross_attention_dim": 32, "in_channels": 4, "layers_per_block": 1,
             "norm_num_groups": 8, "out_channels": 4,
             "use_linear_projection": True},
    "text_encoder": {"hidden_act": "gelu", "hidden_size": 32,
                     "max_position_embeddings": 16, "num_attention_heads": 2,
                     "num_hidden_layers": 2, "vocab_size": 1000},
    "vae": {"block_out_channels": [16, 32], "latent_channels": 4,
            "layers_per_block": 1, "norm_num_groups": 8,
            "scaling_factor": 0.18215},
    "derived": {"attention_head_width": 8, "transformer_layers_per_block": 1},
}


def _edit(path: Path, fn) -> None:
    doc = json.loads(path.read_text())
    fn(doc)
    path.write_text(json.dumps(doc, indent=1))


def make(root: Path) -> Path:
    """Copy the committed benchmark under `root` and shrink it."""
    root = Path(root)
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    cfg, work = root / "benchmark" / "configs", root / "benchmark" / "workloads"
    _edit(cfg / "sd21.json", lambda d: d.update(TINY_SD21))
    _edit(cfg / "sscd-laion12m-share.json",
          lambda d: d.update(rows=4096, embed_dim=64))

    def train(d):
        d["train_config"]["train_batch_size"] = 1      # x 8 virtual devices
        d["train_config"]["mixed_precision"] = "no"
        d["train_config"]["log_every"] = 2
        d["train_config"]["data"]["resolution"] = 16
        d["overrides"] = ["--optim.lr_scheduler=constant",
                          "--optim.lr_warmup_steps=0", "--data.num_workers=2",
                          "--optim.learning_rate=1e-3"]
        d.update(images=24, image_px=24, traced_units=2)
        d["reference"]["row_block"] = 4
        # float32 at this size: the program reads 2e-5 (losses), 1e-4 (first
        # gradient), 6e-4 (change) against the reference, all of it the GEGLU
        # gate's tanh against the published erf
        d["limits"] = {"loss_worst_step": 1e-3,
                       "grad_norm_worst_leaf": 1e-2,
                       "change_norm_worst_leaf": 1e-2}

    _edit(work / "train-256.json", train)
    _edit(work / "search-b64-top1.json", lambda d: d.update(
        query_batch=8, pool_batches=4, store_shard_rows=1024, traced_units=3,
        reference={"row_block": 1024, "query_block": 16}))
    for name, px in (("sample-256", 16), ("sample-512", 32)):
        _edit(work / f"{name}.json", lambda d, px=px: d.update(
            resolution=px, im_batch=2, num_inference_steps=3, prompt_pool=3,
            check_images=2,
            limits={"bad_images": 0, "image_rms_worst": 1e-3}))
    return root
