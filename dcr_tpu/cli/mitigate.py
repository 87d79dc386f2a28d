"""dcr-mitigate: inference-time mitigation demo on known-replication prompts.

Reference sd_mitigation.py (43-113): generate from a fixed list of 12 LAION
prompts that reliably reproduce training images in stock SD-1.4, with the
inference-time mitigations (--rand_noise_lam embedding noise, --rand_augs
prompt augmentation) toggled — the before/after of the mitigation paper's
headline figure. The prompt list is the experimental fixture from
sd_mitigation.py:81 (paper: arXiv:2305.20086), seeds 2/42 per README.md:66-69.
"""

from __future__ import annotations

import logging
import sys

from dcr_tpu.core.config import SampleConfig, parse_cli
from dcr_tpu.sampling.pipeline import generate
from dcr_tpu.sampling.prompts import prompt_augmentation
from dcr_tpu.core.rng import host_python_rng
from dcr_tpu.data.tokenizer import load_tokenizer

# the 12 known-replication LAION prompts (fixture from the mitigation paper's
# evaluation; reference sd_mitigation.py:81)
KNOWN_REPLICATION_PROMPTS = (
    "Wall View 002",
    "Wall View 003",
    "Chamberly - Alloy 5 Piece Sectional",
    "Hopped-Up Gaming: East",
    "Pantomine - Driftwood 4 Piece Sectional",
    "Cresson - Pewter 4 Piece Sectional",
    "Jinllingsly - Chocolate 3 Piece Sectional",
    "Maier - Charcoal 2 Piece Sectional",
    "Classic Cars for Sale",
    "Mothers influence on her young hippo",
    "Living in the Light with Ann Graham Lotz",
    "The No Limits Business Woman Podcast",
)


def main(argv=None) -> None:
    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = parse_cli(SampleConfig, argv)
    if not cfg.savepath:
        suffix = "nomit"
        if cfg.rand_noise_lam > 0:
            suffix = f"glam{cfg.rand_noise_lam}"
        if cfg.rand_augs != "none":
            suffix = f"aug_{cfg.rand_augs}"
        cfg.savepath = f"inferences/mitigation_{suffix}"
    prompts = list(KNOWN_REPLICATION_PROMPTS)
    if cfg.rand_augs != "none":
        tokenizer = load_tokenizer(cfg.model_path or None)
        rng = host_python_rng(cfg.seed, "mitigation_augs")
        prompts = [prompt_augmentation(p, cfg.rand_augs, tokenizer=tokenizer,
                                       rng=rng,
                                       repeat_num=cfg.rand_aug_repeats)
                   for p in prompts]
        cfg.rand_augs = "none"  # already applied; don't re-gate in generate()
    out = generate(cfg, modelstyle="fixed", prompts=prompts)
    logging.getLogger("dcr_tpu").info("mitigation generations -> %s", out)


if __name__ == "__main__":
    main()
