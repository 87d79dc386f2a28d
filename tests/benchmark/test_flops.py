"""benchmark/lib/flops.py against hand counts at tiny shapes, against XLA's
cost analysis at the `tiny` preset (a cross-check, never a numerator), and the
published SD-2.1 parameter count against the configuration's file."""
import json

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import flops as F, harness, sd_stack
from tests.benchmark import tinyroot

SD21 = json.loads((harness.ROOT / "benchmark" / "configs" / "sd21.json").read_text())


def tiny_cfg() -> dict:
    return {**SD21, **tinyroot.TINY_SD21}


def test_hand_counts():
    assert F.conv_flops(4, 4, 3, 2, 5) == 2 * 16 * 9 * 2 * 5
    assert F.linear_flops(7, 3, 5) == 2 * 7 * 3 * 5
    assert F.attention_flops(2, 6, 4, 8) == 4 * 2 * 6 * 4 * 8
    # a resnet block 3 -> 5 channels on a 2x2 map with an 8-wide time embedding
    assert F._resnet(2, 2, 3, 5, 8) == (2 * 4 * 9 * 3 * 5 + 2 * 4 * 9 * 5 * 5
                                        + 2 * 8 * 5 + 2 * 4 * 3 * 5)
    # one text layer of width 4, 2 heads, 3 tokens: 4 projections, attention, MLP
    cfg = {"text_encoder": {"hidden_size": 4, "max_position_embeddings": 3,
                            "num_attention_heads": 2, "num_hidden_layers": 1}}
    assert F.text_flops(cfg) == 4 * 2 * 3 * 4 * 4 + 4 * 2 * 3 * 3 * 2 + 2 * 2 * 3 * 4 * 16


def test_sd21_counts_are_the_ones_perf_md_quotes():
    assert F.unet_forward_flops(SD21, 32) == pytest.approx(0.1811e12, rel=1e-3)
    assert F.unet_forward_flops(SD21, 64) == pytest.approx(0.8043e12, rel=1e-3)
    assert F.train_step_flops(SD21, 256, 16) == pytest.approx(16 * 0.8611e12, rel=1e-3)
    assert F.sample_batch_flops(SD21, 256, 10, 50) == pytest.approx(188.2e12, rel=1e-3)
    assert F.sample_batch_flops(SD21, 512, 4, 50) == pytest.approx(332.1e12, rel=1e-3)


def _model(px: int):
    from dcr_tpu.core.config import TrainConfig, parse_cli
    from dcr_tpu.diffusion.trainer import build_modules

    tc = parse_cli(TrainConfig, sd_stack.model_argv(tiny_cfg(), px)
                   + ["--mixed_precision=no"])
    return tc, build_modules(tc)


# the algorithm's count over XLA's, as read on 2026-10-01 (JAX 0.9.0, CPU):
# unet 1.133, vae_encoder 0.980, vae_decoder 0.989, text 0.736
BAND = {"unet": (1.0, 1.3), "vae_encoder": (0.9, 1.1), "vae_decoder": (0.9, 1.1),
        "text": (0.6, 0.9)}


@pytest.mark.parametrize("part", ["unet", "vae_encoder", "vae_decoder", "text"])
def test_against_xla_cost_analysis_at_the_tiny_preset(part):
    """XLA counts what the compiler emitted on the CPU, and differs from the
    algorithm's count in two ways that pull apart. It adds the norms,
    activations and softmax, which flops.py leaves out (large at these widths
    of 8 to 64 channels, small at SD-2.1's). And it leaves out the taps of a
    3x3 convolution that fall on the zero padding, which flops.py counts as
    the usual convention does (k*k*Cin*Cout an output): on the 8x8 and 4x4
    maps here that is 16% and 31% of a convolution, on SD-2.1's 32x32 it is
    6%. So the two agree only within a band: the UNet (small maps, many
    padded taps) reads 13% over XLA, the VAE (large maps) within 2%, and the
    32-wide text tower, where GELU, softmax and LayerNorm are a quarter of
    XLA's count, 26% under it."""
    cfg = tiny_cfg()
    tc, models = _model(64)
    shapes = sd_stack.weight_shapes(tc)
    S = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt)       # noqa: E731
    if part == "unet":
        fn = lambda p, x, t, c: models.unet.apply({"params": p}, x, t, c)  # noqa: E731
        args = (shapes["unet"], S(1, 8, 8, 4), S(1, dt=jnp.int32), S(1, 16, 32))
        mine = F.unet_forward_flops(cfg, 8)
    elif part == "vae_encoder":
        fn = lambda p, x: models.vae.apply({"params": p}, x, method=models.vae.encode)  # noqa: E731
        args = (shapes["vae"], S(1, 64, 64, 3))
        mine = F.vae_encoder_flops(cfg, 64)
    elif part == "vae_decoder":
        fn = lambda p, z: models.vae.apply({"params": p}, z, method=models.vae.decode)  # noqa: E731
        args = (shapes["vae"], S(1, 32, 32, 4))
        mine = F.vae_decoder_flops(cfg, 64)
    else:
        fn = lambda p, i: models.text_encoder.apply({"params": p}, i).last_hidden_state  # noqa: E731
        args = (shapes["text"], S(1, 16, dt=jnp.int32))
        mine = F.text_flops(cfg)
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    xla = float((cost[0] if isinstance(cost, list) else cost)["flops"])
    low, high = BAND[part]
    assert low < mine / xla < high, (part, mine, xla)


def test_the_configuration_file_holds_sd21_as_published():
    """Every size in benchmark/configs/sd21.json maps onto ModelConfig()'s
    defaults (which mirror the published files), and the UNet it describes has
    the published 865,910,724 parameters."""
    from dcr_tpu.core.config import ModelConfig, TrainConfig, parse_cli

    tc = parse_cli(TrainConfig, sd_stack.model_argv(SD21, 256))
    assert tc.model == ModelConfig(sample_size=32)
    unet = sd_stack.weight_shapes(tc)["unet"]
    count = sum(x.size for x in jax.tree.leaves(unet))
    assert count == SD21["derived"]["unet_parameters"] == 865_910_724
    heads = [c // SD21["derived"]["attention_head_width"]
             for c in SD21["unet"]["block_out_channels"]]
    assert heads == SD21["unet"]["attention_head_dim"]
    assert SD21["reduced"] == []
