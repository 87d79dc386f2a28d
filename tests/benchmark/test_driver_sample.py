"""The `bulk_sample` driver end to end at the `tiny` preset on the CPU, both
sampling cells; the plain reference against the program's own modules; the
planted fault; and the control in fp8."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, sd_stack
from benchmark.reference import sd21
from tests.benchmark import tinyroot
from tests.benchmark.conftest import last_line

SD21 = json.loads((harness.ROOT / "benchmark" / "configs" / "sd21.json").read_text())
CFG = {**SD21, **tinyroot.TINY_SD21}


@pytest.mark.parametrize("cell,trace", [("sd21-sample-256", 0), ("sd21-sample-512", 1)])
def test_runs_end_to_end(tiny, capsys, cell, trace):
    assert harness.main(["--workload", cell, "--seed", str(2**31 + 9),
                         "--seconds", "0.5", "--trace", str(trace)]) == 0
    result, before = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    assert set(result["checks"]) == {"bad_images", "image_rms_worst"}
    if not trace:
        assert set(result["rehearsal"]) == {"sample_images_per_s", "setup_s"}
    window = next(x for x in before if x["bench"] == "window")
    assert window["compilations_in_window"] == 0 and window["units"] >= 1


def test_an_image_altered_where_it_is_produced_is_not_correct(tiny, capsys, monkeypatch):
    from dcr_tpu.sampling import sampler as S

    real = S.make_sampler

    def altered(cfg, models, mesh):
        fn = real(cfg, models, mesh)
        return lambda *args: jnp.clip(fn(*args) + 0.05, 0.0, 1.0)

    monkeypatch.setattr(S, "make_sampler", altered)
    assert harness.main(["--workload", "sd21-sample-256", "--seed", "4",
                         "--seconds", "0.3", "--trace", "0"]) == 0
    result, _ = last_line(capsys)
    assert result["correct"] is False
    c = result["checks"]["image_rms_worst"]
    assert c["value"] > 10 * c["limit"]


@pytest.fixture(scope="module")
def stack():
    from dcr_tpu.core.config import TrainConfig, parse_cli
    from dcr_tpu.diffusion.trainer import build_modules

    tc = parse_cli(TrainConfig, sd_stack.model_argv(CFG, 16) + ["--mixed_precision=no"])
    shapes = sd_stack.weight_shapes(tc)
    return build_modules(tc), shapes, sd_stack.make_weights(shapes, 2**31 + 77)


def test_seeded_weights_are_the_seeds_alone(stack):
    _, shapes, w = stack
    again = sd_stack.make_weights(shapes, 2**31 + 77)
    other = sd_stack.make_weights(shapes, 78)
    leaf = lambda t: np.asarray(t["unet"]["conv_in"]["kernel"])      # noqa: E731
    assert (leaf(w) == leaf(again)).all() and (leaf(w) != leaf(other)).mean() > 0.99
    k = leaf(w)                                # uniform, deviation 1/sqrt(fan_in)
    assert k.std() == pytest.approx(1 / np.sqrt(9 * 4), rel=0.1)
    assert jax.tree.structure(w) == jax.tree.structure(shapes)
    # moved leaves are told from unmoved ones without a second copy
    moved = jax.tree.map(lambda x: x, w["unet"])
    moved["conv_in"]["kernel"] = moved["conv_in"]["kernel"] + 0.5
    norms = sd_stack.change_norms(shapes, "unet", moved, 2**31 + 77)
    assert norms["conv_in/kernel"] == pytest.approx(0.5 * np.sqrt(k.size), rel=1e-5)
    # unmoved leaves read nought, to the one rounding by which a fused
    # multiply-subtract differs from the stored product
    assert max(v for n, v in norms.items() if n != "conv_in/kernel") < 1e-6


def test_reference_agrees_with_the_programs_modules_in_float32(stack):
    """Text tower, UNet, VAE encoder and decoder, the program's flax modules
    against the plain reference on the same seeded weights: float32 round-off,
    once the GEGLU gate uses the tanh GELU the program uses (the published erf
    differs by 1e-4 at this size: PERF.md, Open questions)."""
    models, _, w = stack
    kx, kp, kz = jax.random.split(jax.random.key(1), 3)
    ids = jnp.asarray(sd_stack.prompt_ids(3, 2, 16, 1000))
    x = jax.random.normal(kx, (2, 4, 4, 4))
    t = jnp.array([10, 900])
    with jax.default_matmul_precision("highest"):
        ctx = models.text_encoder.apply({"params": w["text"]}, ids).last_hidden_state
        np.testing.assert_allclose(sd21.text_encode(sd21.EXACT, w["text"], CFG, ids),
                                   ctx, atol=2e-5)
        want = models.unet.apply({"params": w["unet"]}, x, t, ctx)
        got = sd21.unet(sd21.Ops(gelu="tanh"), w["unet"], CFG, x, t, ctx)
        np.testing.assert_allclose(got, want, atol=2e-5)
        erf = sd21.unet(sd21.EXACT, w["unet"], CFG, x, t, ctx)
        assert 1e-6 < float(jnp.abs(erf - want).max()) < 1e-3
        px = jax.random.normal(kp, (2, 32, 32, 3))
        dist = models.vae.apply({"params": w["vae"]}, px, method=models.vae.encode)
        mean, logvar = sd21.vae_encode(sd21.EXACT, w["vae"], CFG, px)
        np.testing.assert_allclose(mean, dist.mean, atol=2e-5)
        np.testing.assert_allclose(logvar, dist.logvar, atol=2e-5)
        z = jax.random.normal(kz, (2, 16, 16, 4))
        np.testing.assert_allclose(
            sd21.vae_decode(sd21.EXACT, w["vae"], CFG, z),
            models.vae.apply({"params": w["vae"]}, z, method=models.vae.decode),
            atol=2e-5)


def test_the_control_in_fp8_fails(stack):
    """The reference computed from fp8 operands, put in the program's place:
    its images lie further from the float32 reference's than the limit."""
    _, _, w = stack
    ids = jnp.asarray(sd_stack.prompt_ids(3, 2, 16, 1000))
    unc = jnp.asarray(np.full((2, 16), 999, np.int32))
    noise = jax.random.normal(jax.random.key(2), (2, 2, 2, 4))
    kw = dict(steps=3, guidance=7.5)
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(sd21.sample_images(sd21.EXACT, w, CFG, ids, unc, noise, **kw))
        fp8 = np.asarray(sd21.sample_images(sd21.Ops(quant="fp8"), w, CFG, ids, unc, noise, **kw))
    rms = float(np.sqrt(np.mean((exact - fp8) ** 2)))
    limit = harness.load_cell("sd21-sample-256", tinyroot.REPO).traffic["limits"]
    assert np.isfinite(fp8).all()
    assert rms > 1e-3                    # the tiny cell's limit (tinyroot.py)
    assert limit["image_rms_worst"] > 0
