import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcr_tpu.core.config import MeshConfig, ModelConfig, SampleConfig
from dcr_tpu.core import rng as rngmod
from dcr_tpu.data.tokenizer import HashTokenizer
from dcr_tpu.diffusion.trainer import build_models
from dcr_tpu.models import schedulers as S
from dcr_tpu.parallel import mesh as pmesh
from dcr_tpu.sampling import prompts as P
from dcr_tpu.sampling.sampler import (SAMPLERS, denoise_images, make_sampler,
                                      sampler_grid, scheduler_step)


@pytest.fixture(scope="module")
def tiny_models():
    from dcr_tpu.core.config import TrainConfig

    cfg = TrainConfig()
    cfg.model = ModelConfig.tiny()
    return build_models(cfg, jax.random.key(0))


def _sample_cfg(**kw):
    d = dict(resolution=16, num_inference_steps=4, guidance_scale=7.5,
             sampler="ddim", im_batch=2, seed=0)
    d.update(kw)
    return SampleConfig(**d)


def test_sampler_shapes_and_determinism(tiny_models, cpu_devices):
    models, params = tiny_models
    mesh = pmesh.make_mesh(MeshConfig())
    cfg = _sample_cfg()
    sampler = make_sampler(cfg, models, mesh)
    tok = HashTokenizer(models.text_encoder.config.text_vocab_size,
                        models.text_encoder.config.text_max_length)
    ids = np.repeat(tok(["a church", "a truck"]), 4, axis=0)  # [8, L]
    unc = np.broadcast_to(tok([""])[0], ids.shape).copy()
    p = {"unet": params["unet"], "vae": params["vae"], "text": params["text"]}
    imgs = np.asarray(sampler(p, ids, unc, rngmod.root_key(1)))
    assert imgs.shape == (8, 16, 16, 3)
    assert imgs.min() >= 0.0 and imgs.max() <= 1.0
    assert np.isfinite(imgs).all()
    imgs2 = np.asarray(sampler(p, ids, unc, rngmod.root_key(1)))
    np.testing.assert_array_equal(imgs, imgs2)
    imgs3 = np.asarray(sampler(p, ids, unc, rngmod.root_key(2)))
    assert not np.array_equal(imgs, imgs3)


@pytest.mark.parametrize("sampler_name", ["dpm++", "ddpm"])
def test_other_samplers_run(tiny_models, cpu_devices, sampler_name):
    models, params = tiny_models
    mesh = pmesh.make_mesh(MeshConfig())
    cfg = _sample_cfg(sampler=sampler_name)
    sampler = make_sampler(cfg, models, mesh)
    tok = HashTokenizer(models.text_encoder.config.text_vocab_size,
                        models.text_encoder.config.text_max_length)
    ids = np.repeat(tok(["x"]), 8, axis=0)
    unc = np.broadcast_to(tok([""])[0], ids.shape).copy()
    p = {"unet": params["unet"], "vae": params["vae"], "text": params["text"]}
    imgs = np.asarray(sampler(p, ids, unc, rngmod.root_key(0)))
    assert imgs.shape == (8, 16, 16, 3) and np.isfinite(imgs).all()


def test_rand_noise_lam_changes_output(tiny_models, cpu_devices):
    models, params = tiny_models
    mesh = pmesh.make_mesh(MeshConfig())
    tok = HashTokenizer(models.text_encoder.config.text_vocab_size,
                        models.text_encoder.config.text_max_length)
    ids = np.repeat(tok(["x"]), 8, axis=0)
    unc = np.broadcast_to(tok([""])[0], ids.shape).copy()
    p = {"unet": params["unet"], "vae": params["vae"], "text": params["text"]}
    base = np.asarray(make_sampler(_sample_cfg(), models, mesh)(p, ids, unc,
                                                                rngmod.root_key(1)))
    noised = np.asarray(make_sampler(_sample_cfg(rand_noise_lam=0.5), models, mesh)(
        p, ids, unc, rngmod.root_key(1)))
    assert not np.array_equal(base, noised)


@pytest.mark.parametrize("sampler_name", list(SAMPLERS))
def test_shared_loop_matches_plain_loop_over_scheduler_step(tiny_models,
                                                            cpu_devices,
                                                            sampler_name):
    """The seam both builders stand on: denoise_images on fixed ctx and x
    reproduces a hand-unrolled python loop over scheduler_step (the reference
    loop of test_dpmpp_fast_scan_matches_dense_reference_loop, for every row
    of the table, dense plan). The ancestral row gets the SAME per-step noise
    on both sides."""
    models, params = tiny_models
    steps, guidance = 5, 3.0
    k = jax.random.key(11)
    x0 = jax.random.normal(jax.random.fold_in(k, 0), (2, 4, 4, 4))
    ctx = jax.random.normal(
        jax.random.fold_in(k, 1),
        (4, models.text_encoder.config.text_max_length,
         models.text_encoder.config.text_hidden_size))

    def step_noise(i):
        return jax.random.normal(jax.random.fold_in(k, 100 + i), x0.shape)

    drawn = []

    def counted_noise(i):
        drawn.append(i)
        return step_noise(i)

    p = {"unet": params["unet"], "vae": params["vae"]}
    images = np.asarray(jax.jit(lambda p, ctx, x: denoise_images(
        models, p, ctx, x, sampler=sampler_name, steps=steps,
        guidance=guidance, step_noise=counted_noise))(p, ctx, x0))
    # nothing is drawn, and nothing traced, unless the row says so
    assert bool(drawn) == SAMPLERS[sampler_name].draws_noise

    ts, prev_ts, lof = sampler_grid(sampler_name, models.schedule, steps)
    x, dpm = x0, S.dpm_init_state(x0.shape)
    for i in range(steps):
        t, prev_t = int(ts[i]), int(prev_ts[i])
        pred = models.unet.apply({"params": p["unet"]},
                                 jnp.concatenate([x, x], axis=0),
                                 jnp.full((4,), t, jnp.int32), ctx)
        pred_u, pred_c = jnp.split(pred, 2, axis=0)
        x, dpm = scheduler_step(
            sampler_name, models.schedule, pred_u + guidance * (pred_c - pred_u),
            x, t, prev_t, dpm, force_first_order=bool(lof) and i == steps - 1,
            noise=step_noise(i))
    ref = models.vae.apply({"params": p["vae"]},
                           x / models.vae.config.vae_scaling_factor,
                           method=models.vae.decode)
    ref = np.asarray(jnp.clip(ref * 0.5 + 0.5, 0.0, 1.0))
    np.testing.assert_allclose(images, ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("sampler_name", [*SAMPLERS, "euler"])
def test_every_gate_accepts_exactly_the_tables_names(sampler_name):
    """A sampler's name is a row of ONE table: the config validator, the
    serve bucket validator and scheduler_step accept its keys and refuse one
    more, each with the error type it always raised."""
    from dcr_tpu.core.config import ServeConfig, validate_serve_config
    from dcr_tpu.serve.queue import GenBucket, InvalidRequestError
    from dcr_tpu.serve.worker import validate_bucket

    x = jnp.zeros((1, 2, 2, 1))
    gates = [
        (ValueError,
         lambda: validate_serve_config(ServeConfig(sampler=sampler_name))),
        (InvalidRequestError,
         lambda: validate_bucket(GenBucket(16, 2, 7.5, sampler_name, 0.0),
                                 vae_scale=4)),
        (ValueError,
         lambda: scheduler_step(sampler_name, S.make_schedule(), x, x, 500,
                                400, S.dpm_init_state(x.shape), noise=x)),
        (ValueError, lambda: sampler_grid(sampler_name, S.make_schedule(), 4)),
    ]
    for error, gate in gates:
        if sampler_name in SAMPLERS:
            gate()
        else:
            with pytest.raises(error):
                gate()


def test_prompt_lists_all_styles(tmp_path):
    tok = HashTokenizer(1000, 16)
    assert P.build_prompt_list("nolevel", 3, seed=0, tokenizer=tok) == ["An image"] * 3
    cl = P.build_prompt_list("classlevel", 5, seed=0, tokenizer=tok)
    assert len(cl) == 5 and all(p.startswith("An image of ") for p in cl)
    assert cl == P.build_prompt_list("classlevel", 5, seed=0, tokenizer=tok)
    assert cl != P.build_prompt_list("classlevel", 5, seed=1, tokenizer=tok)

    caps = {f"img{i}": [f"caption number {i}", "alt"] for i in range(10)}
    j = tmp_path / "caps.json"
    j.write_text(json.dumps(caps))
    bl = P.build_prompt_list("instancelevel_blip", 4, seed=0, tokenizer=tok,
                             caption_json=j)
    assert len(bl) == 4 and all(p.startswith("caption number") for p in bl)

    rnd_caps = {f"img{i}": [str([i + 1, i + 2, i + 3])] for i in range(5)}
    j2 = tmp_path / "rnd.json"
    j2.write_text(json.dumps(rnd_caps))
    rl = P.build_prompt_list("instancelevel_random", 3, seed=0, tokenizer=tok,
                             caption_json=j2)
    assert all(len(p.split()) == 3 for p in rl)

    with pytest.raises(ValueError):
        P.build_prompt_list("instancelevel_blip", 2, seed=0, tokenizer=tok)


def test_prompt_augmentations(tmp_path):
    tok = HashTokenizer(1000, 16)
    rng = np.random.default_rng(0)
    base = "a photo of a church"
    n = P.prompt_augmentation(base, "rand_numb_add", tokenizer=tok, rng=rng)
    assert len(n.split()) == 7
    assert sum(w.isdigit() for w in n.split()) == 2
    w = P.prompt_augmentation(base, "rand_word_add", tokenizer=tok, rng=rng)
    assert len(w.split()) == 7
    r = P.prompt_augmentation(base, "rand_word_repeat", tokenizer=tok, rng=rng)
    assert len(r.split()) == 7 and set(r.split()) == set(base.split())
    with pytest.raises(ValueError):
        P.prompt_augmentation(base, "bogus", tokenizer=tok, rng=rng)
    # augs gate: only instancelevel_blip (reference diff_inference.py:241-242)
    caps = {"a": ["c"]}
    j = tmp_path / "c.json"
    j.write_text(json.dumps(caps))
    with pytest.raises(ValueError):
        P.build_prompt_list("nolevel", 2, seed=0, tokenizer=tok, rand_augs="rand_word_add")


def test_save_prompts(tmp_path):
    path = P.save_prompts(["a", "b"], tmp_path / "out")
    assert path.read_text() == "a\nb\n"
