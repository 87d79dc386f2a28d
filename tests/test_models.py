import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcr_tpu.core.config import ModelConfig
from dcr_tpu.models import layers as L
from dcr_tpu.models.clip_text import CLIPTextModel, init_clip_text
from dcr_tpu.models.unet2d import (UNet2DCondition, init_unet,
                                   self_attention_shapes, unet_param_count)
from dcr_tpu.models.vae import AutoencoderKL, init_vae, vae_scale_factor


@pytest.fixture(scope="module")
def tiny():
    return ModelConfig.tiny()


def test_timestep_embedding_properties():
    emb = L.timestep_embedding(jnp.array([0, 10, 999]), 32)
    assert emb.shape == (3, 32)
    # t=0: cos part = 1, sin part = 0 (flip_sin_to_cos puts cos first)
    np.testing.assert_allclose(np.asarray(emb[0, :16]), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(emb[0, 16:]), 0.0, atol=1e-6)
    assert not np.allclose(np.asarray(emb[1]), np.asarray(emb[2]))


def test_unet_forward_shapes(tiny):
    model, params = init_unet(tiny, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 8, 8, 4))
    t = jnp.array([10, 500])
    ctx = jax.random.normal(jax.random.key(2), (2, 16, 32))
    out = model.apply({"params": params}, x, t, ctx)
    assert out.shape == (2, 8, 8, 4)
    assert out.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(out)))


def test_unet_shape_polymorphic_in_spatial(tiny):
    """Same params serve any spatial size (SD trains 256/512 with one net)."""
    model, params = init_unet(tiny, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 16, 16, 4))
    out = model.apply({"params": params}, x, jnp.array([3]),
                      jnp.zeros((1, 16, 32)))
    assert out.shape == (1, 16, 16, 4)


def test_unet_conditioning_matters(tiny):
    model, params = init_unet(tiny, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 8, 8, 4))
    t = jnp.array([100])
    c1 = jax.random.normal(jax.random.key(2), (1, 16, 32))
    c2 = jax.random.normal(jax.random.key(3), (1, 16, 32))
    o1 = model.apply({"params": params}, x, t, c1)
    o2 = model.apply({"params": params}, x, t, c2)
    assert not np.allclose(np.asarray(o1), np.asarray(o2))
    # timestep matters too
    o3 = model.apply({"params": params}, x, jnp.array([900]), c1)
    assert not np.allclose(np.asarray(o1), np.asarray(o3))


def test_unet_bf16_compute(tiny):
    model = UNet2DCondition(tiny, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 8, 8, 4))
    variables = model.init(jax.random.key(0), x, jnp.array([0]), jnp.zeros((1, 16, 32)))
    out = model.apply(variables, x, jnp.array([0]), jnp.zeros((1, 16, 32)))
    assert out.dtype == jnp.float32  # outputs promoted back
    # params stay f32
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(variables["params"]))


def test_unet_grads_flow_everywhere(tiny):
    model, params = init_unet(tiny, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 8, 8, 4))

    def loss(p):
        out = model.apply({"params": p}, x, jnp.array([5]),
                          jnp.ones((1, 16, 32)))
        return jnp.mean(out ** 2)

    grads = jax.grad(loss)(params)
    flat = jax.tree.leaves_with_path(grads)
    dead = [jax.tree_util.keystr(k) for k, g in flat if float(jnp.max(jnp.abs(g))) == 0.0]
    # only params with no path to the loss may be dead; for this architecture
    # everything should receive gradient
    assert not dead, f"dead params: {dead[:10]}"


def test_sd21_unet_param_count():
    """Full-size config lands in the SD-2.1 ballpark (~0.87B params)."""
    cfg = ModelConfig()
    model = UNet2DCondition(cfg)
    x = jnp.zeros((1, 32, 32, 4))
    params = jax.eval_shape(
        lambda k: model.init(k, x, jnp.zeros((1,), jnp.int32),
                             jnp.zeros((1, 77, 1024)))["params"],
        jax.random.key(0),
    )
    n = sum(np.prod(s.shape) for s in jax.tree.leaves(params))
    assert 0.7e9 < n < 1.1e9, f"param count {n/1e9:.2f}B out of SD-2.1 range"


@pytest.mark.parametrize("cfg,rows,latent", [
    (ModelConfig(), 20, 32), (ModelConfig(), 2, 64), (ModelConfig.tiny(), 3, 8)],
    ids=["sd21_256px", "sd21_512px", "tiny"])
def test_self_attention_shapes_are_what_the_unet_asks(monkeypatch, cfg, rows,
                                                      latent):
    """self_attention_shapes mirrors the UNet's structure by hand; hold it to
    what a traced UNet call hands the dispatcher, site by site."""
    asked = []

    def record(q, k, v, **kw):
        if q.shape[1] == k.shape[1]:          # cross-attention: 77 (16) keys
            asked.append(q.shape)
        return q

    monkeypatch.setattr(L, "dot_product_attention", record)
    model = UNet2DCondition(cfg)
    x = jnp.zeros((rows, latent, latent, 4))
    t = jnp.zeros((rows,), jnp.int32)
    ctx = jnp.zeros((rows, cfg.text_max_length + 1, cfg.cross_attention_dim))
    jax.eval_shape(lambda k: model.init(k, x, t, ctx), jax.random.key(0))
    assert asked == self_attention_shapes(cfg, rows, latent)
    if cfg.attention_head_dim == 64:
        assert len(asked) == 16 and asked[0] == (rows, latent * latent, 5, 64)


def test_vae_roundtrip_shapes(tiny):
    model, params = init_vae(tiny, jax.random.key(0))
    f = vae_scale_factor(tiny)
    px = 8 * f
    x = jax.random.normal(jax.random.key(1), (2, px, px, 3))
    dist = model.apply({"params": params}, x, method=model.encode)
    assert dist.mean.shape == (2, 8, 8, tiny.vae_latent_channels)
    z = dist.sample(jax.random.key(2))
    recon = model.apply({"params": params}, z, method=model.decode)
    assert recon.shape == x.shape
    # sampling is rng-deterministic
    z2 = dist.sample(jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(z2))


def test_clip_text_shapes_and_causality(tiny):
    model, params = init_clip_text(tiny, jax.random.key(0))
    ids = jnp.array([[5, 7, 9, 11] + [0] * 12], jnp.int32)
    out = model.apply({"params": params}, ids)
    assert out.last_hidden_state.shape == (1, 16, tiny.text_hidden_size)
    assert out.penultimate_hidden_state.shape == (1, 16, tiny.text_hidden_size)
    assert out.pooled.shape == (1, tiny.text_hidden_size)
    # causality: changing a later token must not affect earlier positions
    ids2 = ids.at[0, 10].set(99)
    out2 = model.apply({"params": params}, ids2)
    np.testing.assert_allclose(np.asarray(out.last_hidden_state[0, :10]),
                               np.asarray(out2.last_hidden_state[0, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(out.last_hidden_state[0, 10:]),
                           np.asarray(out2.last_hidden_state[0, 10:]))


def test_penultimate_differs_from_last(tiny):
    model, params = init_clip_text(tiny, jax.random.key(0))
    ids = jnp.arange(16, dtype=jnp.int32)[None]
    out = model.apply({"params": params}, ids)
    assert not np.allclose(np.asarray(out.last_hidden_state),
                           np.asarray(out.penultimate_hidden_state))


def test_unet_jit_compiles_once(tiny):
    model, params = init_unet(tiny, jax.random.key(0))
    calls = 0

    @jax.jit
    def fwd(p, x, t, c):
        nonlocal calls
        calls += 1
        return model.apply({"params": p}, x, t, c)

    x = jnp.zeros((1, 8, 8, 4))
    c = jnp.zeros((1, 16, 32))
    fwd(params, x, jnp.array([1]), c)
    fwd(params, x, jnp.array([2]), c)
    assert calls == 1  # traced once, different timestep values don't retrace
