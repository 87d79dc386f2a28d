import jax
import jax.numpy as jnp
import numpy as np

from dcr_tpu.models import schedulers as S

import pytest

pytestmark = pytest.mark.fast


def _sched(pred="epsilon"):
    return S.make_schedule(prediction_type=pred)


def test_beta_schedules_match_closed_form():
    s = S.make_schedule(num_train_timesteps=10, beta_schedule="linear",
                        beta_start=1e-4, beta_end=2e-2)
    betas = np.linspace(1e-4, 2e-2, 10)
    np.testing.assert_allclose(np.asarray(s.betas), betas, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s.alphas_cumprod), np.cumprod(1 - betas), rtol=1e-6)

    s2 = S.make_schedule(num_train_timesteps=10, beta_schedule="scaled_linear",
                         beta_start=0.00085, beta_end=0.012)
    b2 = np.linspace(0.00085 ** 0.5, 0.012 ** 0.5, 10) ** 2
    np.testing.assert_allclose(np.asarray(s2.betas), b2, rtol=1e-6)

    s3 = S.make_schedule(num_train_timesteps=50, beta_schedule="squaredcos_cap_v2")
    assert np.all(np.asarray(s3.betas) > 0) and np.all(np.asarray(s3.betas) <= 0.999)


def test_add_noise_closed_form():
    s = _sched()
    x0 = jnp.ones((2, 4, 4, 1))
    noise = jnp.full_like(x0, 2.0)
    t = jnp.array([0, 500])
    xt = S.add_noise(s, x0, noise, t)
    acp = np.asarray(s.alphas_cumprod)
    for i, ti in enumerate([0, 500]):
        expect = np.sqrt(acp[ti]) * 1.0 + np.sqrt(1 - acp[ti]) * 2.0
        np.testing.assert_allclose(np.asarray(xt[i]), expect, rtol=1e-5)


def test_velocity_and_prediction_conversions_consistent():
    s = _sched("v_prediction")
    key = jax.random.key(0)
    x0 = jax.random.normal(key, (3, 8, 8, 4))
    noise = jax.random.normal(jax.random.fold_in(key, 1), x0.shape)
    t = jnp.array([10, 400, 900])
    v = S.get_velocity(s, x0, noise, t)
    # inverting the v-prediction must recover x0 and eps
    x0_hat, eps_hat = S.pred_to_x0_eps(s, v, S.add_noise(s, x0, noise, t), t)
    np.testing.assert_allclose(np.asarray(x0_hat), np.asarray(x0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(eps_hat), np.asarray(noise), atol=1e-4)


def test_epsilon_conversion_consistent():
    s = _sched()
    key = jax.random.key(1)
    x0 = jax.random.normal(key, (2, 4, 4, 4))
    noise = jax.random.normal(jax.random.fold_in(key, 1), x0.shape)
    t = jnp.array([100, 800])
    xt = S.add_noise(s, x0, noise, t)
    x0_hat, eps_hat = S.pred_to_x0_eps(s, noise, xt, t)
    np.testing.assert_allclose(np.asarray(x0_hat), np.asarray(x0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(eps_hat), np.asarray(noise), atol=1e-6)


def test_training_target_dispatch():
    key = jax.random.key(2)
    x0 = jax.random.normal(key, (2, 4, 4, 4))
    noise = jax.random.normal(jax.random.fold_in(key, 1), x0.shape)
    t = jnp.array([5, 99])
    np.testing.assert_array_equal(
        np.asarray(S.training_target(_sched("epsilon"), x0, noise, t)), np.asarray(noise))
    sv = _sched("v_prediction")
    np.testing.assert_array_equal(
        np.asarray(S.training_target(sv, x0, noise, t)),
        np.asarray(S.get_velocity(sv, x0, noise, t)))


def test_ddim_perfect_model_recovers_x0():
    """With a model that predicts the true eps, DDIM from x_T should march toward x0."""
    s = _sched()
    key = jax.random.key(3)
    x0 = jax.random.normal(key, (1, 4, 4, 1))
    noise = jax.random.normal(jax.random.fold_in(key, 1), x0.shape)
    ts = S.inference_timesteps(s, 10)
    x = S.add_noise(s, x0, noise, jnp.full((1,), int(ts[0])))
    for i in range(len(ts)):
        t = jnp.full((1,), int(ts[i]))
        prev_t = jnp.full((1,), int(ts[i + 1]) if i + 1 < len(ts) else -1)
        # oracle eps for current x: eps = (x - sqrt(acp) x0)/sqrt(1-acp)
        a = jnp.sqrt(s.alphas_cumprod[t]).reshape(-1, 1, 1, 1)
        sd = jnp.sqrt(1 - s.alphas_cumprod[t]).reshape(-1, 1, 1, 1)
        eps = (x - a * x0) / sd
        x = S.ddim_step(s, eps, x, t, prev_t)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x0), atol=1e-3)


def test_ddpm_step_terminal_is_mean_only():
    s = _sched()
    key = jax.random.key(4)
    x0 = jax.random.normal(key, (1, 2, 2, 1))
    noise = jax.random.normal(jax.random.fold_in(key, 1), x0.shape)
    t = jnp.array([0])
    xt = S.add_noise(s, x0, noise, t)
    out1 = S.ddpm_step(s, noise, xt, t, jnp.array([-1]),
                       jax.random.normal(jax.random.key(7), xt.shape))
    out2 = S.ddpm_step(s, noise, xt, t, jnp.array([-1]),
                       jax.random.normal(jax.random.key(8), xt.shape))
    # at prev_t=-1 no noise is added -> deterministic, and equals x0_hat
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(x0), atol=1e-4)


def test_ddpm_step_with_handed_noise_gives_the_keyed_forms_bits():
    """ddpm_step takes the noise, not the key (the samplers own the keying).
    Handed ``normal(key, x.shape, x.dtype)`` — the one draw the keyed form
    made inside — it must give the keyed form's bits: the values below were
    recorded from ``S.ddpm_step(s, eps, x, t, prev_t, jax.random.key(9))``
    at the commit before the signature changed (eager, CPU)."""
    s = _sched()
    key = jax.random.key(6)
    x = jax.random.normal(key, (2, 2, 2, 2))
    eps = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
    t, prev_t = jnp.array([500, 300]), jnp.array([400, -1])
    out = S.ddpm_step(s, eps, x, t, prev_t,
                      jax.random.normal(jax.random.key(9), x.shape, x.dtype))
    keyed_form_bits = np.array(
        [991793152, 1049050062, 3214117976, 3220716424, 1060319644,
         3185310544, 3184241416, 3217891960, 3189440952, 3206424983,
         3169621836, 1054016327, 3165748159, 3222901389, 1062343930,
         1066054571], np.uint32)
    np.testing.assert_array_equal(
        np.asarray(out).view(np.uint32).ravel(), keyed_form_bits)


def test_dpmpp_2m_perfect_model_recovers_x0():
    s = _sched()
    key = jax.random.key(5)
    x0 = jax.random.normal(key, (1, 4, 4, 1))
    ts = S.inference_timesteps(s, 20)
    x = jax.random.normal(jax.random.fold_in(key, 2), x0.shape) * float(
        jnp.sqrt(1 - s.alphas_cumprod[int(ts[0])]))
    x = x + x0 * float(jnp.sqrt(s.alphas_cumprod[int(ts[0])]))
    state = S.dpm_init_state(x.shape)
    for i in range(len(ts)):
        t = jnp.asarray(int(ts[i]))
        prev_t = jnp.asarray(int(ts[i + 1]) if i + 1 < len(ts) else -1)
        a = jnp.sqrt(s.alphas_cumprod[t])
        sd = jnp.sqrt(1 - s.alphas_cumprod[t])
        eps = (x - a * x0) / sd
        x, state = S.dpmpp_2m_step(s, eps, x, t, prev_t, state)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x0), atol=5e-3)


def test_steps_jittable():
    s = _sched()
    x = jnp.zeros((1, 4, 4, 1))
    f = jax.jit(lambda m, x, t, p: S.ddim_step(s, m, x, t, p))
    out = f(x, x, jnp.array([500]), jnp.array([400]))
    assert out.shape == x.shape


def test_steps_support_batched_prev_t():
    """Regression: [B] t/prev_t must broadcast correctly (incl. C == B shapes)."""
    s = _sched()
    key = jax.random.key(6)
    x = jax.random.normal(key, (2, 4, 4, 2))  # channels == batch to catch misbroadcast
    eps = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
    t = jnp.array([500, 300])
    prev_t = jnp.array([400, -1])
    out = S.ddim_step(s, eps, x, t, prev_t)
    assert out.shape == x.shape
    # per-sample result equals the scalar-t computation for that sample
    for i in range(2):
        single = S.ddim_step(s, eps[i:i + 1], x[i:i + 1],
                             t[i:i + 1], prev_t[i:i + 1])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(single[0]), atol=1e-6)
    out2 = S.ddpm_step(s, eps, x, t, prev_t,
                       jax.random.normal(jax.random.key(9), x.shape))
    assert out2.shape == x.shape
    state = S.dpm_init_state(x.shape, batch_shape=t.shape)
    out3, state = S.dpmpp_2m_step(s, eps, x, t, prev_t, state)
    assert out3.shape == x.shape and state.prev_lambda.shape == t.shape


def test_inference_timesteps_guard():
    s = S.make_schedule(num_train_timesteps=10)
    import pytest
    with pytest.raises(ValueError):
        S.inference_timesteps(s, 50)
