"""ops/delta_rule: the chunked gated delta rule against the same rule written
position by position (the plain reference's `delta_rule`), on the CPU in
float32, at several chunk and sub-chunk lengths, at lengths that are not a
multiple of the chunk, under a decay whose inverse over one chunk is past
float32's range, and the counters a traced site leaves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.solar_open2 import delta_rule as recurrence
from dcr_tpu.core import tracing
from dcr_tpu.ops import delta_rule as dr

#: float32 on both sides, the same sums in another order: values of order 1
#: agree to a few ulps of the largest, 1e-6 and under as measured
ATOL = 2e-5

recurrence = jax.jit(recurrence)


def chunked(monkeypatch, args, chunk: int, sub: int):
    """The scan at `chunk` and `sub`, set as the module's constants are (a
    jit of its own: jax keeps a function's traces by the function and the
    shapes, not by the constants it reads)."""
    monkeypatch.setattr(dr, "CHUNK", chunk)
    monkeypatch.setattr(dr, "SUB", sub)
    return jax.jit(lambda *a: dr.chunked_delta_rule(*a))(*args)


def inputs(t: int, *, strongest: float = 0.5, seed: int = 0):
    """(q, k, v, log_alpha, beta) of 2 rows and 3 heads, q and k unit
    vectors, beta in (0, 2); a channel's log decay a step is -exp(x), x
    uniform on [-7, `strongest`]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    b, h, dk, dv = 2, 3, 8, 6

    def unit(key):
        x = jax.random.normal(key, (b, t, h, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    log_alpha = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, dk), minval=-7.0,
                                            maxval=strongest))
    return (unit(ks[0]), unit(ks[1]), jax.random.normal(ks[2], (b, t, h, dv)),
            log_alpha, 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))


@pytest.mark.parametrize("t, chunk, sub", [
    (64, 16, 4), (70, 32, 8), (33, 64, 16), (5, 64, 16), (40, 8, 8)])
def test_the_chunks_compute_the_recurrence(monkeypatch, t, chunk, sub):
    args = inputs(t, seed=t)
    got = chunked(monkeypatch, args, chunk, sub)
    assert got.shape == (2, t, 3, 6) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, recurrence(*args), rtol=0, atol=ATOL)


def test_a_decay_whose_inverse_overflows_float32_gives_no_inf(monkeypatch):
    """Channels that lose up to e^-33 a step: over a chunk of 16 positions
    the cumulative decay of some passes e^-89, past float32's range (its
    largest is e^88.7), so e^{-G} is formed nowhere, or the result would hold
    inf or NaN."""
    args = inputs(50, strongest=3.5, seed=1)
    assert float(jnp.max(-jnp.sum(args[3][:, :16], axis=1))) > 89.0
    got = chunked(monkeypatch, args, 16, 8)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, recurrence(*args), rtol=0, atol=ATOL)


def test_the_constants_are_read_when_called_and_a_site_counts_itself(monkeypatch):
    assert dr.CHUNK % dr.SUB == 0          # a chunk is whole sub-chunks
    reg = tracing.registry()
    before = reg.counters("delta_rule/")
    args = inputs(40)
    monkeypatch.setattr(dr, "CHUNK", 16)
    monkeypatch.setattr(dr, "SUB", 8)
    jax.jit(lambda *a: dr.chunked_delta_rule(*a)).lower(*args)   # a trace, no run
    after = reg.counters("delta_rule/")
    assert after["delta_rule/sites_total"] - before.get("delta_rule/sites_total", 0) == 1
    assert after["delta_rule/chunks_total"] - before.get("delta_rule/chunks_total", 0) == 3
    assert reg.gauge("delta_rule/chunk").value == 16
