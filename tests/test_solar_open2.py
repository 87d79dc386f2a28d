"""The Solar Open 2 text tower at a tiny size on the CPU: against the plain
reference (benchmark/reference/solar_open2.py) in float32, with the delta
rule's chunks cut small so that the scan carries its state from chunk to
chunk; five faults planted in the program that the comparison each fails; the
shares of the expert layer adding up to the uncut layer; the scopes and the
counts the tower leaves; and its config block's validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import solar_open2 as ref
from dcr_tpu.core.config import (ModelConfig, SolarOpen2Config, TrainConfig,
                                 parse_cli, validate_train_config)
from dcr_tpu.models import lm_layers, solar_open2 as so
from dcr_tpu.models.text_tower import build_text_tower, init_text_tower
from dcr_tpu.ops import delta_rule as dr

F32 = jnp.float32
IDS = jax.random.randint(jax.random.key(1), (3, 16), 0, 64)
#: what the tiny encode cell holds the program's states to (its
#: ctx_rms_worst); the program reads 1e-6 against the reference in float32
LIMIT = 1e-4


def tiny_model(first=0, count=-1, **sizes) -> ModelConfig:
    m = ModelConfig.tiny()
    m.text_tower, m.solar = "solar_open2", SolarOpen2Config.tiny()
    for key, value in sizes.items():
        setattr(m.solar, key, value)
    m.solar.held_experts_first, m.solar.held_experts_count = first, count
    m.text_vocab_size, m.text_max_length = 64, 16
    return m


def sizes(m: ModelConfig) -> dict:
    c = dict(vars(m.solar))
    c["held_experts_first"], c["held_experts_count"] = m.solar.held_range()
    return c


def seeded(m: ModelConfig, key=0):
    """(tower, its parameters drawn on the host from the shapes its init
    gives: kernels at 1 / sqrt(fan_in), norm scales 1 +- 0.2 (not all one,
    so that a norm left out or misplaced shows), the decays as Kimi Linear
    draws them; the init's own compile is the slow part of a test)."""
    tower = build_text_tower(m)
    shapes = jax.eval_shape(lambda k: init_text_tower(m, k, tower), jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rng = np.random.default_rng(key)

    def leaf(name, shape):
        if name == "A_log":
            return np.log(rng.uniform(1.0, 16.0, shape))
        if name == "dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            return dt + np.log(-np.expm1(-dt))
        if name == "scale":
            return 1.0 + 0.2 * rng.standard_normal(shape)
        fan_in = np.prod(shape[:-1]) if len(shape) == 2 and name != "embedding" else 1
        return rng.standard_normal(shape) / np.sqrt(fan_in)

    leaves = [jnp.asarray(leaf(str(getattr(path[-1], "key", "")), x.shape), x.dtype)
              for path, x in flat]
    return tower, jax.tree_util.tree_unflatten(treedef, leaves)


def as_f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


def reference(m: ModelConfig, params) -> dict:
    with jax.default_matmul_precision("highest"):
        return ref.forward(sizes(m), IDS, lambda part: as_f32(params)[part])


def program(m: ModelConfig, params, mutable=False):
    tower = build_text_tower(m)
    return jax.jit(lambda p, i: tower.apply({"params": p}, i, mutable=mutable))(
        params, IDS)


def rel_rms(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.fixture()
def small_chunks(monkeypatch):
    """Chunks of 8 positions in sub-chunks of 4: a caption of 16 is two
    chunks, each with pairs inside a sub-chunk and across two."""
    monkeypatch.setattr(dr, "CHUNK", 8)
    monkeypatch.setattr(dr, "SUB", 4)


@pytest.mark.parametrize("first,count", [(0, -1), (2, 4), (6, 2)])
def test_tower_follows_the_reference_in_float32(small_chunks, first, count):
    m = tiny_model(first, count)
    _, params = seeded(m)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    out, kept = program(m, params, ["routing"])
    assert out.last_hidden_state.shape == (3, 16, m.cross_attention_dim)
    want = reference(m, params)
    np.testing.assert_allclose(out.last_hidden_state, want["ctx"], atol=2e-5)
    assert len(want["routing"]) == 4                   # every layer routes
    for i, layer in enumerate(want["routing"]):
        mine = kept["routing"][f"layers_{i}"]["moe"]
        np.testing.assert_allclose(mine["scores"][0], layer["scores"], atol=5e-6)
        assert np.array_equal(np.sort(mine["chosen"][0], 1), np.sort(layer["chosen"], 1))
    stats = jax.tree.map(int, out.moe_stats)
    chosen = np.stack([layer["chosen"] for layer in want["routing"]])
    lo, n = m.solar.held_range()
    here = (chosen >= lo) & (chosen < lo + n)
    assert stats["held"] == int(here.sum()) and stats["dropped"] == 0
    assert stats["unheld"] == int((~here.any(axis=2)).sum()) and stats["zero"] == 0


def _per_chunk(real):
    """The state thrown away at every chunk's start."""
    def run(q, k, v, log_alpha, beta, **kw):
        c = dr.CHUNK
        return jnp.concatenate([real(*(x[:, i:i + c] for x in (q, k, v, log_alpha, beta)),
                                     **kw) for i in range(0, q.shape[1], c)], axis=1)
    return run


def _per_head(real):
    """One decay a head: the mean of its channels'."""
    def run(q, k, v, log_alpha, beta, **kw):
        return real(q, k, v, jnp.broadcast_to(
            jnp.mean(log_alpha, axis=-1, keepdims=True), log_alpha.shape), beta, **kw)
    return run


def _looks_ahead(real):
    """The short convolution's window moved one position on: it reads x_{t+1}."""
    def call(self, x):
        return real(self, jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1))
    return call


@pytest.fixture(scope="module")
def held_2_4():
    """The tiny tower holding experts 2 to 5, its parameters, and the
    reference's states for them (chunks of 8)."""
    m = tiny_model(2, 4)
    _, params = seeded(m)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dr, "CHUNK", 8)
        mp.setattr(dr, "SUB", 4)
        return m, params, reference(m, params)["ctx"]


@pytest.mark.parametrize("fault", ["state_reset_each_chunk", "beta_without_its_2",
                                   "convolution_not_causal", "decay_per_head",
                                   "gqa_gate_left_out"])
def test_a_planted_fault_fails_the_comparison(small_chunks, monkeypatch, held_2_4, fault):
    _, params, want = held_2_4
    m = tiny_model(2, 4)
    if fault == "state_reset_each_chunk":
        monkeypatch.setattr(so, "chunked_delta_rule", _per_chunk(so.chunked_delta_rule))
    elif fault == "beta_without_its_2":
        m.solar.kda_allow_neg_eigval = False
    elif fault == "convolution_not_causal":
        monkeypatch.setattr(so.ShortConv, "__call__", _looks_ahead(so.ShortConv.__call__))
    elif fault == "decay_per_head":
        monkeypatch.setattr(so, "chunked_delta_rule", _per_head(so.chunked_delta_rule))
    else:
        m.solar.use_gqa_gate = False
        params = {**params, "layers_0": {**params["layers_0"], "gqa": {
            k: v for k, v in params["layers_0"]["gqa"].items() if k != "g_proj"}}}
    assert rel_rms(program(m, params).last_hidden_state, want) > 10 * LIMIT


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each of an 8-expert layer: their routed
    parts, with the shared expert (which every chip computes alike) counted
    once, are the uncut reference layer's."""
    whole = tiny_model()
    _, params = seeded(whole)
    moe = params["layers_1"]["moe"]
    x = jax.random.normal(jax.random.key(7), (2, 16, 64), F32)
    with jax.default_matmul_precision("highest"):
        c = sizes(whole)
        flat = x.reshape(-1, 64)
        routing = ref.route(as_f32(moe), c, flat)
        held, shared = ref.moe_parts(ref.EXACT, as_f32(moe), c, flat, routing)
    total, loads = jnp.zeros_like(flat), 0
    for first in range(0, 8, 2):
        m = tiny_model(first, 2)
        share = {k: v for k, v in moe.items() if not k.startswith("expert_")
                 or int(k.split("_")[1]) in (first, first + 1)}
        out, stats = lm_layers.SharedExpertMoE(m.solar, F32, jnp.bfloat16).apply(
            {"params": share}, x)
        total = total + out.reshape(-1, 64) - shared    # the share's routed part
        loads += int(stats["held"])
        assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(total + shared, held + shared, atol=2e-5)
    assert loads == 32 * 3            # every assignment is held by one share


def test_the_scopes_counts_and_gauges_of_the_tower():
    """The mixers' scopes, the expert layer's in every layer, one delta-rule
    site a KDA layer a trace, and the depth from the config."""
    from dcr_tpu.cli.precompute import gauge_layers
    from dcr_tpu.core import tracing

    m = tiny_model(2, 4)
    tower, params = seeded(m)
    reg = tracing.registry()
    before = reg.counters("delta_rule/")
    text = jax.jit(lambda p, i: tower.apply({"params": p}, i)).lower(
        params, IDS).as_text(debug_info=True)
    for scope in ("tower/embed", "tower/ctx_proj", "layers_0/gqa/gqa",
                  "layers_0/gqa/input_layernorm", "layers_1/kda/input_layernorm",
                  *(f"layers_{i}/kda/kda/{part}" for i in (1, 2, 3)
                    for part in ("conv", "gates", "scan", "out")),
                  *(f"layers_{i}/moe/{part}" for i in range(4)
                    for part in ("router", "dispatch", "experts", "shared", "combine"))):
        assert scope in text, scope
    assert "layers_0/kda" not in text and "layers_1/gqa" not in text
    after = reg.counters("delta_rule/")
    assert after["delta_rule/sites_total"] - before.get("delta_rule/sites_total", 0) == 3
    assert after["delta_rule/chunks_total"] - before.get("delta_rule/chunks_total", 0) == 3
    gauge_layers(m)
    assert reg.gauge("tower/layers").value == 4 and reg.gauge("moe/layers").value == 4
    kda = params["layers_1"]["kda"]
    assert kda["q_conv1d"]["kernel"].shape == (4, 64)
    decay = np.exp(so.a_log_init(jax.random.key(3), (4096,)))
    assert 1.0 <= decay.min() and decay.max() <= 16.0
    dt = jax.nn.softplus(so.dt_bias_init(jax.random.key(4), (4096,)))
    np.testing.assert_allclose([dt.min(), dt.max()], [1e-3, 1e-1], rtol=0.05)
    assert kda["A_log"].shape == (4,) and kda["dt_bias"].shape == (64,)
    assert kda["f_a_proj"]["kernel"].shape == (64, 16)     # rank: the head width
    gqa = params["layers_0"]["gqa"]
    assert gqa["k_proj"]["kernel"].shape == (64, 2 * 16)   # two key/value heads
    assert gqa["g_proj"]["kernel"].shape == (64, 4 * 16)


def test_validation_names_the_tower_and_refuses_to_train_it():
    cfg = TrainConfig(model=tiny_model())
    validate_train_config(cfg)
    cfg.train_text_encoder = True
    with pytest.raises(ValueError, match="solar_open2.*16 bytes a parameter"):
        validate_train_config(cfg)
    cfg = TrainConfig(model=tiny_model(6, 4))
    with pytest.raises(ValueError, match="model.solar holds .* not a range"):
        validate_train_config(cfg)
    cfg = TrainConfig(model=tiny_model(num_key_value_heads=3))
    with pytest.raises(ValueError, match="num_key_value_heads must divide"):
        validate_train_config(cfg)
    got = parse_cli(TrainConfig, ["--model.text_tower=solar_open2",
                                  "--model.solar.gqa_layers=0,2",
                                  "--model.solar.num_hidden_layers=3"])
    assert got.model.solar.gqa_layers == (0, 2)
    assert got.model.solar.hidden_size == 4096          # published defaults
    assert SolarOpen2Config().gqa_layers == tuple(range(0, 48, 4))
