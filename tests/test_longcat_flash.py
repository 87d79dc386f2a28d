"""The LongCat-Flash text tower at a tiny size on the CPU: against the plain
reference (benchmark/reference/longcat_flash.py) in float32, the shares of
the expert layer adding up to the uncut layer, MLA against a naive per-head
attention with explicit rotary, router ties, and the tower through
dcr-precompute-latents, dcr-train and dcr-sample by the entry points,
factory and config that `clip` uses."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import longcat_flash as ref
from dcr_tpu.core.config import (LongcatFlashConfig, ModelConfig, TrainConfig,
                                 parse_cli, validate_train_config)
from dcr_tpu.models import longcat_flash as lf
from dcr_tpu.models.text_tower import build_text_tower, init_text_tower

F32 = jnp.float32


def tiny_model(first=0, count=-1) -> ModelConfig:
    m = ModelConfig.tiny()
    m.text_tower, m.longcat = "longcat_flash", LongcatFlashConfig.tiny()
    m.longcat.held_experts_first, m.longcat.held_experts_count = first, count
    m.text_vocab_size, m.text_max_length = 64, 16
    return m


def sizes(m: ModelConfig) -> dict:
    c = dict(vars(m.longcat))
    first, count = m.longcat.held_range()
    c.update(n_routed_experts_total=m.longcat.n_routed_experts,
             held_experts_first=first, held_experts_count=count)
    return c


def seeded(m: ModelConfig, key=0):
    """(tower, its parameters with a router bias that moves some choices)."""
    tower = build_text_tower(m)
    params = init_text_tower(m, jax.random.key(key), tower)
    outputs = m.longcat.n_routed_experts + m.longcat.zero_expert_num
    for i in range(m.longcat.num_layers):
        params[f"layers_{i}"]["moe"]["e_score_correction_bias"] = (
            0.01 * jax.random.normal(jax.random.key(50 + i), (outputs,))
        ).astype(jnp.bfloat16)
    return tower, params


def as_f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


IDS = jax.random.randint(jax.random.key(1), (3, 16), 0, 64)


@pytest.mark.parametrize("first,count", [(0, -1), (2, 4), (6, 2), (0, 0)])
def test_tower_follows_the_reference_in_float32(first, count):
    m = tiny_model(first, count)
    tower, params = seeded(m)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    out, kept = tower.apply({"params": params}, IDS, mutable=["routing"])
    assert out.last_hidden_state.shape == (3, 16, m.cross_attention_dim)
    assert out.last_hidden_state.dtype == F32
    with jax.default_matmul_precision("highest"):
        want = ref.forward(sizes(m), IDS, lambda part: as_f32(params)[part])
    np.testing.assert_allclose(out.last_hidden_state, want["ctx"], atol=2e-5)
    for i, layer in enumerate(want["routing"]):
        mine = kept["routing"][f"layers_{i}"]["moe"]
        np.testing.assert_allclose(mine["scores"][0], layer["scores"], atol=1e-6)
        assert np.array_equal(np.sort(mine["chosen"][0], 1),
                              np.sort(layer["chosen"], 1))
    stats = jax.tree.map(int, out.moe_stats)
    k, layers = m.longcat.moe_topk, m.longcat.num_layers
    assert stats["assignments"] == 3 * 16 * k * layers and stats["dropped"] == 0
    chosen = np.stack([layer["chosen"] for layer in want["routing"]])
    lo, n = m.longcat.held_range()
    assert stats["held"] == int(((chosen >= lo) & (chosen < lo + n)).sum())
    assert stats["zero"] == int((chosen >= m.longcat.n_routed_experts).sum())


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each: their routed parts plus the
    zero-compute part, counted once, are the uncut reference layer's."""
    whole = tiny_model()
    _, params = seeded(whole)
    moe = params["layers_0"]["moe"]
    x = jax.random.normal(jax.random.key(7), (2, 16, 64), F32)
    with jax.default_matmul_precision("highest"):
        c = sizes(whole)
        flat = x.reshape(-1, 64)
        routing = ref.route(as_f32(moe), c, flat)
        held, zero = ref.moe_parts(ref.EXACT, as_f32(moe), c, flat, routing)
    total, loads = jnp.zeros_like(flat), 0
    for first in (0, 2, 4, 6):
        m = tiny_model(first, 2)
        share = {k: v for k, v in moe.items() if not k.startswith("expert_")
                 or int(k.split("_")[1]) in (first, first + 1)}
        out, stats = lf.ScMoE(m.longcat, F32, jnp.bfloat16).apply(
            {"params": share}, x)
        total = total + out.reshape(-1, 64) - zero      # the share's routed part
        loads += int(stats["held"])
        assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(total + zero, held + zero, atol=2e-5)
    assert loads == int((routing["chosen"] < 8).sum())


def test_mla_against_naive_per_head_attention_with_explicit_rotary():
    c = LongcatFlashConfig.tiny()
    x = jax.random.normal(jax.random.key(3), (2, 12, 64), F32)
    mla = lf.MLA(c, F32, F32)
    mask = jnp.tril(jnp.ones((12, 12), bool))[None, None]
    p = mla.init(jax.random.key(4), x, mask)["params"]
    got = np.asarray(mla.apply({"params": p}, x, mask), np.float64)
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    rms = lambda v, s: v / np.sqrt((v * v).mean(-1, keepdims=True) + c.rms_norm_eps) * s  # noqa: E731

    def turn(v, pos):                       # one vector, pair by pair
        out = v.copy()
        for i in range(len(v) // 2):
            a = pos * c.rope_theta ** (-2.0 * i / len(v))
            out[2 * i] = v[2 * i] * np.cos(a) - v[2 * i + 1] * np.sin(a)
            out[2 * i + 1] = v[2 * i] * np.sin(a) + v[2 * i + 1] * np.cos(a)
        return out

    nope, rope, vd, heads = 16, 8, 16, 4
    want = np.zeros_like(x)
    for b in range(2):
        cq = rms(x[b] @ p["q_a_proj"]["kernel"], p["q_a_norm"]["scale"]) * (64 / 32) ** 0.5
        q = (cq @ p["q_b_proj"]["kernel"]).reshape(12, heads, nope + rope)
        kv = x[b] @ p["kv_a_proj_with_mqa"]["kernel"]
        ckv = rms(kv[:, :16], p["kv_a_norm"]["scale"]) * (64 / 16) ** 0.5
        kvb = (ckv @ p["kv_b_proj"]["kernel"]).reshape(12, heads, nope + vd)
        k_rope = np.stack([turn(kv[t, 16:], t) for t in range(12)])
        heads_out = np.zeros((12, heads, vd))
        for h in range(heads):
            for t in range(12):
                qt = np.concatenate([q[t, h, :nope], turn(q[t, h, nope:], t)])
                keys = np.concatenate([kvb[:t + 1, h, :nope], k_rope[:t + 1]], 1)
                logit = keys @ qt / np.sqrt(nope + rope)
                w = np.exp(logit - logit.max())
                heads_out[t, h] = (w / w.sum()) @ kvb[:t + 1, h, nope:]
        want[b] = heads_out.reshape(12, heads * vd) @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_attention_takes_v_narrower_than_q_and_k_on_the_xla_path():
    from dcr_tpu.ops import attention

    q = jax.random.normal(jax.random.key(0), (2, 8, 4, 24))
    k = jax.random.normal(jax.random.key(1), (2, 8, 4, 24))
    v = jax.random.normal(jax.random.key(2), (2, 8, 4, 16))
    assert attention.path_for(q, k, v) == "xla"
    got = attention.dot_product_attention(q, k, v)
    w = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", q, k) / 24 ** 0.5, -1)
    np.testing.assert_allclose(got, jnp.einsum("bhqk,bkhd->bqhd", w, v), atol=1e-5)


@pytest.mark.parametrize("case", ["exact_tie", "bias_decides", "follow",
                                  "follow_outside"])
def test_router_ties(case):
    """Equal scores choose the lower output, as `lax.top_k` does in the
    program and in the reference; the bias decides the choice and never the
    weight; a near tie takes the other side's choice and is counted, unless
    that choice reaches below the tie: then it is counted as outside and the
    reference keeps its own."""
    m = tiny_model()
    c = sizes(m)
    hidden, outputs, k = 64, 12, 3
    kernel = np.zeros((hidden, outputs), np.float32)
    kernel[0, :5] = [4.0, 3.0, 2.0, 2.0, 1.0]          # outputs 2 and 3 tie for third
    bias = np.zeros(outputs, np.float32)
    if case == "bias_decides":
        bias[3] = 1e-3
    x = np.zeros((1, hidden), np.float32)
    x[0, 0] = 1.0
    p = {"router": {"kernel": jnp.asarray(kernel)},
         "e_score_correction_bias": jnp.asarray(bias)}
    follow = {"follow": jnp.asarray([[0, 1, 3]]),
              "follow_outside": jnp.asarray([[0, 1, 4]])}.get(case)
    r = ref.route(p, c, jnp.asarray(x), follow=follow, tie_eps=0.05)
    third = {"exact_tie": 2, "bias_decides": 3, "follow": 3,
             "follow_outside": 2}[case]
    if follow is not None:
        assert bool(r["outside"][0]) == (case == "follow_outside")
        # output 4 scores e^-1 of the third's: that far below the tie
        want = 1.0 - np.exp(-1.0) if case == "follow_outside" else 0.0
        np.testing.assert_allclose(float(r["slack"][0]), want, atol=1e-6)
    assert sorted(np.asarray(r["chosen"][0]).tolist()) == sorted([0, 1, third])
    assert bool(r["near_tie"][0])       # a margin under tie_eps in every case
    scores = np.asarray(r["scores"][0])
    np.testing.assert_allclose(np.sort(np.asarray(r["weights"][0])),
                               np.sort(scores[[0, 1, third]]) * 6.0, rtol=1e-6)
    # the program's layer makes the same choice from the same leaves
    moe_params = {"router": p["router"], "e_score_correction_bias": p["e_score_correction_bias"]}
    share = tiny_model(0, 0)
    _, kept = lf.ScMoE(share.longcat, F32, F32).apply(
        {"params": moe_params}, jnp.asarray(x)[None], mutable=["routing"])
    if follow is None:
        assert sorted(np.asarray(kept["routing"]["chosen"][0][0]).tolist()) == \
            sorted([0, 1, third])


def test_validation_names_the_tower_and_refuses_to_train_it():
    cfg = TrainConfig(model=tiny_model())
    validate_train_config(cfg)
    cfg.train_text_encoder = True
    with pytest.raises(ValueError, match="16 bytes a parameter"):
        validate_train_config(cfg)
    cfg = TrainConfig(model=tiny_model(6, 4))
    with pytest.raises(ValueError, match="not a range"):
        validate_train_config(cfg)
    cfg = TrainConfig(model=ModelConfig.tiny())
    cfg.model.text_tower = "t5"
    with pytest.raises(ValueError, match="text_tower must be one of"):
        validate_train_config(cfg)
    # the tower's sizes are one nested block, addressable from the CLI
    got = parse_cli(TrainConfig, ["--model.text_tower=longcat_flash",
                                  "--model.longcat.moe_topk=5"])
    assert got.model.longcat.moe_topk == 5 and got.model.text_tower == "longcat_flash"


def test_clip_is_still_the_default_tower_and_its_parameters_are_unchanged():
    """The factory hands `clip` programs the module and the parameters they
    always had (the compile manifest's digests are held by tests/test_check)."""
    from dcr_tpu.models.clip_text import CLIPTextModel, init_clip_text

    m = ModelConfig.tiny()
    tower = build_text_tower(m, jnp.bfloat16)
    assert isinstance(tower, CLIPTextModel) and tower.dtype == F32
    mine = init_text_tower(m, jax.random.key(3), tower)
    _, theirs = init_clip_text(m, jax.random.key(3))
    assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                                     mine, theirs))
    ids = jnp.zeros((1, m.text_max_length), jnp.int32)
    assert not hasattr(tower.apply({"params": mine}, ids), "moe_stats")


def test_precompute_train_and_sample_with_the_tower_end_to_end(tmp_path):
    """`text_tower=longcat_flash` through dcr-precompute-latents, one
    dcr-train step from that cache, and dcr-sample from the checkpoint the
    trainer exported: the CLIs' own mains."""
    from PIL import Image

    from dcr_tpu.cli import precompute, sample, train
    from dcr_tpu.core import tracing

    rng = np.random.default_rng(0)
    for i in range(8):
        d = tmp_path / "data" / f"c{i % 2}"
        d.mkdir(parents=True, exist_ok=True)
        Image.fromarray(rng.integers(0, 255, (20, 20, 3), np.uint8)).save(d / f"{i}.png")
    m = tiny_model(2, 4)
    base = TrainConfig(seed=0, train_batch_size=1, max_train_steps=1,
                       mixed_precision="no", save_steps=1000, modelsavesteps=1000,
                       model=m)
    base.data.train_data_dir, base.data.resolution = str(tmp_path / "data"), 16
    base.data.random_flip, base.data.num_workers = False, 1
    base.optim.lr_scheduler, base.optim.lr_warmup_steps = "constant", 0
    from dcr_tpu.core.config import save_config

    save_config(base, tmp_path / "cfg.json")
    common = [f"--config={tmp_path / 'cfg.json'}",
              f"--pipe.latent_cache={tmp_path / 'cache'}"]
    before = tracing.registry().counters("moe/")
    precompute.main(common + [f"--output_dir={tmp_path / 'pre'}",
                              "--pipe.cache_shard_size=8"])
    counts = {k: v - before.get(k, 0)
              for k, v in tracing.registry().counters("moe/").items()}
    assert counts["moe/assignments_total"] == 8 * 16 * 3 * 2
    assert counts["moe/assignments_dropped_total"] == 0
    assert 0 < counts["moe/assignments_held_total"] < counts["moe/assignments_total"]
    manifest = json.loads((tmp_path / "cache" / "manifest.json").read_text())
    assert manifest["total"] == 8
    for name in ("load", "encode", "fetch", "write"):
        assert tracing.timeline(f"precompute/{name}")
    train.main(common + [f"--output_dir={tmp_path / 'run'}"])
    ckpt = tmp_path / "run" / "checkpoint"
    index = json.loads((ckpt / "model_index.json").read_text())
    assert index["model_config"]["text_tower"] == "longcat_flash"
    assert index["text_encoder"] == ["dcr_tpu", "longcat_flash"]
    sample.main([f"--model_path={tmp_path / 'run'}", f"--savepath={tmp_path / 'gen'}",
                 "--modelstyle=nolevel", "--num_batches=1", "--im_batch=1",
                 "--resolution=16", "--num_inference_steps=2", "--sampler=ddim"])
    assert list((tmp_path / "gen" / "generations").glob("*.png"))
