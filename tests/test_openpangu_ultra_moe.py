"""The openPangu-Ultra-MoE text tower at a tiny size on the CPU: against the
plain reference (benchmark/reference/openpangu_ultra_moe.py) in float32, the
sixteen shares of the expert layer adding up to the uncut layer, the router's
renormalised weights and its ties, routing counts of the expert layers only,
the tower table's readers, the parameter trees the move left unchanged, and
the tower through dcr-precompute-latents, dcr-train and dcr-sample by the
entry points, factory and config that `clip` uses."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import openpangu_ultra_moe as ref
from dcr_tpu.core.config import (TEXT_TOWERS, LongcatFlashConfig, ModelConfig,
                                 OpenPanguUltraMoEConfig, SolarOpen2Config,
                                 TrainConfig, parse_cli, validate_train_config)
from dcr_tpu.core.precision import text_param_dtype
from dcr_tpu.models import openpangu_ultra_moe as pg
from dcr_tpu.models.text_tower import build_text_tower, init_text_tower

F32 = jnp.float32


def tiny_model(first=0, count=-1, **sizes) -> ModelConfig:
    m = ModelConfig.tiny()
    m.text_tower, m.openpangu = "openpangu_ultra_moe", OpenPanguUltraMoEConfig.tiny()
    for key, value in sizes.items():
        setattr(m.openpangu, key, value)
    m.openpangu.held_experts_first, m.openpangu.held_experts_count = first, count
    m.text_vocab_size, m.text_max_length = 64, 16
    return m


def sizes(m: ModelConfig) -> dict:
    c = dict(vars(m.openpangu))
    c["held_experts_first"], c["held_experts_count"] = m.openpangu.held_range()
    return c


def seeded(m: ModelConfig, key=0):
    """(tower, its parameters with norm scales that are not all one, so that
    a norm left out or misplaced shows)."""
    tower = build_text_tower(m)
    params = init_text_tower(m, jax.random.key(key), tower)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [
        (1.0 + 0.2 * jax.random.normal(jax.random.key(90 + i), x.shape)
         ).astype(x.dtype) if str(getattr(path[-1], "key", "")) == "scale" else x
        for i, (path, x) in enumerate(flat)]
    return tower, jax.tree_util.tree_unflatten(treedef, leaves)


def as_f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


def path_digest(tree) -> tuple[int, str]:
    """(leaves, sha256 of the [path, shape, dtype] list in flattening order)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    rows = [("/".join(str(getattr(k, "key", k)) for k in path), tuple(x.shape),
             str(x.dtype)) for path, x in flat]
    return len(rows), hashlib.sha256(json.dumps(rows).encode()).hexdigest()


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
    assert len(want["routing"]) == 2
    for i, layer in enumerate(want["routing"], start=1):
        mine = kept["routing"][f"layers_{i}"]["moe"]
        np.testing.assert_allclose(mine["scores"][0], layer["scores"], atol=1e-6)
        assert np.array_equal(np.sort(mine["chosen"][0], 1),
                              np.sort(layer["chosen"], 1))
    stats = jax.tree.map(int, out.moe_stats)
    chosen = np.stack([layer["chosen"] for layer in want["routing"]])
    lo, n = m.openpangu.held_range()
    here = (chosen >= lo) & (chosen < lo + n)
    assert stats["held"] == int(here.sum()) and stats["dropped"] == 0
    assert stats["unheld"] == int((~here.any(axis=2)).sum())
    assert stats["zero"] == 0


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen shares of two experts each of a 32-expert layer: their routed
    parts, with the shared expert (which every chip computes alike) counted
    once, are the uncut reference layer's."""
    whole = tiny_model(n_routed_experts=32)
    _, params = seeded(whole)
    moe = params["layers_1"]["moe"]
    x = jax.random.normal(jax.random.key(7), (2, 16, 64), F32)
    with jax.default_matmul_precision("highest"):
        c = sizes(whole)
        flat = x.reshape(-1, 64)
        routing = ref.route(as_f32(moe), c, flat)
        held, shared = ref.moe_parts(ref.EXACT, as_f32(moe), c, flat, routing)
    total, loads, unheld = jnp.zeros_like(flat), 0, []
    for first in range(0, 32, 2):
        m = tiny_model(first, 2, n_routed_experts=32)
        share = {k: v for k, v in moe.items() if not k.startswith("expert_")
                 or int(k.split("_")[1]) in (first, first + 1)}
        out, stats = pg.SharedExpertMoE(m.openpangu, F32, jnp.bfloat16).apply(
            {"params": share}, x)
        total = total + out.reshape(-1, 64) - shared    # the share's routed part
        loads += int(stats["held"])
        unheld.append(int(stats["unheld"]))
        assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(total + shared, held + shared, atol=2e-5)
    assert loads == 32 * 3            # every assignment is held by one share
    assert all(0 < u < 32 for u in unheld)


@pytest.mark.parametrize("case", ["sum", "exact_tie", "follow", "follow_outside"])
def test_router_weights_and_ties(case):
    """The chosen weights are renormalised to sum to routed_scaling_factor;
    equal scores choose the lower output, as `lax.top_k` does in the program
    and in the reference; a near tie takes the other side's choice and is
    counted, unless that choice reaches below the tie."""
    m = tiny_model(0, 0)
    c = sizes(m)
    hidden, k = 64, 3
    kernel = np.zeros((hidden, 8), np.float32)
    kernel[0, :5] = [4.0, 3.0, 2.0, 2.0, 1.0]          # outputs 2 and 3 tie for third
    if case == "sum":
        kernel = np.asarray(jax.random.normal(jax.random.key(5), (hidden, 8)))
    x = np.zeros((1, hidden), np.float32)
    x[0, 0] = 1.0
    p = {"router": {"kernel": jnp.asarray(kernel)}}
    follow = {"follow": jnp.asarray([[0, 1, 3]]),
              "follow_outside": jnp.asarray([[0, 1, 4]])}.get(case)
    r = ref.route(p, c, jnp.asarray(x), follow=follow, tie_eps=0.05)
    np.testing.assert_allclose(float(r["weights"].sum()), 2.5, rtol=1e-6)
    _, kept = pg.SharedExpertMoE(m.openpangu, F32, F32).apply(
        {"params": {**p, "shared_experts": {
            name: {"kernel": jnp.zeros(shape)} for name, shape in (
                ("gate_proj", (64, 32)), ("up_proj", (64, 32)),
                ("down_proj", (32, 64)))}}},
        jnp.asarray(x)[None], mutable=["routing"])
    mine = sorted(np.asarray(kept["routing"]["chosen"][0][0]).tolist())
    if case == "sum":
        assert mine == sorted(np.asarray(r["chosen"][0]).tolist())
        scores = np.asarray(r["scores"][0])
        np.testing.assert_allclose(
            np.sort(np.asarray(r["weights"][0])),
            np.sort(2.5 * scores[mine] / scores[mine].sum()), rtol=1e-6)
        return
    third = {"exact_tie": 2, "follow": 3, "follow_outside": 2}[case]
    assert sorted(np.asarray(r["chosen"][0]).tolist()) == sorted([0, 1, third])
    assert bool(r["near_tie"][0])
    assert mine == [0, 1, 2]            # the program breaks the tie downwards
    if follow is not None:
        assert bool(r["outside"][0]) == (case == "follow_outside")
        s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0])))
        want = (s[0] - s[1]) / s[0] if case == "follow_outside" else 0.0
        np.testing.assert_allclose(float(r["slack"][0]), want, atol=1e-6)


def test_routing_counts_come_from_the_expert_layers_only():
    """One dense layer, then two expert layers: the counts are of two layers;
    with every layer dense the tower gives none, and the host's counters take
    both; the depth comes from the config, not from the device."""
    from dcr_tpu.cli.precompute import count_routing, gauge_layers
    from dcr_tpu.core import tracing

    m = tiny_model(2, 4)
    tower, params = seeded(m)
    stats = jax.tree.map(int, tower.apply({"params": params}, IDS).moe_stats)
    assert set(stats) == {"assignments", "held", "zero", "dropped", "unheld",
                          "held_load_max"}
    assert stats["assignments"] == 3 * 16 * 3 * 2
    assert 0 < stats["held"] < stats["assignments"] and stats["unheld"] > 0
    reg = tracing.registry()
    before = reg.counters("moe/")
    count_routing(stats)
    after = reg.counters("moe/")
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert moved["moe/tokens_unheld_total"] == stats["unheld"]
    assert moved["moe/assignments_held_total"] == stats["held"]
    assert moved["moe/assignments_zero_total"] == 0
    gauge_layers(m)
    assert reg.gauge("tower/layers").value == 3 and reg.gauge("moe/layers").value == 2
    dense = tiny_model(first_k_dense_replace=3)
    tower, params = seeded(dense)
    assert "moe" not in params["layers_2"] and "mlp" in params["layers_2"]
    assert tower.apply({"params": params}, IDS).moe_stats == {}
    count_routing({})
    assert reg.counters("moe/") == after
    gauge_layers(dense)
    assert reg.gauge("tower/layers").value == 3 and reg.gauge("moe/layers").value == 0
    # LongCat's tower: four keys and the maximum as the benchmark measured it
    # (no `unheld`: its program is the parent's), every layer an expert layer
    lc = ModelConfig.tiny()
    lc.text_tower, lc.longcat = "longcat_flash", LongcatFlashConfig.tiny()
    lc.text_vocab_size, lc.text_max_length = 64, 16
    tower = build_text_tower(lc)
    out = tower.apply({"params": init_text_tower(lc, jax.random.key(0), tower)}, IDS)
    assert set(out.moe_stats) == {"assignments", "held", "zero", "dropped",
                                  "held_load_max"}
    count_routing(jax.tree.map(int, out.moe_stats))
    assert reg.counters("moe/")["moe/tokens_unheld_total"] == after["moe/tokens_unheld_total"]
    gauge_layers(lc)
    assert reg.gauge("tower/layers").value == 2 and reg.gauge("moe/layers").value == 2
    gauge_layers(ModelConfig.tiny())            # CLIP: no gauge moves
    assert reg.gauge("tower/layers").value == 2


def test_the_scopes_name_the_parts_of_the_tower():
    m = tiny_model(2, 4)
    tower, params = seeded(m)
    text = jax.jit(lambda p, i: tower.apply({"params": p}, i)).lower(
        params, IDS).as_text(debug_info=True)
    for scope in ("tower/embed", "tower/ctx_proj", "layers_0/mla", "layers_0/ffn",
                  "layers_1/moe/router", "layers_1/moe/dispatch",
                  "layers_1/moe/experts", "layers_1/moe/shared",
                  "layers_1/moe/combine"):
        assert scope in text, scope
    assert "layers_0/moe" not in text and "moe/zero" not in text


def test_validation_names_the_tower_and_refuses_to_train_it():
    cfg = TrainConfig(model=tiny_model())
    validate_train_config(cfg)
    cfg.train_text_encoder = True
    with pytest.raises(ValueError, match="openpangu_ultra_moe.*16 bytes a parameter"):
        validate_train_config(cfg)
    cfg = TrainConfig(model=tiny_model(6, 4))
    with pytest.raises(ValueError, match="model.openpangu holds .* not a range"):
        validate_train_config(cfg)
    cfg = TrainConfig(model=tiny_model(num_experts_per_tok=9))
    with pytest.raises(ValueError, match="more experts a token"):
        validate_train_config(cfg)
    got = parse_cli(TrainConfig, ["--model.text_tower=openpangu_ultra_moe",
                                  "--model.openpangu.num_experts_per_tok=5"])
    assert got.model.openpangu.num_experts_per_tok == 5
    assert got.model.openpangu.hidden_size == 7680      # published defaults


def test_one_table_of_towers_is_what_every_reader_reads():
    assert list(TEXT_TOWERS) == ["clip", "longcat_flash", "openpangu_ultra_moe",
                                 "solar_open2"]
    for name, row in TEXT_TOWERS.items():
        m = ModelConfig.tiny()
        m.text_tower = name
        m.longcat, m.openpangu = LongcatFlashConfig.tiny(), OpenPanguUltraMoEConfig.tiny()
        m.solar = SolarOpen2Config.tiny()
        tower = build_text_tower(m, jnp.bfloat16)
        assert f"{type(tower).__module__}:{type(tower).__name__}" == row.module
        assert text_param_dtype(name) == jnp.dtype(row.held_dtype)
        assert row.trainable == (row.held_dtype == "float32")
        assert (row.block is None) == (name == "clip")
        if row.block:
            assert tower.param_dtype == jnp.bfloat16 and tower.dtype == jnp.bfloat16
            assert hasattr(m, row.block)


def test_the_move_left_clips_and_longcats_parameter_trees_as_they_were():
    """Paths, shapes, dtypes and ORDER of the leaves (the benchmark seeds leaf
    i from salt i), pinned by digests taken on the commit before the move;
    LongCat's and openPangu's lowered tiny programs too."""
    m = ModelConfig.tiny()
    tower = build_text_tower(m)
    assert path_digest(jax.eval_shape(
        lambda k: init_text_tower(m, k, tower), jax.random.key(0))) == (
            36, "6e4e7f1868362bf8713edd9c266aebb3f7dd63e98465c493683b86032a9daa7a")
    m = ModelConfig.tiny()
    m.text_tower, m.longcat = "longcat_flash", LongcatFlashConfig.tiny()
    m.longcat.held_experts_first, m.longcat.held_experts_count = 2, 4
    m.text_vocab_size, m.text_max_length = 64, 16
    tower = build_text_tower(m)
    assert path_digest(jax.eval_shape(
        lambda k: init_text_tower(m, k, tower), jax.random.key(0))) == (
            79, "f197947425cecaa8f744253d2a52f8cc3ab25cc1fde870571c58b5f26e601dbe")
    # its lowered program too (StableHLO text of the tiny tower in bfloat16,
    # this JAX's): the shared expert layer computes what ScMoE computed
    text = jax.jit(lambda p, i: build_text_tower(m, jnp.bfloat16).apply(
        {"params": p}, i)).lower(
            jax.eval_shape(lambda k: init_text_tower(m, k, tower), jax.random.key(0)),
            jax.ShapeDtypeStruct((3, 16), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "d4600aacfef5de16d61309e5ab419e51ccd13fbf69b4097506e05fcf5ee915c6")
    # openPangu's tree and lowered program, taken before its router rule and
    # shared-expert module moved to models/lm_layers.py for Solar Open 2
    m = tiny_model(2, 4)
    tower = build_text_tower(m)
    shapes = jax.eval_shape(lambda k: init_text_tower(m, k, tower), jax.random.key(0))
    assert path_digest(shapes) == (
        71, "76fd583a013dca7c82971963e58fede97d43650e818601439dc0bba33d8d371a")
    text = jax.jit(lambda p, i: build_text_tower(m, jnp.bfloat16).apply(
        {"params": p}, i)).lower(shapes, jax.ShapeDtypeStruct((3, 16), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "4c5c87d2bc61003e7d69d882a7b7ab5a78bde9badfd5894cbc63f36420354c36")
    # LongCat at the benchmark's own sizes: four double layers, 16 held
    m = ModelConfig()
    m.text_tower = "longcat_flash"
    m.longcat.num_layers, m.longcat.held_experts_count = 4, 16
    m.text_vocab_size, m.text_max_length = 16384, 256
    tower = build_text_tower(m)
    assert path_digest(jax.eval_shape(
        lambda k: init_text_tower(m, k, tower), jax.random.key(0))) == (
            299, "169cd50addd6c69b45c995cc4afea2e0a0a6305238f9ebafb36aa926f1463681")


def test_precompute_train_and_sample_with_the_tower_end_to_end(tmp_path):
    """`text_tower=openpangu_ultra_moe` through dcr-precompute-latents, one
    dcr-train step from that cache, and dcr-sample from the checkpoint the
    trainer exported: the CLIs' own mains."""
    from PIL import Image

    from dcr_tpu.cli import precompute, sample, train
    from dcr_tpu.core import tracing
    from dcr_tpu.core.config import save_config

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
    assert counts["moe/assignments_zero_total"] == 0
    assert 0 < counts["moe/assignments_held_total"] < counts["moe/assignments_total"]
    assert 0 < counts["moe/tokens_unheld_total"] < 8 * 16 * 2
    assert tracing.registry().gauge("moe/layers").value == 2
    assert tracing.registry().gauge("tower/layers").value == 3
    manifest = json.loads((tmp_path / "cache" / "manifest.json").read_text())
    assert manifest["total"] == 8
    for name in ("load", "encode", "fetch", "write"):
        assert tracing.timeline(f"precompute/{name}")
    train.main(common + [f"--output_dir={tmp_path / 'run'}"])
    ckpt = tmp_path / "run" / "checkpoint"
    index = json.loads((ckpt / "model_index.json").read_text())
    assert index["model_config"]["text_tower"] == "openpangu_ultra_moe"
    assert index["text_encoder"] == ["dcr_tpu", "openpangu_ultra_moe"]
    exported = json.loads((ckpt / "text_encoder" / "config.json").read_text())
    assert exported["architectures"] == ["openpangu_ultra_moe"]
    assert exported["first_k_dense_replace"] == 1 and exported["vocab_size"] == 64
    sample.main([f"--model_path={tmp_path / 'run'}", f"--savepath={tmp_path / 'gen'}",
                 "--modelstyle=nolevel", "--num_batches=1", "--im_batch=1",
                 "--resolution=16", "--num_inference_steps=2", "--sampler=ddim"])
    assert list((tmp_path / "gen" / "generations").glob("*.png"))
