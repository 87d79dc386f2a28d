"""The `encode_leg_solar` driver end to end at a tiny size on the CPU (8
virtual devices, per-device batch 1, the delta rule's chunks cut to 8
positions so that the scan carries its state), `correct` coming out false for
beta without its factor of 2, the control in fp8 failing the cell's limits,
the configuration's file against the catalog's row, the cell as appended
entries, and the new readers: `encode_device_share.kda` and `.gqa` on the
tiny tower's own scope paths and on a hand-made trace, `kda_scan_roofline`
on a hand-made trace."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness, trace as T
from tests.benchmark import tinyroot
from tests.benchmark.conftest import last_line

CELL = "solar-open2-250b-ep8-encode-256"
CONFIG = "benchmark/configs/solar-open2-250b-ep8.json"
TINY_TOWER = {
    "vocab_size": 1000, "hidden_size": 64, "num_hidden_layers": 4,
    "gqa_layers": [0], "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts": 2,
    "num_experts_per_tok": 3, "text_max_length": 16,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "share": {"chips_per_layer": 4, "held_experts_first": 2,
              "held_experts_count": 2, "router_outputs": 8, "vocab_first": 0},
    **{k: tinyroot.TINY_SD21[k] for k in ("unet", "vae", "derived")},
}


@pytest.fixture()
def tiny_encode(tiny, monkeypatch):
    from dcr_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule, "CHUNK", 8)
    monkeypatch.setattr(delta_rule, "SUB", 4)
    tinyroot._edit(tiny / CONFIG, lambda d: d.update(TINY_TOWER))

    def traffic(d):
        d["train_config"].update(train_batch_size=1, mixed_precision="no")
        d["train_config"]["data"]["resolution"] = 16
        d["train_config"]["pipe"]["cache_shard_size"] = 8
        d["overrides"] = ["--data.num_workers=1"]
        d.update(images=24, image_px=24, caption_tokens=[3, 10], check_rows=2)
        # float32 at this size: the program reads 1e-6 against the reference
        d["limits"] = {"ctx_rms_worst": 1e-4, "moments_rms_worst": 1e-4,
                       "router_score_error": 1e-4,
                       "near_tie_tokens_share": 0.5,
                       "choice_outside_tie_share": 0.01, "dropped_assignments": 0}
        d["reference"]["tie_eps"] = 1e-3

    tinyroot._edit(tiny / "benchmark/workloads/encode-256-t256-solar.json", traffic)
    return tiny


def run(capsys, seed: int, trace: int = 0):
    assert harness.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", str(trace)]) == 0
    return last_line(capsys)


def test_runs_end_to_end_and_follows_the_reference(tiny_encode, capsys):
    result, before = run(capsys, 2**31 + 9, trace=1)
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    assert result["rehearsal"] == []        # every reader is silent off the chip
    assert set(result["checks"]) == {
        "ctx_rms_worst", "moments_rms_worst", "router_score_error",
        "near_tie_tokens_share", "choice_outside_tie_share",
        "dropped_assignments"}
    compared = next(x for x in before if x["bench"] == "compared")
    assert compared["second_pass_gap"] == 0.0
    assert len(compared["router_score_rms"]) == 4      # every layer routes
    made = next(x for x in before if x["bench"] == "weights_made")
    assert made["weights_seed"] == harness.load_cell(CELL).config["weights_seed"] == 35101
    first = next(x for x in before if x["bench"] == "first_unit")
    # eight captions of 16 positions, top 3, four expert layers
    assert first["moe/assignments_total"] == 8 * 16 * 3 * 4
    assert first["moe/layers"] == 4 and first["tower/layers"] == 4
    assert first["delta_rule/chunk"] == 8
    assert first["delta_rule/sites_total"] >= 3 and first["delta_rule/chunks_total"] >= 6
    window = next(x for x in before if x["bench"] == "window")
    assert window["compilations_in_window"] == 0 and window["units"] >= 1
    routing = next(x for x in before if x["bench"] == "routing")
    assert routing["moe/assignments_dropped_total"] == 0
    assert routing["held_load_max_over_mean"] >= 1.0


def test_the_flops_and_counters_of_a_unit_are_the_towers(tiny_encode):
    from benchmark.lib import solar_flops, solar_stack
    from benchmark.reference import solar_open2
    from tests.benchmark.test_driver_encode import drive

    driver, got, lines = drive(CELL, 37)
    assert (driver.stack, driver.flops, driver.ref) == (
        solar_stack, solar_flops, solar_open2)
    cfg, routing = driver.cfg, lines["routing"]
    held = routing["moe/assignments_held_total"] / 3
    work, moved = solar_flops.scan_work(cfg, driver.batch, 16)
    assert got == {
        "flops_per_unit": solar_flops.encode_unit_flops(
            cfg, 16, driver.batch, 16, held),
        "held_assignments_per_unit": held,
        "dropped_assignments": 0,
        "held_load_max_over_mean": float(np.mean(driver.load_max) / (
            held / (cfg["n_routed_experts"] * 4))),
        "kda_scan_flops_per_site": work, "kda_scan_bytes_per_site": moved}
    assert {"moe/tokens_unheld_total", "delta_rule/sites_total",
            "delta_rule/chunks_total", "delta_rule/chunk"} <= set(routing)


def test_beta_without_its_factor_of_two_is_not_correct(tiny_encode, capsys, monkeypatch):
    """The program built with `kda_allow_neg_eigval` off: its beta lies in
    (0, 1), the reference's in (0, 2)."""
    from benchmark.lib import solar_stack

    real = solar_stack.model_argv
    monkeypatch.setattr(solar_stack, "model_argv", lambda config, px: [
        *real(config, px), "--model.solar.kda_allow_neg_eigval=false"])
    result, _ = run(capsys, 19)
    assert result["correct"] is False
    worst = result["checks"]["ctx_rms_worst"]
    assert worst["value"] > worst["limit"]


def test_the_control_in_fp8_fails_the_limits(tiny_encode):
    """The reference put in the program's place with fp8 operands in the
    tower's products has to fail one of the cell's numbers, at the limits the
    tiny cell runs under; the reference against itself reads nought."""
    from benchmark.lib import lm_stack, solar_stack
    from benchmark.reference import solar_open2 as ref
    from dcr_tpu.core.config import TrainConfig, parse_cli

    cell = harness.load_cell(CELL)
    driver = harness.load_module("drivers", "encode_leg_solar", tiny_encode)
    cfg = parse_cli(TrainConfig, solar_stack.model_argv(cell.config, 16))
    shapes = lm_stack.weight_shapes(cfg)
    ids = np.random.default_rng(0).integers(0, 1000, (2, 16))
    sizes = solar_stack.reference_sizes(cell.config)
    part = lambda name: solar_stack.tower_leaves(shapes, 5, name, "float32")  # noqa: E731

    def numbers(ops, follow=None):
        with jax.default_matmul_precision("highest"):
            out = ref.forward(sizes, ids, part, ops=ops, tie_eps=1e-3,
                              follow=follow)
        moments = np.zeros((2, 4))
        return {"ctx": np.asarray(out["ctx"]), "mean": moments, "std": moments + 1,
                "dropped": 0, "routing": [
                    {k: np.asarray(v) for k, v in layer.items()}
                    for layer in out["routing"]]}

    chosen = lambda side: [r["chosen"] for r in side["routing"]]    # noqa: E731
    quiet = lambda *a, **k: None                                    # noqa: E731
    program = numbers(ref.EXACT)
    same = driver.compare(program, numbers(ref.EXACT, chosen(program)),
                          cell.traffic["limits"], quiet)
    assert harness.checks_pass(same)
    assert max(c["value"] for c in same if c["name"] != "near_tie_tokens_share") == 0.0
    control = numbers(ref.Ops(quant="fp8"))
    control = driver.compare(control, numbers(ref.EXACT, chosen(control)),
                             cell.traffic["limits"], quiet)
    assert not harness.checks_pass(control), control


PANGU = "openpangu-ultra-moe-718b-ep16-encode-256"
#: the metrics the cell reports, in their order: the other encode cells' as
#: appended entries, then its own two
REPORTED = ["train_images_per_s", "encode_step_mfu", "device_idle_share.encode",
            "encode_phase_share.load", "encode_phase_share.encode",
            "encode_phase_share.fetch", "encode_phase_share.write",
            "moe_held_load_max_over_mean", "encode_device_share.experts",
            "encode_device_share.kda", "kda_scan_roofline",
            "encode_device_share.gqa"]


def test_the_cell_is_entries_appended_and_new_files_only():
    """BENCHMARK.json gained one configuration, one cell after openPangu's,
    the cell's name at the end of nine `workloads` lists and three metrics
    at the end of `per_layer`; its traffic is LongCat's job to the letter."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert bench["configs"][-1]["name"] == "solar-open2-250b-ep8"
    assert [w["name"] for w in bench["workloads"]][-2:] == [PANGU, CELL]
    assert bench["workloads"][-1]["chips"] == 1
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert [m["name"] for m in metrics if CELL in m.get("workloads", [])] == REPORTED
    for m in metrics:
        names = m.get("workloads", [])
        if CELL in names and len(names) > 1:
            assert names[-2:] == [PANGU, CELL], m["name"]
    assert [(m["name"], m["workloads"]) for m in bench["per_layer"][-3:]] == [
        ("encode_device_share.kda", [CELL]), ("kda_scan_roofline", [CELL]),
        ("encode_device_share.gqa", [CELL])]
    cell = harness.load_cell(CELL)
    assert cell.traffic["driver"] == "encode_leg_solar"
    theirs = harness.load_cell("longcat-flash-chat-ep32-encode-256").traffic
    for key in ("train_config", "overrides", "images", "image_px",
                "caption_tokens", "check_rows", "traced_units"):
        assert cell.traffic[key] == theirs[key], key


def test_the_configuration_keeps_the_published_widths():
    """Every key of the catalog's `config` is in the file under the same key;
    only the four keys of `reduced` differ, with the published values beside
    them; the tower's parameters are counted, layer by layer."""
    from benchmark.lib import flops, lm_stack, solar_flops, solar_stack
    from dcr_tpu.core.config import TrainConfig, parse_cli

    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
        "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "gqa_layers": list(range(0, 48, 4)),
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 320,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    doc = json.loads((harness.ROOT / CONFIG).read_text())
    differ = sorted(k for k, v in published.items() if doc[k] != v)
    assert differ == sorted(doc["reduced"]) == [
        "gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert {k: doc["published"][k] for k in differ} == {k: published[k] for k in differ}
    assert (doc["num_hidden_layers"], doc["gqa_layers"], doc["n_routed_experts"],
            doc["vocab_size"]) == (4, [0], 40, 24576)
    share = doc["share"]
    assert share["chips_per_layer"] * doc["n_routed_experts"] == share["router_outputs"] == 320
    assert share["held_experts_count"] == 40 and share["experts_per_token"] == 8
    assert share["vocab_rows"] == doc["vocab_size"] == 196608 // 8
    for key in ("layer kinds", "norms", "kda gates", "beta", "kda output", "gqa gate",
                "scoring function", "renormalisation", "experts", "decay draws",
                "weights_seed", "weights", "left out"):
        assert doc["assumed"][key], key
    cfg = parse_cli(TrainConfig, solar_stack.model_argv(doc, 256))
    tower = cfg.model.solar
    assert cfg.model.text_tower == "solar_open2" and cfg.model.text_max_length == 256
    assert tower.held_range() == (0, 40) and tower.n_routed_experts == 320
    assert (tower.num_hidden_layers, tower.gqa_layers) == (4, (0,))
    shapes = lm_stack.weight_shapes(cfg)
    leaves = {"/".join(p): int(np.prod(s)) for _, p, s in lm_stack.tower_specs(shapes)}
    assert sum(leaves.values()) == doc["derived"]["tower_parameters"] == 3211883072
    under = lambda prefix: sum(n for p, n in leaves.items() if p.startswith(prefix))  # noqa: E731
    assert under("layers_0/") == 755245056 and under("layers_0/gqa/") == 109051904
    assert under("layers_1/") == 783925440 and under("layers_1/kda/") == 137732288
    assert under("layers_1/moe/") + 2 * 4096 == 646193152
    assert under("layers_1/moe/expert_7/") == under("layers_1/moe/shared_experts/") == 15728640
    assert under("embed/") == 24576 * 4096
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes["text"]))
    # 10 TFLOP a unit at 1/8 of 8 choices held, the KDA mixers 3.5 of it
    unit = solar_flops.encode_unit_flops(doc, 256, 16, 256, 4 * 4096)
    vae = 16 * flops.vae_encoder_flops(doc, 256)
    assert 9.8e12 < unit < 10.2e12 and 5.5e12 < unit - vae < 5.8e12
    assert 3.5e12 < 16 * 3 * solar_flops.kda_flops(doc, 256) < 3.56e12
    work, moved = solar_flops.scan_work(doc, 16, 256)
    assert 47.0e9 < work < 47.5e9 and 335e6 < moved < 337e6


# -- the readers -----------------------------------------------------------------

def test_the_kda_and_gqa_scopes_select_the_mixers_and_nothing_else():
    """On the tiny tower's compiled program: `kda` holds the three KDA
    layers' norms and mixers, `kda/scan` the delta rule's ops (its loop
    over chunks among them) and none of the rest; `gqa` the GQA layer's."""
    from dcr_tpu.core.config import ModelConfig, SolarOpen2Config
    from dcr_tpu.models.text_tower import build_text_tower, init_text_tower

    m = ModelConfig.tiny()
    m.text_tower, m.solar = "solar_open2", SolarOpen2Config.tiny()
    m.text_vocab_size, m.text_max_length = 64, 16
    tower = build_text_tower(m)
    params = jax.eval_shape(lambda k: init_text_tower(m, k, tower), jax.random.key(0))
    text = jax.jit(lambda p, i: tower.apply({"params": p}, i)).lower(
        params, jax.ShapeDtypeStruct((2, 16), jnp.int32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    kda = {n for n in names if T.in_scope(n, "kda")}
    scan = {n for n in names if T.in_scope(n, "kda/scan")}
    assert kda == {n for n in names if "/kda/" in n}
    assert scan and scan <= kda and any("/while/" in n for n in scan)
    assert {re.search(r"/(layers_\d+)/", n).group(1) for n in kda} == {
        "layers_1", "layers_2", "layers_3"}
    assert not any(T.in_scope(n, "moe/experts") or T.in_scope(n, "gqa") for n in kda)
    gqa = {n for n in names if T.in_scope(n, "gqa")}
    assert gqa and {re.search(r"/(layers_\d+)/", n).group(1) for n in gqa} == {"layers_0"}
    assert not any(T.in_scope(n, "moe/experts") for n in gqa)


def a_run(trace, counters, peaks=harness.load_peaks()["tpu v5 lite"]):
    cell = harness.Cell("c", 1, "cfg", {}, {}, [], [], harness.ROOT)
    return harness.Run(cell, peaks, harness.Window(), {}, counters, trace, "")


MS = 1e6      # nanoseconds
SCAN = "jit(encode)/tower/layers_{}/kda/kda/scan/{}"


def hand_made() -> T.Trace:
    """Two runs of the program (0-10 and 10-20 ms), each with the scan of
    layers 1 and 2 (a `while` and a body op inside it, then an op after it);
    an op of another scope after them. The traced stretch is 3-20 ms, so
    the first run's layer-1 site (1-2.5 ms) lies outside it."""
    ops, paths = [], []
    for base in (0.0, 10 * MS):
        for layer, at in ((1, 1 * MS), (2, 4 * MS)):
            for name, start, dur in (("while", 0.0, 1 * MS), ("fusion", 0.2 * MS, 0.5 * MS),
                                     ("fusion", 1 * MS, 0.5 * MS)):
                ops.append((f"%{name}.1 = f32[8]{{0}} {name}(f32[8]{{0}} %x)",
                            base + at + start, dur))
                paths.append(SCAN.format(layer, "while" if start == 0.0 else
                                         "while/body/dot_general" if name == "fusion"
                                         and start < MS else "dot_general"))
        ops.append(("%copy.2 = f32[8]{0} copy(f32[8]{0} %x)", base + 6 * MS, 1 * MS))
        paths.append("jit(encode)/tower/layers_1/moe/experts/copy")
    return T.Trace(ops={0: ops}, modules={0: [("jit_encode(1)", 0.0, 10 * MS),
                                              ("jit_encode(1)", 10 * MS, 10 * MS)]},
                   host=[("bench/traced", 3 * MS, 17 * MS)], scopes={0: paths})


def test_the_scan_roofline_counts_whole_sites_of_the_stretch():
    reader = harness.load_module("metrics", "kda_scan_roofline")
    peaks = harness.load_peaks()["tpu v5 lite"]
    counters = {"kda_scan_flops_per_site": 197e9, "kda_scan_bytes_per_site": 409.5e6}
    least = max(197e9 / peaks["bf16_flops_per_s"], 409.5e6 / peaks["hbm_bytes_per_s"])
    assert least == pytest.approx(1e-3)
    # three whole sites (the second run's two, the first run's layer 2), each
    # busy 1.5 ms: the `while`, the op inside it once, the op after it
    assert len(reader.sites(hand_made(), 0)) == 4
    assert reader.read(a_run(hand_made(), counters)) == pytest.approx(100 * 3 / 4.5)


def test_the_scan_roofline_reads_nothing_where_there_is_nothing():
    reader = harness.load_module("metrics", "kda_scan_roofline")
    counters = {"kda_scan_flops_per_site": 1e9, "kda_scan_bytes_per_site": 1e6}
    assert reader.read(a_run(None, counters)) is None                # no trace
    assert reader.read(a_run(hand_made(), counters, peaks=None)) is None
    assert reader.read(a_run(hand_made(), {})) is None               # no counters
    tr = hand_made()
    tr.scopes = {0: ["jit(encode)/tower/layers_1/gqa/gqa/dot_general"] * len(tr.ops[0])}
    assert reader.read(a_run(tr, counters)) is None                  # none in scope
    share = harness.load_module("metrics", "encode_device_share")
    run = a_run(hand_made(), counters)
    run.group = "kda"
    assert share.scope_of(run) == "kda"
    assert share.read(run) == pytest.approx(100 * 4.5 / 6.5)
    run.group = "gqa"
    assert share.scope_of(run) == "gqa"
    assert share.read(run) is None                                   # no GQA op
    run = a_run(tr, counters)
    run.group = "gqa"
    assert share.read(run) == pytest.approx(100.0)                   # every op
