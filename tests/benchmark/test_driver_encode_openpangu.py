"""The `encode_leg_openpangu` driver end to end at a tiny size on the CPU (8
virtual devices, per-device batch 1), `correct` coming out false for faults
planted in the timed path (a post-norm skipped, the shared expert left out,
chosen weights not renormalised), the control in fp8 failing the cell's
limits, and the configuration's file against the catalog's row."""
import json

import jax
import numpy as np
import pytest

from benchmark.lib import harness
from tests.benchmark import tinyroot
from tests.benchmark.conftest import last_line

CELL = "openpangu-ultra-moe-718b-ep16-encode-256"
CONFIG = "benchmark/configs/openpangu-ultra-moe-718b-ep16.json"
TINY_TOWER = {
    "vocab_size": 1000, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "kv_lora_rank": 16,
    "q_lora_rank": 32, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "qk_nope_head_dim": 16, "n_routed_experts": 2, "num_experts_per_tok": 3,
    "text_max_length": 16,
    "share": {"chips_per_layer": 4, "held_experts_first": 2,
              "held_experts_count": 2, "router_outputs": 8, "vocab_first": 0},
    **{k: tinyroot.TINY_SD21[k] for k in ("unet", "vae", "derived")},
}


@pytest.fixture()
def tiny_encode(tiny):
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

    tinyroot._edit(tiny / "benchmark/workloads/encode-256-t256-openpangu.json",
                   traffic)
    return tiny


def run(capsys, seed: int, trace: int = 0):
    assert harness.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", str(trace)]) == 0
    return last_line(capsys)


def test_runs_end_to_end_and_follows_the_reference(tiny_encode, capsys):
    result, before = run(capsys, 2**31 + 7, trace=1)
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    assert result["rehearsal"] == []        # every reader is silent off the chip
    assert set(result["checks"]) == {
        "ctx_rms_worst", "moments_rms_worst", "router_score_error",
        "near_tie_tokens_share", "choice_outside_tie_share",
        "dropped_assignments"}
    compared = next(x for x in before if x["bench"] == "compared")
    assert compared["second_pass_gap"] == 0.0
    assert len(compared["router_score_rms"]) == 2      # the two expert layers
    made = next(x for x in before if x["bench"] == "weights_made")
    assert made["weights_seed"] == harness.load_cell(CELL).config["weights_seed"] == 32101
    first = next(x for x in before if x["bench"] == "first_unit")
    # eight captions of 16 positions, top 3, two expert layers of three
    assert first["moe/assignments_total"] == 8 * 16 * 3 * 2
    assert first["moe/assignments_zero_total"] == 0
    assert first["moe/layers"] == 2 and first["tower/layers"] == 3
    assert 0 < first["moe/tokens_unheld_total"] <= 8 * 16 * 2
    assert all(3 <= n <= 10 for n in first["real_tokens"])
    window = next(x for x in before if x["bench"] == "window")
    assert window["compilations_in_window"] == 0 and window["units"] >= 1
    routing = next(x for x in before if x["bench"] == "routing")
    assert routing["moe/assignments_dropped_total"] == 0
    assert routing["held_load_max_over_mean"] >= 1.0
    assert routing["moe/tokens_unheld_total"] > first["moe/tokens_unheld_total"]
    assert not (tiny_encode / "benchmark" / ".work" / CELL).exists()


def test_the_flops_and_counters_of_a_unit_are_the_towers(tiny_encode):
    """The driver names this tower's modules and runs `encode_leg`'s set-up
    and counters: the FLOPs of a unit are `pangu_flops`', the mean load is
    over the expert layers' held experts only, and the log's lines carry the
    counters PR 32 added and the window's phases, as before."""
    from benchmark.drivers.encode_leg import MOE_COUNTERS
    from benchmark.lib import pangu_flops, pangu_stack
    from benchmark.reference import openpangu_ultra_moe
    from tests.benchmark.test_driver_encode import drive

    driver, got, lines = drive(CELL, 31)
    assert (driver.stack, driver.flops, driver.ref) == (
        pangu_stack, pangu_flops, openpangu_ultra_moe)
    assert not {"setup", "counters", "release", "reference"} & set(vars(type(driver)))
    cfg, routing = driver.cfg, lines["routing"]
    assert routing["units"] == 3
    held = routing["moe/assignments_held_total"] / 3
    assert got == {
        "flops_per_unit": pangu_flops.encode_unit_flops(
            cfg, 16, driver.batch, cfg["text_max_length"], held),
        "held_assignments_per_unit": held,
        "dropped_assignments": 0,
        "held_load_max_over_mean": float(np.mean(driver.load_max) / (
            held / (cfg["n_routed_experts"] * 2)))}      # two expert layers
    new = {"moe/tokens_unheld_total", "moe/layers", "tower/layers"}
    assert set(lines["first_unit"]) == {
        "bench", "real_tokens", *MOE_COUNTERS, *new, "compile_seconds",
        "compilations", "cache_hits", "cache_misses"}
    assert set(routing) == {"bench", "units", *MOE_COUNTERS, *new, *got} - {
        "flops_per_unit"}
    assert {"load_s", "encode_s", "fetch_s", "write_s"} <= set(lines["phases"])


def test_a_post_norm_skipped_is_not_correct(tiny_encode, capsys, monkeypatch):
    """The fault planted in the timed path: the norm on the FFN's output is
    computed and thrown away, so the sublayer joins the residual unnormed."""
    from dcr_tpu.models import lm_layers

    real = lm_layers.RMSNorm.__call__

    def skipping(self, x):
        y = real(self, x)
        return x.astype(y.dtype) if self.name == "post_mlp_layernorm" else y

    monkeypatch.setattr(lm_layers.RMSNorm, "__call__", skipping)
    result, _ = run(capsys, 13)
    assert result["correct"] is False
    worst = result["checks"]["ctx_rms_worst"]
    assert worst["value"] > worst["limit"]


def test_a_shared_expert_left_out_is_not_correct(tiny_encode, capsys, monkeypatch):
    """The program is handed shared experts whose output kernel is nought (the
    reference keeps the seeded one): every token loses the shared term."""
    from benchmark.lib import pangu_stack

    real = pangu_stack.tower_leaves

    def without_shared(shapes, seed, part=None, dtype="bfloat16"):
        tree = real(shapes, seed, part, dtype)
        if dtype == "float32":          # the reference's leaves
            return tree
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if "shared_experts" in str(path)
            and "down_proj" in str(path) else x, tree)

    monkeypatch.setattr(pangu_stack, "tower_leaves", without_shared)
    result, _ = run(capsys, 17)
    assert result["correct"] is False
    worst = result["checks"]["ctx_rms_worst"]
    assert worst["value"] > worst["limit"]


def test_chosen_weights_not_renormalised_are_not_correct(tiny_encode, capsys, monkeypatch):
    """The program is built with `norm_topk_prob` off, so its three chosen
    weights are 2.5 times the plain sigmoid scores and sum to about 5, not to
    2.5; the first expert layer's choices stay (its input is untouched), so
    there only the states can show it."""
    from benchmark.lib import pangu_stack

    real = pangu_stack.model_argv
    monkeypatch.setattr(pangu_stack, "model_argv", lambda config, px: [
        *real(config, px), "--model.openpangu.norm_topk_prob=false"])
    result, before = run(capsys, 19)
    assert result["correct"] is False
    worst = result["checks"]["ctx_rms_worst"]
    assert worst["value"] > worst["limit"]
    compared = next(x for x in before if x["bench"] == "compared")
    assert compared["choice_outside_tie_share"][0] == 0.0


def test_the_control_in_fp8_fails_the_limits(tiny_encode):
    """The reference put in the program's place with fp8 operands in the
    tower's products has to fail one of the cell's numbers, at the limits the
    tiny cell runs under; the reference against itself reads nought."""
    from benchmark.lib import lm_stack, pangu_stack
    from benchmark.reference import openpangu_ultra_moe as ref
    from dcr_tpu.core.config import TrainConfig, parse_cli

    cell = harness.load_cell(CELL)
    driver = harness.load_module("drivers", "encode_leg_openpangu", tiny_encode)
    cfg = parse_cli(TrainConfig, pangu_stack.model_argv(cell.config, 16))
    shapes = lm_stack.weight_shapes(cfg)
    ids = np.random.default_rng(0).integers(0, 1000, (2, 16))
    sizes = pangu_stack.reference_sizes(cell.config)
    part = lambda name: pangu_stack.tower_leaves(shapes, 5, name, "float32")  # noqa: E731

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


LONGCAT = "longcat-flash-chat-ep32-encode-256"
#: the metrics the cell reported when PR 32 brought it, in their order
REPORTED = ["train_images_per_s", "encode_step_mfu", "device_idle_share.encode",
            "encode_phase_share.load", "encode_phase_share.encode",
            "encode_phase_share.fetch", "encode_phase_share.write",
            "moe_held_load_max_over_mean"]


def check_the_cell(bench: dict) -> None:
    """The configuration and the cell, found by name, each directly after
    LongCat's, and the cell directly after LongCat's in every `workloads`
    list that names both; the eight metrics PR 32 listed it under come first
    among those that list it now, in their order."""
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    at = configs.index("openpangu-ultra-moe-718b-ep16")
    assert configs[at - 1] == "longcat-flash-chat-ep32"
    assert cells[cells.index(CELL) - 1] == LONGCAT
    assert bench["workloads"][cells.index(CELL)]["chips"] == 1
    metrics = bench["end_to_end"] + bench["per_layer"]
    listed = [m["name"] for m in metrics if CELL in m.get("workloads", [])]
    assert listed[:len(REPORTED)] == REPORTED
    for m in metrics:
        names = m.get("workloads", [])
        if CELL in names and LONGCAT in names:
            assert names.index(CELL) == names.index(LONGCAT) + 1, m["name"]


def test_the_cell_is_entries_appended_and_new_files_only():
    """BENCHMARK.json gained one configuration, one cell and the cell's name
    in eight `workloads` lists; its traffic is LongCat's job to the letter."""
    check_the_cell(json.loads((harness.ROOT / "BENCHMARK.json").read_text()))
    cell = harness.load_cell(CELL)
    assert cell.traffic["driver"] == "encode_leg_openpangu"
    theirs = harness.load_cell(LONGCAT).traffic
    for key in ("train_config", "overrides", "images", "image_px",
                "caption_tokens", "check_rows", "traced_units"):
        assert cell.traffic[key] == theirs[key], key


def test_the_configuration_keeps_the_published_widths():
    """Every key of the catalog's `config` is in the file under the same key;
    only the four keys of `reduced` differ, with the published counts beside
    them; the tower's parameters are counted, not written."""
    from benchmark.lib import flops, lm_stack, pangu_flops, pangu_stack
    from dcr_tpu.core.config import TrainConfig, parse_cli

    published = {
        "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
        "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_theta": 25600000, "routed_scaling_factor": 2.5,
        "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600}
    doc = json.loads((harness.ROOT / CONFIG).read_text())
    differ = sorted(k for k, v in published.items() if doc[k] != v)
    assert differ == sorted(doc["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert {k: doc["published"][k] for k in differ} == {k: published[k] for k in differ}
    assert (doc["num_hidden_layers"], doc["first_k_dense_replace"],
            doc["n_routed_experts"], doc["vocab_size"]) == (5, 1, 16, 19200)
    share = doc["share"]
    assert share["chips_per_layer"] * doc["n_routed_experts"] == share["router_outputs"] == 256
    assert share["held_experts_count"] == 16 and share["experts_per_token"] == 8
    assert share["vocab_rows"] == doc["vocab_size"] == 153600 // 8
    assert len(pangu_stack.expert_layers(doc)) == 4         # the guide's floor
    for key in ("scoring function", "renormalisation", "activation", "rotary",
                "sandwich_norm", "final norm", "ctx_proj", "text_max_length",
                "tokenizer", "weights_seed", "weights", "left out"):
        assert doc["assumed"][key], key
    cfg = parse_cli(TrainConfig, pangu_stack.model_argv(doc, 256))
    tower = cfg.model.openpangu
    assert cfg.model.text_tower == "openpangu_ultra_moe" and cfg.model.text_max_length == 256
    assert tower.held_range() == (0, 16) and tower.n_routed_experts == 256
    assert (tower.num_hidden_layers, tower.first_k_dense_replace) == (5, 1)
    shapes = lm_stack.weight_shapes(cfg)
    leaves = {"/".join(p): int(np.prod(s)) for _, p, s in lm_stack.tower_specs(shapes)}
    count = sum(leaves.values())
    assert count == doc["derived"]["tower_parameters"] == 4779548160
    under = lambda prefix: sum(n for p, n in leaves.items() if p.startswith(prefix))  # noqa: E731
    assert under("layers_0/") == 621281280                  # a dense layer
    assert under("layers_1/") == 1000734720                 # an expert layer's share
    assert under("layers_1/self_attn/") == 196577280
    assert under("layers_1/moe/expert_3/") == under("layers_1/moe/shared_experts/") == 47185920
    assert under("layers_1/moe/router/") == 7680 * 256
    assert under("embed/") == 19200 * 7680
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes["text"]))
    # 18.6 TFLOP a unit at 2,048 held assignments a layer, the tower three quarters
    unit = pangu_flops.encode_unit_flops(doc, 256, 16, 256, 4 * 2048)
    assert 18.4e12 < unit < 18.8e12
    vae = 16 * flops.vae_encoder_flops(doc, 256)
    assert 0.74 < (unit - vae) / unit < 0.78
    assert 0.7e12 < pangu_flops.expert_flops(doc, 4 * 2048) < 0.85e12
