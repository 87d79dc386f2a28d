"""The `encode_leg` driver end to end at a tiny size on the CPU (8 virtual
devices, per-device batch 1), `correct` coming out false for a fault planted
in the timed path, and the control in fp8 failing the cell's limits."""
import json
import shutil

import jax
import numpy as np
import pytest

from benchmark.lib import harness
from tests.benchmark import tinyroot
from tests.benchmark.conftest import last_line

CELL = "longcat-flash-chat-ep32-encode-256"
TINY_TOWER = {
    "vocab_size": 1000, "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "n_routed_experts": 2,
    "zero_expert_num": 4, "moe_topk": 3, "text_max_length": 16,
    "share": {"chips_per_layer": 4, "held_experts_first": 2,
              "held_experts_count": 2, "router_outputs": 12, "vocab_first": 0},
    **{k: tinyroot.TINY_SD21[k] for k in ("unet", "vae", "derived")},
}


@pytest.fixture()
def tiny_encode(tiny):
    tinyroot._edit(tiny / "benchmark/configs/longcat-flash-chat-ep32.json",
                   lambda d: d.update(TINY_TOWER))

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

    tinyroot._edit(tiny / "benchmark/workloads/encode-256-t256.json", traffic)
    return tiny


def run(capsys, seed: int, trace: int = 0):
    assert harness.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", str(trace)]) == 0
    return last_line(capsys)


def test_runs_end_to_end_and_follows_the_reference(tiny_encode, capsys):
    result, before = run(capsys, 2**31 + 5, trace=1)
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    assert result["rehearsal"] == []        # every reader is silent off the chip
    assert set(result["checks"]) == {
        "ctx_rms_worst", "moments_rms_worst", "router_score_error",
        "near_tie_tokens_share", "choice_outside_tie_share",
        "dropped_assignments"}
    compared = next(x for x in before if x["bench"] == "compared")
    # the second pass that reads the scores routed as the timed pass did
    assert compared["second_pass_gap"] == 0.0
    # the tower's weights are the configuration's, whatever --seed is
    made = next(x for x in before if x["bench"] == "weights_made")
    assert made["weights_seed"] == harness.load_cell(CELL).config["weights_seed"] == 28101
    first = next(x for x in before if x["bench"] == "first_unit")
    assert first["moe/assignments_total"] == 8 * 16 * 3 * 2
    assert all(3 <= n <= 10 for n in first["real_tokens"])
    window = next(x for x in before if x["bench"] == "window")
    assert window["compilations_in_window"] == 0 and window["units"] >= 1
    routing = next(x for x in before if x["bench"] == "routing")
    assert routing["moe/assignments_dropped_total"] == 0
    assert routing["held_load_max_over_mean"] >= 1.0
    assert not (tiny_encode / "benchmark" / ".work" / CELL).exists()


def drive(cell_name: str, seed: int):
    """A driver's set-up, two more units and its counters, called as
    `harness.run` calls them. -> (the driver, its counters, its log lines)"""
    cell = harness.load_cell(cell_name)
    lines = []
    bench = harness.Bench(cell, seed, 0.0, False, jax.devices()[:cell.chips],
                          harness.CompileMeter())
    bench.log = lambda what, **fields: lines.append({"bench": what, **fields})
    shutil.rmtree(bench.work, ignore_errors=True)
    bench.work.mkdir(parents=True)
    driver = harness.load_module("drivers", cell.traffic["driver"]).Driver(bench)
    try:
        driver.setup()
        for _ in range(2):
            driver.unit()
        driver.drain()
        counters = driver.counters(bench.window)
    finally:
        driver.close()
        shutil.rmtree(bench.work, ignore_errors=True)
    return driver, counters, {x["bench"]: x for x in lines}


def test_the_flops_and_counters_of_a_unit_are_longcats(tiny_encode):
    """The driver names its tower's modules; the FLOPs of a unit and the
    counters are what `lm_flops` and the program's counters give, over every
    double layer's held experts, as before the tower became an attribute."""
    from benchmark.drivers.encode_leg import MOE_COUNTERS
    from benchmark.lib import lm_flops, lm_stack
    from benchmark.reference import longcat_flash

    driver, got, lines = drive(CELL, 29)
    assert (driver.stack, driver.flops, driver.ref) == (lm_stack, lm_flops, longcat_flash)
    cfg, routing = driver.cfg, lines["routing"]
    assert routing["units"] == 3
    held = routing["moe/assignments_held_total"] / 3
    assert got == {
        "flops_per_unit": lm_flops.encode_unit_flops(
            cfg, 16, driver.batch, cfg["text_max_length"], held),
        "held_assignments_per_unit": held,
        "dropped_assignments": 0,
        "held_load_max_over_mean": float(np.mean(driver.load_max) / (
            held / (cfg["n_routed_experts"] * cfg["num_layers"])))}
    assert set(lines["first_unit"]) == {
        "bench", "real_tokens", *MOE_COUNTERS, "compile_seconds", "compilations",
        "cache_hits", "cache_misses"}
    assert set(routing) == {"bench", "units", *MOE_COUNTERS, *got} - {"flops_per_unit"}
    assert {"load_s", "encode_s", "fetch_s", "write_s"} <= set(lines["phases"])


def test_an_expert_layer_that_adds_half_of_its_part_is_not_correct(tiny_encode, capsys, monkeypatch):
    """The fault planted in the timed path: every expert layer's shortcut
    (routed and zero-compute parts) halved; the dense half stays."""
    from dcr_tpu.models import longcat_flash as lf

    real = lf.ScMoE.__call__

    def halved(self, n):
        out, stats = real(self, n)
        return out * 0.5, stats

    monkeypatch.setattr(lf.ScMoE, "__call__", halved)
    result, _ = run(capsys, 13)
    assert result["correct"] is False
    worst = result["checks"]["ctx_rms_worst"]
    assert worst["value"] > worst["limit"]


def test_a_router_that_leaves_the_bias_out_is_not_correct(tiny_encode, capsys, monkeypatch):
    """The fault planted in the timed path's routing: the program is handed a
    score-correction bias of nought (the reference keeps the seeded one), so
    it chooses by the plain scores; its choices then lie outside what any
    scores within `tie_eps` of the reference's would choose."""
    from benchmark.lib import lm_stack

    real = lm_stack.tower_leaves

    def without_bias(shapes, seed, part=None, dtype="bfloat16"):
        tree = real(shapes, seed, part, dtype)
        if dtype == "float32":          # the reference's leaves
            return tree
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if "e_score_correction_bias" in str(path[-1])
            else x, tree)

    monkeypatch.setattr(lm_stack, "tower_leaves", without_bias)
    result, _ = run(capsys, 17)
    assert result["correct"] is False
    outside = result["checks"]["choice_outside_tie_share"]
    assert outside["value"] > 0.2 > outside["limit"]


def test_the_control_in_fp8_fails_the_limits(tiny_encode):
    """The reference put in the program's place with fp8 operands in the
    tower's products has to fail one of the cell's numbers, at the limits the
    tiny cell runs under; the reference against itself reads nought."""
    from benchmark.lib import lm_stack
    from benchmark.reference import longcat_flash as ref
    from dcr_tpu.core.config import TrainConfig, parse_cli

    cell = harness.load_cell(CELL)
    driver = harness.load_module("drivers", "encode_leg", tiny_encode)
    cfg = parse_cli(TrainConfig, lm_stack.model_argv(cell.config, 16))
    shapes = lm_stack.weight_shapes(cfg)
    ids = np.random.default_rng(0).integers(0, 1000, (2, 16))
    sizes = lm_stack.reference_sizes(cell.config)
    part = lambda name: lm_stack.tower_leaves(shapes, 5, name, "float32")  # noqa: E731

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


#: what PR 28 appended to `per_layer`, in its order
PR_28 = ["encode_step_mfu", "device_idle_share.encode", "encode_phase_share.load",
         "encode_phase_share.encode", "encode_phase_share.fetch",
         "encode_phase_share.write", "moe_held_load_max_over_mean"]


def check_pr_28_follows_the_ten(bench: dict) -> None:
    """PR 26's ten stand together and in order, and PR 28's seven follow
    them directly, in order; what later PRs append after those is theirs."""
    from tests.benchmark.test_program_spans import NEW, WANT

    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW and set(WANT) == set(NEW)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"][at:at + len(NEW)]:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["workloads"] and set(m["workloads"]) <= set(
            e2e[m["moves"]]["workloads"])
    after = at + len(NEW)
    assert names[after:after + len(PR_28)] == PR_28


def test_the_span_metrics_of_pr_26_stand_as_they_were():
    """`test_program_spans.py::check_the_ten` asks that PR 26's ten stand in
    order; here they stand together, and PR 28's seven directly after them."""
    check_pr_28_follows_the_ten(json.loads((harness.ROOT / "BENCHMARK.json").read_text()))


def test_the_configuration_keeps_the_published_widths():
    """Every number of the catalog's `config` is in the file under the same
    key; only the three keys of `reduced` differ, with the published counts
    beside them; the tower's parameters are counted."""
    from benchmark.lib import lm_flops, lm_stack
    from dcr_tpu.core.config import TrainConfig, parse_cli

    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
        "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}
    doc = json.loads((harness.ROOT / "benchmark/configs/longcat-flash-chat-ep32.json").read_text())
    differ = sorted(k for k, v in published.items() if doc[k] != v)
    assert differ == sorted(doc["reduced"]) == ["n_routed_experts", "num_layers", "vocab_size"]
    assert {k: doc["published"][k] for k in differ} == {k: published[k] for k in differ}
    assert (doc["num_layers"], doc["n_routed_experts"], doc["vocab_size"]) == (4, 16, 16384)
    assert doc["share"]["chips_per_layer"] * doc["n_routed_experts"] == 512
    cfg = parse_cli(TrainConfig, lm_stack.model_argv(doc, 256))
    assert cfg.model.text_tower == "longcat_flash" and cfg.model.text_max_length == 256
    assert cfg.model.longcat.held_range() == (0, 16) and cfg.model.longcat.n_routed_experts == 512
    shapes = lm_stack.weight_shapes(cfg)
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["text"]))
    assert count == doc["derived"]["tower_parameters"] == 5078377472
    assert all(str(x.dtype) == "bfloat16" for x in jax.tree.leaves(shapes["text"]))
    # 25.8 TFLOP a unit, the tower more than four fifths of it
    unit = lm_flops.encode_unit_flops(doc, 256, 16, 256, 4096)
    assert 25e12 < unit < 27e12
    from benchmark.lib import flops
    assert 16 * flops.vae_encoder_flops(doc, 256) < 0.2 * unit
