"""chip_smoke.py rehearsed on the CPU: the script's own phases at
``ModelConfig.tiny()``, steered from here by shrinking ``chip_smoke.SIZE``
and never by an option of the program, plus pins of its contract with the
driver (last line, exit codes) and of the two helpers PR 24 made strict."""

import json
import os
from pathlib import Path

import jax
import pytest

import chip_smoke

TINY_MODEL_ARGV = (
    "--model.sample_size=8", "--model.block_out_channels=32,64",
    "--model.layers_per_block=1", "--model.attention_head_dim=8",
    "--model.cross_attention_dim=32", "--model.norm_num_groups=8",
    "--model.vae_block_out_channels=16,32", "--model.vae_layers_per_block=1",
    "--model.text_vocab_size=1000", "--model.text_hidden_size=32",
    "--model.text_layers=2", "--model.text_heads=2",
    "--model.text_max_length=16", "--model.flash_attention=false",
    "--mixed_precision=no", "--data.num_workers=2")

TINY = {
    "platform": "cpu",
    "kernel_shape": (1, 256, 2, 64), "kernel_interpret": True,
    # per-device batch 1: the CLI spreads it over conftest's 8 virtual devices
    "images": 16, "image_px": 40, "train_px": 16, "train_batch": 1,
    "model_argv": TINY_MODEL_ARGV,
    "samples": ((16, 2), (32, 1)), "sample_steps": 3,
    "embed_px": 32, "embed_batch": 8,
}


@pytest.fixture()
def tiny(monkeypatch, tmp_path):
    for key, value in TINY.items():
        monkeypatch.setitem(chip_smoke.SIZE, key, value)
    monkeypatch.setattr(chip_smoke, "WORK", tmp_path / "work")


def _lines(capsys) -> list[dict]:
    # the CLIs print reports of their own to stdout, indented over many lines
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"')]


@pytest.mark.parametrize("only", [
    "kernel", "train,sample,search", "sample", "search"])
def test_phases_run_on_cpu_at_tiny_size(tiny, capsys, only):
    """Rehearsal 1: the phases' control flow, paths and CLI arguments; the
    stand-ins --only makes for a phase that was left out."""
    assert chip_smoke.main([f"--only={only}"]) == 0
    lines = _lines(capsys)
    phases = [line["phase"] for line in lines[:-1]]
    assert phases == ["device"] + only.split(",")
    for line in lines[:-1]:
        assert line["ok"] is True, line
        assert {"seconds", "compile_seconds", "cache_hits", "cache_misses",
                "peak_bytes_in_use"} <= set(line)
        assert "not run" not in json.dumps(line)
    by_phase = {line["phase"]: line for line in lines[:-1]}
    if "train" in by_phase:
        assert len(by_phase["train"]["losses"]) == 3
        assert by_phase["train"]["jpeg_decoder"] == "native"
    if "search" in by_phase:
        assert by_phase["search"]["keys_equal"] is True
        assert set(by_phase["search"]["max_abs_score_diff"]) == {
            "brute", "store"}
    # (a) the exact shape of the last line
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert list(lines[-1]) == ["ok", "device"]
    assert list(lines[-1]["device"]) == ["platform", "kind", "count"]
    assert not chip_smoke.WORK.exists()       # nothing is left behind


def test_four_chip_phase_on_virtual_devices(tiny, capsys, monkeypatch):
    """Rehearsal 2: the --chips 4 path, on four of the virtual CPU devices."""
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    monkeypatch.setitem(chip_smoke.SIZE, "train_batch", 8)   # global here
    monkeypatch.setitem(chip_smoke.SIZE, "images", 24)       # three batches
    assert chip_smoke.main(["--chips=4"]) == 0
    lines = _lines(capsys)
    assert [line["phase"] for line in lines[:-1]] == [
        "device", "train_four_chips"]
    run = lines[1]
    assert run["four_chips"]["devices_holding_parameters"] == [4]
    assert run["four_chips"]["devices_holding_batch"] == 4
    assert run["four_chips"]["batch_rows_per_device"] == [2, 2, 2, 2]
    assert run["one_chip"]["devices_holding_parameters"] == [1]
    assert run["max_rel_diff"] <= run["rel_tol"]
    assert lines[-1]["device"]["count"] == 4


def test_platform_other_than_tpu_fails_before_any_model(capsys, monkeypatch):
    """(b) what the driver's sandbox run must see: non-zero, ``"ok":
    false``, and no phase after ``device``."""
    monkeypatch.setattr(chip_smoke, "WORK", Path("/nonexistent/never-made"))
    assert chip_smoke.main([]) != 0
    lines = _lines(capsys)
    assert [line.get("phase") for line in lines] == ["device", None]
    assert lines[0]["ok"] is False and "tpu" in lines[0]["error"]
    assert lines[-1] == {"ok": False, "failed_phase": "device"}


def test_a_phase_that_raises_ends_the_run(tiny, capsys, monkeypatch):
    """(c) no phase is caught and carried past: the run stops there."""
    def boom():
        raise RuntimeError("kernel fault")

    monkeypatch.setattr(chip_smoke, "phase_kernel", boom)
    assert chip_smoke.main(["--only=kernel,search"]) != 0
    lines = _lines(capsys)
    assert [line.get("phase") for line in lines] == ["device", "kernel", None]
    assert lines[1]["ok"] is False and "kernel fault" in lines[1]["error"]
    assert lines[-1]["ok"] is False
    assert not any(line.get("ok") is True and "phase" not in line
                   for line in lines)


def test_a_cli_that_exits_by_itself_ends_the_run(tiny, capsys, monkeypatch):
    def exits():
        raise SystemExit(83)

    monkeypatch.setattr(chip_smoke, "phase_kernel", exits)
    assert chip_smoke.main(["--only=kernel"]) != 0
    assert _lines(capsys)[-1] == {"ok": False, "failed_phase": "kernel"}


@pytest.mark.parametrize("on_tpu,px,images,sites", [
    (True, 512, 1, 5),      # the top level's 4,096 tokens; 1,024 x 20 heads stay on XLA
    (True, 256, 4, 5),      # 8 rows x 5 heads at 1,024 tokens: past XLA's on-chip logits
    (True, 256, 2, 0),      # 4 rows x 5 heads: XLA's
    (False, 512, 1, 0),     # no Mosaic kernel exists off the TPU
])
def test_flash_sites_follow_the_dispatcher(monkeypatch, tmp_path, on_tpu, px,
                                           images, sites):
    """The sampler's expected tpu_custom_call count is derived from the
    exported UNet's sites and the dispatcher's policy, not written down."""
    import dataclasses

    from dcr_tpu.core.config import ModelConfig
    from dcr_tpu.ops import attention

    monkeypatch.setattr(chip_smoke, "WORK", tmp_path)
    ckpt = tmp_path / "run" / "checkpoint"
    ckpt.mkdir(parents=True)
    (ckpt / "model_index.json").write_text(json.dumps(
        {"model_config": dataclasses.asdict(ModelConfig())}))
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    assert chip_smoke.flash_sites(px, images) == sites


def test_compile_cache_helper(monkeypatch):
    """(d) variable set -> nothing is set in code; unset -> the checkout's
    .jax_cache, from the package's own path."""
    from dcr_tpu.cli import setup_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert setup_compile_cache() == "/some/where"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(Path(chip_smoke.__file__).resolve().parent / ".jax_cache")
    assert setup_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_conftest_cache_yields_to_the_variable():
    """conftest's fixed directory is a default of the variable, not an
    override of it."""
    from tests import conftest

    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(conftest._cache)


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197.0),
                                       ("TPU v4", 275.0), ("cpu", None)])
def test_chip_peak_tflops_known_kinds(monkeypatch, kind, peak):
    from dcr_tpu.utils import profiling

    platform = "cpu" if kind == "cpu" else "tpu"
    monkeypatch.setattr(jax, "devices", lambda: [_Device(platform, kind)])
    assert profiling.chip_peak_tflops() == peak


def test_chip_peak_tflops_unknown_kind_raises(monkeypatch):
    """(e) a device that is not in the table is an error, not a default."""
    from dcr_tpu.utils import profiling

    monkeypatch.setattr(jax, "devices", lambda: [_Device("tpu", "TPU v9")])
    with pytest.raises(ValueError, match="tpu v9"):
        profiling.chip_peak_tflops()
