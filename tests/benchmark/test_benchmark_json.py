"""BENCHMARK.json against the harness and the contract's letter: every cell,
configuration, driver and metric it names resolves to a file; names and units
use only the allowed characters; and a later PR's cell is files and entries."""
import copy
import json
import re
import shutil

import pytest

from benchmark.lib import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_names_units_and_lines():
    names = []
    for group, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                        ("workloads", {"name", "config", "traffic", "chips", "why"})):
        for entry in BENCH[group]:
            assert set(entry) == keys, entry
            names.append(entry["name"])
            for key in ("why", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                        and "\t" not in entry[key], (entry["name"], key)
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_name_resolves_to_a_file():
    cells = {w["name"] for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert c["file"].startswith("benchmark/configs/")
        doc = json.loads((harness.ROOT / c["file"]).read_text())
        assert doc["reduced"] == c["reduced"] and doc["source"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    e2e_names = {m["name"] for m in BENCH["end_to_end"]}
    for name in cells:
        cell = harness.load_cell(name)
        driver = harness.load_module("drivers", cell.traffic["driver"])
        assert hasattr(driver, "Driver")
        assert "limits" in cell.traffic
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, name
        for m in cell.per_layer:
            assert m["moves"] in reported, (name, m["name"])
            reader = harness.load_module("metrics", m["name"].partition(".")[0])
            assert callable(reader.read)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
        if "moves" in m:
            assert m["moves"] in e2e_names


def test_the_order_checks_stand_when_entries_are_appended():
    """The three checks of where earlier PRs' entries stand ask nothing of
    what follows them: on a copy with one more configuration, cell and
    per-layer entry appended they pass, and a cell put before its neighbour
    fails them."""
    from tests.benchmark.test_driver_encode import check_pr_28_follows_the_ten
    from tests.benchmark.test_driver_encode_openpangu import check_the_cell
    from tests.benchmark.test_program_spans import check_the_ten

    checks = (check_the_ten, check_pr_28_follows_the_ten, check_the_cell)
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "y", "source": "test", "reduced": [],
                             "file": "benchmark/configs/y.json", "why": "test"})
    bench["workloads"].append({"name": "y.x", "config": "y", "traffic": "x",
                               "chips": 1, "why": "test"})
    encode = bench["per_layer"][-1]["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m.get("workloads") == encode or m["name"] == "train_images_per_s":
            m["workloads"] = [*m["workloads"], "y.x"]
    bench["per_layer"].append({"name": "z.part", "unit": "%", "better": "lower",
                               "source": "device_trace", "layer": "expert layer",
                               "moves": "train_images_per_s",
                               "workloads": [*encode, "y.x"]})
    for check in checks:
        check(bench)
    swapped = copy.deepcopy(bench)
    swapped["workloads"][-3:-1] = swapped["workloads"][-2:-4:-1]
    with pytest.raises(AssertionError):
        check_the_cell(swapped)
    with pytest.raises(AssertionError):
        check_pr_28_follows_the_ten({**bench, "per_layer": [
            m for m in bench["per_layer"] if m["name"] != "encode_step_mfu"]})


def test_peaks_name_their_source_and_refuse_an_unknown_chip():
    peaks = harness.load_peaks()
    for kind in ("tpu v5 lite", "tpu v5e"):
        assert peaks[kind]["bf16_flops_per_s"] == 197e12
        assert peaks[kind]["hbm_bytes_per_s"] == 819e9
        assert "Google Cloud" in peaks[kind]["source"]

    class Device:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    with pytest.raises(harness.BenchFailure, match="no peaks on record"):
        harness.peaks_for(Device())
    Device.platform = "cpu"
    assert harness.peaks_for(Device()) is None


def test_the_train_workload_copies_the_repos_config():
    """The finetune job is configs/imagenette_sd21_256.json as it stands; the
    workload's copy (the yardstick, under `paths`) leaves out only the paths
    the run sets."""
    repo = json.loads((harness.ROOT / "configs" / "imagenette_sd21_256.json").read_text())
    mine = json.loads((harness.ROOT / "benchmark" / "workloads" / "train-256.json").read_text())["train_config"]
    repo.pop("output_dir")
    for key in ("train_data_dir", "caption_jsons"):
        repo["data"].pop(key)
    assert mine == repo


def test_a_new_cell_is_files_and_entries(tmp_path, monkeypatch, capsys):
    """A later PR adds benchmark/workloads/x.json, benchmark/configs/y.json,
    benchmark/metrics/z.py and entries in BENCHMARK.json, edits no file that
    is there, and its cell runs."""
    from tests.benchmark import tinyroot

    root = tinyroot.make(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    shutil.copy(root / "benchmark/configs/sscd-laion12m-share.json",
                root / "benchmark/configs/y.json")
    doc = json.loads((root / "benchmark/workloads/search-b64-top1.json").read_text())
    doc.update(query_batch=4, pool_batches=2)
    (root / "benchmark/workloads/x.json").write_text(json.dumps(doc))
    (root / "benchmark/metrics/z.py").write_text(
        "def read(run):\n    return float(run.window.units)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "y", "source": "test", "reduced": ["rows"],
                             "file": "benchmark/configs/y.json", "why": "test"})
    bench["workloads"].append({"name": "y.x", "config": "y", "traffic": "x",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"].startswith("search_"):
            m["workloads"].append("y.x")
    bench["per_layer"].append({"name": "z", "unit": "calls", "better": "higher",
                               "source": "program_counter", "layer": "search",
                               "moves": "search_queries_per_s",
                               "workloads": ["y.x"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    monkeypatch.setattr(harness, "setup_compile_cache", lambda root=None: "tests")
    cell = harness.load_cell("y.x")
    assert [m["name"] for m in cell.per_layer] == ["z"]
    assert harness.main(["--workload", "y.x", "--seed", "3", "--seconds", "0.2",
                         "--trace", "1"]) == 0
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert result["correct"] is True and result["attempted"] > 0
    assert result["metrics"] == {} and "z" in result["rehearsal"]
    assert {p: p.read_bytes() for p in before} == before     # nothing edited
