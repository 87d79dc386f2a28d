#!/usr/bin/env python3
"""Readings that the limits in the workload files were set from (PERF.md).

Run by hand through the chip tool, at the cell's own size, never by the
benchmark's own runs. For each seed it drives the cell's driver through its
set-up, a short window where the cell needs one, and its comparison with the
plain reference (the LOWER readings: sound runs of the program); with
`--control` it also puts the reference in the program's place, computed in
the precision below the one the configuration states (the UPPER readings),
and, for the training cell, the planted fault 'half of the batch left out'.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control] [--seconds 3] [--out chiprun_out/readings_<cell>.jsonl]
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark.lib import harness  # noqa: E402


def numbers(checks) -> dict:
    return {c["name"]: c["value"] for c in checks}


def search_cell(bench, driver, seeds, seconds, control, emit) -> None:
    import numpy as np

    from benchmark.reference import topk

    mod = sys.modules[type(driver).__module__]
    driver.setup()
    c, t = driver.cfg, driver.traffic
    rows, dim = int(c["rows"]), int(c["embed_dim"])
    for seed in seeds:
        bench.seed = seed
        driver.pool = mod.query_pool(
            seed, int(c["corpus_seed"]), rows, dim, int(t["pool_batches"]),
            int(t["query_batch"]), float(t["near_copy_share"]),
            float(t["near_copy_noise"]))
        driver.answers.clear()
        driver._i = 0
        bench.window = harness.Window()
        window, _ = harness.run_window(bench, driver)
        engine, driver.engine = driver.engine, None    # verify frees nothing here
        line = {"seed": seed, "program": numbers(driver.verify(window)),
                "calls": window.units}
        driver.engine = engine
        # the planted fault 'an answer altered where it is produced': the key
        # of one answer replaced by the next row's
        i, _, keys = driver.answers[0]
        other = (int(keys[0]) + 1) % rows
        q0 = driver.pool[i][:1]
        line["fault_altered_key"] = {"best_score_gap": float(
            driver.ref_best_flat.reshape(len(driver.used), -1)[
                driver.used.index(i), 0]
            - topk.scores_of(q0, driver.rows_of(np.array([other])))[0])}
        if control:
            # the reference in the program's place at Precision.HIGH (three
            # bf16 passes), the step below float32 at HIGHEST
            flat = driver.pool[driver.used].reshape(-1, dim)
            score, idx = topk.best_rows(
                flat, driver.rows_of, rows,
                row_block=int(t["reference"]["row_block"]),
                query_block=int(t["reference"]["query_block"]), precision="high")
            ref_score = topk.scores_of(flat, driver.rows_of(idx))
            line["control_high"] = {
                "bad_keys": 0.0,
                "best_score_gap": float(np.max(driver.ref_best_flat - ref_score)),
                "score_error": float(np.max(np.abs(score - ref_score)))}
        emit(line)


def train_cell(bench, driver_cls, seeds, control, emit) -> None:
    import jax

    from benchmark.lib import sd_stack
    from benchmark.reference import finetune, sd21

    for n, seed in enumerate(seeds):
        bench.seed = seed
        shutil.rmtree(bench.work, ignore_errors=True)
        bench.work.mkdir(parents=True, exist_ok=True)
        driver = driver_cls(bench)
        mod = sys.modules[driver_cls.__module__]
        t0 = time.perf_counter()
        driver.setup()
        driver.drain()
        driver.release()
        line = {"seed": seed, "setup_s": time.perf_counter() - t0,
                "program": numbers(driver.verify(None)),
                "losses": driver.losses}
        if control and n < control:
            ref, t, tc = driver.reference, driver.traffic, driver.train_cfg
            hyper = {k: getattr(tc.optim, k) for k in (
                "learning_rate", "adam_beta1", "adam_beta2", "adam_epsilon",
                "adam_weight_decay", "max_grad_norm")}
            key = sd21.stream(jax.random.key(harness.seed31(seed)), "train")
            fresh = lambda: sd_stack.make_weights(driver.shapes, seed)  # noqa: E731
            for name, kwargs in (
                    ("control_fp8", {"ops": sd21.Ops(quant="fp8")}),
                    ("fault_half_batch", {"rows": slice(0, len(
                        driver.fed[0]["input_ids"]) // 2)})):
                with jax.default_matmul_precision("highest"):
                    other = finetune.reference_steps(
                        driver.cfg, fresh, driver.fed, key, hyper,
                        row_block=int(t["reference"]["row_block"]), **kwargs)
                line[name] = numbers(mod.compare(other, ref, t["limits"],
                                                 lambda *a, **k: None))
        emit(line)
        driver.close()
        del driver
        gc.collect()


def sample_cell(bench, driver_cls, seeds, units, control, tanh, emit) -> None:
    from benchmark.reference import sd21

    for n, seed in enumerate(seeds):
        bench.seed = seed
        driver = driver_cls(bench)
        mod = sys.modules[driver_cls.__module__]
        t0 = time.perf_counter()
        driver.setup()
        for _ in range(units):
            driver.unit()
        served_all = list(driver.done)
        driver.release()
        checks = driver.verify(None)
        line = {"seed": seed, "seconds": time.perf_counter() - t0,
                "program": numbers(checks), "units": len(served_all)}
        if control and n < control:
            variants = [("control_fp8", sd21.Ops(quant="fp8"))]
            if tanh:            # one more float32 program to compile
                variants.append(("tanh_gelu", sd21.Ops(gelu="tanh")))
            for name, ops in variants:
                other = driver.reference_images(ops)
                line[name] = {"image_rms_worst": mod.image_rms_worst(
                    other, driver.reference)[0]}
        emit(line)
        driver.close()
        del driver
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--units", type=int, default=2)
    ap.add_argument("--control", type=int, default=0,
                    help="run the control (and faults) on the first N seeds")
    ap.add_argument("--tanh", action="store_true",
                    help="sampling cells: also read the tanh-gated reference")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache(cell.root)
    meter = harness.CompileMeter()
    devices = harness.find_devices(cell.chips)
    bench = harness.Bench(cell, seeds[0], args.seconds, False, devices, meter)
    bench.work.mkdir(parents=True, exist_ok=True)
    out = Path(args.out or REPO / "chiprun_out" / f"readings_{cell.name}.jsonl")
    out.parent.mkdir(parents=True, exist_ok=True)

    def emit(line: dict) -> None:
        text = json.dumps(line)
        print("READING " + text, flush=True)
        with out.open("a") as f:
            f.write(text + "\n")

    driver_cls = harness.load_module("drivers", cell.traffic["driver"],
                                     cell.root).Driver
    kind = cell.traffic["driver"]
    try:
        if kind == "store_search":
            search_cell(bench, driver_cls(bench), seeds, args.seconds,
                        args.control, emit)
        elif kind == "train_step":
            train_cell(bench, driver_cls, seeds, args.control, emit)
        elif kind == "bulk_sample":
            sample_cell(bench, driver_cls, seeds, args.units, args.control,
                        args.tanh, emit)
        else:
            raise SystemExit(f"no readings for driver {kind!r}")
    finally:
        shutil.rmtree(bench.work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
