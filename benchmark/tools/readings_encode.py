#!/usr/bin/env python3
"""Readings that the limits of the encode-leg cell were set from (PERF.md);
`tools/readings.py` for the `encode_leg` driver, a file of its own because no
file the benchmark had may be edited by the PR that brought the cell.

Run by hand through the chip tool, at the cell's own size. For each seed:
the driver's set-up and first unit (the timed path's own program), the
program's router scores, then the plain reference; the LOWER readings are the
program's gaps. With `--control N` the first N seeds also put the reference
in the program's place with fp8 operands in every product of the tower and
of the VAE (the precision below the bfloat16 the configuration states; the
driver's own reference's `Ops`): the UPPER readings.

    python3 benchmark/tools/readings_encode.py --seeds 1,2,3 [--control 1] \
        [--workload longcat-flash-chat-ep32-encode-256] [--out <file>]
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark.lib import harness  # noqa: E402


def numbers(checks) -> dict:
    return {c["name"]: c["value"] for c in checks}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="longcat-flash-chat-ep32-encode-256")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache(cell.root)
    meter = harness.CompileMeter()
    devices = harness.find_devices(cell.chips)
    out = Path(args.out or REPO / "chiprun_out" / f"readings_{cell.name}.jsonl")
    out.parent.mkdir(parents=True, exist_ok=True)
    module = harness.load_module("drivers", cell.traffic["driver"], cell.root)
    from benchmark.reference import sd21

    for n, seed in enumerate(seeds):
        bench = harness.Bench(cell, seed, 1.0, False, devices, meter)
        shutil.rmtree(bench.work, ignore_errors=True)
        bench.work.mkdir(parents=True, exist_ok=True)
        driver = module.Driver(bench)
        try:
            driver.setup()
            driver.release()
            harness.trim_host_memory()
            line = {"seed": seed,
                    "program": numbers(driver.verify(bench.window))}
            if n < args.control:
                control = driver.reference(ops=driver.ref.Ops(quant="fp8"),
                                           vae_ops=sd21.Ops(quant="fp8"))
                control["dropped"] = 0
                exact = driver.reference(
                    follow=[r["chosen"] for r in control["routing"]])
                line["control_fp8"] = numbers(module.compare(
                    control, exact, cell.traffic["limits"],
                    lambda name, **kw: print("CONTROL " + json.dumps(
                        {name: kw}), flush=True)))
        finally:
            driver.close()
            shutil.rmtree(bench.work, ignore_errors=True)
            del driver
            gc.collect()
        text = json.dumps(line)
        print("READING " + text, flush=True)
        with out.open("a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
