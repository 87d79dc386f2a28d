#!/usr/bin/env python3
"""Record a small device trace on the chip and describe it.

Run by hand through the chip tool; the `.xplane.pb` it leaves under
`chiprun_out/trace_probe/` is the recorded trace kept beside
`tests/benchmark/test_trace.py`, and the description it prints is how the
plane, line and event names that `benchmark/lib/trace.py` relies on were found.

    python3 benchmark/tools/record_trace.py [out_dir]
"""
from __future__ import annotations

import collections
import glob
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def describe(path: str, top: int = 12) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            names = collections.Counter()
            dur = collections.Counter()
            n = 0
            first = None
            for ev in line.events:
                n += 1
                names[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                if first is None:
                    first = {"name": ev.name, "start_ns": ev.start_ns,
                             "duration_ns": ev.duration_ns,
                             "stats": {k: str(v)[:200] for k, v in ev.stats}}
            lines[line.name] = {"events": n, "first": first, "top": [
                [k, v, names[k]] for k, v in dur.most_common(top)]}
        out[plane.name] = lines
    return out


def main() -> int:
    import jax
    import jax.numpy as jnp

    out_dir = Path(sys.argv[1] if len(sys.argv) > 1
                   else REPO / "chiprun_out" / "trace_probe")
    out_dir.mkdir(parents=True, exist_ok=True)
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}))

    from dcr_tpu.ops import flash_attention as fa

    @jax.jit
    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def flash(q, k, v):
        return fa.flash_attention(q, k, v)

    ka, kb, kq = jax.random.split(jax.random.key(0), 3)
    a = jax.random.normal(ka, (64, 512), jnp.float32)
    b = jax.random.normal(kb, (512, 65536), jnp.float32)
    q = jax.random.normal(kq, (2, 4096, 5, 64), jnp.bfloat16)
    on_tpu = jax.devices()[0].platform == "tpu"
    mm(a, b).block_until_ready()
    if on_tpu:
        flash(q, q, q).block_until_ready()
    trace_dir = out_dir / "raw"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            r = mm(a, b)
        with jax.profiler.TraceAnnotation("bench/fetch"):
            jax.device_get(r)
        with jax.profiler.TraceAnnotation("bench/host_merge"):
            time.sleep(0.002)
        if on_tpu:
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                flash(q, q, q).block_until_ready()
    jax.profiler.stop_trace()
    paths = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    print(json.dumps({"xplane": paths,
                      "bytes": [Path(p).stat().st_size for p in paths]}))
    desc = describe(paths[0])
    (out_dir / "describe.json").write_text(json.dumps(desc, indent=1))
    shutil.copy(paths[0], out_dir / "probe.xplane.pb")
    shutil.rmtree(trace_dir, ignore_errors=True)
    for plane, lines in desc.items():
        print("PLANE", plane)
        for name, info in lines.items():
            print("  LINE", name, info["events"], json.dumps(info["top"][:6])[:600])
            print("    first", json.dumps(info["first"])[:700])
    return 0


if __name__ == "__main__":
    sys.exit(main())
