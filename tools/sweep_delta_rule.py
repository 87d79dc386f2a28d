"""Device-time sweep of the gated delta rule's chunked scan on the local chip:
the XLA form of `ops/delta_rule.py` against the Pallas kernel of
`ops/delta_rule_kernel.py` at several chunk and sub-chunk lengths, at the
Solar Open 2 encode cell's site shape by default, and at several numbers of
heads a program. `delta_rule_kernel.CHUNK`, `SUB` and `HEADS` are set from
what this prints (the readings are kept in PERF.md).

Times are device times of the ops in ONE profiler capture, reduced by
benchmark/lib/trace.py, never a host clock: every variant is a jitted
function of its own name, each of its runs one `XLA Modules` event, and the
`XLA Ops` inside it split into the kernel (`tpu_custom_call`) and the rest
(the transpose of beta, and all of the XLA form). Each variant's result is
compared with the XLA form's at the shipped lengths (`max_abs_err_vs_xla`);
`--check` also compares both, in float32, with the position-by-position
recurrence of the plain reference.

    python tools/sweep_delta_rule.py [--out sweeps/delta_rule]
    python tools/sweep_delta_rule.py --site 16x256x64x128:bfloat16 --lengths 64/16/2 32/8/1
    python tools/sweep_delta_rule.py --tiny     # CPU rehearsal: interpret mode, no times
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax
import jax.numpy as jnp

from benchmark.lib import trace as tracelib
from dcr_tpu.ops import delta_rule as dr, delta_rule_kernel as dk

#: (chunk, sub, heads a program) the kernel is swept over
LENGTHS = [(64, 16, 1), (64, 16, 2), (64, 16, 4), (128, 16, 1), (128, 16, 2),
           (128, 16, 4), (64, 8, 2), (32, 8, 2), (32, 16, 2), (128, 8, 2),
           (128, 32, 2), (256, 16, 2)]


def inputs(shape, dtype, seed: int = 0):
    """q, k unit vectors (q at d^-1/2, as KDA hands them), v normal, log decays
    -exp(x) with x uniform on [-7, 0.5], beta in (0, 2)."""
    b, t, h, d = shape
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(key):
        x = jax.random.normal(key, shape, jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    log_alpha = -jnp.exp(jax.random.uniform(ks[3], shape, minval=-7.0, maxval=0.5))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return ((unit(ks[0]) * d ** -0.5).astype(dtype), unit(ks[1]).astype(dtype),
            jax.random.normal(ks[2], shape).astype(dtype), log_alpha, beta)


def variant(path, interpret: bool):
    """The jitted scan of one path: "xla" at the module's lengths, or the
    kernel at (chunk, sub, heads)."""
    if path == "xla":
        return lambda *a: dr._xla_form(*a, dr.CHUNK, dr.SUB)
    chunk, sub, heads = path
    return lambda *a: dk.delta_rule_fwd(*a, interpret=interpret, chunk=chunk,
                                        sub=sub, heads=heads)


def tag_of(shape, dtype: str, path) -> str:
    name = path if isinstance(path, str) else "pallas_" + "_".join(map(str, path))
    return f"dr_{'x'.join(map(str, shape))}_{dtype}_{name}"


def reduce_runs(trace: tracelib.Trace, tag: str) -> dict | None:
    """Median device milliseconds of one variant's runs: the whole module,
    the kernel, and every other op."""
    runs = []
    for chip, modules in trace.modules.items():
        for name, start, dur in modules:
            if not name.startswith(f"jit_{tag}("):
                continue
            kernel = rest = 0.0
            for op, _, op_dur in tracelib.in_window(trace.ops.get(chip, []),
                                                    start, start + dur):
                _, opcode = tracelib.op_kind(op)
                if opcode in tracelib.CONTAINERS:
                    continue
                if 'custom_call_target="tpu_custom_call"' in op:
                    kernel += op_dur / 1e6
                else:
                    rest += op_dur / 1e6
            runs.append((dur / 1e6, kernel, rest))
    if not runs:
        return None
    return {"runs": len(runs),
            "module_ms": statistics.median(r[0] for r in runs),
            "kernel_ms": statistics.median(r[1] for r in runs),
            "rest_ms": statistics.median(r[2] for r in runs)}


def parse_site(text: str):
    shape, dtype = text.split(":")
    return tuple(int(x) for x in shape.split("x")), dtype


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(REPO / "sweeps" / "delta_rule"))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="compare with the recurrence in float32 too")
    ap.add_argument("--site", type=parse_site, default=None,
                    help="BxTxHxD:dtype (the Solar cell's 16x256x64x128:bfloat16)")
    ap.add_argument("--lengths", nargs="+", default=None,
                    help="chunk/sub/heads, e.g. 64/16/2 32/8/1")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform}),
          flush=True)
    if device.platform != "tpu" and not args.tiny:
        print("no TPU: device times come from the chip only (--tiny rehearses)")
        return 2

    shape, dtype = args.site or (((2, 70, 2, 128), "bfloat16") if args.tiny
                                 else ((16, 256, 64, 128), "bfloat16"))
    lengths = ([tuple(int(x) for x in p.split("/")) for p in args.lengths]
               if args.lengths else ([(16, 4, 1), (32, 8, 2)] if args.tiny else LENGTHS))
    operands = inputs(shape, jnp.dtype(dtype))
    records, reference = [], None
    for path in ["xla"] + lengths:
        rec = {"shape": list(shape), "dtype": dtype,
               "path": "xla" if path == "xla" else "pallas",
               "lengths": [dr.CHUNK, dr.SUB] if path == "xla" else list(path),
               "shipped": path == "xla" or path == (dk.CHUNK, dk.SUB, dk.heads_per_program(
                   shape[2], shape[1], shape[3], shape[3], jnp.dtype(dtype).itemsize)),
               "tag": tag_of(shape, dtype, path)}
        fn = variant(path, args.tiny)
        fn.__name__ = rec["tag"]
        call = jax.jit(fn)
        try:
            out = jax.block_until_ready(call(*operands))
        except Exception as e:          # lengths the chip's compiler refuses
            rec["error"] = repr(e)[:400]
            call = None
        else:
            if reference is None:
                reference = out
            else:
                rec["max_abs_err_vs_xla"] = float(jnp.max(jnp.abs(out - reference)))
        records.append((rec, call))

    if args.check:
        from benchmark.reference.solar_open2 import delta_rule as recurrence

        exact = inputs(shape, jnp.float32, seed=1)
        want = jax.jit(recurrence)(*exact)
        for name, fn in (("xla", variant("xla", args.tiny)),
                         ("pallas", variant((dk.CHUNK, dk.SUB, None), args.tiny))):
            got = jax.jit(fn)(*exact)
            rec = {"check": name, "dtype": "float32",
                   "max_abs_err_vs_recurrence": float(jnp.max(jnp.abs(got - want))),
                   "finite": bool(jnp.all(jnp.isfinite(got)))}
            print(json.dumps(rec), flush=True)
            records.append((rec, None))

    trace_dir = out_dir / "raw"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    for _, call in records:
        if call is not None:
            for _ in range(args.iters):
                result = call(*operands)
            jax.block_until_ready(result)
    jax.profiler.stop_trace()
    path = tracelib.find_xplane(trace_dir)
    trace = tracelib.read(path) if path else tracelib.Trace()
    shutil.rmtree(trace_dir, ignore_errors=True)

    with (out_dir / "sweep.jsonl").open("w") as f:
        for rec, call in records:
            if call is not None:
                rec["device_ms"] = reduce_runs(trace, rec["tag"])
            f.write(json.dumps(rec) + "\n")
            if "check" not in rec:
                print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
