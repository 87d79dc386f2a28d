"""Device-time sweep of self-attention on the local chip: XLA's fused attention
against the Pallas flash kernels, per SD-2.1 site shape and per (block_q,
block_k). `FLASH_MIN_SEQ` and `_resolve_blocks` in dcr_tpu/ops/flash_attention.py
are set from what this prints (the readings are kept in PERF.md).

Times are device times of the ops in ONE profiler capture, reduced by
benchmark/lib/trace.py, never a host clock: every variant is a jitted function
of its own name, each of its runs is one `XLA Modules` event, and the `XLA Ops`
inside that event split into the kernels (`tpu_custom_call`) and the rest (the
relayouts round the kernel, or all of XLA's attention). Operands are
[B, S, H*D], as the UNet's to_q/to_k/to_v hand them over, and the result is
[B, S, H*D], as to_out takes it, so both paths pay their own relayouts. The
kernel's variants take and return them row-major, the layout the custom call
asks of the projections that feed it inside a model (a bare parameter of
[B, S, 320] would sit S-minor on the TPU and be copied); XLA's variant is
left the layout the compiler picks. Since PR 31 the kernels read that layout
themselves, so their "rest" reads about nothing.

    python tools/sweep_flash.py [--out chiprun_out/sweep_flash] [--iters 10]
    python tools/sweep_flash.py --default-blocks --sites 8x1024x5x64:float32:fwd ...
    python tools/sweep_flash.py --tiny      # CPU rehearsal: interpret mode, no times
"""

from __future__ import annotations

import argparse
import itertools
import json
import shutil
import statistics
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from benchmark.lib import trace as tracelib
from dcr_tpu.ops import attention, flash_attention as fa

# (B, S, H, D), dtype, differentiated: the self-attention sites of SD-2.1 in
# the benchmark's cells. Forward-only float32 is the sampler (20 rows at
# 256 px, 2 at 512 px); bfloat16 forward and backward is the train step.
SITES = [
    ((20, 1024, 5, 64), "float32", False),
    ((2, 1024, 10, 64), "float32", False),
    ((20, 256, 10, 64), "float32", False),
    ((2, 256, 20, 64), "float32", False),
    ((2, 4096, 5, 64), "float32", False),
    ((8, 4096, 5, 64), "float32", False),     # 512 px at im_batch 4 (ROADMAP M1)
    ((16, 1024, 5, 64), "bfloat16", True),
    ((16, 256, 10, 64), "bfloat16", True),
]
TINY_SITES = [((1, 256, 2, 64), "float32", False),
              ((1, 256, 2, 64), "bfloat16", True)]
BLOCKS = (256, 512, 1024)
SWEPT_SEQ = (1024, 4096)      # other lengths run the default blocks only


def block_candidates(seq: int, itemsize: int, sweep: bool = True
                     ) -> list[tuple[int | None, int | None]]:
    """(None, None) is whatever _resolve_blocks ships; the explicit pairs are
    the others, clamped to the sequence, so S = 256 has nothing to sweep."""
    if not sweep or seq not in SWEPT_SEQ:
        return [(None, None)]
    pairs = {(min(bq, seq), min(bk, seq))
             for bq, bk in itertools.product(BLOCKS, repeat=2)}
    pairs.discard(fa._resolve_blocks(seq, seq, None, None, itemsize))
    return [(None, None)] + sorted(pairs)


def parse_site(text: str):
    """'20x1024x5x64:float32:fwd' or '...:bfloat16:fwdbwd'."""
    shape, dtype, direction = text.split(":")
    return (tuple(int(x) for x in shape.split("x")), dtype,
            {"fwd": False, "fwdbwd": True}[direction])


def variant(shape, differentiated: bool, blocks, interpret: bool):
    """The jitted call of one path over [B, S, H*D] operands: `blocks` None is
    XLA's attention, a pair the kernel with those blocks."""
    b, s, h, d = shape

    def attend(xq, xk, xv):
        q, k, v = (x.reshape(b, s, h, d) for x in (xq, xk, xv))
        if blocks is None:
            out = attention._xla_attention(q, k, v, None)
        else:
            out = fa.flash_attention(q, k, v, interpret, *blocks)
        return out.reshape(b, s, h * d)

    if not differentiated:
        return attend

    def attend_and_grads(xq, xk, xv, g):
        out, vjp = jax.vjp(attend, xq, xk, xv)
        return out, vjp(g)

    return attend_and_grads


def tag_of(shape, dtype: str, differentiated: bool, blocks) -> str:
    path = "xla" if blocks is None else "flash_" + "_".join(
        "d" if x is None else str(x) for x in blocks)
    return (f"att_{'x'.join(map(str, shape))}_{dtype}_"
            f"{'fwdbwd' if differentiated else 'fwd'}_{path}")


def reduce_runs(trace: tracelib.Trace, tag: str) -> dict | None:
    """Median device milliseconds of one variant's runs: the whole module, the
    Pallas kernels by name, and every other op (relayouts, or XLA's attention)."""
    runs = []
    for chip, modules in trace.modules.items():
        for name, start, dur in modules:
            if not name.startswith(f"jit_{tag}("):
                continue
            kernels: dict[str, float] = {}
            rest = 0.0
            for op, _, op_dur in tracelib.in_window(trace.ops.get(chip, []),
                                                    start, start + dur):
                base, opcode = tracelib.op_kind(op)
                if opcode in tracelib.CONTAINERS:
                    continue
                if 'custom_call_target="tpu_custom_call"' in op:
                    kernels[base] = kernels.get(base, 0.0) + op_dur / 1e6
                else:
                    rest += op_dur / 1e6
            runs.append((dur / 1e6, kernels, rest))
    if not runs:
        return None
    names = sorted({k for _, kernels, _ in runs for k in kernels})
    return {"runs": len(runs),
            "module_ms": statistics.median(r[0] for r in runs),
            "kernel_ms": {k: statistics.median(r[1].get(k, 0.0) for r in runs)
                          for k in names},
            "rest_ms": statistics.median(r[2] for r in runs)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(REPO / "chiprun_out" / "sweep_flash"))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--sites", nargs="+", type=parse_site, default=None,
                    help="BxSxHxD:dtype:fwd|fwdbwd in place of SD-2.1's sites")
    ap.add_argument("--default-blocks", action="store_true",
                    help="the shipped blocks only, no sweep")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform}),
          flush=True)
    if device.platform != "tpu" and not args.tiny:
        print("no TPU: device times come from the chip only (--tiny rehearses)")
        return 2

    row_major = Format(Layout(major_to_minor=(0, 1, 2)),
                       SingleDeviceSharding(device))
    records = []          # (what is printed, the jitted call or None, its operands)
    sites = args.sites or (TINY_SITES if args.tiny else SITES)
    for shape, dtype, differentiated in sites:
        b, s, h, d = shape
        keys = jax.random.split(jax.random.key(s * h + b), 4)
        operands = [jax.random.normal(k, (b, s, h * d), jnp.dtype(dtype))
                    for k in keys[:4 if differentiated else 3]]
        kernel_operands = [jax.device_put(x, row_major) for x in operands]
        reference = None
        itemsize = jnp.dtype(dtype).itemsize
        for blocks in [None] + block_candidates(s, itemsize,
                                                not args.default_blocks):
            rec = {"shape": list(shape), "dtype": dtype,
                   "differentiated": differentiated,
                   "path": "xla" if blocks is None else "flash",
                   "blocks": None if blocks is None else list(
                       fa._resolve_blocks(s, s, *blocks, itemsize)),
                   "default_blocks": blocks == (None, None),
                   "tag": tag_of(shape, dtype, differentiated, blocks)}
            fn = variant(shape, differentiated, blocks, args.tiny)
            fn.__name__ = rec["tag"]
            if blocks is None:
                call, given = jax.jit(fn), operands
            else:
                call, given = jax.jit(fn, in_shardings=row_major,
                                      out_shardings=row_major), kernel_operands
            try:
                result = jax.block_until_ready(call(*given))
            except Exception as e:       # a kernel the chip's compiler refuses
                rec["error"] = repr(e)[:400]
                call = None
            else:
                out = (result[0] if differentiated else result).astype(jnp.float32)
                if blocks is None:
                    reference = out
                else:
                    rec["max_abs_err_vs_xla"] = float(jnp.max(jnp.abs(out - reference)))
            records.append((rec, call, given))

    trace_dir = out_dir / "raw"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    for _, call, operands in records:
        if call is not None:
            for _ in range(args.iters):
                result = call(*operands)
            jax.block_until_ready(result)
    jax.profiler.stop_trace()
    path = tracelib.find_xplane(trace_dir)
    trace = tracelib.read(path) if path else tracelib.Trace()
    shutil.rmtree(trace_dir, ignore_errors=True)

    with (out_dir / "sweep.jsonl").open("w") as f:
        for rec, call, _ in records:
            if call is not None:
                rec["device_ms"] = reduce_runs(trace, rec["tag"])
            f.write(json.dumps(rec) + "\n")
            print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
