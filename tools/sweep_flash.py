"""Device-time sweep of self-attention on the local chip: XLA's fused attention
against the Pallas flash kernels, per SD-2.1 site shape and per (block_q,
block_k), and XLA's attention whole against the row groups the dispatcher cuts
a site into whose float32 logits would not stay on the chip (`xla_grouped`;
PR 33). `FLASH_MIN_SEQ`, `FLASH_MIN_LOGITS_BYTES` and `_resolve_blocks` in
dcr_tpu/ops/flash_attention.py are set from what this prints (the readings are
kept in PERF.md). A site may name a v width and a causal mask (a language-model
tower's latent attention): the kernel takes neither, so such a site runs XLA's
two variants only.

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
    python tools/sweep_flash.py --sites 16x256x128x192:128:causal:bfloat16:fwd
    python tools/sweep_flash.py --tiny      # CPU rehearsal: interpret mode, no times
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import shutil
import statistics
import sys
from pathlib import Path
from typing import NamedTuple

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from benchmark.lib import trace as tracelib
from dcr_tpu.ops import attention, flash_attention as fa

class Site(NamedTuple):
    shape: tuple[int, int, int, int]    # (B, S, H, D) of q and k
    dtype: str
    differentiated: bool
    v_width: int | None = None          # None: D
    causal: bool = False


# The self-attention sites of SD-2.1 in the benchmark's cells. Forward-only
# float32 is the sampler (20 rows at 256 px, 2 at 512 px); bfloat16 forward
# and backward is the train step.
SITES = [
    Site((20, 1024, 5, 64), "float32", False),
    Site((2, 1024, 10, 64), "float32", False),
    Site((20, 256, 10, 64), "float32", False),
    Site((2, 256, 20, 64), "float32", False),
    Site((2, 4096, 5, 64), "float32", False),
    Site((8, 4096, 5, 64), "float32", False),     # 512 px at im_batch 4 (ROADMAP M1)
    Site((16, 1024, 5, 64), "bfloat16", True),
    Site((16, 256, 10, 64), "bfloat16", True),
]
TINY_SITES = [Site((1, 256, 2, 64), "float32", False),
              Site((1, 256, 2, 64), "bfloat16", True),
              Site((4, 128, 2, 24), "bfloat16", True, 16, True)]
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


def parse_site(text: str) -> Site:
    """'20x1024x5x64:float32:fwd', '...:bfloat16:fwdbwd', or with a v width
    and a causal mask between: '16x256x128x192:128:causal:bfloat16:fwd'."""
    shape, *options, dtype, direction = text.split(":")
    return Site(tuple(int(x) for x in shape.split("x")), dtype,
                {"fwd": False, "fwdbwd": True}[direction],
                next((int(o) for o in options if o.isdigit()), None),
                "causal" in options)


def kernel_takes(site: Site) -> bool:
    b, s, h, d = site.shape
    x = jax.ShapeDtypeStruct(site.shape, jnp.dtype(site.dtype))
    return (not site.causal and site.v_width in (None, d)
            and fa.supported(x, x, x))


def grouped_floor(site: Site, rehearsal: bool) -> int:
    """The floor `xla_grouped` cuts at: the dispatcher's own, or in a CPU
    rehearsal one row's logits, so that a tiny site is cut."""
    _, s, h, _ = site.shape
    return 4 * h * s * s if rehearsal else fa.FLASH_MIN_LOGITS_BYTES


def variant(site: Site, path, interpret: bool):
    """The jitted call of one path over [B, S, H*D] operands. `path` "xla" is
    XLA's attention whole, "xla_grouped" what the dispatcher's XLA path does
    with the site (row groups past `grouped_floor`), a pair the kernel with
    those blocks."""
    b, s, h, d = site.shape
    dv = site.v_width or d
    mask = jnp.tril(jnp.ones((s, s), bool))[None, None] if site.causal else None
    floor = {"xla": math.inf,
             "xla_grouped": grouped_floor(site, interpret)}.get(path)

    def attend(xq, xk, xv):
        q, k = xq.reshape(b, s, h, d), xk.reshape(b, s, h, d)
        v = xv.reshape(b, s, h, dv)
        if isinstance(path, str):
            out = attention._xla_attention(q, k, v, mask, floor=floor)
        else:
            out = fa.flash_attention(q, k, v, interpret, *path)
        return out.reshape(b, s, h * dv)

    if not site.differentiated:
        return attend

    def attend_and_grads(xq, xk, xv, g):
        out, vjp = jax.vjp(attend, xq, xk, xv)
        return out, vjp(g)

    return attend_and_grads


def tag_of(site: Site, path) -> str:
    name = path if isinstance(path, str) else "flash_" + "_".join(
        "d" if x is None else str(x) for x in path)
    latent = (f"v{site.v_width}" if site.v_width else "") + (
        "c" if site.causal else "")
    return (f"att_{'x'.join(map(str, site.shape))}{latent}_{site.dtype}_"
            f"{'fwdbwd' if site.differentiated else 'fwd'}_{name}")


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
    for site in sites:
        b, s, h, d = site.shape
        dv = site.v_width or d
        keys = jax.random.split(jax.random.key(s * h + b), 4)
        operands = [jax.random.normal(k, (b, s, h * w), jnp.dtype(site.dtype))
                    for k, w in zip(keys, (d, d, dv, dv)[
                        :4 if site.differentiated else 3])]
        kernel_operands = [jax.device_put(x, row_major) for x in operands]
        reference = None
        itemsize = jnp.dtype(site.dtype).itemsize
        blocks = (block_candidates(s, itemsize, not args.default_blocks)
                  if kernel_takes(site) else [])
        for path in ["xla", "xla_grouped"] + blocks:
            kernel = not isinstance(path, str)
            rec = {"shape": list(site.shape), "dtype": site.dtype,
                   "v_width": dv, "causal": site.causal,
                   "differentiated": site.differentiated,
                   "path": "flash" if kernel else path,
                   "blocks": list(fa._resolve_blocks(s, s, *path, itemsize))
                   if kernel else None,
                   "default_blocks": path == (None, None),
                   "tag": tag_of(site, path)}
            if path == "xla_grouped":
                rec["group"] = list(attention._group_of(
                    b, h, s, s, grouped_floor(site, args.tiny)))
            fn = variant(site, path, args.tiny)
            fn.__name__ = rec["tag"]
            if kernel:
                call, given = jax.jit(fn, in_shardings=row_major,
                                      out_shardings=row_major), kernel_operands
            else:
                call, given = jax.jit(fn), operands
            try:
                result = jax.block_until_ready(call(*given))
            except Exception as e:       # a kernel the chip's compiler refuses
                rec["error"] = repr(e)[:400]
                call = None
            else:
                out = (result[0] if site.differentiated else result
                       ).astype(jnp.float32)
                if path == "xla":
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
