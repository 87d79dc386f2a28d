"""flash_fwd_roofline (%): the Pallas flash-attention FORWARD kernel's share of
its roofline, from the device trace.

The kernel's events are the `XLA Ops` events whose instruction is a
`tpu_custom_call` with a two-part result `(out [BH, Sq, D], lse f32 [BH, Sq,
128])`: that is `_fwd_kernel` of dcr_tpu/ops/flash_attention.py (the dq kernel
has one result, the dk/dv kernel two of the operands' type). Its shapes are read
out of the instruction's own text, so the count follows whatever the program
ran: FLOPs 4*BH*Sq*Sk*D (QK^T and PV), bytes q + k + v + out + lse once.
The least time is max(FLOPs / peak, bytes / bandwidth); at S = 4096, D = 64
the FLOPs bound."""
import math

from benchmark.lib import readers, trace as tracelib

PATTERN = r'custom_call_target="tpu_custom_call"'


def kernel_work(text: str):
    """(FLOPs, bytes) of one forward call from its instruction text, or None
    if the text is not the forward kernel's."""
    shapes = tracelib.shapes_in(text)
    if len(shapes) < 5:
        return None
    (t_out, out), (t_lse, lse), q, k, v = shapes[:5]
    if t_lse != "f32" or len(out) != 3 or len(lse) != 3 or lse[:2] != out[:2]:
        return None
    if t_out == "f32" and shapes[2][0] != "f32":
        return None
    bh, sq, d = out
    sk = k[1][1]
    flops = 4.0 * bh * sq * sk * d
    size = lambda s: tracelib.ITEMSIZE[s[0]] * math.prod(s[1])   # noqa: E731
    return flops, float(sum(size(s) for s in (shapes[0], shapes[1], q, k, v)))


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t0, t1 = run.trace.window()
    least = seconds = 0.0
    for events in run.trace.ops.values():
        _, _, hits = tracelib.seconds_by(tracelib.in_window(events, t0, t1),
                                         PATTERN)
        for name, _, dur in hits:
            work = kernel_work(name)
            if work is None:
                continue
            least += max(work[0] / run.peaks["bf16_flops_per_s"],
                         work[1] / run.peaks["hbm_bytes_per_s"])
            seconds += dur / 1e9
    return readers.roofline_share(least, seconds)
