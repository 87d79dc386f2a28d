"""topk_roofline (%): the least time one `search/topk` call could take on this
chip, max(2*B*N*D / peak FLOP/s, N*D*4 / HBM bytes/s), over the device time of
the program's runs in the trace (`XLA Modules` events `jit_topk(...)`). At 64
queries the bytes bound: the scan of the rows."""
from benchmark.lib import readers, trace as tracelib


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t0, t1 = run.trace.window()
    seconds = calls = 0
    for events in run.trace.modules.values():
        s, n, _ = tracelib.seconds_by(tracelib.in_window(events, t0, t1),
                                      run.counters["module_pattern"])
        seconds, calls = seconds + s, calls + n
    if not calls:
        return None
    least = max(run.counters["flops_per_unit"] / run.peaks["bf16_flops_per_s"],
                run.counters["bytes_per_unit"] / run.peaks["hbm_bytes_per_s"])
    return readers.roofline_share(least * calls, seconds)
