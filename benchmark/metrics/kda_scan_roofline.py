"""kda_scan_roofline (%): the gated delta rule's chunked scan against its
roofline, from the device trace.

A site is one run of the scan in one layer: the device ops whose scope path
holds `kda/scan` (the Solar Open 2 tower's KDA mixer wraps
`ops/delta_rule.chunked_delta_rule` in it), grouped by the program run (the
`XLA Modules` event) they fall in and by the `layers_<i>` of their path. The
sites that lie wholly inside the traced stretch are counted; their time is
the union of their ops' intervals (a `while` and its body count once). The
least time of a site is max(FLOPs / peak, bytes / bandwidth) from the
driver's counters `kda_scan_flops_per_site` and `kda_scan_bytes_per_site`
(`benchmark/lib/solar_flops.scan_work`: the chunked algorithm at a nominal
chunk of 64, every operand at 2 bytes), the same whatever implements the
scan. Nothing where no op is in the scope, no counter was given, or off the
chip."""
import bisect
import re

from benchmark.lib import readers, trace as tracelib

SCOPE = "kda/scan"
LAYER = re.compile(r"/(layers_\d+)/")


def sites(trace, chip: int) -> list[list]:
    """The ops of `chip` under the scope, one list a site."""
    runs = sorted(s for _, s, _ in trace.modules.get(chip, []))
    out: dict = {}
    for event, path in zip(trace.ops[chip], tracelib.paths_of(trace, chip)):
        if path and tracelib.in_scope(path, SCOPE):
            layer = LAYER.search(path)
            key = (bisect.bisect_right(runs, event[1]) - 1,
                   layer.group(1) if layer else "")
            out.setdefault(key, []).append(event)
    return list(out.values())


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    work = run.counters.get("kda_scan_flops_per_site")
    moved = run.counters.get("kda_scan_bytes_per_site")
    if not work or not moved:
        return None
    least = max(work / run.peaks["bf16_flops_per_s"],
                moved / run.peaks["hbm_bytes_per_s"])
    t0, t1 = run.trace.window()
    count, seconds = 0, 0.0
    for chip in run.trace.ops:
        for ops in sites(run.trace, chip):
            if min(s for _, s, _ in ops) >= t0 and max(s + d for _, s, d in ops) <= t1:
                count += 1
                seconds += tracelib.busy_seconds(ops, t0, t1)
    return readers.roofline_share(count * least, seconds)
