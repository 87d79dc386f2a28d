"""search_host_share (%): the share of the window's `search/query` seconds in
which the caller was NOT waiting for the device: 1 - sum `search/device_wait`
/ sum `search/query`, over the calls of the measured window (profiler off). It
is what a plain run idles the chip for, which the traced stretch, run after a
profiler session has opened, cannot give. Layer: search. Moves
search_queries_per_s."""
from benchmark.lib import program_spans as ps


def read(run):
    got = ps.per_call(run, "search/query", "search/device_wait")
    if got is None:
        return None
    calls, waits = got
    return 100.0 * (1.0 - sum(waits) / sum(d for _, d in calls))
