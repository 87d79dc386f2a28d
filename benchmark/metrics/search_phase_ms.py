"""search_phase_ms.<phase> (ms): what one `engine.query()` call is made of.
The median, over the calls of the measured window, of the milliseconds of the
program's span `search/<phase>` inside each `search/query` (by containment of
start times; the cell has one caller). Phases, in the order they run: `put`
(the queries' `device_put`), `dispatch` (the call of the compiled program),
`device_wait` (block until the result is ready: the device's share), `fetch`
(both `np.asarray`), `merge` (key lookup, `np.where`, `merge_topk`). Layer:
search. Moves search_batch_ms_p95."""
import statistics

from benchmark.lib import program_spans as ps


def read(run):
    got = ps.per_call(run, "search/query", "search/" + run.group)
    return None if got is None else 1e3 * statistics.median(got[1])
