"""device_idle_share.<group> (%): 1 - union of the device-op intervals over
the traced stretch, from the profiler's trace. The dotted suffix only says
which end-to-end metric the cell reports; the reading is the same."""
from benchmark.lib import readers


def read(run):
    return readers.device_idle_share(run)
