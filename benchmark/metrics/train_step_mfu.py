"""train_step_mfu (%): the algorithm's FLOPs of the work completed in the measured
window (benchmark/lib/flops.py, from shapes) over the window's seconds and the
chip's peak bf16 FLOP/s (benchmark/peaks.json)."""
from benchmark.lib import readers


def read(run):
    return readers.step_mfu(run)
