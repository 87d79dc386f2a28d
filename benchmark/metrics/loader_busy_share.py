"""loader_busy_share (%): seconds the loader's worker threads spent building
batches (the program's span `data/batch`, one a batch, on the worker that
decoded it) over the measured window times the workers (the program's gauge
`data/workers`): how much of the decode capacity the job uses. Layer: host
input. Moves train_images_per_s."""
from benchmark.lib import program_spans as ps


def read(run):
    workers = ps.gauge("data/workers")
    if not workers:
        return None
    return ps.window_share(run, "data/batch", lanes=workers)
