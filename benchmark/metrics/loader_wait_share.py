"""loader_wait_share.<part> (%): seconds the loader's CONSUMER spent waiting,
over the measured window: `.fill` reads the program's span `data/fill` (the
workers' start to the first batch in hand: the bubble every epoch opens
with), `.steady` reads `data/wait` (each later wait on the queue). Together
they are `data_wait_share` seen from inside `DataLoader.epoch()`. Layer: host
input. Moves train_images_per_s."""
from benchmark.lib import program_spans as ps

SPAN = {"fill": "data/fill", "steady": "data/wait"}


def read(run):
    return ps.window_share(run, SPAN[run.group])
