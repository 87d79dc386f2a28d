"""data_wait_share (%): host seconds the train loop waited in `next()` on the
trainer's DataLoader (the benchmark's own span `data_wait`), over the
measured window. Layer: host input. Moves train_images_per_s."""
from benchmark.lib import readers


def read(run):
    return readers.span_share(run, "data_wait")
