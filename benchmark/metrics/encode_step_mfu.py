"""encode_step_mfu (%): the whole encode unit's share of the chip's peak: the
algorithm's FLOPs of the units completed in the measured window (VAE encoder
and tower from shapes, the routed experts' term from the assignments the held
experts really got: the FLOP module the cell's driver names as its `flops`,
benchmark/lib/lm_flops.py or pangu_flops.py) over the window's seconds and
the chip's peak bf16 FLOP/s. Layer: encode step. Moves train_images_per_s."""
from benchmark.lib import readers


def read(run):
    return readers.step_mfu(run)
