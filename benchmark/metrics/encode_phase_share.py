"""encode_phase_share.<phase> (%): seconds of the measured window inside one
phase of `dcr-precompute-latents`' per-batch body, from the program's own
spans: `.load` reads `precompute/load` (decode, tokenize, place: of the batch
named as next, while the device is on this one), `.encode`
`precompute/encode` (the caller's wait for the device), `.fetch`
`precompute/fetch`, `.write` `precompute/write` (the cache shard, on the
writer's thread). Load, wait and fetch run in series in the one caller;
the write runs beside them, so the four can pass 100% together. In a program
whose body runs them one after the other (the first form of PR 28) they sum
to the window less the loop's own overhead. Layer: encode step. Moves
train_images_per_s."""
from benchmark.lib import program_spans as ps


def read(run):
    return ps.window_share(run, f"precompute/{run.group}")
