"""sample_d2h_share (%): seconds of the device-to-host copy of the sampled
images alone (the program's span `xfer/d2h` inside `pmesh.to_host`, after
`xfer/device_wait` has returned) over the measured window: time in which a
caller that fetches batch by batch has nothing in flight. Layer: transfers.
Moves sample_images_per_s."""
from benchmark.lib import program_spans as ps


def read(run):
    return ps.window_share(run, "xfer/d2h")
