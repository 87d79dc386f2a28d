"""Driver `encode_leg_openpangu`: the finetune's frozen leg,
`dcr-precompute-latents`, bounded by seconds, on the openPangu-Ultra-MoE text
tower.

The job, the unit, the window, the set-up, the FLOPs and counters, the
second pass over the router and the comparison that decides `correct` are
`encode_leg`'s to the letter (a `PrecomputeJob` built as the CLI builds it,
`encode_batch(number, then)` round and round the dataset, back to back, one
caller); the tower is the three modules the class names:
`lib/pangu_stack.py` (how the configuration's file becomes
`--model.openpangu.*`, the seeded leaves' deviations, and a stack whose first
layer has no expert layer, so that the routing is read from the layers that
have one), `lib/pangu_flops.py` and `reference/openpangu_ultra_moe.py`. What
is this driver's own: the counters and gauges PR 32 added to the program, for
the log.

Traffic parameters (the workload's file): `train_config`, `overrides`,
`images`, `image_px`, `caption_tokens`, `check_rows`, `reference.tie_eps`,
`limits`.
"""
from __future__ import annotations

from benchmark.drivers import encode_leg
from benchmark.drivers.encode_leg import compare  # noqa: F401  (tools and tests read it here)
from benchmark.lib import pangu_flops, pangu_stack
from benchmark.reference import openpangu_ultra_moe

#: what this tower's program counts beside `encode_leg.MOE_COUNTERS`
NEW_COUNTERS = ("moe/tokens_unheld_total",)
NEW_GAUGES = ("moe/layers", "tower/layers")


def new_counters() -> dict:
    """The counters and gauges PR 32 added to the program, as the registry
    holds them; a program without them gives none."""
    from benchmark.lib import program_spans as ps
    from dcr_tpu.core import tracing

    have = tracing.registry().counters("moe/")
    out = {name: int(have[name]) for name in NEW_COUNTERS if name in have}
    for name in NEW_GAUGES:
        value = ps.gauge(name)
        if value is not None:
            out[name] = int(value)
    return out


class Driver(encode_leg.Driver):
    stack = pangu_stack
    flops = pangu_flops
    ref = openpangu_ultra_moe

    def tower_counts(self) -> dict:
        return new_counters()
