"""Driver `encode_leg_solar`: the finetune's frozen leg,
`dcr-precompute-latents`, bounded by seconds, on the Solar Open 2 text tower.

The job, the window, the set-up, the FLOPs and counters, the second pass
over the router and the comparison that decides `correct` are
`encode_leg`'s to the letter (a `PrecomputeJob` built as the CLI builds it,
`encode_batch(number, then)` round and round the dataset, back to back, one
caller); but each call names the next `PrecomputeJob.AHEAD` batches as the
CLI's loop does, where `encode_leg`'s names the next one. The tower is the
three modules the class names:
`lib/solar_stack.py` (how the configuration's file becomes `--model.solar.*`,
the seeded leaves with Kimi Linear's decay draws, a stack whose every layer
has an expert layer), `lib/solar_flops.py` and `reference/solar_open2.py`.
What is this driver's own: the counters of the program's delta rule and
expert layer, for the log, and the least work of one scan site, for
`kda_scan_roofline`.

Traffic parameters (the workload's file): `train_config`, `overrides`,
`images`, `image_px`, `caption_tokens`, `check_rows`, `reference.tie_eps`,
`limits`.
"""
from __future__ import annotations

from benchmark.drivers import encode_leg
from benchmark.drivers.encode_leg import compare  # noqa: F401  (tools and tests read it here)
from benchmark.lib import solar_flops, solar_stack
from benchmark.reference import solar_open2

#: what this tower's program counts beside `encode_leg.MOE_COUNTERS`
NEW_COUNTERS = ("moe/tokens_unheld_total", "delta_rule/sites_total",
                "delta_rule/chunks_total")
NEW_GAUGES = ("moe/layers", "tower/layers", "delta_rule/chunk")


def new_counters() -> dict:
    """The counters and gauges above as the registry holds them; a program
    without them gives none."""
    from benchmark.lib import program_spans as ps
    from dcr_tpu.core import tracing

    have = {**tracing.registry().counters("moe/"),
            **tracing.registry().counters("delta_rule/")}
    out = {name: int(have[name]) for name in NEW_COUNTERS if name in have}
    for name in NEW_GAUGES:
        value = ps.gauge(name)
        if value is not None:
            out[name] = int(value)
    return out


class Driver(encode_leg.Driver):
    stack = solar_stack
    flops = solar_flops
    ref = solar_open2

    def tower_counts(self) -> dict:
        return new_counters()

    def unit(self) -> None:
        from benchmark.lib import program_spans as ps

        batches, n = len(self.job), self._next
        self.job.encode_batch(n % batches, [
            (n + k) % batches for k in range(1, self.job.AHEAD + 1)])
        self._next += 1
        self.load_max.append(ps.gauge("moe/held_expert_load_max") or 0)

    def counters(self, window) -> dict:
        """`encode_leg`'s, and the FLOPs and bytes of one site of the
        chunked scan at the cell's batch and length."""
        work, moved = solar_flops.scan_work(
            self.cfg, self.batch, int(self.cfg["text_max_length"]))
        return {**super().counters(window), "kda_scan_flops_per_site": work,
                "kda_scan_bytes_per_site": moved}
