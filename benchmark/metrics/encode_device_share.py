"""encode_device_share.<part> (%): the device time of one part of the encode
program, from the profiler's trace: the union of the intervals of the device
ops whose scope path (the `tf_op` of the op's metadata: the program's
`jax.named_scope`s and Flax modules) holds the part's scope, over the union
of all device ops, both inside the traced stretch. A `while` and the ops of
its body count once. The part's scope is data, one file a part beside this
one: `encode_device_share.<part>.json`, `{"scope": "moe/experts"}`, so a
later tower's mixer is one more file and one more entry. Nothing where no
op carries a path or none is in the scope, and nothing off the chip."""
import json

from benchmark.lib import trace as tracelib


def scope_of(run) -> str:
    path = run.cell.root / "benchmark" / "metrics" / f"encode_device_share.{run.group}.json"
    return json.loads(path.read_text())["scope"]


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return tracelib.scope_share(run.trace, scope_of(run))
