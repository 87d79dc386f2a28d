"""Block-size sweep for the Pallas flash-attention kernels on the local chip.

Times flash fwd and fwd+bwd against XLA's fused attention across sequence
lengths and (block_q, block_k) candidates; appends one JSON object per
measurement to SWEEP_FLASH.jsonl so a killed run still leaves data.

Usage: python tools/sweep_flash.py  (run on a box where jax sees the TPU)
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

OUT = Path(__file__).resolve().parent.parent / "SWEEP_FLASH.jsonl"

# SD-2.1 UNet spatial self-attention shapes: 256px -> S=1024 (H5 at C320),
# 512px -> S=4096, 1024px-equivalent long-context -> S=16384.
SHAPES = [  # (B, H, S, D)
    (4, 20, 256, 64),
    (4, 10, 512, 64),
    (4, 5, 1024, 64),
    (4, 10, 4096, 64),
    (1, 5, 16384, 64),
]
BLOCKS = [(512, 256), (512, 512), (1024, 256), (1024, 512), (1024, 1024),
          (2048, 512), (256, 256)]


def emit(rec: dict) -> None:
    rec["t"] = time.strftime("%H:%M:%S")
    with OUT.open("a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def timeit(fn, *args, iters: int = 20) -> float:
    """ms/iter: ``iters`` back-to-back calls ended by block_until_ready,
    best of three, after a compile + warm-up call."""
    jax.block_until_ready(fn(*args))

    def run() -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    return min(run() for _ in range(3)) / iters * 1e3


def main() -> None:
    from dcr_tpu.ops import flash_attention as fa

    emit({"phase": "devices", "devices": [str(d) for d in jax.devices()]})
    rng = np.random.default_rng(0)

    for (b, h, s, d) in SHAPES:
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)

        def loss_xla(q, k, v):
            return jnp.sum(jax.nn.dot_product_attention(q, k, v).astype(jnp.float32) ** 2)

        xla_fwd = jax.jit(lambda q, k, v: jax.nn.dot_product_attention(q, k, v))
        xla_grad = jax.jit(jax.grad(loss_xla, argnums=(0, 1, 2)))
        try:
            ms_f = timeit(xla_fwd, q, k, v)
            ms_g = timeit(xla_grad, q, k, v)
            emit({"impl": "xla", "shape": [b, h, s, d], "fwd_ms": round(ms_f, 3),
                  "fwd_bwd_ms": round(ms_g, 3)})
        except Exception as e:
            emit({"impl": "xla", "shape": [b, h, s, d], "error": repr(e)[:300]})

        for (bq, bk) in BLOCKS:
            if s % bq or s % bk:
                continue

            def fl_fwd(q, k, v, bq=bq, bk=bk):
                return fa.flash_attention(q, k, v, False, bq, bk)

            def loss_fl(q, k, v, bq=bq, bk=bk):
                return jnp.sum(fa.flash_attention(q, k, v, False, bq, bk)
                               .astype(jnp.float32) ** 2)

            jf = jax.jit(fl_fwd)
            jg = jax.jit(jax.grad(loss_fl, argnums=(0, 1, 2)))
            try:
                ms_f = timeit(jf, q, k, v)
                ms_g = timeit(jg, q, k, v)
                # correctness spot-check vs XLA
                err = float(jnp.max(jnp.abs(
                    jf(q, k, v).astype(jnp.float32)
                    - xla_fwd(q, k, v).astype(jnp.float32))))
                emit({"impl": "flash", "shape": [b, h, s, d], "blocks": [bq, bk],
                      "fwd_ms": round(ms_f, 3), "fwd_bwd_ms": round(ms_g, 3),
                      "max_abs_err_vs_xla": round(err, 5)})
            except Exception as e:
                emit({"impl": "flash", "shape": [b, h, s, d], "blocks": [bq, bk],
                      "error": repr(e)[:300]})

    emit({"phase": "done"})


if __name__ == "__main__":
    main()
