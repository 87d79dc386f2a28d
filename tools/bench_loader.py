"""Host input-pipeline benchmark: images/sec through the data loader's decode
+transform path, PIL-only vs the native libjpeg scaled-decode fast path.

A step benchmark on synthetic batches never shows the host pipeline's
contribution; this tool measures it directly on CPU — no TPU needed. The
number that matters for training is images/sec/core vs the chip's demand
(~92 img/s/chip at 256px, measured 2026-07-29 on a backend since retired; to
be re-measured by the benchmark): a v5e host has
dozens of cores feeding each chip, so per-core decode throughput × cores
must exceed chip demand with headroom.

Covers SURVEY §7.3's "host-side data pipeline throughput" hard part and
gives the first-party C++ component (dcr_tpu/native/jpeg_decode.cc) a
measured, committed number. Writes LOADER_BENCH.json.

Usage: python tools/bench_loader.py [n_images] [src_px] [out_px]
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np
from PIL import Image

OUT = Path(__file__).resolve().parent.parent / "LOADER_BENCH.json"


def make_corpus(root: Path, n: int, px: int) -> list[str]:
    """JPEGs with photographic-ish statistics (smooth gradients + noise —
    all-noise images compress pathologically and skew decode cost)."""
    rng = np.random.default_rng(0)
    paths = []
    for i in range(n):
        yy, xx = np.mgrid[0:px, 0:px].astype(np.float32) / px
        base = (np.stack([yy, xx, (xx + yy) / 2], -1) * 200).astype(np.uint8)
        noise = rng.integers(0, 40, (px, px, 3), np.uint8)
        img = Image.fromarray(base + noise)
        p = root / f"{i}.jpg"
        img.save(p, quality=90)
        paths.append(str(p))
    return paths


def time_decode(paths: list[str], out_px: int, *, use_native: bool,
                repeats: int = 3) -> dict:
    from dcr_tpu.data import dataset as DS
    from dcr_tpu.native import jpeg_decoder

    if use_native and not jpeg_decoder.available():
        return {"available": False}

    # gate the fast path exactly where the dataset does (_open_image checks
    # jpeg_decoder.available()); to measure PIL-only, monkeypatch it off
    orig = jpeg_decoder.available
    jpeg_decoder.available = (lambda: False) if not use_native else orig
    try:
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for p in paths:
                arr = DS.load_and_transform(p, out_px, center_crop=True,
                                            random_flip=False, rng=None)
                assert arr.shape == (out_px, out_px, 3), arr.shape
            best = min(best, time.perf_counter() - t0)
    finally:
        jpeg_decoder.available = orig
    return {"available": True,
            "images_per_sec_per_core": round(len(paths) / best, 1),
            "ms_per_image": round(best / len(paths) * 1e3, 3)}


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200
    src_px = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    out_px = int(sys.argv[3]) if len(sys.argv) > 3 else 256

    with tempfile.TemporaryDirectory() as td:
        paths = make_corpus(Path(td), n, src_px)
        pil = time_decode(paths, out_px, use_native=False)
        native = time_decode(paths, out_px, use_native=True)

    result = {
        "n_images": n, "src_px": src_px, "out_px": out_px,
        "pil": pil, "native_scaled_decode": native,
        "speedup": (round(native["images_per_sec_per_core"]
                          / pil["images_per_sec_per_core"], 2)
                    if native.get("available") else None),
        "t": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    OUT.write_text(json.dumps(result, indent=1))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
