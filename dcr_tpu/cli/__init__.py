"""L5: thin CLI entry points.

    python -m dcr_tpu.cli.train    --data.train_data_dir=... [--key=value ...]
    python -m dcr_tpu.cli.sample   --model_path=... --num_batches=...
    python -m dcr_tpu.cli.evaluate --query_dir=... --values_dir=...
    python -m dcr_tpu.cli.search   embed|search --...
    python -m dcr_tpu.cli.mitigate --model_path=... [--rand_noise_lam=...]
    python -m dcr_tpu.cli.serve    --model_path=... --port=8000

Each maps one reference script (diff_train.py, diff_inference.py,
diff_retrieval.py, embedding_search/*, sd_mitigation.py) onto the library
APIs; config parsing is the shared dotted-key system (core.config.parse_cli).

Every ``main`` calls :func:`setup_compile_cache` before it builds anything.
The platform is JAX's own choice (the TPU where there is one); tests and
rehearsals pin the CPU with ``JAX_PLATFORMS=cpu``.
"""

import os
from pathlib import Path


def setup_compile_cache() -> str:
    """The ONE place that decides where JAX's persistent compilation cache
    lives; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no directory
    is set in code. Unset: ``<checkout>/.jax_cache`` (git-ignored), resolved
    from this package's own path — the path is part of the cache key, so it
    must never move between runs."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
