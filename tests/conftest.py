"""Test configuration: force an 8-device virtual CPU platform so every
mesh/pjit/collective test runs without TPU hardware (SURVEY.md §4 item 3)."""

import os
from pathlib import Path

# all before jax is imported: jax reads these variables when it loads, and
# the subprocess tests inherit them through os.environ
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# Persistent compile cache: the suite's dominant cost on a small box is XLA
# recompiles of identical programs (every Trainer/make_train_step call is a new
# closure -> new jit object). Cache survives across tests AND across runs. A
# fixed directory, which yields to a JAX_COMPILATION_CACHE_DIR that is already
# set; the CLIs' setup_compile_cache() then sets nothing on top of it.
_cache = Path(os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    str(Path(__file__).parent / ".jax_cache_cpu")))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


_cache_before: set = set()


def pytest_sessionstart(session):
    global _cache_before
    _cache_before = {p.name for p in _cache.glob("*")} if _cache.exists() else set()


def pytest_sessionfinish(session, exitstatus):
    """Cache hit/miss accounting: entries present before the session that the
    run did NOT touch are prune candidates (an entry is rewritten/refreshed on
    miss, so `new` counts this run's compiles). Regenerate the committed cache
    with JAX_COMPILATION_CACHE_DIR=<fresh dir> + a full run, then swap directories."""
    if not _cache.exists():
        return
    now = {p.name for p in _cache.glob("*")}
    new = now - _cache_before
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.write_line(
            f"jax compile cache [{_cache.name}]: {len(now)} entries, "
            f"{len(new)} written this run (cache misses)")


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs


@pytest.fixture()
def rng_np():
    return np.random.default_rng(0)
