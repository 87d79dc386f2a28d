"""Compile the `search/topk` program at the search cell's own size for a
described (not attached) v5e: 3,145,728 x 512 float32 rows, 64 queries, top-1.
Nothing runs; the chip's compiler says whether the program and its 6.44 GB of
rows fit one chip's memory.

The topology is described inside a module fixture and never at import (only
one process may load the TPU library, and under pytest-xdist every worker
imports every test file); all of this directory's compile-for-the-chip tests
live in THIS file, so one worker owns them. Skipped, not failed, where the
topology cannot be described."""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.lib import harness


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip (it would warn and recompile)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_topk_at_the_cells_size_fits_one_v5e(one_chip):
    from dcr_tpu.search.shardindex import make_topk

    cell = harness.load_cell("sscd-laion12m-share-search")
    rows, dim = cell.config["rows"], cell.config["embed_dim"]
    batch, k = cell.traffic["query_batch"], cell.traffic["top_k"]
    assert (rows, dim, batch, k) == (3_145_728, 512, 64, 1)
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    compiled = make_topk(k).lower(S((rows, dim), jnp.float32), S((rows,), jnp.bool_),
                                  S((batch, dim), jnp.float32)).compile()
    memory = compiled.memory_analysis()
    hbm = harness.load_peaks()["tpu v5 lite"]["hbm_bytes"]
    resident = rows * dim * 4
    assert memory.argument_size_in_bytes >= resident        # the rows are an argument
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             + memory.temp_size_in_bytes + memory.generated_code_size_in_bytes)
    assert total < 0.95 * hbm, (total, hbm)
    # the cell's floor: the rows alone fill over a quarter of the chip
    assert resident / hbm > 0.25
    assert json.dumps(cell.config["reduced"]) == '["rows"]'
