"""Compile the flash-attention kernel for a described (not attached) v5e.

The TPU compiler is installed next to the CPU backend, so these tests catch
what Pallas interpret mode cannot — a kernel that asks for more VMEM than a
core has, a slice the tiling refuses — at no chip time. Shapes are the ones
SD-2.1 hands the kernel: (B, 4096, 5, 64) at 512 px, (B, 9216, 5, 64) at
768 px.

The topology is described inside a fixture and never at import: only one
process may load the TPU library, and under pytest-xdist every worker imports
every test file. All such tests live in THIS file, so one worker owns them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dcr_tpu.ops import flash_attention as fa


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


def _forward(q, k, v):
    return fa.flash_attention(q, k, v)


def _forward_backward(q, k, v):
    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _compile(fn, shape, dtype, sharding):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return jax.jit(fn).lower(x, x, x).compile()


@pytest.mark.parametrize("fn,calls", [(_forward, 1), (_forward_backward, 3)],
                         ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape,dtype", [
    ((2, 4096, 5, 64), jnp.bfloat16),     # 512 px, what training feeds it
    ((2, 9216, 5, 64), jnp.bfloat16),     # 768 px
    ((2, 4096, 5, 64), jnp.float32),      # 512 px, what the sampler feeds it
], ids=["512px_bf16", "768px_bf16", "512px_f32"])
def test_kernel_compiles_for_v5e(one_chip, shape, dtype, fn, calls):
    x = jax.ShapeDtypeStruct(shape, dtype)
    assert fa.should_use(x, x, x)
    text = _compile(fn, shape, dtype, one_chip).as_text()
    assert text.count("tpu_custom_call") >= calls


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_every_supported_shape_compiles(one_chip, head_dim, dtype):
    """supported() must refuse what the chip's compiler refuses: walk key
    lengths to 16384 (a 1024 px image) and compile, forward and backward,
    each one the dispatcher would send into the kernel."""
    accepted = []
    for seq in range(1024, 16384 + 1, 1024):
        shape = (1, seq, 5, head_dim)
        x = jax.ShapeDtypeStruct(shape, dtype)
        if fa.supported(x, x, x):
            accepted.append(seq)
            _compile(_forward_backward, shape, dtype, one_chip)
    assert accepted, "supported() accepts no shape at all"
    if head_dim == 64 and dtype == jnp.bfloat16:
        # SD-2.1 at 768 px stays on the kernel; 1024 px does not fit VMEM
        assert 9216 in accepted and 16384 not in accepted
