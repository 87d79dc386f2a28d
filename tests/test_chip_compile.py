"""Compile the flash-attention kernel for a described (not attached) v5e.

The TPU compiler is installed next to the CPU backend, so these tests catch
what Pallas interpret mode cannot — a kernel that asks for more VMEM than a
core has, a slice the tiling refuses — at no chip time. Shapes are the ones
SD-2.1 hands the kernel: (B, 1024, 5, 64) at 256 px, (B, 4096, 5, 64) and
(B, 1024, 10, 64) at 512 px, (B, 9216, 5, 64) at 768 px.

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
    ((20, 1024, 5, 64), jnp.float32),     # 256 px, the sampler at 10 images
    ((4, 1024, 10, 64), jnp.float32),     # 512 px second level, 2 images
    ((16, 1024, 5, 64), jnp.bfloat16),    # 256 px, the train step at batch 16
], ids=["512px_bf16", "768px_bf16", "512px_f32", "256px_f32_20rows",
        "512px_level2_f32", "256px_bf16_16rows"])
def test_kernel_compiles_for_v5e(one_chip, shape, dtype, fn, calls):
    x = jax.ShapeDtypeStruct(shape, dtype)
    assert fa.should_use(x, x, x)
    text = _compile(fn, shape, dtype, one_chip).as_text()
    assert text.count("tpu_custom_call") >= calls


@pytest.mark.parametrize("rows", [2, 4, 8, 20])
def test_f32_4096_keys_compile_at_every_batch(one_chip, rows):
    """The default blocks fit scoped VMEM whatever the batch, so bulk sampling
    at 512 px is not held to one image a batch by the kernel."""
    assert fa._resolve_blocks(4096, 4096, None, None, 4) == (512, 1024)
    _compile(_forward, (rows, 4096, 5, 64), jnp.float32, one_chip)


def test_f32_4096_keys_refused_with_1024_blocks(one_chip):
    """Why block_q is 512 there: (1024, 1024) is refused from 4 rows on
    ("Scoped allocation with size 16.34M and limit 16.00M exceeded scoped
    vmem limit")."""
    with pytest.raises(Exception, match="vmem"):
        _compile(lambda q, k, v: fa.flash_attention(q, k, v, False, 1024, 1024),
                 (4, 4096, 5, 64), jnp.float32, one_chip)


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
