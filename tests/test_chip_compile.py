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


@pytest.mark.parametrize("limit_mib,shape,dtype", [
    (16, (4, 4096, 5, 64), jnp.float32),       # Mosaic's default: 16.08M asked
    (16, (2, 9216, 5, 64), jnp.bfloat16),      # 16.60M asked
    (None, (1, 16384, 5, 64), jnp.bfloat16),   # what supported() refuses
], ids=["default_limit_f32_4096", "default_limit_bf16_9216",
        "stated_limit_bf16_16384"])
def test_vmem_limit_is_what_the_resident_slabs_need(one_chip, monkeypatch,
                                                    limit_mib, shape, dtype):
    """Why the kernels state VMEM_LIMIT_BYTES: a 128-lane K and V slab and a
    batch row's lse, double-buffered, pass Mosaic's own 16 MiB at shapes the
    dispatcher sends ("Scoped allocation with size 16.08M and limit 16.00M
    exceeded scoped vmem limit"); and why supported() stops where it does:
    1,024 px (16,384 keys) passes the stated limit too."""
    x = jax.ShapeDtypeStruct(shape, dtype)
    assert fa.supported(x, x, x) is (limit_mib is not None)
    if limit_mib is not None:
        _compile(_forward, shape, dtype, one_chip)      # fits as shipped
        monkeypatch.setattr(fa, "VMEM_LIMIT_BYTES", limit_mib * 2**20)
    with pytest.raises(Exception, match="vmem"):
        # a function of its own: jit would hand back _forward's first trace
        _compile(lambda q, k, v: fa.flash_attention(q, k, v), shape, dtype,
                 one_chip)


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


def _entry_instructions(text):
    """(name, opcode, whole line) of the entry computation's instructions."""
    import re

    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    found = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (?:\(.*?\)|\S+) "
                       r"([\w\-]+)\((.*)$", entry, re.M)
    return [(name, opcode, rest) for name, opcode, rest in found]


@pytest.mark.parametrize("shape,dtype,fn", [
    ((20, 1024, 5, 64), jnp.float32, _forward),
    ((2, 4096, 5, 64), jnp.float32, _forward),
    ((16, 1024, 5, 64), jnp.bfloat16, _forward_backward),
], ids=["256px_f32_20rows", "512px_f32", "256px_bf16_16rows_fwd_bwd"])
def test_kernel_reads_the_projections_own_layout(one_chip, shape, dtype, fn):
    """[B, S, H*D] in, as to_q/to_k/to_v write it, through the reshape the
    model makes, the kernel, and the reshape back: the compiled program is the
    kernels and nothing else, no transpose and no copy. And the forward call
    is what benchmark/metrics/flash_fwd_roofline.py looks for: a
    tpu_custom_call whose result is the pair (out [B, S, H*D], lse f32
    [B, S, 128]) and whose first operands are q, k, v."""
    import re

    from jax.experimental.layout import Format, Layout

    from benchmark.metrics import flash_fwd_roofline

    b, s, h, d = shape
    # row-major, the layout the custom call asks of whatever feeds it (a bare
    # parameter of [B, S, 320] would otherwise sit S-minor on the TPU)
    fmt = Format(Layout(major_to_minor=(0, 1, 2)), one_chip)
    x = jax.ShapeDtypeStruct((b, s, h * d), dtype, sharding=fmt)

    def through_the_models_reshapes(*flat):
        results = fn(*(a.reshape(b, s, h, d) for a in flat))
        return jax.tree.map(lambda r: r.reshape(b, s, h * d), results)

    text = jax.jit(through_the_models_reshapes, out_shardings=fmt
                   ).lower(x, x, x).compile().as_text()
    instructions = _entry_instructions(text)
    opcodes = [opcode for _, opcode, _ in instructions]
    assert "transpose" not in opcodes and "copy" not in opcodes
    parameters = {name: int(re.match(r"(\d+)\)", rest).group(1))
                  for name, opcode, rest in instructions
                  if opcode == "parameter"}
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == (1 if fn is _forward else 3)
    forward = [line for line in kernels if "flash_fwd" in line]
    assert len(forward) == 1
    t = {jnp.float32: "f32", jnp.bfloat16: "bf16"}[dtype]
    match = re.search(
        rf"= \({t}\[{b},{s},{h * d}\]\S*, f32\[{b},{s},128\]\S*\) "
        r"custom-call\(%([\w.\-]+), %([\w.\-]+), %([\w.\-]+)\)", forward[0])
    assert match, forward[0][:300]
    assert [parameters[name] for name in match.groups()] == [0, 1, 2]
    flops, moved = flash_fwd_roofline.kernel_work(forward[0].strip())
    assert flops == 4.0 * b * s * s * h * d
    itemsize = jnp.dtype(dtype).itemsize
    assert moved == 4 * b * s * h * d * itemsize + b * s * 128 * 4


@pytest.mark.parametrize("rows,dtype,backward", [(20, jnp.float32, False),
                                                 (16, jnp.bfloat16, True)],
                         ids=["sampler_256px", "train_step_256px"])
def test_no_relayout_between_the_projections_and_the_kernels(
        one_chip, monkeypatch, rows, dtype, backward):
    """The model's own self-attention layer at the 256 px UNet's top level
    (to_q/to_k/to_v, the dispatcher, to_out), forward as the sampler runs it
    and differentiated as the train step does: the program holds no transpose
    and no copy at all, and every kernel operand is a parameter, another
    kernel's result or what a fusion (a projection) wrote."""
    import re

    from dcr_tpu.models import layers
    from dcr_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    layer = layers.CrossAttention(num_heads=5, head_dim=64, out_dim=320,
                                  dtype=dtype)
    x = jax.ShapeDtypeStruct((rows, 1024, 320), dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(jax.random.key(0),
                                          jnp.zeros(x.shape, dtype))))

    def forward(params, x):
        return layer.apply(params, x)

    def forward_backward(params, x):
        return jax.grad(lambda p, x: forward(p, x).astype(jnp.float32).sum(),
                        argnums=(0, 1))(params, x)

    text = jax.jit(forward_backward if backward else forward
                   ).lower(params, x).compile().as_text()
    instructions = _entry_instructions(text)
    opcode_of = {name: opcode for name, opcode, _ in instructions}
    assert "transpose" not in opcode_of.values()
    assert "copy" not in opcode_of.values()
    kernels = [(name, rest) for name, opcode, rest in instructions
               if 'custom_call_target="tpu_custom_call"' in rest]
    assert len(kernels) == (3 if backward else 1)
    through = {"get-tuple-element", "bitcast", "copy-done", "copy-start"}

    def source(name):       # past tuple reads, bitcasts and VMEM prefetches
        while opcode_of[name] in through:
            rest = next(r for n, _, r in instructions if n == name)
            name = re.match(r"%([\w.\-]+)", rest).group(1)
        return opcode_of[name]

    for name, rest in kernels:
        operands = re.findall(r"%([\w.\-]+)", rest[:rest.index(")")])
        assert {source(o) for o in operands} <= {"fusion", "custom-call",
                                                 "parameter"}, (name, operands)


@pytest.mark.parametrize("shape,dtype", [
    ((16, 256, 64, 128), jnp.bfloat16),   # the Solar Open 2 encode cell's site
    ((2, 200, 4, 128), jnp.float32),      # padded to whole chunks, float32
    ((1, 2048, 2, 256), jnp.bfloat16),    # two slabs a head, a long sequence
], ids=["solar_site_bf16", "padded_f32", "long_two_slabs"])
def test_the_delta_rule_kernel_compiles_for_v5e(one_chip, shape, dtype):
    """The gated delta rule's kernel at the shapes the dispatcher hands it:
    `HEADS` heads of one sequence a program, their states in VMEM."""
    from dcr_tpu.ops import delta_rule_kernel as dk

    b, t, h, d = shape
    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    decay = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((b, t, h), jnp.float32, sharding=one_chip)
    assert dk.supported(qkv, qkv, qkv, decay, beta)
    text = jax.jit(dk.delta_rule_fwd).lower(qkv, qkv, qkv, decay, beta
                                            ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
