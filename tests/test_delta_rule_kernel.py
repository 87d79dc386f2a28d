"""ops/delta_rule_kernel: the Pallas kernel of the chunked gated delta rule,
run through the Pallas interpreter on the CPU, against the XLA form of
ops/delta_rule and the position-by-position recurrence (the plain
reference's `delta_rule`) at 128-wide heads: lengths that are a multiple of
the chunk, that are not, and shorter than one; a decay whose inverse over a
chunk is past float32's range; bfloat16 operands; the gradient through the
dispatcher; and which path the dispatcher takes, and counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.solar_open2 import delta_rule as recurrence
from dcr_tpu.core import tracing
from dcr_tpu.ops import attention, delta_rule as dr, delta_rule_kernel as dk

#: as tests/test_delta_rule.py: float32 on both sides, the same sums in
#: another order
ATOL = 2e-5

recurrence = jax.jit(recurrence)


def inputs(t: int, *, b: int = 2, h: int = 2, d: int = 128, dv: int = 128,
           strongest: float = 0.5, seed: int = 0, dtype=jnp.float32):
    """(q, k, v, log_alpha, beta): q and k unit vectors, beta in (0, 2); a
    channel's log decay a step is -exp(x), x uniform on [-7, `strongest`]."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (b, t, h, d))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    log_alpha = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, d), minval=-7.0,
                                            maxval=strongest))
    return (unit(ks[0]).astype(dtype), unit(ks[1]).astype(dtype),
            jax.random.normal(ks[2], (b, t, h, dv)).astype(dtype), log_alpha,
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))


def kernel(args, chunk: int, sub: int):
    return jax.jit(lambda *a: dk.delta_rule_fwd(*a, interpret=True, chunk=chunk,
                                                sub=sub))(*args)


def xla(args, chunk: int, sub: int):
    return jax.jit(lambda *a: dr._xla_form(*a, chunk, sub))(*args)


@pytest.mark.parametrize("t, chunk, sub, heads, d", [
    (64, 16, 4, 2, 128),      # a multiple of the chunk
    (70, 32, 8, 2, 128),      # not a multiple: the last chunk padded
    (5, 64, 16, 2, 128),      # shorter than one chunk
    (128, 64, 16, 3, 128),    # the shipped lengths, three heads
    (300, 64, 16, 2, 128),    # two windows, the second mostly padding
    (40, 32, 16, 1, 256),     # two lane slabs a head
], ids=["whole_chunks", "padded_chunk", "under_one_chunk", "shipped_lengths",
        "two_windows", "two_slabs_a_head"])
def test_the_kernel_computes_the_recurrence_and_the_xla_form(t, chunk, sub, heads, d):
    args = inputs(t, h=heads, d=d, dv=d, seed=t)
    got = kernel(args, chunk, sub)
    assert got.shape == (2, t, heads, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, recurrence(*args), rtol=0, atol=ATOL)
    np.testing.assert_allclose(got, xla(args, chunk, sub), rtol=0, atol=ATOL)


def test_a_decay_whose_inverse_overflows_float32_gives_no_inf_in_the_kernel():
    """As the XLA form's test: over a chunk of 16 some channel's cumulative
    decay passes e^-89, so an e^{-G} anywhere would be inf."""
    args = inputs(50, strongest=3.5, seed=1)
    assert float(jnp.max(-jnp.sum(args[3][:, :16], axis=1))) > 89.0
    got = kernel(args, 16, 8)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, recurrence(*args), rtol=0, atol=ATOL)


def test_bfloat16_operands_agree_with_the_xla_form():
    """Both forms take the off-diagonal blocks and the state's products in
    bfloat16 with float32 accumulation and everything else in float32; they
    round different float32 values (the prefix sums' order) to bfloat16, so
    they agree to a few bfloat16 roundings of the output's scale (2^-8 is one),
    not bit for bit."""
    args = inputs(96, seed=5, dtype=jnp.bfloat16)
    got, want = kernel(args, 32, 8), xla(args, 32, 8)
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 4 * 2.0 ** -8 * scale
    # and neither is further from the float32 recurrence than a few roundings
    exact = recurrence(*(x.astype(jnp.float32) for x in args))
    for out in (got, want):
        assert float(jnp.max(jnp.abs(out - exact))) <= 8 * 2.0 ** -8 * scale


@pytest.fixture
def on_tpu_in_interpreter(monkeypatch):
    """The dispatcher as it decides on the TPU, with the kernel run by the
    interpreter (the CPU cannot lower a Mosaic call)."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    real = dk.delta_rule_fwd
    monkeypatch.setattr(dk, "delta_rule_fwd",
                        lambda *a, **kw: real(*a, interpret=True, **kw))


def test_the_gradient_through_the_dispatcher_is_the_xla_forms(on_tpu_in_interpreter):
    """The kernel is forward-only; its custom VJP is the XLA form's, so a
    gradient through the dispatcher on the kernel's path is the XLA form's."""
    args = inputs(40, seed=3)
    assert dr.path_for(*args) == "pallas"
    w = jax.random.normal(jax.random.key(9), (2, 40, 2, 128))

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                argnums=(0, 1, 2, 3, 4)))(*args)

    got = grads(dr.chunked_delta_rule)
    want = grads(lambda *a: dr._xla_form(*a, dr.CHUNK, dr.SUB))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def _shapes(d=128, dv=128, dtype=jnp.bfloat16, b=16, t=256, h=4):
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((b, t, h, d), dtype),
            jax.ShapeDtypeStruct((b, t, h, d), dtype),
            jax.ShapeDtypeStruct((b, t, h, dv), dtype),
            jax.ShapeDtypeStruct((b, t, h, d), f32),
            jax.ShapeDtypeStruct((b, t, h), f32))


def _mesh(**axes):
    from dcr_tpu.core.config import MeshConfig
    from dcr_tpu.parallel import mesh as pmesh

    return pmesh.make_mesh(MeshConfig(**axes), devices=jax.devices()[:4])


def test_the_path_by_platform_width_and_mesh(monkeypatch):
    """The kernel on the TPU at widths of whole 128-lane slabs, for operands
    it takes and one device's share a mesh divides; XLA otherwise."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: False)
    assert dr.path_for(*_shapes()) == "xla"                     # off the TPU
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert dr.path_for(*_shapes()) == "pallas"
    assert dr.path_for(*_shapes(dtype=jnp.float32)) == "pallas"
    assert dr.path_for(*_shapes(d=256, dv=256)) == "pallas"     # two slabs a head
    assert dr.path_for(*_shapes(d=64, dv=64)) == "xla"          # half a slab
    assert dr.path_for(*_shapes(dv=64)) == "xla"
    assert dr.path_for(*_shapes(d=16, dv=16, b=3, t=16)) == "xla"   # the tiny tower's
    assert dr.path_for(*_shapes(dtype=jnp.float16)) == "xla"
    assert dr.path_for(*_shapes(t=2 ** 16)) == "xla"            # over VMEM
    assert dr.path_for(*_shapes(), mesh=_mesh(data=4)) == "pallas"
    assert dr.path_for(*_shapes(), mesh=_mesh(data=2, tensor=2)) == "pallas"
    assert dr.path_for(*_shapes(b=6), mesh=_mesh(data=4)) == "xla"
    assert dr.path_for(*_shapes(h=3), mesh=_mesh(data=2, tensor=2)) == "xla"


def test_heads_a_program_divide_the_heads_and_fit_vmem():
    assert dk.HEADS == 4
    assert dk.heads_per_program(64, 256, 128, 128, 2) == 4      # the Solar cell's site
    assert dk.heads_per_program(6, 256, 128, 128, 2) == 3
    assert dk.heads_per_program(5, 256, 128, 128, 2) == 1
    # one head's blocks fit at 3,968 positions, four heads' do not
    assert dk.heads_per_program(64, 3968, 128, 128, 2) == 1
    assert dk.resident_bytes(3968, 128, 128, 2) <= dk.RESIDENT_MAX_BYTES


@pytest.mark.parametrize("tpu", [True, False], ids=["tpu", "cpu"])
def test_a_site_counts_itself_once_a_trace_by_its_path(monkeypatch, tpu):
    """`delta_rule/sites_total/<path>` beside `delta_rule/sites_total`, once a
    trace each; the chunks and the chunk length are the taken path's."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: tpu)
    monkeypatch.setattr(dr, "CHUNK", 16)
    monkeypatch.setattr(dr, "SUB", 8)
    reg = tracing.registry()
    reg.reset("delta_rule/")
    # traced, never lowered (a new function: jax keeps a trace by the function)
    jax.eval_shape(lambda *a: dr.chunked_delta_rule(*a), *_shapes(t=200))
    path, chunk = ("pallas", dk.CHUNK) if tpu else ("xla", 16)
    assert reg.counters("delta_rule/") == {
        "delta_rule/sites_total": 1, f"delta_rule/sites_total/{path}": 1,
        "delta_rule/chunks_total": -(-200 // chunk)}
    assert reg.gauge("delta_rule/chunk").value == chunk


@pytest.mark.parametrize("axes", [{"data": 4}, {"data": 2, "tensor": 2}],
                         ids=["data4", "data2_tensor2"])
def test_the_kernel_runs_per_device_under_a_mesh(on_tpu_in_interpreter, axes):
    """A Mosaic call is not partitioned automatically: over a mesh each device
    runs the kernel on its own rows and heads, and the XLA form's numbers come
    out."""
    from dcr_tpu.parallel import mesh as pmesh

    mesh = _mesh(**axes)
    args = inputs(24, b=4, h=2, seed=7)
    placed = [jax.device_put(x, pmesh.batch_sharding(mesh)) for x in args]
    assert dr.path_for(*placed, mesh=mesh) == "pallas"
    got = jax.jit(lambda *a: dr.chunked_delta_rule(*a, mesh=mesh))(*placed)
    np.testing.assert_allclose(got, xla(args, dr.CHUNK, dr.SUB), rtol=0, atol=ATOL)
