import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcr_tpu.ops import attention as A
from dcr_tpu.ops import flash_attention as FA


def _rand_qkv(key, b=2, sq=512, sk=256, h=2, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, h, d), dtype)
    k = jax.random.normal(kk, (b, sk, h, d), dtype)
    v = jax.random.normal(kv, (b, sk, h, d), dtype)
    return q, k, v


def test_supported_shapes():
    q, k, v = _rand_qkv(jax.random.key(0))
    assert FA.supported(q, k, v)
    q2, k2, v2 = _rand_qkv(jax.random.key(0), sq=100)
    assert not FA.supported(q2, k2, v2)
    q3, k3, v3 = _rand_qkv(jax.random.key(0), sk=77)
    assert not FA.supported(q3, k3, v3)  # CLIP cross-attn length falls back to XLA
    q4, k4, v4 = _rand_qkv(jax.random.key(0), d=48)
    assert not FA.supported(q4, k4, v4)
    wide = _site((1, 256, 2, 256))       # a head is a slab or half of one:
    assert not FA.supported(wide, wide, wide)     # no second layout for D = 256
    many = _site((1, 256, 129, 64))      # a lane of lse a head
    assert not FA.supported(many, many, many)
    long_q = _site((1, 32768, 2, 64))    # a batch row's lse is resident
    short_k = _site((1, 256, 2, 64))
    assert not FA.supported(long_q, short_k, short_k)
    at_768px = _site((1, 9216, 5, 64), jnp.bfloat16)
    assert FA.supported(at_768px, at_768px, at_768px)


def _site(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_dispatch_policy():
    """should_use = capability AND the measured win on the v5e: at least
    FLASH_MIN_SEQ keys, and f32 logits past what XLA keeps on the chip."""
    heads = FA.FLASH_MIN_LOGITS_BYTES // (4 * FA.FLASH_MIN_SEQ ** 2)
    short = _site((64, FA.FLASH_MIN_SEQ // 2, heads, 64))
    assert FA.supported(short, short, short)
    assert not FA.should_use(short, short, short)       # too few keys
    small = _site((1, FA.FLASH_MIN_SEQ, heads, 64))
    assert FA.supported(small, small, small)
    assert not FA.should_use(small, small, small)       # logits stay on chip
    large = _site((1, FA.FLASH_MIN_SEQ, heads + 1, 64))
    assert FA.should_use(large, large, large)
    ragged = _site((1, FA.FLASH_MIN_SEQ + 64, 4 * heads, 64))
    assert not FA.should_use(ragged, ragged, ragged)    # policy never widens capability


# SD-2.1's self-attention sites in the benchmark's four cells (rows, tokens,
# heads, head dim), and the path each takes as shipped (PERF.md section 5)
SD21_SITES = [
    ((20, 1024, 5, 64), jnp.float32, True),      # sample-256, top level
    ((2, 1024, 10, 64), jnp.float32, False),     # sample-512, second level
    ((20, 256, 10, 64), jnp.float32, False),     # sample-256, second level
    ((2, 256, 20, 64), jnp.float32, False),      # sample-512, third level
    ((2, 4096, 5, 64), jnp.float32, True),       # sample-512, top level
    ((16, 1024, 5, 64), jnp.bfloat16, True),     # train-256, top level
    ((16, 256, 10, 64), jnp.bfloat16, False),    # train-256, second level
]


@pytest.mark.parametrize("shape,dtype,flash", SD21_SITES, ids=[
    f"{'x'.join(map(str, shape))}-{jnp.dtype(dtype).name}"
    for shape, dtype, _ in SD21_SITES])
def test_dispatch_for_sd21_sites(monkeypatch, shape, dtype, flash):
    x = _site(shape, dtype)
    assert FA.should_use(x, x, x) is flash
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    assert A.path_for(x, x, x) == ("flash" if flash else "xla")
    assert A.path_for(x, x, x, use_flash=False) == "xla"
    assert A.path_for(x, x, x, mask=_site((1, 1, 1, shape[1]), jnp.bool_)) == "xla"
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    assert A.path_for(x, x, x) == "xla"


def test_sites_counter_counts_each_path_once_a_trace(monkeypatch):
    """`attention/sites_total/<path>` is counted while tracing: a program
    with one site on the kernel and one on XLA says one and one."""
    from dcr_tpu.core import tracing

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    tracing.registry().reset("attention/")

    def two_sites(big, small):
        return (A.dot_product_attention(big, big, big).sum()
                + A.dot_product_attention(small, small, small).sum())

    big, small = _site((1, 1024, 32, 64)), _site((1, 128, 2, 64))
    jax.eval_shape(two_sites, big, small)     # traced, never lowered: no chip
    assert tracing.registry().counters("attention/") == {
        "attention/sites_total/flash": 1, "attention/sites_total/xla": 1}


def _mesh(**axes):
    from dcr_tpu.core.config import MeshConfig
    from dcr_tpu.parallel import mesh as pmesh

    return pmesh.make_mesh(MeshConfig(**axes), devices=jax.devices()[:4])


def test_policy_reads_one_devices_share_under_a_mesh(monkeypatch):
    """Over a mesh the dispatcher asks the policy about a device's rows and
    heads; a batch the mesh does not divide cannot be sharded, so XLA."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    rows64, rows16, rows6 = (_site((b, 1024, 5, 64), jnp.bfloat16)
                             for b in (64, 16, 6))
    assert A.path_for(rows64, rows64, rows64, mesh=_mesh(data=4)) == "flash"
    assert A.path_for(rows16, rows16, rows16) == "flash"
    assert A.path_for(rows16, rows16, rows16, mesh=_mesh(data=4)) == "xla"
    assert A.path_for(rows64, rows64, rows64,
                      mesh=_mesh(data=2, tensor=2)) == "xla"   # 5 heads / 2
    assert A.path_for(rows6, rows6, rows6, mesh=_mesh(data=4)) == "xla"


@pytest.mark.parametrize("axes", [{"data": 4}, {"data": 2, "fsdp": 2},
                                  {"data": 2, "tensor": 2}],
                         ids=["data4", "data2_fsdp2", "data2_tensor2"])
def test_kernel_runs_per_device_under_a_mesh(monkeypatch, axes):
    """A Mosaic kernel is never partitioned automatically ("wrap the call in
    a shard_map"): over a mesh each device runs it on its own rows and heads,
    forward and backward, nothing gathered, and XLA's numbers."""
    from dcr_tpu.parallel import mesh as pmesh

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(FA, "FLASH_MIN_SEQ", 256)
    monkeypatch.setattr(FA, "FLASH_MIN_LOGITS_BYTES", 0)
    kernel = FA.flash_attention
    monkeypatch.setattr(FA, "flash_attention",
                        lambda q, k, v: kernel(q, k, v, True))
    mesh = _mesh(**axes)
    keys = jax.random.split(jax.random.key(11), 4)
    q, k, v, g = (jax.device_put(jax.random.normal(key, (4, 256, 2, 64)),
                                 pmesh.batch_sharding(mesh)) for key in keys)

    def attend(path):
        def run(q, k, v, g):
            out, vjp = jax.vjp(path, q, k, v)
            return out, vjp(g)
        return jax.jit(run)

    on_mesh = attend(lambda *x: A.dot_product_attention(*x, mesh=mesh))
    assert A.path_for(q, k, v, mesh=mesh) == "flash"
    out, grads = on_mesh(q, k, v, g)
    ref, ref_grads = attend(lambda *x: A._xla_attention(*x, None))(q, k, v, g)
    for a, b in zip((out, *grads), (ref, *ref_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
    # (the backward's psum over the mesh's size-1 axes stays in the CPU's text
    # as an all-reduce over groups of one; the TPU's compiler drops it)
    text = on_mesh.lower(q, k, v, g).compile().as_text()
    assert "all-gather(" not in text and "all-to-all(" not in text


def test_block_resolution():
    """Explicit blocks win; defaults are the v5e's and clamp to divide the
    sequence lengths; float32 past 1,024 keys takes the block_q that fits."""
    assert FA._resolve_blocks(4096, 4096, 256, 128, 2) == (256, 128)
    assert FA._resolve_blocks(1024, 1024, None, None, 4) == (1024, 1024)
    assert FA._resolve_blocks(1024, 1024, None, None, 2) == (1024, 1024)
    assert FA._resolve_blocks(4096, 4096, None, None, 4) == (512, 1024)
    assert FA._resolve_blocks(4096, 4096, None, None, 2) == (1024, 1024)
    assert FA._resolve_blocks(256, 256, None, None, 4) == (256, 256)
    bq, bk = FA._resolve_blocks(384, 384, None, None, 2)  # 384 = 3*128
    assert 384 % bq == 0 and 384 % bk == 0


def test_flash_matches_xla_forward():
    q, k, v = _rand_qkv(jax.random.key(1))
    ref = A.dot_product_attention(q, k, v, use_flash=False)
    out = FA.flash_attention(q, k, v, True)  # interpret mode on CPU
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_matches_xla_self_attention_4096():
    """The 512px UNet shape the kernel exists for (S=4096)."""
    q, k, v = _rand_qkv(jax.random.key(2), b=1, sq=1024, sk=1024, h=1, d=64)
    ref = A.dot_product_attention(q, k, v, use_flash=False)
    out = FA.flash_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_bf16_close_to_f32():
    q, k, v = _rand_qkv(jax.random.key(3), dtype=jnp.bfloat16)
    ref = A.dot_product_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                  v.astype(jnp.float32), use_flash=False)
    out = FA.flash_attention(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_flash_gradients_match_xla():
    q, k, v = _rand_qkv(jax.random.key(4), b=1, sq=256, sk=128, h=1, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(FA.flash_attention(q, k, v, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(A.dot_product_attention(q, k, v, use_flash=False) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 6e-2)],
                         ids=["f32", "bf16"])
def test_flash_matches_xla_at_1024_keys_with_shipped_blocks(dtype, tol):
    """The shape the dispatch threshold moved onto the kernel (S = 1024, the
    256 px UNet's top level), forward AND gradients against _xla_attention,
    with the blocks _resolve_blocks ships for it."""
    assert FA._resolve_blocks(1024, 1024, None, None,
                              jnp.dtype(dtype).itemsize) == (1024, 1024)
    q, k, v = _rand_qkv(jax.random.key(8), b=1, sq=1024, sk=1024, h=2, d=64,
                        dtype=dtype)
    g = jax.random.normal(jax.random.key(9), q.shape, dtype)
    out, vjp = jax.vjp(lambda *x: FA.flash_attention(*x, True), q, k, v)
    ref, ref_vjp = jax.vjp(lambda *x: A._xla_attention(*x, None),
                           *(x.astype(jnp.float32) for x in (q, k, v)))
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)
    for a, b in zip(vjp(g), ref_vjp(g.astype(jnp.float32))):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), atol=tol, rtol=tol)


# heads to a 128-lane slab: H = 5 at D = 64 leaves the last slab half filled
# (its other half is padding, NaN under the interpreter), H = 1 is that slab
# alone, H = 2 one full slab, H = 3 at D = 128 a head a slab
SLAB_CASES = [(5, 64), (1, 64), (2, 64), (4, 64), (3, 128)]


def _logsumexp_by_head(q, k):
    logits = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / q.shape[-1] ** 0.5
    return jax.nn.logsumexp(logits, axis=-1)              # [B, Sq, H]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 6e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h,d", SLAB_CASES,
                         ids=[f"h{h}_d{d}" for h, d in SLAB_CASES])
def test_flash_matches_xla_by_slab_filling(h, d, dtype, tol):
    """Forward AND gradients against _xla_attention with Sq != Sk, for every
    way heads fill the slabs of [B, S, H*D]."""
    q, k, v = _rand_qkv(jax.random.key(20 + h), b=2, sq=256, sk=128, h=h, d=d,
                        dtype=dtype)
    g = jax.random.normal(jax.random.key(21), q.shape, dtype)
    out, vjp = jax.vjp(lambda *x: FA.flash_attention(*x, True), q, k, v)
    ref, ref_vjp = jax.vjp(lambda *x: A._xla_attention(*x, None),
                           *(x.astype(jnp.float32) for x in (q, k, v)))
    assert out.dtype == dtype and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)
    for a, b in zip(vjp(g), ref_vjp(g.astype(jnp.float32))):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), atol=tol, rtol=tol)


@pytest.mark.parametrize("h,d", SLAB_CASES,
                         ids=[f"h{h}_d{d}" for h, d in SLAB_CASES])
def test_lse_lane_h_is_head_hs_logsumexp(h, d):
    """The forward's second result is ONE [B, Sq, 128] f32 array: lane h holds
    head h's logsumexp, lanes from H on read 0 (the backward reads it as is)."""
    q, k, v = _rand_qkv(jax.random.key(30 + h), b=2, sq=256, sk=128, h=h, d=d)
    flat = lambda x: x.reshape(*x.shape[:2], h * d)
    out, lse = FA._flash_fwd(flat(q), flat(k), flat(v), d, interpret=True,
                             block_q=128, block_k=128)
    assert out.shape == (2, 256, h * d)
    assert lse.shape == (2, 256, FA.LANES) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse[..., :h]),
                               np.asarray(_logsumexp_by_head(q, k)),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(lse[..., h:]).any()


def test_nan_in_the_last_slabs_padding_does_not_reach_the_result():
    """320 lanes are 2.5 slabs: the kernels see lanes 320 to 383 of the last
    one as padding that may hold anything. The interpreter fills it with NaN
    (and starts every output as NaN); a product over the slab would carry it
    into the logits, a select does not. Forward, lse and all three gradients
    stay finite and equal to a run whose every lane is real (H = 6 with the
    sixth head cut off afterwards)."""
    from jax._src.pallas import primitives

    assert np.isnan(primitives.uninitialized_value((), jnp.float32))
    q6, k6, v6 = _rand_qkv(jax.random.key(40), b=1, sq=256, sk=256, h=6, d=64)
    g6 = jax.random.normal(jax.random.key(41), q6.shape)
    q, k, v, g = (x[:, :, :5] for x in (q6, k6, v6, g6))
    out, vjp = jax.vjp(lambda *x: FA.flash_attention(*x, True, 128, 128), q, k, v)
    out6, vjp6 = jax.vjp(lambda *x: FA.flash_attention(*x, True, 128, 128),
                         q6, k6, v6)
    for a, b in zip((out, *vjp(g)), (out6, *vjp6(g6))):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b[:, :, :5]))


def test_softmax_stability_large_logits():
    """Online softmax must survive logits that would overflow naive exp."""
    q, k, v = _rand_qkv(jax.random.key(5), b=1, sq=256, sk=128, h=1, d=64)
    q = q * 100.0
    out = FA.flash_attention(q, k, v, True)
    assert np.all(np.isfinite(np.asarray(out)))
    ref = A.dot_product_attention(q, k, v, use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_fused_backward_rectangular_and_bf16():
    """dK/dV kernel loops over query blocks (sq != sk) and bf16 grads stay
    close to the f32 XLA reference."""
    q, k, v = _rand_qkv(jax.random.key(7), b=1, sq=512, sk=256, h=2, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(FA.flash_attention(q, k, v, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(A.dot_product_attention(q, k, v, use_flash=False) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)

    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    gb = jax.grad(lambda *xs: jnp.sum(FA.flash_attention(*xs, True).astype(jnp.float32) ** 2),
                  argnums=(0, 1, 2))(qb, kb, vb)
    for a, b in zip(gb, gr):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32), np.asarray(b),
                                   atol=0.15, rtol=0.1)


def test_sweep_reduces_a_recorded_v5e_trace():
    """tools/sweep_flash.py splits a variant's runs into kernel and the rest
    by the events of a device trace: hold its reduction to the small v5e
    capture kept with the benchmark's tests (three runs of a bf16 forward at
    (2, 4096, 5, 64) and three of a plain product)."""
    from pathlib import Path

    from benchmark.lib import trace as tracelib
    from tools import sweep_flash

    trace = tracelib.read(Path(__file__).parent / "benchmark" / "data"
                          / "probe.xplane.pb")
    flash = sweep_flash.reduce_runs(trace, "flash")
    assert flash["runs"] == 3 and len(flash["kernel_ms"]) == 1
    kernel = sum(flash["kernel_ms"].values())
    assert 0.6 < kernel < flash["module_ms"] < 0.8
    assert abs(kernel + flash["rest_ms"] - flash["module_ms"]) < 0.01
    plain = sweep_flash.reduce_runs(trace, "mm")
    assert plain["kernel_ms"] == {} and plain["rest_ms"] > 0.2
    assert sweep_flash.reduce_runs(trace, "no_such_variant") is None
    assert sweep_flash.block_candidates(256, 4) == [(None, None)]
    assert (1024, 1024) not in sweep_flash.block_candidates(1024, 4)
    assert (512, 1024) not in sweep_flash.block_candidates(4096, 4)
    assert len(sweep_flash.block_candidates(4096, 2)) == 9
