"""The dispatcher's XLA path in row groups (PR 33): a site whose float32
logits would not stay on the chip runs as groups of rows that each do. On the
CPU at tiny sizes, the floor handed in so that tiny shapes cross it: a cut site
equals the whole one forward and in its gradients, the counters say what was
cut, under the floor the lowered program is the bare call's, and a
data-parallel tower cuts every device's own share without gathering q, k or v.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcr_tpu.core import tracing
from dcr_tpu.core.config import MeshConfig
from dcr_tpu.models.text_tower import build_text_tower, init_text_tower
from dcr_tpu.ops import attention as A
from dcr_tpu.ops import flash_attention as fa
from dcr_tpu.parallel import mesh as pmesh

MIB = 2**20


def logits_bytes(rows: int, heads: int, s: int) -> int:
    return 4 * rows * heads * s * s


def operands(b, s, h, d, dv, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, (b, s, h, w), dtype)
            for k, w in zip(keys, (d, d, dv, dv))]


def causal(s):
    return jnp.tril(jnp.ones((s, s), bool))[None, None]


def some_mask(shape, seed=7):
    """True with probability 0.7, the diagonal always: no row is all False."""
    s = shape[-1]
    return (jax.random.bernoulli(jax.random.key(seed), 0.7, shape)
            | jnp.eye(s, dtype=bool)[None, None])


# name: (B, S, H, D, Dv), the mask, the floor in rows' worth of logits (a
# fraction: less than one row), the group that follows
CASES = {
    "causal_latent": ((8, 16, 4, 24, 16), lambda: causal(16), 2, (2, 4)),
    "no_mask": ((8, 16, 4, 16, 16), lambda: None, 4, (4, 4)),
    "prime_rows": ((7, 16, 4, 24, 16), lambda: causal(16), 3, (1, 4)),
    "one_row_over": ((2, 16, 6, 24, 16), lambda: causal(16), 0.5, (1, 3)),
    "mask_a_row": ((4, 16, 2, 8, 8), lambda: some_mask((4, 1, 16, 16)), 1, (1, 2)),
    "mask_a_head": ((2, 16, 4, 8, 8), lambda: some_mask((2, 4, 16, 16)), 0.5, (1, 2)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_a_cut_site_equals_the_whole_one_forward_and_in_its_gradients(case, dtype):
    (b, s, h, d, dv), make_mask, rows, group = CASES[case]
    floor = int(rows * logits_bytes(1, h, s))
    assert A._group_of(b, h, s, s, floor) == group
    q, k, v, g = operands(b, s, h, d, dv, dtype)
    mask = make_mask()

    def run(floor):
        def site(q, k, v):
            return A._xla_attention(q, k, v, mask, floor=floor)
        out, vjp = jax.vjp(jax.jit(site), q, k, v)
        return out, vjp(g)

    whole, whole_grads = run(math.inf)
    cut, cut_grads = run(floor)
    assert cut.shape == (b, s, h, dv) and cut.dtype == dtype
    # the same call on the same values, row for row: equal, not close
    np.testing.assert_array_equal(np.asarray(cut, np.float32),
                                  np.asarray(whole, np.float32))
    for got, want in zip(cut_grads, whole_grads):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=0, atol=1e-6 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("shape, floor, group", [
    # the two towers' sites, a row 32 and 16 MiB of logits
    ((16, 128, 256, 256), fa.FLASH_MIN_LOGITS_BYTES, (2, 128)),
    ((16, 64, 256, 256), fa.FLASH_MIN_LOGITS_BYTES, (4, 64)),
    # the largest sites an SD cell leaves on XLA: whole
    ((2, 10, 1024, 1024), fa.FLASH_MIN_LOGITS_BYTES, (2, 10)),
    ((16, 1, 1024, 1024), fa.FLASH_MIN_LOGITS_BYTES, (16, 1)),
    ((20, 10, 256, 256), fa.FLASH_MIN_LOGITS_BYTES, (20, 10)),
    # exactly the floor is under it
    ((7, 4, 1024, 1024), 112 * MIB, (7, 4)),
    # a masked 1,024-token site of 128 heads: one row is 512 MiB
    ((2, 128, 1024, 1024), fa.FLASH_MIN_LOGITS_BYTES, (1, 16)),
    # one HEAD over the floor: a head a group, nothing smaller to cut
    ((2, 3, 8192, 8192), fa.FLASH_MIN_LOGITS_BYTES, (1, 1)),
    ((1, 1, 8192, 8192), fa.FLASH_MIN_LOGITS_BYTES, (1, 1)),
])
def test_the_group_is_the_largest_divisor_under_the_floor(shape, floor, group):
    b, h, sq, sk = shape
    assert A._group_of(b, h, sq, sk, floor) == group
    rows, heads = group
    assert b % rows == 0 and h % heads == 0


def counters():
    got = tracing.registry().counters("attention/")
    return {name: got.get(f"attention/{name}", 0) for name in
            ("sites_total/xla", "sites_total/flash", "xla_row_groups_total")}


@pytest.mark.parametrize("on_tpu, rows_under_floor, groups", [
    (True, 2, 4), (True, 0.5, 16), (True, 8, 0),
    # the floor is the TPU's: off it the dispatcher leaves every site whole
    (False, 2, 0)])
def test_the_counter_counts_the_groups_and_the_site_still_once(
        monkeypatch, on_tpu, rows_under_floor, groups):
    b, s, h = 8, 16, 4
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(fa, "FLASH_MIN_LOGITS_BYTES",
                        int(rows_under_floor * logits_bytes(1, h, s)))
    q, k, v, _ = operands(b, s, h, 24, 16, jnp.float32)
    before = counters()

    def site(q, k, v):
        return A.dot_product_attention(q, k, v, mask=causal(s))
    text = jax.jit(site).lower(q, k, v).as_text()        # a trace, no run
    assert ("stablehlo.while" in text) == (groups > 0)
    after = counters()
    assert after["sites_total/xla"] - before["sites_total/xla"] == 1
    assert after["sites_total/flash"] == before["sites_total/flash"]
    assert after["xla_row_groups_total"] - before["xla_row_groups_total"] == groups


@pytest.mark.parametrize("latent", [True, False], ids=["masked_latent", "plain"])
def test_under_the_floor_the_lowered_program_is_the_bare_calls(latent):
    """What `_xla_attention` was before PR 33, written out: every site under
    the floor must lower to the same StableHLO, text for text."""
    b, s, h, d = 4, 32, 4, 24
    dv = 16 if latent else d
    q, k, v, _ = operands(b, s, h, d, dv, jnp.bfloat16)
    mask = causal(s) if latent else None

    def before(q, k, v):
        with jax.named_scope("attention_xla"):
            if dv < d:
                v = jnp.pad(v, ((0, 0),) * 3 + ((0, d - dv),))
            return jax.nn.dot_product_attention(q, k, v, mask=mask)[..., :dv]

    def site(q, k, v):
        return A._xla_attention(q, k, v, mask)

    before.__name__ = "site"
    text = jax.jit(site).lower(q, k, v).as_text()
    assert text == jax.jit(before).lower(q, k, v).as_text()
    assert "while" not in text
    # and over the floor it is another program: the loop over the groups
    cut = jax.jit(lambda q, k, v: A._xla_attention(
        q, k, v, mask, floor=logits_bytes(2, h, s))).lower(q, k, v).as_text()
    assert "stablehlo.while" in cut


def tiny_tower_config():
    from dcr_tpu.core.config import ModelConfig, OpenPanguUltraMoEConfig
    m = ModelConfig.tiny()
    m.text_tower, m.openpangu = "openpangu_ultra_moe", OpenPanguUltraMoEConfig.tiny()
    m.text_vocab_size, m.text_max_length = 64, 16
    return m


@pytest.mark.parametrize("ways, tensor", [(8, 1), (4, 2)],
                         ids=["data8", "data4_tensor2"])
def test_a_data_parallel_tower_cuts_each_devices_own_share(monkeypatch, ways, tensor):
    """A tower over a mesh of 8 virtual devices, 4 rows (two heads) a device,
    the floor at one row's logits on ONE device: every site is cut into the
    groups of a device's share, and the compiled program moves no q, k or v
    between devices to form them."""
    m = tiny_tower_config()
    c = m.openpangu
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    mesh = pmesh.make_mesh(MeshConfig(data=ways, tensor=tensor))
    tower = build_text_tower(m, jnp.float32, mesh=mesh)
    params = jax.eval_shape(
        lambda: init_text_tower(m, jax.random.key(0), tower))
    rows = 4 * ways
    ids = jax.ShapeDtypeStruct((rows, m.text_max_length), jnp.int32,
                               sharding=pmesh.batch_sharding(mesh))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=pmesh.replicated(mesh)), params)

    def compiled_text():
        def encode(params, ids):    # a new function: jit keeps a trace by it
            return tower.apply({"params": params}, ids).last_hidden_state

        before = counters()
        text = jax.jit(encode).lower(params, ids).compile().as_text()
        after = counters()
        return text, {k: after[k] - before[k] for k in after}

    whole, counted = compiled_text()
    assert counted == {"sites_total/xla": c.num_hidden_layers,
                       "sites_total/flash": 0, "xla_row_groups_total": 0}
    heads = c.num_attention_heads
    assert heads % tensor == 0
    monkeypatch.setattr(fa, "FLASH_MIN_LOGITS_BYTES", logits_bytes(
        1, heads // tensor, m.text_max_length))
    cut, counted = compiled_text()
    # 4 rows a device, a row a group: 4 groups a site, whatever the mesh
    assert counted["xla_row_groups_total"] == 4 * c.num_hidden_layers
    assert counted["sites_total/xla"] == c.num_hidden_layers
    assert "while" in cut
    for collective in ("all-gather", "all-to-all", "collective-permute"):
        assert cut.count(collective) == whole.count(collective), collective


@pytest.mark.parametrize("text, site, kernel", [
    ("20x1024x5x64:float32:fwd",
     ((20, 1024, 5, 64), "float32", False, None, False), True),
    ("16x256x128x192:128:causal:bfloat16:fwd",
     ((16, 256, 128, 192), "bfloat16", False, 128, True), False),
    ("16x1024x5x64:causal:bfloat16:fwdbwd",
     ((16, 1024, 5, 64), "bfloat16", True, None, True), False),
    ("2x256x4x128:64:float32:fwd",
     ((2, 256, 4, 128), "float32", False, 64, False), False),
])
def test_the_sweeps_site_form_takes_a_v_width_and_a_causal_mask(text, site, kernel):
    """tools/sweep_flash.py: a latent or masked site runs XLA's two variants
    only, and its tag tells it from the plain site of the same shape."""
    from tools import sweep_flash
    got = sweep_flash.parse_site(text)
    assert tuple(got) == site
    assert sweep_flash.kernel_takes(got) == kernel
    plain = sweep_flash.Site(got.shape, got.dtype, got.differentiated)
    tags = {sweep_flash.tag_of(s, p) for s in (got, plain)
            for p in ("xla", "xla_grouped", (None, None))}
    assert len(tags) == (3 if got == plain else 6)


@pytest.mark.parametrize("rows_under_floor, group", [
    (math.inf, (4, 8)), (2, (2, 8)), (0.5, (1, 4)),
    # never fewer heads than share one key/value head
    (0.25, (1, 4))])
def test_grouped_key_value_heads_whole_by_rows_and_by_heads(rows_under_floor, group):
    """8 query heads on 2 key/value heads, query head h reading k/v head
    h // 4: the site whole, in row groups and in head groups equals
    attention over k and v repeated to every query head."""
    b, s, h, kv, d = 4, 16, 8, 2, 8
    floor = rows_under_floor * logits_bytes(1, h, s)
    assert A._group_of(b, h, s, s, floor, kv) == group
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (b, s, h, d))
    k, v = (jax.random.normal(key, (b, s, kv, d)) for key in keys[1:])
    got = jax.jit(lambda q, k, v: A._xla_attention(q, k, v, causal(s), floor=floor))(q, k, v)
    want = A._xla_attention(q, jnp.repeat(k, h // kv, axis=2),
                            jnp.repeat(v, h // kv, axis=2), causal(s), floor=math.inf)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_grouped_key_value_heads_take_the_xla_path(monkeypatch):
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((8, 1024, 8, 64), jnp.bfloat16)
    assert A.path_for(q, q, q) == "flash"               # 256 MiB of logits
    kv = jax.ShapeDtypeStruct((8, 1024, 2, 64), jnp.bfloat16)
    assert A.path_for(q, kv, kv) == "xla"
