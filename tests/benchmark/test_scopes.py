"""The scope path of a device op (ISSUE 34): `benchmark/lib/xplane.py` on a
hand-built `.xplane.pb` and on the recorded v5e probe, against JAX's own
`ProfileData`; the scope match on the tiny LongCat and openPangu towers' own
`op_name`s; the union of a `while` and its body; the breakdown's labels; the
idle gaps named by the program's spans; and the `encode_device_share.*`
readers, silent where there is nothing to read."""
import re
import struct

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import harness, trace as T, xplane
from tests.benchmark.test_trace import PROBE

MS = 1e6      # nanoseconds


# -- a serialized XSpace, by hand --------------------------------------------

def varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    """A varint for an int, a length-delimited field for bytes or str, a
    fixed64 for a float."""
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def msg(*fields: bytes) -> bytes:
    return b"".join(fields)


#: stat metadata of the device plane: a name each; 7's NAME is a path, as
#: XLA's trace keeps a string that repeats
STATS = {1: "tf_op", 2: "hlo_category", 3: "flops", 4: "offset", 5: "raw",
         7: "jit(encode)/tower/layers_1/moe/experts/while/body/dot_general:"}
OPS = [  # metadata id, name, tf_op (str, ref or None), offset_ps, duration_ps
    (11, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kOutput",
     "jit(encode)/tower/layers_0/mla/self_attn/q_a_proj/dot_general:", 0, 2_000_000_000),
    (12, "%while.3 = (s32[], /*index=1*/f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b",
     "jit(encode)/tower/layers_1/moe/experts/while:", 5_000_000_000, 3_000_000_000),
    (13, "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kOutput",
     ("ref", 7), 5_500_000_000, 1_000_000_000),
    (14, "%fusion.8 = f32[8]{0} fusion(f32[8]{0} %x), kind=kOutput",
     ("ref", 7), 7_000_000_000, 500_000_000),
    (15, "%copy.9 = f32[8]{0} copy(f32[8]{0} %x)", None, 9_500_000_000, 500_000_000),
]
LINE_NS = 1_000_000          # the XLA Ops line's timestamp


def stat(sid: int, value) -> bytes:
    if isinstance(value, tuple):
        return msg(field(1, sid), field(7, value[1]))
    num = {float: 2, str: 5, bytes: 6}.get(type(value), 4)
    return msg(field(1, sid), field(num, value))


def hand_built() -> bytes:
    metas = []
    for mid, name, tf_op, _, _ in OPS:
        stats = [stat(2, "convolution fusion"), stat(3, 2.5e9), stat(4, -7),
                 stat(5, b"\x00\x01")]
        if tf_op is not None:
            stats.append(stat(1, tf_op))
        metas.append(field(4, msg(field(1, mid), field(2, msg(
            field(1, mid), field(2, name), field(4, name.split(" ")[0][1:]),
            *[field(5, s) for s in stats])))))
    stat_meta = [field(5, msg(field(1, sid), field(2, msg(field(1, sid), field(2, n)))))
                 for sid, n in STATS.items()]
    events = [field(4, msg(field(1, mid), field(2, off), field(3, dur)))
              for mid, _, _, off, dur in OPS]
    ops = field(3, msg(field(1, 3), field(2, "XLA Ops"), field(3, LINE_NS), *events))
    modules = field(3, msg(field(1, 2), field(2, "XLA Modules"), field(3, LINE_NS),
                           field(4, msg(field(1, 20), field(2, 0),
                                        field(3, 10_000_000_000)))))
    module_meta = field(4, msg(field(1, 20), field(2, msg(field(1, 20),
                                                          field(2, "jit_encode(1)")))))
    device = msg(field(1, 2), field(2, "/device:TPU:0"), ops, modules,
                 *metas, module_meta, *stat_meta)
    host_events = [("bench/traced", 0, 10 * MS), ("dcr/precompute/load", 2 * MS, 4 * MS),
                   ("dcr/precompute/decode", 3 * MS, 1.5 * MS), ("other", 0, MS)]
    host = msg(field(1, 1), field(2, "/host:CPU"), field(3, msg(
        field(1, 7), field(2, "python3"), field(3, LINE_NS),
        *[field(4, msg(field(1, 30 + i), field(2, int(s * 1000)), field(3, int(d * 1000))))
          for i, (_, s, d) in enumerate(host_events)])),
        *[field(4, msg(field(1, 30 + i), field(2, msg(field(1, 30 + i), field(2, n)))))
          for i, (n, _, _) in enumerate(host_events)])
    return msg(field(1, host), field(1, device), field(2, "an error"))


def test_the_decoder_reads_planes_lines_metadata_and_stats():
    data = hand_built()
    planes = xplane.planes(data)
    assert [p.name for p in planes] == ["/host:CPU", "/device:TPU:0"]
    device = planes[1]
    assert [x.name for x in device.lines] == ["XLA Ops", "XLA Modules"]
    ops = device.lines[0]
    assert ops.timestamp_ns == LINE_NS
    assert ops.events == [(mid, off, dur) for mid, _, _, off, dur in OPS]
    meta = device.event_metadata[11]
    assert meta.name == OPS[0][1]
    assert device.stat(meta, "tf_op") == OPS[0][2]            # a str value
    assert device.stat(device.event_metadata[13], "tf_op") == STATS[7]   # a ref
    assert device.stat(meta, "flops") == 2.5e9                # a double
    assert device.stat(meta, "offset") == -7                  # a signed int64
    assert device.stat(meta, "raw") == b"\x00\x01"            # bytes
    assert device.stat(device.event_metadata[15], "tf_op") is None
    # a plane `want` refuses is skipped unread
    assert [p.name for p in xplane.planes(data, lambda n: n.startswith("/device"))] \
        == ["/device:TPU:0"]


def test_the_decoder_agrees_with_jax_on_the_hand_built_file():
    """JAX's own reader of the same bytes is the witness: every event's name,
    start and duration."""
    data = hand_built()
    theirs = jax.profiler.ProfileData.from_serialized_xspace(data)
    for plane in xplane.planes(data):
        other = next(p for p in theirs.planes if p.name == plane.name)
        for line, their_line in zip(plane.lines, other.lines):
            mine = [(plane.event_metadata[m].name, (line.timestamp_ns * 1000 + o) // 1000,
                     d // 1000) for m, o, d in line.events]
            assert mine == [(e.name, e.start_ns, e.duration_ns)
                            for e in their_line.events]


def test_the_decoder_agrees_with_jax_on_the_recorded_probe():
    theirs = jax.profiler.ProfileData.from_file(str(PROBE))
    planes = xplane.read(PROBE)
    assert len(planes) == len(list(theirs.planes))
    count = 0
    for plane in planes:
        other = next(p for p in theirs.planes if p.name == plane.name)
        for line, their_line in zip(plane.lines, other.lines):
            assert line.name == their_line.name
            mine = [(plane.event_metadata[m].name, (line.timestamp_ns * 1000 + o) // 1000,
                     d // 1000) for m, o, d in line.events]
            assert mine == [(e.name, e.start_ns, e.duration_ns)
                            for e in their_line.events]
            count += len(mine)
    assert count == 498
    tr = T.read(PROBE)
    paths = tr.scopes[0]
    assert len(paths) == len(tr.ops[0])
    by_name = dict(zip((n.split(" ")[0] for n, _, _ in tr.ops[0]), paths))
    assert by_name["%fusion"] == "jit(mm)/dot_general"
    assert by_name["%flash.1"] == "jit(flash)/pallas_call"
    assert by_name["%copy-start"] == ""            # an op without a path


def test_read_keeps_a_path_an_op_and_the_programs_spans(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(hand_built())
    tr = T.read(path)
    assert [n for n, _, _ in tr.ops[0]] == [name for _, name, _, _, _ in OPS]
    assert tr.scopes[0] == [
        "jit(encode)/tower/layers_0/mla/self_attn/q_a_proj/dot_general",
        "jit(encode)/tower/layers_1/moe/experts/while",
        STATS[7][:-1], STATS[7][:-1], ""]
    assert [n for n, _, _ in tr.host] == [
        "bench/traced", "dcr/precompute/load", "dcr/precompute/decode"]
    # ops that do not pair with the file's line get no path at all
    shorter = {0: tr.ops[0][:-1]}
    assert T.scope_paths(path, shorter) == {0: [""] * (len(OPS) - 1)}
    renamed = {0: [("%x = f32[] x()", s, d) for _, s, d in tr.ops[0]]}
    assert T.scope_paths(path, renamed) == {0: [""] * len(OPS)}


# -- what a scope holds -------------------------------------------------------

def hand_made() -> T.Trace:
    """The hand-built file's timeline in milliseconds: MLA 0-2, the experts'
    `while` 5-8 with two body ops inside it, an op without a path 9.5-10."""
    ops = [(name, off / 1e3, dur / 1e3) for _, name, _, off, dur in OPS]
    paths = ["jit(encode)/tower/layers_0/mla/self_attn/q_a_proj/dot_general",
             "jit(encode)/tower/layers_1/moe/experts/while",
             STATS[7][:-1], STATS[7][:-1], ""]
    return T.Trace(ops={0: ops}, modules={0: []},
                   host=[("bench/traced", 0.0, 10 * MS)], scopes={0: paths})


def test_a_while_and_its_body_count_once():
    tr = hand_made()
    assert T.scoped_seconds(tr, lambda p: T.in_scope(p, "moe/experts")) == (
        pytest.approx(3e-3), pytest.approx(5.5e-3))
    assert T.scope_share(tr, "moe/experts") == pytest.approx(100 * 3 / 5.5)
    assert T.scope_share(tr, "mla") == pytest.approx(100 * 2 / 5.5)
    assert T.unscoped_share(tr) == pytest.approx(100 * 0.5 / 5.5)
    assert T.scope_share(tr, "moe/router") is None           # no op in it
    tr.scopes[0][1] = ""           # a `while` without a path, its body with one
    assert T.unscoped_share(tr) == pytest.approx(100 * 2.0 / 5.5)
    assert T.scope_share(tr, "moe/experts") == pytest.approx(100 * 1.5 / 5.5)
    tr.scopes = {0: [""] * 5}
    assert T.scope_share(tr, "mla") is None and T.unscoped_share(tr) is None


def test_whole_components_in_order():
    path = "jit(f)/tower/layers_1/moe/experts/while/body/dot_general"
    assert T.in_scope(path, "moe/experts") and T.in_scope(path, "experts")
    assert T.in_scope(path, "moe/experts/while/body")
    assert not T.in_scope(path, "moe/expert")
    assert not T.in_scope(path, "experts/moe")
    assert not T.in_scope(path, "dot_general")           # the op's kind
    assert not T.in_scope("jit(f)/moe/experts_x/dot_general", "moe/experts")
    assert T.in_scope("jit(f)/a/add;jit(f)/moe/experts/mul", "moe/experts")
    assert T.scope_name(path) == "experts"
    assert T.scope_name("jit(f)/mla/self_attn/attention_xla/vmap(BNTS)/dot_general") \
        == "attention_xla"
    assert T.scope_name("jit(mm)/dot_general") == ""
    # LongCat's attention in row groups, as its trace names it on the chip
    assert T.scope_name("jit(f)/mla/mla_0/attention_xla/while/body/closed_call/"
                        "dot_general") == "attention_xla"


def test_the_breakdown_names_an_op_by_its_scope_and_leaves_containers_out():
    top = dict(T.top_ops(hand_made()))
    assert top == {"q_a_proj:fusion:kOutput": pytest.approx(2e-3),
                   "experts:fusion:kOutput": pytest.approx(1.5e-3),
                   "copy": pytest.approx(0.5e-3)}
    assert T.op_kind(OPS[1][1]) == ("while", "while")


def test_an_idle_gap_goes_to_the_innermost_program_span():
    tr = hand_made()
    tr.host += [("dcr/precompute/load", 2 * MS, 3.5 * MS),
                ("dcr/precompute/decode", 2.5 * MS, 2 * MS),
                ("bench/unit", 8.5 * MS, 1.5 * MS)]
    # gaps: 2-5 ms (load holds decode, which overlaps 2 of the 3 ms) and
    # 8-9.5 ms (the unit's span overlaps 1 ms; 0.5 ms before it)
    assert dict(T.attribute_gaps(tr)) == {"precompute/decode": pytest.approx(3e-3),
                                          "unit": pytest.approx(1.5e-3)}
    tr.host = [e for e in tr.host if e[0] != "dcr/precompute/decode"]
    assert dict(T.attribute_gaps(tr))["precompute/load"] == pytest.approx(3e-3)
    tr.host = [("bench/traced", 0.0, 10 * MS)]
    assert dict(T.attribute_gaps(tr)) == {"unattributed": pytest.approx(4.5e-3)}


# -- the towers' own paths ----------------------------------------------------

def tower_op_names(tower_name: str) -> set[str]:
    """Every `op_name` of the tiny tower's compiled program: what the
    profiler's `tf_op` carries on the chip."""
    from dcr_tpu.core.config import (LongcatFlashConfig, ModelConfig,
                                     OpenPanguUltraMoEConfig)
    from dcr_tpu.models.text_tower import build_text_tower, init_text_tower

    m = ModelConfig.tiny()
    m.text_tower = tower_name
    if tower_name == "longcat_flash":
        m.longcat = LongcatFlashConfig.tiny()
    else:
        m.openpangu = OpenPanguUltraMoEConfig.tiny()
        m.openpangu.first_k_dense_replace = 1
    m.text_vocab_size, m.text_max_length = 64, 16
    tower = build_text_tower(m)
    params = init_text_tower(m, jax.random.key(0), tower)
    text = jax.jit(lambda p, i: tower.apply({"params": p}, i)).lower(
        params, jnp.ones((2, 16), jnp.int32)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("tower_name", ["longcat_flash", "openpangu_ultra_moe"])
def test_the_scopes_select_their_part_of_each_tower_and_nothing_else(tower_name):
    names = tower_op_names(tower_name)
    picked = {scope: {n for n in names if T.in_scope(n, scope)}
              for scope in ("mla", "moe/experts")}
    assert picked["mla"] == {n for n in names if "/mla/" in n}
    assert picked["moe/experts"] == {n for n in names if "/moe/experts/" in n}
    assert not picked["mla"] & picked["moe/experts"]
    assert any("attention_xla" in n for n in picked["mla"])
    assert any("/while/body/" in n for n in picked["moe/experts"])
    others = ("/ffn/", "/moe/router/", "/moe/dispatch/", "/moe/combine/",
              "/moe/shared/", "/moe/zero/", "tower/embed", "tower/ctx_proj")
    for n in picked["mla"] | picked["moe/experts"]:
        assert not any(other in n for other in others), n
    assert not any(T.in_scope(n, "moe/expert") for n in names)
    layers = {re.search(r"/(layers_\d+)/", n).group(1) for n in picked["moe/experts"]}
    dense = 1 if tower_name == "openpangu_ultra_moe" else 0
    assert "layers_0" not in layers if dense else "layers_0" in layers
    assert {T.scope_name(n) for n in picked["moe/experts"]} == {"experts"}


# -- the readers ---------------------------------------------------------------

def a_run(part: str, trace, peaks=harness.load_peaks()["tpu v5 lite"]):
    cell = harness.Cell("c", 1, "cfg", {}, {}, [], [], harness.ROOT)
    return harness.Run(cell, peaks, harness.Window(), {}, {}, trace, part)


@pytest.mark.parametrize("part, scope, share", [
    ("attention", "mla", 100 * 2 / 5.5), ("experts", "moe/experts", 100 * 3 / 5.5)])
def test_the_encode_device_shares(part, scope, share):
    reader = harness.load_module("metrics", "encode_device_share")
    assert reader.scope_of(a_run(part, None)) == scope
    assert reader.read(a_run(part, hand_made())) == pytest.approx(share)


@pytest.mark.parametrize("part", ["attention", "experts"])
def test_the_encode_device_shares_read_nothing_where_there_is_nothing(part):
    reader = harness.load_module("metrics", "encode_device_share")
    assert reader.read(a_run(part, None)) is None                 # no trace
    tr = hand_made()
    assert reader.read(a_run(part, tr, peaks=None)) is None      # off the chip
    tr.scopes = {0: [""] * len(tr.ops[0])}
    assert reader.read(a_run(part, tr)) is None                  # no path
    tr.scopes = {0: ["jit(f)/tower/norm/mul"] * len(tr.ops[0])}
    assert reader.read(a_run(part, tr)) is None                  # none in scope
