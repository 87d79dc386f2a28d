"""One span path from the program to the profiler (PR 26): `tracing.span` on
`time.perf_counter()` with a bounded in-memory timeline a name, the same block
as a `dcr/<name>` host event in a `jax.profiler` capture, the spans inside
`engine.query`, `DataLoader.epoch` and `pmesh.to_host`, and the named scopes
on the parts of the step and the samplers that are no Flax module."""
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from PIL import Image

from dcr_tpu.core import tracing
from tools import trace_report


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


# ---------------------------------------------------------------------------
# the clock and the memory
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_a_spans_timeline_entry_lies_on_perf_counter():
    before = time.perf_counter()
    with tracing.span("x/y", k=1):
        time.sleep(0.002)
    after = time.perf_counter()
    (start, seconds), = tracing.timeline("x/y")
    assert before <= start <= start + seconds <= after
    assert seconds >= 0.002
    # the record's duration is the same reading; its `ts` is a wall stamp
    rec = tracing.flight_records()[-1]
    assert rec["dur"] == round(seconds * 1e6)
    assert abs(rec["ts"] / 1e6 - time.time()) < 60
    assert tracing.timeline("never/fired") == []


@pytest.mark.fast
def test_the_timeline_is_bounded_and_the_totals_are_not(monkeypatch):
    monkeypatch.setattr(tracing, "TIMELINE_SPANS", 8)
    for i in range(20):
        with tracing.span("x/bounded", i=i):
            pass
    line = tracing.timeline("x/bounded")
    assert len(line) == 8
    assert line == sorted(line)                  # the newest eight, in order
    totals = tracing.span_totals()["x/bounded"]
    assert totals["count"] == 20
    assert totals["seconds"] >= sum(d for _, d in line)
    assert tracing.TIMELINE_SPANS == 8 and tracing._SpanRecord().timeline.maxlen == 8


@pytest.mark.fast
def test_begin_span_feeds_the_timeline_and_complete_span_does_not():
    h = tracing.begin_span("x/handle")
    h.end()
    h.end()                                      # idempotent: one entry
    tracing.complete_span("x/elsewhere", start_wall=time.time(), dur_s=0.5)
    assert len(tracing.timeline("x/handle")) == 1
    assert tracing.timeline("x/elsewhere") == []


@pytest.mark.fast
def test_threads_on_one_name_lose_no_span():
    """More threads than cores on one span name, the interpreter switching
    often: the count a lost update would break is exact."""
    threads, each = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work():
            for _ in range(each):
                with tracing.span("x/raced"):
                    pass
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    totals = tracing.span_totals()["x/raced"]
    line = tracing.timeline("x/raced")
    assert totals["count"] == len(line) == threads * each
    assert totals["seconds"] == pytest.approx(sum(d for _, d in line))


@pytest.mark.fast
def test_the_flight_recorder_dump_carries_the_totals(tmp_path):
    import json

    with tracing.span("x/dumped"):
        pass
    path = tracing.dump_flight_recorder("test", directory=tmp_path)
    doc = json.loads(path.read_text())
    assert doc["span_totals"]["x/dumped"]["count"] == 1


# ---------------------------------------------------------------------------
# the profiler
# ---------------------------------------------------------------------------

def _host_events(trace_dir) -> list[tuple[str, float, float]]:
    import glob

    paths = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                             recursive=True))
    assert paths, "the capture wrote no .xplane.pb"
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_a_profiler_capture_holds_the_span_as_a_host_event(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("x/y"):
            with tracing.span("x/inner"):
                jnp.ones(8).block_until_ready()
        h = tracing.begin_span("x/not_lexical")      # not mirrored
        h.end()
    finally:
        jax.profiler.stop_trace()
    events = {name: (s, d) for name, s, d in _host_events(tmp_path)
              if name.startswith("dcr/")}
    assert set(events) == {"dcr/x/y", "dcr/x/inner"}
    (s, d), (si, di) = events["dcr/x/y"], events["dcr/x/inner"]
    assert s <= si and si + di <= s + d           # nested as in the program
    # and the same block on the program's own clock
    assert len(tracing.timeline("x/y")) == 1


# ---------------------------------------------------------------------------
# engine.query
# ---------------------------------------------------------------------------

SEARCH_CHILDREN = ("search/put", "search/topk", "search/merge")
TOPK_CHILDREN = ("search/dispatch", "search/device_wait", "search/fetch")


@pytest.mark.parametrize("ahead", [0, 1], ids=["serial", "ahead"])
def test_one_query_is_one_root_whose_children_fit_inside_it(tmp_path, rng_np,
                                                            ahead):
    """The same tree in both orders of the loop (PR 29): serial, a chunk's
    `search/topk` holds its own dispatch, wait and fetch; a chunk ahead, it
    holds the NEXT chunk's dispatch (the first holds two, the last none)
    before this chunk's wait and fetch."""
    from dcr_tpu.search.shardindex import open_engine
    from dcr_tpu.search.store import EmbeddingStoreWriter

    writer = EmbeddingStoreWriter.create(tmp_path / "store", embed_dim=16,
                                         shard_rows=16)
    writer.add(rng_np.standard_normal((40, 16)).astype(np.float32),
               np.arange(40).astype(str))
    writer.finalize()
    engine = open_engine(tmp_path / "store", top_k=2, query_batch=4)
    # a CPU device reports no memory, so build() decided serial: the other
    # order is set on the built object
    assert engine._ahead == 0
    engine._ahead = ahead
    q = rng_np.standard_normal((6, 16)).astype(np.float32)     # two chunks
    engine.query(q)                     # compiles; the spans of this one go
    tracing.reset_for_tests()
    scores, keys = engine.query(q)
    assert scores.shape == (6, 2) and keys.shape == (6, 2)

    (start, seconds), = tracing.timeline("search/query")
    inside = lambda name: [(s, d) for s, d in tracing.timeline(name)
                           if start <= s and s + d <= start + seconds]
    for name in SEARCH_CHILDREN + TOPK_CHILDREN:
        assert inside(name) == tracing.timeline(name), name
    scans = 2 * engine.num_segments
    assert len(inside("search/put")) == 1
    for name in ("search/topk", "search/merge") + TOPK_CHILDREN:
        assert len(inside(name)) == scans, name
    total = lambda names: sum(d for n in names for _, d in inside(n))
    assert total(SEARCH_CHILDREN) <= seconds
    assert total(TOPK_CHILDREN) <= total(["search/topk"])
    root = next(r for r in tracing.flight_records()
                if r["name"] == "search/query")
    assert root["args"] == {"rows": 6, "chunks": 2,
                            "segments": engine.num_segments}
    by_id = {r["id"]: r for r in tracing.flight_records()}
    for r in tracing.flight_records():
        if r["name"] in TOPK_CHILDREN:
            assert by_id[r["parent"]]["name"] == "search/topk"
        elif r["name"] in SEARCH_CHILDREN:
            assert r["parent"] == root["id"]
    # the counters that were there stay
    counters = tracing.registry().counters("search/")
    assert counters["search/query_total"] == 1
    assert counters["search/segments_scanned_total"] == scans
    # and the two that say how the call was collected
    assert counters["search/host_copy_queued_total"] == 2 * scans
    assert counters["search/dispatch_ahead_total"] == (
        ahead * engine.num_segments)
    dispatches = sorted(tracing.timeline("search/dispatch"))
    waits = sorted(tracing.timeline("search/device_wait"))
    for seg in range(engine.num_segments):
        # two chunks a segment: ahead, both are dispatched before the first
        # is waited for; serial, the second only after it
        second, first_wait = dispatches[2 * seg + 1], waits[2 * seg]
        assert (second[0] < first_wait[0]) == bool(ahead)


# ---------------------------------------------------------------------------
# DataLoader.epoch
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny_loader(tmp_path):
    from dcr_tpu.core.config import DataConfig
    from dcr_tpu.data.dataset import ObjectAttributeDataset
    from dcr_tpu.data.loader import DataLoader
    from dcr_tpu.data.tokenizer import HashTokenizer

    rng = np.random.default_rng(0)
    for cls in ("c0", "c1"):
        d = tmp_path / "data" / cls
        d.mkdir(parents=True)
        for i in range(6):
            Image.fromarray(rng.integers(0, 255, (40, 52, 3), np.uint8)).save(
                d / f"{cls}_{i}.png")
    cfg = DataConfig(train_data_dir=str(tmp_path / "data"), resolution=32,
                     class_prompt="nolevel", num_workers=2, seed=7)
    ds = ObjectAttributeDataset(cfg, HashTokenizer(100, 16))
    return DataLoader(ds, batch_size=2, num_workers=3, seed=1, prefetch=2)


@pytest.mark.fast
def test_two_epochs_are_two_fills_and_no_span_stays_open_across_a_yield(tiny_loader):
    steps = tiny_loader.steps_per_epoch()
    assert steps == 6
    with tracing.span("train/data_wait") as outer:
        for epoch in range(2):
            for batch in tiny_loader.epoch(epoch):
                # handed back with the context variable as the caller left it
                assert tracing.current_span_id() == outer.id
                assert batch["pixel_values"].shape == (2, 32, 32, 3)
    fills, waits = tracing.timeline("data/fill"), tracing.timeline("data/wait")
    assert len(fills) == 2
    assert len(waits) <= 2 * (steps - 1)         # none for a batch in hand
    assert len(tracing.timeline("data/batch")) == 2 * steps
    assert tracing.registry().snapshot()["gauges"]["data/workers"] == 3
    for r in tracing.flight_records():
        if r["name"] in ("data/fill", "data/wait"):
            assert r["parent"] == outer.id        # train/data_wait's children
    # a resumed epoch fills too, and an exhausted one does not
    list(tiny_loader.epoch(2, start_step=4))
    assert len(tracing.timeline("data/fill")) == 3
    assert list(tiny_loader.epoch(3, start_step=steps)) == []
    assert len(tracing.timeline("data/fill")) == 3


# ---------------------------------------------------------------------------
# pmesh.to_host
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_to_host_waits_then_copies():
    from dcr_tpu.parallel import mesh as pmesh

    out = pmesh.to_host(jnp.arange(6.0).reshape(2, 3) * 2)
    np.testing.assert_array_equal(out, np.arange(6.0).reshape(2, 3) * 2)
    (ws, wd), = tracing.timeline("xfer/device_wait")
    (cs, cd), = tracing.timeline("xfer/d2h")
    assert ws + wd <= cs                          # one after the other


# ---------------------------------------------------------------------------
# scopes in the lowered programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_stack():
    from dcr_tpu.core.config import MeshConfig, ModelConfig, TrainConfig
    from dcr_tpu.diffusion.trainer import build_models
    from dcr_tpu.parallel import mesh as pmesh

    cfg = TrainConfig()
    cfg.model = ModelConfig.tiny()
    cfg.mixed_precision = "no"
    cfg.ema_decay = 0.999
    models, params = build_models(cfg, jax.random.key(0))
    return cfg, models, params, pmesh.make_mesh(MeshConfig())


def _lowered_text(jitted, *args) -> str:
    return jitted.lower(*args).as_text(debug_info=True)


def test_the_lowered_train_step_names_what_flax_does_not(tiny_stack, cpu_devices):
    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.diffusion import train as T

    cfg, models, params, mesh = tiny_stack
    state = jax.eval_shape(lambda: T.init_train_state(
        cfg, models, unet_params=params["unet"], text_params=params["text"],
        vae_params=params["vae"]))
    assert state.ema_params is not None
    px = 8 * 2 ** (len(cfg.model.vae_block_out_channels) - 1)
    batch = {"pixel_values": jax.ShapeDtypeStruct((8, px, px, 3), jnp.float32),
             "input_ids": jax.ShapeDtypeStruct(
                 (8, cfg.model.text_max_length), jnp.int32)}
    text = _lowered_text(T.make_train_step(cfg, models, mesh), state, batch,
                         rngmod.root_key(0))
    # `jit(step_fn)/optimizer/mul`; under the gradient `.../jvp(loss)/sub`
    for scope in ("optimizer", "grad_clip", "ema", "noising", "loss"):
        assert re.search(rf"jit\(step_fn\)/(\w+\()*{scope}\)*/", text), scope
    # a Flax module path, which Flax names itself
    assert "UNet2DCondition)/down_0_res_0/norm1" in text
    assert "/blocks_0/attn1/attention_xla/" in text
    # metadata only: the text the manifest and warmcache hash names none
    plain = T.make_train_step(cfg, models, mesh).lower(
        state, batch, rngmod.root_key(0)).as_text()
    assert "jit(step_fn)/" not in plain and "/optimizer/" not in plain


def test_the_lowered_sampler_names_the_scheduler_step_and_the_cfg_combine(
        tiny_stack, cpu_devices):
    from dcr_tpu.core import rng as rngmod
    from dcr_tpu.core.config import SampleConfig
    from dcr_tpu.sampling.sampler import make_sampler

    _, models, params, mesh = tiny_stack
    cfg = SampleConfig(resolution=16, num_inference_steps=3, sampler="dpm++",
                       guidance_scale=7.5, im_batch=2, seed=0)
    length = models.text_encoder.config.text_max_length
    ids = jax.ShapeDtypeStruct((8, length), jnp.int32)
    p = {"unet": params["unet"], "vae": params["vae"], "text": params["text"]}
    text = _lowered_text(make_sampler(cfg, models, mesh), p, ids, ids,
                         rngmod.root_key(1))
    # inside the scan's body a location starts at the body: "cfg/mul"
    for scope in ("scheduler_step", "cfg", "attention_xla"):
        assert re.search(rf'["/]{scope}/', text), scope


@pytest.mark.fast
def test_the_three_flash_kernels_are_named():
    from dcr_tpu.ops import flash_attention as fa

    q = jnp.ones((1, 256, 2, 64), jnp.float32)
    grad = jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=True).sum(),
        argnums=(0, 1, 2)))(q, q, q)
    text = str(grad)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name


# ---------------------------------------------------------------------------
# trace_report and the schema
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_the_stage_table_counts_no_second_twice(tmp_path):
    tracing.configure(tmp_path, rank=0)
    with tracing.span("search/query"):
        with tracing.span("search/put"):
            time.sleep(0.001)
        with tracing.span("search/topk"):
            for name in TOPK_CHILDREN:
                with tracing.span(name):
                    time.sleep(0.001)
        with tracing.span("search/merge"):
            time.sleep(0.001)
    with tracing.span("train/data_wait"):
        with tracing.span("data/fill"):
            time.sleep(0.001)
        with tracing.span("data/wait"):
            time.sleep(0.001)
    with tracing.span("xfer/d2h"):
        pass
    tracing.reset_for_tests()                       # closes the file
    schema = trace_report.load_schema()
    records, errors = trace_report.load_trace(tmp_path, schema)
    assert errors == []
    summary = trace_report.summarize(records)
    names, cats = summary["by_name"], summary["categories"]
    assert set(trace_report.NESTED_SPANS) <= set(names)   # rows of their own
    assert cats["search"]["count"] == 3            # put, topk, merge
    assert cats["search"]["total_ms"] == pytest.approx(
        sum(names[n]["total_ms"] for n in SEARCH_CHILDREN))
    assert cats["search"]["total_ms"] <= names["search/query"]["total_ms"]
    assert cats["data"]["count"] == 1              # train/data_wait alone
    assert cats["xfer"]["count"] == 1
    known = schema["known_names"]
    assert "xfer/" in known["span_prefixes"]
    assert set(trace_report.NESTED_SPANS) | {
        "search/put", "search/merge", "xfer/device_wait",
        "xfer/d2h"} <= set(known["spans"])
