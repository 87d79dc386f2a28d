"""trace_report: turn trace.jsonl files — one process or a whole fleet —
into answers.

    python -m tools.trace_report <path> [<path> ...] [--chrome out.json]
                                 [--json]

Each path is a directory (searched RECURSIVELY for ``trace*.jsonl``, so a
fleet dir whose supervisor writes ``trace.jsonl`` and whose workers write
``worker_<i>/trace.jsonl`` merges in one invocation) or a single trace
file. Size-capped rotation segments (``trace.jsonl.1..N``, core/tracing.py)
are read oldest-first as part of their base file's stream. Every record is
validated against the checked-in ``tools/trace_schema.json``. The report:

- stage-time breakdown: wall time per span name and per category
  (data vs step vs ckpt vs eval vs serve), with p50/p99 per name;
- serve queue-wait percentiles (the ``serve/queue_wait`` spans) and
  recompile count per bucket (``serve/compile`` events);
- fault timeline: every ``fault/*`` event in chronological order, plus any
  flight-recorder dumps present in the directory;
- memory (dcr-hbm): resident-delta per stage and a peak timeline from the
  ``hbm_peak``/``hbm_delta`` attrs hot-region spans carry on backends with
  ``memory_stats()``, plus the compiled surfaces ranked by XLA temp bytes
  (``memwatch/surface_memory`` events);
- search (dcr-store): store-backed top-k segment-scan throughput
  (``search/topk`` spans), brute-force chunk time (``search/chunk``), and
  store ingestion volume (``search/ingest``);
- copy risk (dcr-watch): flagged-generation count, gen↔train similarity
  percentiles (from ``serve/risk_score`` / ``risk/score`` span ``sims``),
  the most-hit train keys, and a flagged-request timeline from
  ``risk/flagged`` events;
- fleet section (when spans carry distributed trace ids): per-file clock
  offsets anchored on supervisor ``fleet/dispatch`` ↔ worker
  ``serve/assemble`` pairs (a dispatch causally precedes its assemble, so a
  worker file whose assemble timestamps land before their dispatch is
  shifted forward by the largest violation — zero on one host), then one
  span tree per trace id across processes: connectivity, cross-process
  reach, requeue attempts, and partial spans left by attempts that died
  mid-flight.

``--chrome`` additionally writes a Chrome-trace JSON (``traceEvents`` array)
loadable in Perfetto / chrome://tracing, one track (pid) per source
process. ``--max-compiles N`` is the recompile budget (ROADMAP item 3): the
report counts XLA compiles (``serve/compile`` events + ``warmcache/compile``
spans) per PROCESS INCARNATION — streams tell respawns apart by the
``os_pid`` attr those records carry — and exits 3 when any incarnation
exceeds N, so a code change that silently introduces new recompiles (or a
respawn that should have been served from the persistent executable cache)
fails pre-merge. Exit codes: 0 = report produced (budget OK when given),
1 = no trace records found, 2 = schema violations (the trace is corrupt or
a writer drifted from the schema — CI fails on this), 3 = recompile budget
exceeded.

Pure stdlib on purpose (like tools/lint): runs on a bare checkout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SCHEMA_PATH = Path(__file__).resolve().parent / "trace_schema.json"

_TYPES = {
    "string": str,
    "integer": int,
    "number": (int, float),
    "object": dict,
    "integer_or_null": (int, type(None)),
}


def load_schema(path: Path = SCHEMA_PATH) -> dict:
    return json.loads(path.read_text())


def validate_record(rec: dict, schema: dict) -> list[str]:
    """Field-level problems with one record ([] = valid)."""
    problems = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]
    for field, tname in schema["required"].items():
        if field not in rec:
            problems.append(f"missing required field {field!r}")
        elif not isinstance(rec[field], _TYPES[tname]) or isinstance(rec[field], bool):
            problems.append(f"field {field!r} is {type(rec[field]).__name__}, "
                            f"want {tname}")
    ph = rec.get("ph")
    if ph not in schema["allowed_ph"]:
        problems.append(f"ph={ph!r} not in {schema['allowed_ph']}")
    if ph == "X":
        for field, tname in schema["span_required"].items():
            if field not in rec:
                problems.append(f"span missing required field {field!r}")
            elif not isinstance(rec[field], _TYPES[tname]):
                problems.append(f"span field {field!r} is "
                                f"{type(rec[field]).__name__}, want {tname}")
    for field, tname in schema.get("optional", {}).items():
        if field in rec and not isinstance(rec[field], _TYPES[tname]):
            problems.append(f"field {field!r} is {type(rec[field]).__name__}, "
                            f"want {tname}")
    return problems


def _rotation_index(path: Path) -> int:
    """0 for a base ``trace*.jsonl``, N for a rotated ``trace*.jsonl.N``."""
    suffix = path.name.rpartition(".jsonl")[2]
    return int(suffix[1:]) if suffix.startswith(".") else 0


def discover_streams(paths: list[Path]) -> list[tuple[str, list[Path]]]:
    """[(label, [files oldest-first])] — one stream per writing process.

    A stream is a base ``trace*.jsonl`` plus its size-rotation segments
    (``.1`` newest rotated … ``.N`` oldest), read oldest-first so records
    stay time-ordered per process. Directories are searched recursively
    (a fleet dir nests worker traces in ``worker_<i>/``); labels are the
    base file's path relative to the argument that found it."""
    streams: list[tuple[str, list[Path]]] = []
    seen: set[Path] = set()
    labels: set[str] = set()
    for arg in paths:
        bases = ([arg] if arg.is_file() else
                 sorted(p for p in arg.rglob("trace*.jsonl") if p.is_file()))
        for base in bases:
            base = base.resolve()
            if base in seen:
                continue
            seen.add(base)
            segments = sorted(
                (p for p in base.parent.glob(base.name + ".*")
                 if p.name[len(base.name) + 1:].isdigit()),
                key=_rotation_index, reverse=True)
            try:
                label = str(base.relative_to(arg.resolve())) \
                    if arg.is_dir() else str(arg)
            except ValueError:
                label = str(base)
            if label in labels:
                # two args with identical relative layouts (two fleet dirs):
                # labels must stay 1:1 with streams — clock offsets, per-tree
                # process sets and Chrome tracks all key on them
                label = f"{arg}:{label}"
            while label in labels:
                label += "'"
            labels.add(label)
            streams.append((label, segments + [base]))
    return streams


def _anchor_offsets(records: list[dict],
                    labels: list[str]) -> dict[str, int]:
    """Per-stream clock offset (microseconds to ADD) from dispatch↔assemble
    causality: a supervisor's ``fleet/dispatch`` span for a batch begins
    before any member's ``serve/assemble`` on the worker. A stream whose
    assemble starts earlier than its anchoring dispatch has a clock behind
    the supervisor's; shift it forward by the largest violation. Streams
    sharing a host clock (the common fleet-on-one-host case) get 0."""
    dispatches = [r for r in records
                  if r["ph"] == "X" and r["name"] == "fleet/dispatch"]
    if not dispatches:
        return {lab: 0 for lab in labels}
    by_trace: dict[str, int] = {}          # trace id -> earliest dispatch ts
    for d in dispatches:
        for t in d["args"].get("trace_ids") or []:
            if t is not None:
                by_trace[t] = min(by_trace.get(t, d["ts"]), d["ts"])
    offsets = {lab: 0 for lab in labels}
    for r in records:
        if r["ph"] != "X" or r["name"] != "serve/assemble":
            continue
        anchors = [by_trace[t] for t in (r["args"].get("trace_ids") or [])
                   if t in by_trace]
        if anchors:
            violation = min(anchors) - r["ts"]
            offsets[r["_plabel"]] = max(offsets[r["_plabel"]], violation)
    return offsets


def load_fleet(paths: list[Path],
               schema: dict) -> tuple[list[dict], list[str], dict]:
    """(records, errors, meta) across every stream under ``paths``.

    Each record gains ``_proc`` (stream index — the Chrome-export pid, since
    fleet processes are all jax rank 0) and ``_plabel`` (stream label);
    timestamps are clock-offset-adjusted per stream (see
    :func:`_anchor_offsets`). ``meta`` carries the stream labels and the
    applied offsets."""
    records: list[dict] = []
    errors: list[str] = []
    streams = discover_streams(paths)
    for proc, (label, files) in enumerate(streams):
        for path in files:
            for lineno, line in enumerate(path.read_text().splitlines(), 1):
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError as e:
                    errors.append(f"{path.name}:{lineno}: not JSON ({e})")
                    continue
                problems = validate_record(rec, schema)
                if problems:
                    errors.append(f"{path.name}:{lineno}: "
                                  + "; ".join(problems))
                    continue
                rec["_proc"] = proc
                rec["_plabel"] = label
                records.append(rec)
    labels = [label for label, _ in streams]
    offsets = _anchor_offsets(records, labels)
    for rec in records:
        rec["ts"] += offsets[rec["_plabel"]]
    records.sort(key=lambda r: r["ts"])
    meta = {"processes": labels,
            "clock_offset_us": {k: v for k, v in offsets.items() if v}}
    return records, errors, meta


def load_trace(run_dir: Path, schema: dict) -> tuple[list[dict], list[str]]:
    """(records, errors) across every trace*.jsonl under run_dir (all ranks,
    rotated segments included). Compatibility wrapper over load_fleet."""
    records, errors, _ = load_fleet([run_dir], schema)
    return records, errors


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

# span-name prefix -> report category (the "where did the time go" buckets)
_CATEGORIES = (
    ("train/data_wait", "data"),
    ("data/", "data"),
    ("train/step", "step"),
    ("ckpt/", "ckpt"),
    ("stage/eval", "eval"),
    ("serve/risk_score", "risk"),
    ("serve/", "serve"),
    ("stage/", "stage"),
    ("train/", "train"),
    ("risk/", "risk"),
    ("search/", "search"),
    ("ingest/", "ingest"),
)


# spans that lie inside, or round, a span the stage table already counts:
# search/query encloses search/topk, whose own children are dispatch,
# device_wait and fetch; the loader's fill and wait are the consumer's
# train/data_wait seen from inside. They keep their rows under
# "per-span-name" and stay out of the stage table, so that no second is
# counted twice there. (xfer/* is a category of its own: bulk sampling
# fetches under no other span.)
NESTED_SPANS = frozenset({
    "search/query", "search/dispatch", "search/device_wait", "search/fetch",
    "data/fill", "data/wait"})


def category_of(name: str) -> str:
    for prefix, cat in _CATEGORIES:
        if name.startswith(prefix):
            return cat
    return name.split("/", 1)[0]


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile without numpy (stdlib-only tool)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, round(q / 100 * (len(sorted_vals) - 1))))
    return sorted_vals[int(idx)]


def assemble_trace_trees(records: list[dict]) -> list[dict]:
    """One document per distributed trace id: the cross-process span tree.

    Span ids are process-local (core/tracing.py counts from 1 in every
    process), so tree edges resolve per stream: a span's ``parent`` points
    within its own file, while a worker's ``serve/request`` root crosses
    streams via ``args.remote_parent`` — the supervisor root span id shipped
    in the wire context. A trace is **connected** when exactly one global
    root exists and every remote_parent reference names it. Spans whose
    parent was never written (an attempt SIGKILLed mid-batch emits children
    before its root ends) are counted as ``orphan_spans`` — expected debris
    of a crashed attempt, attributed to the trace by id but outside the
    tree."""
    spans = [r for r in records if r["ph"] == "X" and r.get("trace")]
    by_trace: dict[str, list[dict]] = {}
    for s in spans:
        by_trace.setdefault(s["trace"], []).append(s)
    trees = []
    for trace_id, group in sorted(by_trace.items()):
        roots = [s for s in group if s["parent"] is None
                 and s["args"].get("remote_parent") is None]
        remote_roots = [s for s in group
                        if s["args"].get("remote_parent") is not None]
        root = roots[0] if len(roots) == 1 else None
        links_ok = root is not None and all(
            r["args"]["remote_parent"] == root["id"]
            and r["_proc"] != root["_proc"] for r in remote_roots)
        # reachability: same-process parent edges + the remote hops
        anchored_keys: set[tuple[int, int]] = set()
        if root is not None:
            frontier = [root] + (remote_roots if links_ok else [])
            anchored_keys = {(s["_proc"], s["id"]) for s in frontier}
            grew = True
            while grew:
                grew = False
                for s in group:
                    key = (s["_proc"], s["id"])
                    if key in anchored_keys or s["parent"] is None:
                        continue
                    if (s["_proc"], s["parent"]) in anchored_keys:
                        anchored_keys.add(key)
                        grew = True
        anchored = len(anchored_keys)
        attempts = [int(s["args"].get("attempt", 1)) for s in remote_roots]
        trees.append({
            "trace": trace_id,
            "spans": len(group),
            "processes": sorted({s["_plabel"] for s in group}),
            "roots": len(roots),
            "connected": len(roots) == 1 and links_ok,
            "anchored_spans": anchored,
            "orphan_spans": len(group) - anchored,
            "attempts": max(attempts) if attempts else 1,
            "names": sorted({s["name"] for s in group}),
        })
    return trees


# per-trace tree documents kept in the summary/--json output. Aggregates
# cover everything; the individual docs are for drill-down, and a long
# single-process serve run (every request carries a trace id) would
# otherwise embed one doc per lifetime request.
_MAX_TREES = 50


def fleet_summary(records: list[dict], meta: dict) -> dict | None:
    """The distributed-trace section of the report (None when nothing
    carries a trace id — e.g. train/eval runs keep their old report shape).
    Aggregate counts cover every trace; ``trees`` lists the interesting ones
    first (disconnected, then requeued) capped at ``_MAX_TREES`` with the
    overflow counted in ``trees_truncated``."""
    trees = assemble_trace_trees(records)
    if not trees:
        return None
    shown = sorted(trees, key=lambda t: (t["connected"], -t["attempts"]))
    return {
        "processes": meta.get("processes", []),
        "clock_offset_us": meta.get("clock_offset_us", {}),
        "traces": len(trees),
        "connected": sum(t["connected"] for t in trees),
        "cross_process": sum(len(t["processes"]) > 1 for t in trees),
        "requeued": sum(t["attempts"] > 1 for t in trees),
        "max_attempts": max(t["attempts"] for t in trees),
        "orphan_spans": sum(t["orphan_spans"] for t in trees),
        "trees": shown[:_MAX_TREES],
        "trees_truncated": max(0, len(trees) - _MAX_TREES),
    }


def copy_risk_summary(records: list[dict]) -> dict | None:
    """The "Copy risk" section (dcr-watch): similarity percentiles from the
    per-row ``sims`` attr that ``serve/risk_score`` (serving) and
    ``risk/score`` (training sample grids) spans carry, plus the flagged
    timeline from ``risk/flagged`` events. None when nothing was scored —
    pre-dcr-watch traces keep their old report shape."""
    sims: list[float] = []
    for r in records:
        if r["ph"] == "X" and r["name"] in ("serve/risk_score", "risk/score"):
            sims.extend(float(s) for s in (r["args"].get("sims") or []))
    flagged = [r for r in records
               if r["ph"] == "i" and r["name"] == "risk/flagged"]
    if not sims and not flagged:
        return None
    sims_sorted = sorted(sims)
    top_keys: dict[str, int] = {}
    for e in flagged:
        key = str(e["args"].get("top_key", "?"))
        top_keys[key] = top_keys.get(key, 0) + 1
    timeline = [{
        "time": time.strftime("%H:%M:%S", time.localtime(e["ts"] / 1e6)),
        "ts": e["ts"],
        "request_id": e["args"].get("request_id"),
        "max_sim": e["args"].get("max_sim"),
        "top_key": e["args"].get("top_key"),
        "prompt": e["args"].get("prompt"),
    } for e in flagged]
    return {
        "scored": len(sims),
        "flagged": len(flagged),
        "sim_p50": round(_percentile(sims_sorted, 50), 6),
        "sim_p90": round(_percentile(sims_sorted, 90), 6),
        "sim_p99": round(_percentile(sims_sorted, 99), 6),
        "sim_max": round(sims_sorted[-1], 6) if sims_sorted else 0.0,
        "flagged_train_keys": dict(sorted(top_keys.items(),
                                          key=lambda kv: -kv[1])[:10]),
        "flagged_timeline": timeline[:50],
    }


def search_summary(records: list[dict]) -> dict | None:
    """The "Search" section (dcr-store): similarity-search time breakdown.

    Built from three span families: ``search/topk`` (the store-backed
    mesh-sharded query program — one span per segment scan, carrying
    ``rows`` and ``batch``), ``search/chunk`` (the brute-force per-folder
    matmul+host-merge path), and ``search/ingest`` (store shard writes).
    None when nothing searched/ingested — other traces keep their shape.
    """
    topk = [r for r in records
            if r["ph"] == "X" and r["name"] == "search/topk"]
    chunk = [r for r in records
             if r["ph"] == "X" and r["name"] == "search/chunk"]
    ingest = [r for r in records
              if r["ph"] == "X" and r["name"] == "search/ingest"]
    if not topk and not chunk and not ingest:
        return None
    out: dict = {}
    if topk:
        durs = sorted(r["dur"] / 1e3 for r in topk)
        rows = sum(int(r["args"].get("rows", 0)) for r in topk)
        total_ms = sum(durs)
        out["store_topk"] = {
            "segment_scans": len(topk),
            "rows_scanned": rows,
            "total_ms": round(total_ms, 3),
            "p50_ms": round(_percentile(durs, 50), 3),
            "p99_ms": round(_percentile(durs, 99), 3),
            "rows_per_s": round(rows / max(total_ms / 1e3, 1e-9)),
        }
    if chunk:
        durs = sorted(r["dur"] / 1e3 for r in chunk)
        out["brute_chunks"] = {
            "chunks": len(chunk),
            "total_ms": round(sum(durs), 3),
            "p50_ms": round(_percentile(durs, 50), 3),
            "p99_ms": round(_percentile(durs, 99), 3),
        }
    if ingest:
        out["ingest"] = {
            "shards": len(ingest),
            "rows": sum(int(r["args"].get("rows", 0)) for r in ingest),
            "total_ms": round(sum(r["dur"] for r in ingest) / 1e3, 3),
        }
    return out


def ann_summary(records: list[dict]) -> dict | None:
    """The "ANN" section (dcr-ann): IVF approximate-search health.

    Built from the ``search/ivf_scan`` spans (one per probed segment scan:
    nprobe, lists hit, segment rows), the ``search/ivf_rerank`` spans (the
    exact f32 re-rank of the shortlist union), the ``ann/query_funnel``
    events (the probe -> shortlist -> re-rank funnel per query chunk, plus
    the segment skip ratio — the sublinearity evidence), the ``search/
    kmeans`` spans (training Lloyd iterations), and the ``ann/
    recall_spot_check`` events (sampled recall vs the exact oracle). None
    when the ann tier never ran — other traces keep their shape.
    """
    scans = [r for r in records
             if r["ph"] == "X" and r["name"] == "search/ivf_scan"]
    reranks = [r for r in records
               if r["ph"] == "X" and r["name"] == "search/ivf_rerank"]
    kmeans = [r for r in records
              if r["ph"] == "X" and r["name"] == "search/kmeans"]
    funnels = [r for r in records
               if r["ph"] == "i" and r["name"] == "ann/query_funnel"]
    recalls = [r for r in records
               if r["ph"] == "i" and r["name"] == "ann/recall_spot_check"]
    if not scans and not kmeans and not funnels:
        return None
    out: dict = {}
    if scans:
        durs = sorted(r["dur"] / 1e3 for r in scans)
        nprobes: dict[str, int] = {}
        for r in scans:
            key = str(r["args"].get("nprobe", "?"))
            nprobes[key] = nprobes.get(key, 0) + 1
        out["scan"] = {
            "segment_scans": len(scans),
            "lists_scanned": sum(int(r["args"].get("lists", 0))
                                 for r in scans),
            "rows_scanned": sum(int(r["args"].get("rows", 0))
                                for r in scans),
            "total_ms": round(sum(durs), 3),
            "p50_ms": round(_percentile(durs, 50), 3),
            "p99_ms": round(_percentile(durs, 99), 3),
            "nprobe_distribution": dict(sorted(nprobes.items(),
                                               key=lambda kv: kv[0])),
        }
    if funnels:
        scanned = sum(int(e["args"].get("segments_scanned", 0))
                      for e in funnels)
        skipped = sum(int(e["args"].get("segments_skipped", 0))
                      for e in funnels)
        out["funnel"] = {
            "query_chunks": len(funnels),
            "queries": sum(int(e["args"].get("batch", 0)) for e in funnels),
            "lists_probed": sum(int(e["args"].get("lists_probed", 0))
                                for e in funnels),
            "shortlist_candidates": sum(int(e["args"].get("shortlist", 0))
                                        for e in funnels),
            "reranked_to_top_k": sum(
                int(e["args"].get("batch", 0)) * int(e["args"].get("top_k", 1))
                for e in funnels),
            "segments_scanned": scanned,
            "segments_skipped": skipped,
            "segment_skip_pct": round(
                100.0 * skipped / max(scanned + skipped, 1), 1),
        }
    if reranks:
        durs = sorted(r["dur"] / 1e3 for r in reranks)
        out["rerank"] = {
            "calls": len(reranks),
            "candidates": sum(int(r["args"].get("candidates", 0))
                              for r in reranks),
            "total_ms": round(sum(durs), 3),
            "p50_ms": round(_percentile(durs, 50), 3),
            "p99_ms": round(_percentile(durs, 99), 3),
        }
    if kmeans:
        restarts = max((int(r["args"].get("restart", 0)) for r in kmeans),
                       default=0)
        out["train"] = {
            "lloyd_iters": len(kmeans),
            "restarts": restarts,
            "total_ms": round(sum(r["dur"] for r in kmeans) / 1e3, 3),
        }
    if recalls:
        # sample-count-weighted (dcr-slo): a 256-query check must outweigh
        # a 4-query one — an unweighted mean of check means is not a recall
        vals = sorted(float(e["args"].get("recall", 0.0)) for e in recalls)
        weighted = sum(float(e["args"].get("recall", 0.0))
                       * max(1, int(e["args"].get("queries", 1)))
                       for e in recalls)
        samples = sum(max(1, int(e["args"].get("queries", 1)))
                      for e in recalls)
        out["recall_spot_checks"] = {
            "checks": len(recalls),
            "k": int(recalls[-1]["args"].get("k", 0)),
            "samples": samples,
            "min_recall": round(vals[0], 4),
            "mean_recall": round(weighted / samples, 4),
        }
    probes = [r for r in records
              if r["ph"] == "i" and r["name"] == "ann/recall_probe"]
    if probes:
        weighted = sum(float(e["args"].get("recall", 0.0))
                       * max(1, int(e["args"].get("queries", 1)))
                       for e in probes)
        samples = sum(max(1, int(e["args"].get("queries", 1)))
                      for e in probes)
        last = probes[-1]["args"]
        out["recall_online"] = {
            "probes": len(probes),
            "k": int(last.get("k", 0)),
            "samples": samples,
            "mean_recall": round(weighted / samples, 4),
            "last_rolling": round(float(last.get("rolling", 0.0)), 4),
        }
    return out


def _fmt_ts(ts_us: float) -> str:
    return time.strftime("%H:%M:%S", time.localtime(ts_us / 1e6))


def ingest_summary(records: list[dict]) -> dict | None:
    """The "Ingest" section (dcr-live): streaming-provenance health.

    Built from the ``ingest/append`` spans (WAL append throughput + fsync
    latency percentiles), the ``ingest/compact`` spans (the compaction
    timeline: rows folded, snapshot published, duration), and the
    ``ingest/recover`` spans + ``ingest/recovered`` events (what a restart
    replayed, how many torn tails it truncated). None when nothing
    ingested — other traces keep their shape.
    """
    appends = [r for r in records
               if r["ph"] == "X" and r["name"] == "ingest/append"]
    compacts = [r for r in records
                if r["ph"] == "X" and r["name"] == "ingest/compact"]
    recovers = [r for r in records
                if r["ph"] == "X" and r["name"] == "ingest/recover"]
    if not appends and not compacts and not recovers:
        return None
    out: dict = {}
    if appends:
        durs = sorted(r["dur"] / 1e3 for r in appends)
        rows = sum(int(r["args"].get("rows", 0)) for r in appends)
        wall_s = (max(r["ts"] + r["dur"] for r in appends)
                  - min(r["ts"] for r in appends)) / 1e6
        out["append"] = {
            "records": len(appends),
            "rows": rows,
            "total_ms": round(sum(durs), 3),
            "p50_ms": round(_percentile(durs, 50), 3),
            "p99_ms": round(_percentile(durs, 99), 3),
            "rows_per_s": round(rows / max(wall_s, 1e-9)),
        }
    if compacts:
        out["compactions"] = [
            {"time": _fmt_ts(r["ts"]),
             "rows": int(r["args"].get("rows", 0)),
             "records": int(r["args"].get("records", 0)),
             "snapshot": r["args"].get("snapshot"),
             "ms": round(r["dur"] / 1e3, 3)}
            for r in sorted(compacts, key=lambda r: r["ts"])][:50]
    if recovers:
        out["recoveries"] = [
            {"time": _fmt_ts(r["ts"]),
             "rows": int(r["args"].get("rows", 0)),
             "torn": int(r["args"].get("torn", 0)),
             "segments": int(r["args"].get("segments", 0)),
             "ms": round(r["dur"] / 1e3, 3)}
            for r in sorted(recovers, key=lambda r: r["ts"])][:50]
    return out


def slo_summary(records: list[dict]) -> dict | None:
    """The "SLO" section (dcr-slo): breach/recover timeline per objective.

    Built from the ``slo/breach`` and ``slo/recover`` instant events the
    supervisor-side engine emits on every state transition. Each breach is
    paired with the next recover of the same objective so the rendered
    timeline shows breach duration; an unrecovered breach is marked open.
    None when no SLO events — other traces keep their shape.
    """
    transitions = sorted((r for r in records if r["ph"] == "i"
                          and r["name"] in ("slo/breach", "slo/recover")),
                         key=lambda r: r["ts"])
    if not transitions:
        return None
    objectives: dict[str, dict] = {}
    timeline = []
    open_breach: dict[str, dict] = {}
    for r in transitions:
        obj = str(r["args"].get("objective", "?"))
        st = objectives.setdefault(obj, {"breaches": 0, "recoveries": 0})
        entry = {
            "time": _fmt_ts(r["ts"]), "ts": r["ts"],
            "event": r["name"].split("/", 1)[1],
            "objective": obj,
            "value": r["args"].get("value"),
            "target": r["args"].get("target"),
        }
        if r["name"] == "slo/breach":
            st["breaches"] += 1
            entry["burn"] = r["args"].get("burn_short")
            open_breach[obj] = entry
        else:
            st["recoveries"] += 1
            entry["breach_s"] = r["args"].get("breach_s")
            open_breach.pop(obj, None)
        timeline.append(entry)
    return {
        "objectives": dict(sorted(objectives.items())),
        "open_breaches": sorted(open_breach),
        "timeline": timeline[:100],
    }


def _interval_overlap_us(a: list[tuple[float, float]],
                         b: list[tuple[float, float]]) -> float:
    """Total pairwise intersection of two interval lists (start, end),
    linear merge over the sorted lists — the encode-vs-denoise overlap."""
    a = sorted(a)
    b = sorted(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def pipeline_summary(records: list[dict]) -> dict | None:
    """The "Pipeline" section (dcr-pipe): how well the frozen-encoder
    producer stage overlaps the denoiser hot loop. Built from the
    ``train/encode`` spans (producer thread), ``train/step`` spans (the
    denoiser in pipelined runs), and ``train/encode_wait`` spans (the train
    thread blocked on the prefetch ring — the pipeline bubble). None when
    nothing was pipelined — fused traces keep their old report shape.

    - ``bubble_pct``: encode_wait time over (encode_wait + step) time — the
      fraction of the hot loop spent stalled on the producer;
    - ``overlap_pct``: wall-clock intersection of encode spans with step
      spans over total encode time — how much encoder work genuinely hid
      behind the denoiser (≈0 on a single-core host, where the win comes
      from the latent cache instead);
    - ``data_wait``: the producer's own stall on the host loader, to tell a
      loader-bound pipeline from an encode-bound one.
    """
    encode = [r for r in records
              if r["ph"] == "X" and r["name"] == "train/encode"]
    if not encode:
        return None
    waits = [r["dur"] / 1e3 for r in records
             if r["ph"] == "X" and r["name"] == "train/encode_wait"]
    steps = [r for r in records
             if r["ph"] == "X" and r["name"] == "train/step"]
    data_waits = [r["dur"] / 1e3 for r in records
                  if r["ph"] == "X" and r["name"] == "train/data_wait"]
    encode_ms = sum(r["dur"] for r in encode) / 1e3
    step_ms = sum(r["dur"] for r in steps) / 1e3
    wait_ms = sum(waits)
    overlap_ms = _interval_overlap_us(
        [(r["ts"], r["ts"] + r["dur"]) for r in encode],
        [(r["ts"], r["ts"] + r["dur"]) for r in steps]) / 1e3
    waits_sorted = sorted(waits)
    return {
        "encoded_batches": len(encode),
        "encode_total_ms": round(encode_ms, 3),
        "denoise_total_ms": round(step_ms, 3),
        "encode_wait_total_ms": round(wait_ms, 3),
        "data_wait_total_ms": round(sum(data_waits), 3),
        "bubble_pct": round(100 * wait_ms / max(wait_ms + step_ms, 1e-9), 2),
        "overlap_ms": round(overlap_ms, 3),
        "overlap_pct": round(100 * overlap_ms / max(encode_ms, 1e-9), 2),
        "encode_wait_p50_ms": round(_percentile(waits_sorted, 50), 3),
        "encode_wait_p99_ms": round(_percentile(waits_sorted, 99), 3),
    }


def memory_summary(records: list[dict]) -> dict | None:
    """The "Memory" section (dcr-hbm): where the device memory went.

    Built from two record families: hot-region spans carrying
    ``hbm_peak``/``hbm_delta`` attrs (``train/step``, ``train/encode``,
    ``serve/device_step`` — obs/memwatch.span_hbm; only emitted on backends
    with real ``memory_stats()``), and ``memwatch/surface_memory`` events
    (one per AOT-compiled surface, carrying its XLA memory analysis).
    None when nothing carries memory info — CPU-backend traces keep their
    pre-dcr-hbm report shape.

    - ``resident_delta_by_stage``: summed ``hbm_delta`` per span name — the
      stages that grew (or released) resident memory;
    - ``peak_timeline``: the last 50 ``hbm_peak`` samples in time order —
      how the high-water mark moved across the run;
    - ``top_surfaces_by_temp_bytes``: the compiled programs ranked by XLA
      temp (scratch) bytes — the first place to look when a peak says the
      device is fuller than the params explain.
    """
    spans = [r for r in records
             if r["ph"] == "X" and "hbm_peak" in r["args"]]
    surfaces: dict[str, dict] = {}
    for r in records:
        if r["ph"] == "i" and r["name"] == "memwatch/surface_memory":
            label = (f"{r['args'].get('surface', '?')}"
                     f"@{str(r['args'].get('key', ''))[:8]}")
            surfaces[label] = r["args"]
    if not spans and not surfaces:
        return None
    by_stage: dict[str, dict] = {}
    for s in sorted(spans, key=lambda r: r["ts"]):
        row = by_stage.setdefault(
            s["name"], {"count": 0, "delta_bytes": 0, "peak_bytes": 0})
        row["count"] += 1
        row["delta_bytes"] += int(s["args"].get("hbm_delta", 0))
        row["peak_bytes"] = max(row["peak_bytes"],
                                int(s["args"].get("hbm_peak", 0)))
    timeline = [{"ts": s["ts"], "peak_bytes": int(s["args"]["hbm_peak"])}
                for s in sorted(spans, key=lambda r: r["ts"])][-50:]
    top = sorted(
        surfaces.items(),
        key=lambda kv: -(kv[1].get("temp_bytes") or 0))[:10]
    return {
        "sampled_spans": len(spans),
        # over ALL spans, not the truncated timeline: in a merged fleet
        # trace the process that peaked highest may have died early, and
        # its samples must not fall out of the headline number
        "peak_bytes": max((int(s["args"]["hbm_peak"]) for s in spans),
                          default=0),
        "resident_delta_by_stage": by_stage,
        "peak_timeline": timeline,
        "surfaces": len(surfaces),
        "top_surfaces_by_temp_bytes": [{
            "surface": label,
            "temp_bytes": mem.get("temp_bytes"),
            "argument_bytes": mem.get("argument_bytes"),
            "output_bytes": mem.get("output_bytes"),
            "total_bytes": mem.get("total_bytes"),
        } for label, mem in top],
    }


def fast_sampling_summary(records: list[dict]) -> dict | None:
    """The "Fast sampling" section (dcr-fast): denoiser-call reduction from
    ``sample/fast`` spans — one per accelerated batch EXECUTION, carrying
    the static ``steps`` (solver steps taken) and ``unet_calls`` (denoiser
    calls actually made) of its plan plus ``batch`` (trajectories sharing
    it: the plan is batch-uniform, so per-trajectory totals are the span
    numbers weighted by batch). None when nothing ran fast — dense traces
    keep their pre-fast report shape."""
    spans = [r for r in records
             if r["ph"] == "X" and r["name"] == "sample/fast"]
    rows = []
    for s in spans:
        steps = s["args"].get("steps")
        calls = s["args"].get("unet_calls")
        batch = s["args"].get("batch")
        if isinstance(steps, int) and isinstance(calls, int) and steps > 0:
            rows.append((steps, calls,
                         batch if isinstance(batch, int) and batch > 0
                         else 1))
    if not rows:
        return None
    total_steps = sum(s * b for s, _, b in rows)
    total_calls = sum(c * b for _, c, b in rows)
    # calls-saved histogram: how many trajectories skipped how many calls
    saved_hist: dict[str, int] = {}
    for steps, calls, batch in rows:
        key = str(steps - calls)
        saved_hist[key] = saved_hist.get(key, 0) + batch
    return {
        "executions": len(rows),
        "trajectories": sum(b for _, _, b in rows),
        "steps_total": total_steps,
        "unet_calls_total": total_calls,
        "calls_saved_total": total_steps - total_calls,
        "call_reduction": round(total_steps / max(1, total_calls), 3),
        "calls_saved_histogram": dict(sorted(saved_hist.items(),
                                             key=lambda kv: int(kv[0]))),
    }


def compiles_per_incarnation(records: list[dict]) -> dict[str, int]:
    """XLA compiles per PROCESS INCARNATION — the recompile-budget unit.

    A respawned worker appends to the same per-stream trace file, so
    incarnations within a stream are told apart by the ``os_pid`` attr that
    ``serve/compile`` events and ``warmcache/compile`` spans carry (each
    respawn is a fresh pid). Per group the count is
    ``max(warmcache/compile spans, serve/compile events)``: on dcr-warm
    streams every real compile produces a warmcache span (bucket compiles
    additionally emit the serve event — counting both would double-bill),
    while pre-dcr-warm traces have only the events.
    ``warmcache/load_compile`` spans (an export-tier cache entry's
    compile-on-load) count too: they are real XLA compiles, and excluding
    them would let a broken executable tier pass a ``--max-compiles 0``
    gate while every boot silently recompiles."""
    spans: dict[str, int] = {}
    events: dict[str, int] = {}
    for r in records:
        if r["ph"] == "X" and r["name"] in ("warmcache/compile",
                                            "warmcache/load_compile"):
            bucket = spans
        elif r["ph"] == "i" and r["name"] == "serve/compile":
            bucket = events
        else:
            continue
        key = f"{r['_plabel']}@pid{r['args'].get('os_pid', '?')}"
        bucket[key] = bucket.get(key, 0) + 1
    return {k: max(spans.get(k, 0), events.get(k, 0))
            for k in sorted(set(spans) | set(events))}


def summarize(records: list[dict], meta: dict | None = None) -> dict:
    """The report document (also the --json output)."""
    spans = [r for r in records if r["ph"] == "X"]
    events = [r for r in records if r["ph"] == "i"]
    by_name: dict[str, list[float]] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s["dur"] / 1e3)  # ms
    names = {}
    categories: dict[str, dict] = {}
    for name, durs in sorted(by_name.items()):
        durs_sorted = sorted(durs)
        row = {
            "count": len(durs),
            "total_ms": round(sum(durs), 3),
            "mean_ms": round(sum(durs) / len(durs), 3),
            "p50_ms": round(_percentile(durs_sorted, 50), 3),
            "p99_ms": round(_percentile(durs_sorted, 99), 3),
        }
        names[name] = row
        if name in NESTED_SPANS:
            continue
        cat = categories.setdefault(category_of(name), {"count": 0, "total_ms": 0.0})
        cat["count"] += row["count"]
        cat["total_ms"] = round(cat["total_ms"] + row["total_ms"], 3)

    queue_waits = sorted(by_name.get("serve/queue_wait", []))
    queue_wait = {
        "count": len(queue_waits),
        "p50_ms": round(_percentile(queue_waits, 50), 3),
        "p90_ms": round(_percentile(queue_waits, 90), 3),
        "p99_ms": round(_percentile(queue_waits, 99), 3),
    } if queue_waits else None

    recompiles: dict[str, int] = {}
    for e in events:
        if e["name"] == "serve/compile":
            bucket = str(e["args"].get("bucket", "?"))
            recompiles[bucket] = recompiles.get(bucket, 0) + 1

    faults = [{
        "time": time.strftime("%H:%M:%S", time.localtime(e["ts"] / 1e6)),
        "ts": e["ts"],
        "rank": e["pid"],
        "name": e["name"],
        "args": e["args"],
    } for e in events if e["name"].startswith("fault/")]

    ranks = sorted({r["pid"] for r in records})
    span_ts = [s["ts"] for s in spans]
    return {
        "records": len(records),
        "spans": len(spans),
        "events": len(events),
        "ranks": ranks,
        "wall_span_s": (round((max(span_ts) - min(span_ts)) / 1e6, 3)
                        if span_ts else 0.0),
        "categories": categories,
        "by_name": names,
        "serve_queue_wait": queue_wait,
        "serve_recompiles_per_bucket": recompiles,
        "compiles_per_incarnation": compiles_per_incarnation(records),
        "copy_risk": copy_risk_summary(records),
        "search": search_summary(records),
        "ann": ann_summary(records),
        "ingest": ingest_summary(records),
        "fast_sampling": fast_sampling_summary(records),
        "pipeline": pipeline_summary(records),
        "memory": memory_summary(records),
        "fault_timeline": faults,
        "slo": slo_summary(records),
        "fleet": fleet_summary(records, meta or {}),
    }


def chrome_trace(records: list[dict]) -> dict:
    """Chrome-trace/Perfetto document: spans -> complete ('X') events, instants
    -> 'i' events with thread scope, plus process_name/thread_name metadata —
    one track (pid) per SOURCE PROCESS (stream), since fleet supervisor and
    workers are all jax rank 0 and would otherwise collapse onto one row."""
    out = []
    seen_procs = set()
    seen_threads = set()
    for r in records:
        pid = r.get("_proc", r["pid"])
        if pid not in seen_procs:
            seen_procs.add(pid)
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0,
                        "args": {"name": r.get("_plabel", f"rank {r['pid']}")}})
        key = (pid, r["tid"])
        if key not in seen_threads:
            seen_threads.add(key)
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": r["tid"], "args": {"name": r["tname"]}})
        ev = {"ph": r["ph"], "name": r["name"], "ts": r["ts"],
              "pid": pid, "tid": r["tid"], "cat": category_of(r["name"]),
              "args": dict(r["args"], id=r["id"], parent=r.get("parent"),
                           **({"trace": r["trace"]} if r.get("trace")
                              else {}))}
        if r["ph"] == "X":
            ev["dur"] = r["dur"]
        else:
            ev["s"] = "t"
        out.append(ev)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def render_text(summary: dict, paths: list[Path] | Path) -> str:
    paths = [paths] if isinstance(paths, Path) else list(paths)
    lines = [f"trace report: {', '.join(map(str, paths))}",
             f"  {summary['spans']} spans / {summary['events']} events "
             f"from ranks {summary['ranks']} over {summary['wall_span_s']}s"]
    fleet = summary.get("fleet")
    if fleet:
        lines.append(
            f"\nfleet: {fleet['traces']} distributed trace(s) across "
            f"{len(fleet['processes'])} process file(s) — "
            f"{fleet['connected']} connected, "
            f"{fleet['cross_process']} cross-process, "
            f"{fleet['requeued']} requeued (max attempt "
            f"{fleet['max_attempts']}), "
            f"{fleet['orphan_spans']} orphan span(s) from dead attempts")
        for lab, off in sorted(fleet["clock_offset_us"].items()):
            lines.append(f"  clock offset {lab}: +{off} us "
                         "(anchored on dispatch<->assemble)")
        broken = [t for t in fleet["trees"] if not t["connected"]]
        for t in broken[:10]:
            lines.append(f"  DISCONNECTED trace {t['trace']}: "
                         f"{t['roots']} root(s), spans {t['names']}")
    lines.append("\nstage-time breakdown (host wall time per category):")
    total = sum(c["total_ms"] for c in summary["categories"].values()) or 1.0
    for cat, row in sorted(summary["categories"].items(),
                           key=lambda kv: -kv[1]["total_ms"]):
        lines.append(f"  {cat:<8} {row['total_ms']:>12.1f} ms  "
                     f"({100 * row['total_ms'] / total:5.1f}%)  "
                     f"x{row['count']}")
    lines.append("\nper-span-name:")
    for name, row in sorted(summary["by_name"].items(),
                            key=lambda kv: -kv[1]["total_ms"]):
        lines.append(f"  {name:<24} x{row['count']:<6} total "
                     f"{row['total_ms']:>10.1f} ms  mean {row['mean_ms']:>8.2f}  "
                     f"p50 {row['p50_ms']:>8.2f}  p99 {row['p99_ms']:>8.2f}")
    if summary["serve_queue_wait"]:
        q = summary["serve_queue_wait"]
        lines.append(f"\nserve queue wait: x{q['count']}  p50 {q['p50_ms']} ms  "
                     f"p90 {q['p90_ms']} ms  p99 {q['p99_ms']} ms")
    if summary["serve_recompiles_per_bucket"]:
        lines.append("serve compiles per bucket:")
        for bucket, n in sorted(summary["serve_recompiles_per_bucket"].items()):
            lines.append(f"  {n}x {bucket}")
    if summary.get("compiles_per_incarnation"):
        lines.append("XLA compiles per process incarnation:")
        for inc, n in summary["compiles_per_incarnation"].items():
            lines.append(f"  {n}x {inc}")
    pipe = summary.get("pipeline")
    if pipe:
        lines.append(
            f"\npipeline: {pipe['encoded_batches']} batch(es) through the "
            f"encoder producer — bubble {pipe['bubble_pct']}% "
            f"(encode_wait {pipe['encode_wait_total_ms']} ms vs denoise "
            f"{pipe['denoise_total_ms']} ms), encode-vs-denoise overlap "
            f"{pipe['overlap_pct']}% of {pipe['encode_total_ms']} ms encode")
        lines.append(
            f"  encode_wait p50 {pipe['encode_wait_p50_ms']} ms  "
            f"p99 {pipe['encode_wait_p99_ms']} ms  "
            f"producer data_wait {pipe['data_wait_total_ms']} ms")
    fast = summary.get("fast_sampling")
    if fast:
        lines.append(
            f"\nfast sampling: {fast['trajectories']} trajectory(ies) in "
            f"{fast['executions']} execution(s) — "
            f"{fast['unet_calls_total']} UNet calls for "
            f"{fast['steps_total']} solver steps "
            f"({fast['call_reduction']}x fewer calls, "
            f"{fast['calls_saved_total']} saved)")
        for saved, count in fast["calls_saved_histogram"].items():
            lines.append(f"  {count}x trajectories saved {saved} call(s)")
    mem = summary.get("memory")
    if mem:
        lines.append(
            f"\nmemory: peak {mem['peak_bytes']} bytes across "
            f"{mem['sampled_spans']} sampled span(s), "
            f"{mem['surfaces']} compiled surface(s) accounted")
        for name, row in sorted(mem["resident_delta_by_stage"].items(),
                                key=lambda kv: -abs(kv[1]["delta_bytes"])):
            lines.append(f"  {name:<24} x{row['count']:<6} resident delta "
                         f"{row['delta_bytes']:+d} B  peak "
                         f"{row['peak_bytes']} B")
        for s in mem["top_surfaces_by_temp_bytes"][:5]:
            lines.append(f"  surface {s['surface']:<40} temp "
                         f"{s['temp_bytes']} B  total {s['total_bytes']} B")
    search = summary.get("search")
    if search:
        lines.append("\nsearch:")
        topk = search.get("store_topk")
        if topk:
            lines.append(
                f"  store top-k: {topk['segment_scans']} segment scan(s), "
                f"{topk['rows_scanned']} rows in {topk['total_ms']} ms "
                f"({topk['rows_per_s']} rows/s)  p50 {topk['p50_ms']} ms  "
                f"p99 {topk['p99_ms']} ms")
        brute = search.get("brute_chunks")
        if brute:
            lines.append(
                f"  brute force: {brute['chunks']} chunk(s) in "
                f"{brute['total_ms']} ms  p50 {brute['p50_ms']} ms  "
                f"p99 {brute['p99_ms']} ms")
        ing = search.get("ingest")
        if ing:
            lines.append(
                f"  ingest: {ing['shards']} shard(s), {ing['rows']} rows in "
                f"{ing['total_ms']} ms")
    annsec = summary.get("ann")
    if annsec:
        lines.append("\nANN (IVF approximate search):")
        scan = annsec.get("scan")
        if scan:
            lines.append(
                f"  scan: {scan['segment_scans']} segment scan(s), "
                f"{scan['lists_scanned']} list(s) over "
                f"{scan['rows_scanned']} rows in {scan['total_ms']} ms  "
                f"p50 {scan['p50_ms']} ms  p99 {scan['p99_ms']} ms")
            dist = ", ".join(f"nprobe={k}: x{v}" for k, v in
                             scan["nprobe_distribution"].items())
            lines.append(f"  nprobe distribution: {dist}")
        fun = annsec.get("funnel")
        if fun:
            lines.append(
                f"  funnel: {fun['queries']} query(ies) probed "
                f"{fun['lists_probed']} list(s) -> "
                f"{fun['shortlist_candidates']} shortlist candidate(s) -> "
                f"{fun['reranked_to_top_k']} re-ranked slot(s)")
            lines.append(
                f"  segments: {fun['segments_scanned']} scanned, "
                f"{fun['segments_skipped']} skipped "
                f"({fun['segment_skip_pct']}% skipped)")
        rr = annsec.get("rerank")
        if rr:
            lines.append(
                f"  re-rank: {rr['calls']} call(s), {rr['candidates']} "
                f"candidate(s) in {rr['total_ms']} ms  p50 {rr['p50_ms']} ms"
                f"  p99 {rr['p99_ms']} ms")
        tr = annsec.get("train")
        if tr:
            lines.append(
                f"  train: {tr['lloyd_iters']} Lloyd iteration(s), "
                f"{tr['restarts']} restart(s), {tr['total_ms']} ms")
        rc = annsec.get("recall_spot_checks")
        if rc:
            lines.append(
                f"  recall spot-check: {rc['checks']} check(s) at "
                f"k={rc['k']} over {rc['samples']} query(ies) — "
                f"sample-weighted mean {rc['mean_recall']}, "
                f"min {rc['min_recall']}")
        ro = annsec.get("recall_online")
        if ro:
            lines.append(
                f"  online recall (shadow-oracle probes): {ro['probes']} "
                f"probe(s) at k={ro['k']} over {ro['samples']} query(ies) — "
                f"sample-weighted mean {ro['mean_recall']}, "
                f"last rolling {ro['last_rolling']}")
    ing = summary.get("ingest")
    if ing:
        lines.append("\ningest:")
        ap = ing.get("append")
        if ap:
            lines.append(
                f"  append: {ap['records']} record(s), {ap['rows']} rows "
                f"({ap['rows_per_s']} rows/s)  p50 {ap['p50_ms']} ms  "
                f"p99 {ap['p99_ms']} ms")
        for c in ing.get("compactions", []):
            lines.append(
                f"  {c['time']} compacted {c['rows']} rows "
                f"({c['records']} record(s)) -> snapshot v{c['snapshot']} "
                f"in {c['ms']} ms")
        for rec in ing.get("recoveries", []):
            lines.append(
                f"  {rec['time']} recovered {rec['rows']} rows from "
                f"{rec['segments']} segment(s), {rec['torn']} torn tail(s) "
                f"truncated, in {rec['ms']} ms")
    risk = summary.get("copy_risk")
    if risk:
        lines.append(f"\ncopy risk: {risk['scored']} generation(s) scored, "
                     f"{risk['flagged']} flagged — sim p50 {risk['sim_p50']}"
                     f"  p90 {risk['sim_p90']}  p99 {risk['sim_p99']}"
                     f"  max {risk['sim_max']}")
        for key, count in risk["flagged_train_keys"].items():
            lines.append(f"  {count}x nearest train key {key}")
        for f in risk["flagged_timeline"][:10]:
            lines.append(f"  {f['time']} FLAGGED req {f['request_id']} "
                         f"sim {f['max_sim']} -> {f['top_key']}")
    slo = summary.get("slo")
    if slo:
        counts = ", ".join(
            f"{name}: {st['breaches']} breach(es)/{st['recoveries']} "
            f"recovery(ies)" for name, st in slo["objectives"].items())
        lines.append(f"\nSLO: {counts}")
        if slo["open_breaches"]:
            lines.append(
                "  still in breach at end of trace: "
                + ", ".join(slo["open_breaches"]))
        for t in slo["timeline"]:
            mark = "BREACH " if t["event"] == "breach" else "recover"
            extra = (f"burn {t.get('burn')}" if t["event"] == "breach"
                     else f"after {t.get('breach_s')}s in breach")
            lines.append(
                f"  {t['time']} {mark} {t['objective']:<20} "
                f"value={t.get('value')} target={t.get('target')}  {extra}")
    if summary["fault_timeline"]:
        lines.append("\nfault timeline:")
        for f in summary["fault_timeline"]:
            lines.append(f"  {f['time']} r{f['rank']} {f['name']} {f['args']}")
    else:
        lines.append("\nfault timeline: clean (no fault/* events)")
    flightrecs = sorted({p for d in paths if d.is_dir()
                         for p in d.rglob("flightrec_*.json")})
    if flightrecs:
        lines.append("flight-recorder dumps:")
        for p in flightrecs:
            try:
                reason = json.loads(p.read_text()).get("reason", "?")
            except (OSError, json.JSONDecodeError) as e:
                reason = f"<unreadable: {e}>"
            lines.append(f"  {p.name}: {reason}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.trace_report",
        description="Stage-time breakdown + fault timeline + fleet trace "
                    "merge from trace.jsonl files; optional Chrome-trace "
                    "export.")
    ap.add_argument("paths", type=Path, nargs="+", metavar="PATH",
                    help="directories searched recursively for trace*.jsonl "
                         "(a run's output_dir, a serve --logdir, or a fleet "
                         "dir) and/or individual trace files")
    ap.add_argument("--chrome", type=Path, default=None, metavar="OUT.json",
                    help="also write a Chrome-trace/Perfetto JSON export "
                         "(one track per source process)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of text")
    ap.add_argument("--max-compiles", type=int, default=None, metavar="N",
                    help="recompile budget: fail (exit 3) when any process "
                         "incarnation (stream + os_pid) performed more than "
                         "N XLA compiles (serve/compile events and "
                         "warmcache/compile spans). --max-compiles 0 asserts "
                         "a fully warm run — e.g. a respawned worker served "
                         "entirely from the persistent executable cache")
    args = ap.parse_args(argv)

    for p in args.paths:
        if not p.is_dir() and not p.is_file():
            print(f"trace_report: {p} is not a directory or file",
                  file=sys.stderr)
            return 1
    schema = load_schema()
    records, errors, meta = load_fleet(args.paths, schema)
    if errors:
        for e in errors[:20]:
            print(f"trace_report: SCHEMA: {e}", file=sys.stderr)
        print(f"trace_report: {len(errors)} invalid record(s)", file=sys.stderr)
        return 2
    if not records:
        print(f"trace_report: no trace records under "
              f"{', '.join(map(str, args.paths))} "
              "(no trace*.jsonl, or all files empty)", file=sys.stderr)
        return 1
    summary = summarize(records, meta)
    if args.chrome:
        args.chrome.write_text(json.dumps(chrome_trace(records)))
        print(f"trace_report: wrote chrome trace -> {args.chrome}", file=sys.stderr)
    print(json.dumps(summary, indent=1) if args.json
          else render_text(summary, args.paths))
    if args.max_compiles is not None:
        over = {inc: n for inc, n
                in summary["compiles_per_incarnation"].items()
                if n > args.max_compiles}
        if over:
            for inc, n in over.items():
                print(f"trace_report: RECOMPILE BUDGET: {inc} performed "
                      f"{n} compile(s) > budget {args.max_compiles}",
                      file=sys.stderr)
            return 3
        print(f"trace_report: recompile budget OK (<= {args.max_compiles} "
              f"per incarnation)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
