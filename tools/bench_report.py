"""bench_report: one schema-validated progress table over every banked
``BENCH_*.json`` artifact.

    python -m tools.bench_report [--format=github] [--dir=.]

Every perf PR banks its gate artifact at the repo root (BENCH_SEARCH,
BENCH_ANN, BENCH_INGEST, ...), each with its own shape — which means a
regression in an OLD artifact rots silently: nothing re-reads it, nothing
renders it, CI only ever checks the artifact the current PR touches. This
tool is the anti-rot layer (dcr-slo satellite): it knows the schema of
every banked artifact, extracts each one's gate rows (gate name, banked
value, floor, pass/fail), fails LOUDLY on an unknown ``BENCH_*.json``
(a new bench must register here — silent omission is the failure mode
this tool exists to kill), and exits 1 when any banked gate is failing.

Stdlib-only on purpose: the CI job runs it on a bare checkout next to
the static-analysis gates, before any pip install.

Artifact registry:
- enforced gates (``gate`` blocks, FASTSAMPLE's top-level ``pass``,
  CHAOS's zero-drop + bit-identical pins) become pass/fail rows;
- info-only artifacts (RISK overhead, SERVE/SERVE_FAST speedups) render
  as gate-less rows so the table is the one place to read progress;
- raw run logs (BENCH_PROGRESS_*, BENCH_SAMPLE.jsonl) are explicitly
  skipped, not unknown.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

#: raw run logs and probe dumps — present at the root, not gate artifacts
SKIP_RE = re.compile(r"^BENCH_PROGRESS_.*\.json$")


class SchemaError(ValueError):
    """A banked artifact no longer matches its registered shape."""


def _req(doc: dict, name: str, *keys):
    cur = doc
    for k in keys:
        if not isinstance(cur, dict) or k not in cur:
            raise SchemaError(f"{name}: missing required field "
                              f"{'.'.join(str(x) for x in keys)}")
        cur = cur[k]
    return cur


def _gate_block(doc: dict, name: str, value_key: str, floor_key: str,
                label: str) -> list[dict]:
    """The common ``gate: {passed, <value>, <floor>, enforced}`` shape."""
    gate = _req(doc, name, "gate")
    row = {
        "artifact": name, "gate": label,
        "value": _req(doc, name, "gate", value_key),
        "floor": _req(doc, name, "gate", floor_key),
        "passed": bool(_req(doc, name, "gate", "passed")),
        "enforced": bool(gate.get("enforced", True)),
    }
    return [row]


def _extract_search(doc, name):
    return _gate_block(doc, name, "speedup", "min_speedup",
                       "store speedup vs brute")


def _extract_pipe(doc, name):
    gate = _req(doc, name, "gate")
    return [{"artifact": name,
             "gate": f"{gate.get('mode', 'pipeline')} speedup "
                     f"(bs{gate.get('batch_size', '?')})",
             "value": _req(doc, name, "gate", "speedup"),
             "floor": _req(doc, name, "gate", "min_speedup"),
             "passed": bool(_req(doc, name, "gate", "passed")),
             "enforced": True}]


def _extract_ann(doc, name):
    gate = _req(doc, name, "gate")
    enforced = bool(gate.get("enforced", True))
    return [
        {"artifact": name,
         "gate": f"recall@nprobe={gate.get('nprobe', '?')}",
         "value": _req(doc, name, "gate", "recall"),
         "floor": _req(doc, name, "gate", "min_recall"),
         "passed": bool(gate["passed"]), "enforced": enforced},
        {"artifact": name, "gate": "ann speedup vs exact",
         "value": _req(doc, name, "gate", "speedup"),
         "floor": _req(doc, name, "gate", "min_speedup"),
         "passed": bool(gate["passed"]), "enforced": enforced},
    ]


def _extract_ingest(doc, name):
    rows = _gate_block(doc, name, "rows_per_s", "min_rows_per_s",
                       "append throughput (rows/s)")
    rp = _req(doc, name, "response_path")
    rows.append({"artifact": name, "gate": "response-path added p99 (ms)",
                 "value": _req(doc, name, "response_path", "added_p99_ms"),
                 "floor": rp.get("slack_ms", 1.0), "kind": "max",
                 "passed": bool(_req(doc, name, "response_path", "passed")),
                 "enforced": True})
    return rows


def _extract_fastsample(doc, name):
    point = _req(doc, name, "default_point")
    return [
        {"artifact": name, "gate": "default-point call reduction",
         "value": _req(doc, name, "default_point", "call_reduction"),
         "floor": _req(doc, name, "min_call_reduction"),
         "passed": bool(_req(doc, name, "pass")), "enforced": True},
        {"artifact": name, "gate": "default-point SSCD sim (mean)",
         "value": point.get("sscd_sim_mean"),
         "floor": doc.get("sim_budget_mean"),
         "passed": bool(doc["pass"]), "enforced": True},
    ]


def _extract_chaos(doc, name):
    dropped = _req(doc, name, "dropped_accepted_requests")
    identical = _req(doc, name, "bit_identical_responses")
    return [
        {"artifact": name, "gate": "dropped accepted requests",
         "value": dropped, "floor": 0, "kind": "max",
         "passed": dropped == 0, "enforced": True},
        {"artifact": name, "gate": "bit-identical responses across churn",
         "value": bool(identical), "floor": True,
         "passed": bool(identical), "enforced": True},
        {"artifact": name, "gate": "availability under churn (%)",
         "value": _req(doc, name, "availability_pct"),
         "floor": None, "passed": None, "enforced": False},
    ]


def _extract_risk(doc, name):
    return [{"artifact": name, "gate": "scoring overhead (%)",
             "value": _req(doc, name, "scoring_overhead_pct"),
             "floor": None, "passed": None, "enforced": False}]


def _extract_serve(doc, name):
    return [{"artifact": name, "gate": "batched speedup vs sequential",
             "value": _req(doc, name, "speedup"),
             "floor": None, "passed": None, "enforced": False}]


def _extract_serve_fast(doc, name):
    return [{"artifact": name, "gate": "fast-path call reduction",
             "value": _req(doc, name, "call_reduction"),
             "floor": None, "passed": None, "enforced": False}]


#: artifact basename -> row extractor; every gate-bearing BENCH_* file at
#: the repo root MUST appear here (or in SKIP_RE) or the report fails
EXTRACTORS = {
    "BENCH_SEARCH.json": _extract_search,
    "BENCH_PIPE.json": _extract_pipe,
    "BENCH_ANN.json": _extract_ann,
    "BENCH_INGEST.json": _extract_ingest,
    "BENCH_FASTSAMPLE.json": _extract_fastsample,
    "BENCH_SERVE_CHAOS.json": _extract_chaos,
    "BENCH_RISK.json": _extract_risk,
    "BENCH_SERVE.json": _extract_serve,
    "BENCH_SERVE_FAST.json": _extract_serve_fast,
}


def collect_rows(root: Path) -> tuple[list[dict], list[str]]:
    """(rows, errors) over every BENCH_*.json under ``root``."""
    rows: list[dict] = []
    errors: list[str] = []
    for path in sorted(root.glob("BENCH_*.json")):
        if SKIP_RE.match(path.name):
            continue
        extractor = EXTRACTORS.get(path.name)
        if extractor is None:
            errors.append(f"{path.name}: unknown bench artifact — register "
                          "an extractor in tools/bench_report.py")
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"{path.name}: unreadable: {e}")
            continue
        try:
            rows.extend(extractor(doc, path.name))
        except SchemaError as e:
            errors.append(str(e))
    return rows, errors


def _fmt_val(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, bool):
        return "yes" if v else "no"
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def _fmt_floor(row) -> str:
    if row.get("floor") is None:
        return "(info)"
    sign = "<=" if row.get("kind") == "max" else ">="
    return f"{sign} {_fmt_val(row['floor'])}"


def _status(row) -> str:
    if row.get("passed") is None:
        return "info"
    return "PASS" if row["passed"] else "FAIL"


def render(rows: list[dict], errors: list[str], fmt: str) -> str:
    header = ("artifact", "gate", "banked", "floor", "status")
    table = [(r["artifact"], r["gate"], _fmt_val(r.get("value")),
              _fmt_floor(r), _status(r)) for r in rows]
    lines = []
    if fmt == "github":
        lines.append("| " + " | ".join(header) + " |")
        lines.append("|" + "|".join(" --- " for _ in header) + "|")
        for row in table:
            lines.append("| " + " | ".join(row) + " |")
        for err in errors:
            lines.append(f"| SCHEMA | {err} | - | - | FAIL |")
    else:
        widths = [max(len(h), *(len(r[i]) for r in table)) if table
                  else len(h) for i, h in enumerate(header)]
        lines.append("  ".join(h.ljust(w) for h, w in zip(header, widths)))
        for row in table:
            lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths)))
        for err in errors:
            lines.append(f"SCHEMA FAIL: {err}")
    failed = [r for r in rows if r.get("passed") is False]
    lines.append("")
    lines.append(f"{len(rows)} gate row(s), {len(failed)} failing, "
                 f"{len(errors)} schema error(s)")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="bench_report",
        description="Aggregate banked BENCH_*.json gates into one table.")
    parser.add_argument("--dir", default=".",
                        help="directory holding the banked artifacts")
    parser.add_argument("--format", choices=("plain", "github"),
                        default="plain")
    args = parser.parse_args(argv)
    rows, errors = collect_rows(Path(args.dir))
    print(render(rows, errors, args.format))
    if errors or any(r.get("passed") is False for r in rows):
        return 1
    if not rows:
        print("bench_report: no BENCH_*.json artifacts found",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
