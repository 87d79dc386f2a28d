#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line on standard output is the result, one JSON object; every line
before it (phase seconds, compile counts, cache hits, memory, losses) is for
the reader of a log. See benchmark/README.md.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.lib import harness  # noqa: E402  (stamps the process start)

if __name__ == "__main__":
    sys.exit(harness.main())
