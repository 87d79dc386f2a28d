"""dcr-eval: replication metrics (reference diff_retrieval.py CLI)."""

from __future__ import annotations

import logging
import sys

from dcr_tpu.core.config import EvalConfig, parse_cli
from dcr_tpu.eval.runner import run_eval


def main(argv=None) -> None:
    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    argv = list(sys.argv[1:] if argv is None else argv)
    extra = {}
    rest = []
    for arg in argv:
        for key in ("query_caption_json", "values_caption_json"):
            if arg.startswith(f"--{key}="):
                extra[key] = arg.split("=", 1)[1]
                break
        else:
            rest.append(arg)
    cfg = parse_cli(EvalConfig, rest)
    scalars = run_eval(cfg, **extra)
    logging.getLogger("dcr_tpu").info("eval scalars: %s", scalars)


if __name__ == "__main__":
    main()
