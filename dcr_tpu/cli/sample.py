"""dcr-sample: bulk generation from a checkpoint (reference diff_inference.py).

The conditioning style comes from the run's serialized config.json when
present (replacing the reference's parse-the-path-substring heuristics,
diff_inference.py:230-239); --modelstyle overrides explicitly.
"""

from __future__ import annotations

import json
import logging
import sys
from pathlib import Path

from dcr_tpu.core.config import SampleConfig, parse_cli
from dcr_tpu.sampling.pipeline import generate


def infer_modelstyle(model_path: str) -> str:
    """Conditioning regime from the run's config.json; falls back to
    "nolevel" — LOUDLY, never silently (DCR006 discipline): a config.json
    that exists but lacks data.class_prompt usually means a foreign or
    truncated run dir, and a silent fallback would sample with the wrong
    prompt regime and poison every downstream replication metric."""
    cfg_file = Path(model_path) / "config.json"
    if cfg_file.exists():
        try:
            return json.loads(cfg_file.read_text())["data"]["class_prompt"]
        except (KeyError, TypeError, json.JSONDecodeError) as e:
            from dcr_tpu.core.resilience import log_event

            log_event("modelstyle_fallback", path=str(cfg_file),
                      missing_key="data.class_prompt", error=repr(e),
                      fallback="nolevel")
    return "nolevel"


def main(argv=None) -> None:
    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    argv = list(sys.argv[1:] if argv is None else argv)
    modelstyle = None
    caption_json = None
    rest = []
    for arg in argv:
        if arg.startswith("--modelstyle="):
            modelstyle = arg.split("=", 1)[1]
        elif arg.startswith("--caption_json="):
            caption_json = arg.split("=", 1)[1]
        else:
            rest.append(arg)
    cfg = parse_cli(SampleConfig, rest)
    modelstyle = modelstyle or infer_modelstyle(cfg.model_path)
    out = generate(cfg, modelstyle=modelstyle, caption_json=caption_json)
    logging.getLogger("dcr_tpu").info("generations -> %s", out)


if __name__ == "__main__":
    main()
