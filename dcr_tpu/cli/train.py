"""dcr-train: finetune the diffusion stack (reference diff_train.py CLI)."""

from __future__ import annotations

import logging

from dcr_tpu.core.config import TrainConfig, parse_cli
from dcr_tpu.diffusion.sample_hook import make_sample_hook
from dcr_tpu.diffusion.trainer import Trainer


def main(argv=None) -> None:
    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    # force=True: orbax/absl imports grab the root logger first, which would
    # silently drop every INFO line (including the resume/recovery messages
    # the fault-tolerance contract requires to be visible)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s", force=True)
    cfg = parse_cli(TrainConfig, argv)
    log = logging.getLogger("dcr_tpu")
    # make an injected run unmistakable in the log from line one: DCR_FAULTS
    # drives the deterministic fault harness (utils/faults.py)
    from dcr_tpu.utils import faults

    reg = faults.registry()
    if reg:
        log.warning("fault injection ACTIVE (DCR_FAULTS): %s", reg.pending())
    if cfg.warm.dir:
        # dcr-warm: after restore, the Trainer pre-populates the train-step
        # and params-finite programs from this persistent executable cache —
        # a preempted pod's first step is a cache load, not an XLA recompile
        log.info("warm cache enabled: %s (train step pre-populated after "
                 "restore)", cfg.warm.dir)
    # periodic sample grids every save_steps (the reference's visual check)
    trainer = Trainer(cfg, sample_hook=make_sample_hook())
    trainer.install_preemption_handler()
    metrics = trainer.train()
    if reg and reg.pending():
        log.warning("fault entries never fired (check coordinates): %s",
                    reg.pending())
    if trainer.preempted_exit:
        from dcr_tpu.core.coordination import EXIT_PREEMPTED

        # distinct, deliberate exit code: the restart wrapper can tell "final
        # checkpoint written, restart me" (EXIT_PREEMPTED) apart from both
        # success (0) and a crash — every rank of a pod exits with it together
        log.warning("preempted: final checkpoint written; exiting with code "
                    "%d for the restart wrapper", EXIT_PREEMPTED)
        raise SystemExit(EXIT_PREEMPTED)
    log.info("training done: %s", metrics)


if __name__ == "__main__":
    main()
