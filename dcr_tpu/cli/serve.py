"""dcr-serve: keep a compiled sampler resident and answer generation requests.

No reference equivalent — somepago/DCR only generates offline (diff_inference
loads, renders a fixed list, exits). One entry point, three roles, selected
by ``fleet.*`` config:

- **single-process** (default, ``fleet.workers == 0``): load the generation
  stack ONCE (the same :func:`load_generation_stack` the bulk pipeline uses,
  so the paths cannot drift), then serve ``POST /generate`` with dynamic
  batching and an embedding cache until SIGTERM;
- **fleet supervisor** (``--fleet.workers=N``): no model load — own the HTTP
  front end, the bounded admission queue, and the durable request journal;
  spawn N worker subprocesses and requeue/respawn around their deaths
  (:mod:`dcr_tpu.serve.supervisor`). Exits 83 on drain like every other
  role, or **1** when the whole fleet failed (every slot retired);
- **fleet worker** (``--fleet.worker_index=I``, spawned by the supervisor):
  single-process serving plus membership — bind port 0, publish the real
  port in a heartbeat-renewed lease, answer ``POST /generate_batch`` from
  the supervisor's dispatch channel.

Every role drains gracefully on SIGTERM:

1. admission stops (new requests get typed 503s, /healthz flips to
   "draining" so balancers rotate the replica out);
2. queued + in-flight batches finish and every accepted request receives
   its response;
3. the process exits with ``coordination.EXIT_PREEMPTED`` (83) — the same
   "clean, restart me" code a preempted trainer uses, so one restart
   wrapper handles both.

A second signal kills the process immediately (escape hatch while stuck in
a compile). A wedged sampler step trips the hang watchdog (exit 89) when
``--hang_timeout_s`` is set, instead of leaving a dead port listening.
"""

from __future__ import annotations

import logging
import os
import tempfile
import threading
from pathlib import Path

from dcr_tpu.core.config import (SampleConfig, ServeConfig, parse_cli,
                                 validate_serve_config)

log = logging.getLogger("dcr_tpu")


def main(argv=None) -> None:
    from dcr_tpu.cli import setup_compile_cache

    setup_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s", force=True)
    cfg = parse_cli(ServeConfig, argv)
    validate_serve_config(cfg)
    if cfg.fleet.workers > 0:
        _run_supervisor(cfg)
    else:
        _run_worker(cfg)


def _run_supervisor(cfg: ServeConfig) -> None:
    """Fleet front end: admission + journal + worker lifecycle, no devices."""
    from dcr_tpu.core import resilience as R
    from dcr_tpu.core import tracing
    from dcr_tpu.core.coordination import EXIT_PREEMPTED
    from dcr_tpu.serve.server import make_server
    from dcr_tpu.serve.supervisor import FleetSupervisor

    if not cfg.fleet.dir:
        # the control plane (leases, journal, worker logs) must live
        # somewhere concrete before the config is serialized for workers
        cfg.fleet.dir = (str(Path(cfg.logdir) / "fleet") if cfg.logdir
                         else tempfile.mkdtemp(prefix="dcr-fleet-"))
    # trace sink falls back to the fleet dir: workers mirror this (their
    # files land under <fleet.dir>/worker_<i>/), so a fleet run is ALWAYS
    # mergeable by `tools/trace_report <fleet.dir>` — one connected span
    # tree per request across supervisor + workers — without any --logdir
    tracing.configure(cfg.logdir or cfg.fleet.dir)

    drained = threading.Event()
    # fleet-fatal (every slot retired) unblocks the same wait as SIGTERM:
    # pending work was already failed with typed errors, so the only thing
    # left is to stop the front end and exit nonzero
    sup = FleetSupervisor(cfg, on_fatal=drained.set)
    sup.start()
    httpd = make_server(cfg, sup)
    server_thread = threading.Thread(target=httpd.serve_forever,
                                     name="serve-http", daemon=True)
    server_thread.start()
    log.info("dcr-serve supervisor listening on http://%s:%d (%d workers, "
             "fleet dir %s, max_batch=%d, queue_depth=%d, "
             "dispatch_timeout=%.0fs)",
             cfg.host, httpd.server_address[1], cfg.fleet.workers,
             cfg.fleet.dir, cfg.max_batch, cfg.queue_depth,
             cfg.fleet.dispatch_timeout_s)

    R.install_signal_drain(lambda signum: drained.set())
    # unbounded BY DESIGN: the main thread's only job is to sleep until the
    # signal handler (or the fleet-fatal callback) fires — there is no peer
    # or producer that could wedge this wait, and any deadline would just
    # turn an idle supervisor into a spurious exit
    drained.wait()  # dcr-lint: disable=DCR009

    fatal = sup.fatal
    log.warning("drain: admission stopped; %d request(s) pending",
                sup.journal.pending_count())
    sup.begin_drain()
    if not fatal and not sup.join_drained(cfg.request_timeout_s):
        R.log_event("fleet_drain_incomplete",
                    pending=sup.journal.pending_count())
    httpd.shutdown()
    httpd.server_close()       # joins handler threads: responses are on the wire
    server_thread.join(timeout=5.0)   # serve_forever returned on shutdown()
    sup.shutdown()
    # re-read: a fleet can fail DURING the drain (every slot exhausting its
    # respawn budget while we wait) — the pre-drain snapshot alone would
    # report that as a clean 83 and a restart wrapper would loop it
    fatal = fatal or sup.fatal
    if fatal:
        # the flight recorder already dumped on the fatal path; exit nonzero
        # so a restart wrapper treats this as a failure, not a preemption
        log.error("fleet failed: every worker slot exhausted its respawn "
                  "budget — exiting 1")
        raise SystemExit(1)
    tracing.dump_flight_recorder("preempted: fleet supervisor drained")
    log.warning("drained: exiting with code %d for the restart wrapper",
                EXIT_PREEMPTED)
    raise SystemExit(EXIT_PREEMPTED)


def _run_worker(cfg: ServeConfig) -> None:
    """Single-process serving; with ``fleet.worker_index >= 0`` also a fleet
    member (lease publish + heartbeat, port learned from the bound socket)."""
    from dcr_tpu.core import dist
    from dcr_tpu.core import resilience as R
    from dcr_tpu.core import tracing
    from dcr_tpu.core.coordination import EXIT_PREEMPTED
    from dcr_tpu.core.metrics import MetricWriter
    from dcr_tpu.models.vae import vae_scale_factor
    from dcr_tpu.sampling.pipeline import load_generation_stack
    from dcr_tpu.serve.server import make_server
    from dcr_tpu.serve.worker import GenerationService

    index = cfg.fleet.worker_index
    logdir = cfg.logdir
    if index >= 0:
        # fault targeting: `@rank=` on serve-side kinds means the worker
        # index (the supervisor exports this too; setdefault keeps a
        # hand-launched worker targetable)
        os.environ.setdefault("DCR_WORKER_INDEX", str(index))
        # per-worker telemetry sink — N workers sharing the supervisor's
        # logdir would interleave writes into one trace.jsonl. Without
        # --logdir a fleet worker falls back to the fleet dir, mirroring
        # the supervisor, so `tools/trace_report <fleet.dir>` always sees
        # every process's file
        base = logdir or cfg.fleet.dir
        logdir = str(Path(base) / f"worker_{index}") if base else None

    dist.initialize()
    if logdir:
        # spans (request trees, compiles, stage boundaries) -> logdir/
        # trace.jsonl; flight-recorder dumps (hang exit 89, drain exit 83)
        # land next to it. Without --logdir the bounded ring still records.
        tracing.configure(logdir)
    with R.stage("serve_load"):
        stack = load_generation_stack(SampleConfig(
            model_path=cfg.model_path, iternum=cfg.iternum,
            resolution=cfg.resolution, mesh=cfg.mesh))
    writer = MetricWriter(logdir, use_tensorboard=False) if logdir else None
    service = GenerationService(cfg, stack, writer=writer)
    # warming state flips BEFORE the port opens: /healthz must never say
    # "ok" while the warm plan (previous incarnation's bucket set + the
    # default bucket) is still compiling / cache-loading
    planned = service.begin_warm()
    service.start()

    httpd = make_server(cfg, service)
    server_thread = threading.Thread(target=httpd.serve_forever,
                                     name="serve-http", daemon=True)
    server_thread.start()
    port = httpd.server_address[1]
    log.info("dcr-serve listening on http://%s:%d (model %s, default bucket "
             "%s, max_batch=%d, max_wait=%.0fms, queue_depth=%d, "
             "warm plan=%d bucket(s)%s)",
             cfg.host, port, cfg.model_path, service.default_bucket(),
             cfg.max_batch, cfg.max_wait_ms, cfg.queue_depth, planned,
             f", cache {cfg.warm.dir}" if cfg.warm.dir else "")

    heartbeat = None
    lease = None
    risk_lease_thread = None
    if index >= 0:
        from dcr_tpu.serve.fleet import (LeaseHeartbeat, WorkerLease,
                                         fleet_paths, write_lease)

        # publish the lease EARLY with ready=False: the supervisor sees a
        # live, warming worker (and spawn_timeout_s covers load + warm
        # start), but attaches no dispatch channel until ready flips — it
        # never dispatches into a cold worker
        paths = fleet_paths(cfg.fleet.dir).ensure()
        lease = WorkerLease(
            index=index, pid=os.getpid(), port=port,
            vae_scale=vae_scale_factor(stack.models.vae.config),
            lease_s=cfg.fleet.lease_s,
            ready=False, buckets_warm=0, buckets_total=planned,
            risk=service.risk_status())
        heartbeat = LeaseHeartbeat(paths, lease,
                                   cfg.fleet.heartbeat_s).start()
        log.info("fleet worker %d warming: lease %s (heartbeat %.1fs, "
                 "lease %.1fs)", index, paths.lease_file(index),
                 cfg.fleet.heartbeat_s, cfg.fleet.lease_s)

    with R.stage("serve_warm"):
        warm = service.warm_start()
    if heartbeat is not None:
        # readiness rides the lease payload: flip + republish immediately
        # (the heartbeat keeps renewing the ready lease from here; counts
        # are written before `ready` so a racing heartbeat can publish a
        # stale-but-warming lease, never a ready-with-stale-counts one)
        lease.buckets_warm = warm["buckets_warm"]
        lease.buckets_total = warm["buckets_total"]
        lease.risk = service.risk_status()
        lease.ready = True
        write_lease(paths, lease)
        log.info("fleet worker %d ready: %d/%d bucket(s) warm in %.2fs "
                 "(risk %s)", index, warm["buckets_warm"],
                 warm["buckets_total"], warm["seconds"],
                 service.risk_status())

    drained = threading.Event()
    R.install_signal_drain(lambda signum: drained.set())

    if lease is not None and cfg.risk.index_path:
        # the risk index loads in the background; republish the lease the
        # moment its status terminalizes (ok | failed) so the supervisor's
        # /check routing and fleet health never act on a stale "loading".
        # Readiness is deliberately NOT gated on it — a failed index load
        # degrades to scoring-disabled, never a worker that won't serve.
        def _sync_risk_lease() -> None:
            while not service.wait_risk_ready(timeout=1.0):
                if drained.is_set():
                    return
            lease.risk = service.risk_status()
            write_lease(paths, lease)
            log.info("fleet worker %d risk index: %s", index,
                     service.risk_status())

        risk_lease_thread = threading.Thread(
            target=_sync_risk_lease, daemon=True, name="risk-lease-sync")
        risk_lease_thread.start()
    # unbounded BY DESIGN: the main thread's only job is to sleep until the
    # signal handler fires — there is no peer or producer that could wedge
    # this wait, and any deadline would just turn an idle server into a
    # spurious exit
    drained.wait()  # dcr-lint: disable=DCR009

    # drain: stop admission -> finish backlog -> flush in-flight responses.
    # The lease keeps renewing THROUGH the drain: the supervisor must not
    # lease-lapse-kill a worker that is finishing accepted work; it learns of
    # the exit from the process table after responses are on the wire.
    log.warning("drain: admission stopped; finishing %d queued request(s)",
                service.queue.depth())
    service.begin_drain()
    if not service.join_drained(timeout=cfg.request_timeout_s):
        R.log_event("serve_drain_incomplete", queued=service.queue.depth())
    httpd.shutdown()
    httpd.server_close()       # joins handler threads: responses are on the wire
    server_thread.join(timeout=5.0)   # serve_forever returned on shutdown()
    if risk_lease_thread is not None:
        risk_lease_thread.join(timeout=2.0)   # exits once drained is set
    if heartbeat is not None:
        heartbeat.stop()
    if writer is not None:
        writer.close()
    # exit-83 path: preserve the final seconds (in-flight request spans,
    # metrics snapshot) for the operator of the restart
    tracing.dump_flight_recorder("preempted: serve drained")
    log.warning("drained: exiting with code %d for the restart wrapper",
                EXIT_PREEMPTED)
    raise SystemExit(EXIT_PREEMPTED)


if __name__ == "__main__":
    main()
