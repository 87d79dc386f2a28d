"""Host data loader: deterministic sampling plan + threaded prefetch.

Replaces the reference's torch DataLoader + WeightedRandomSampler stack
(diff_train.py:470-487) with a TPU-host-friendly design:

- a *sampling plan* is computed up front per (seed, epoch): weighted-with-
  replacement under dup regimes, shuffled otherwise — so every process knows
  the full global order and takes its own slice (no sampler state to sync);
- worker threads decode/augment (PIL releases the GIL for the heavy parts) into
  a bounded queue; batches are contiguous numpy, ready for shard_batch;
- iteration order is fully reproducible given (seed, epoch), including across
  restarts mid-epoch via `start_step`.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional

import numpy as np

from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing
from dcr_tpu.core.config import FaultToleranceConfig
from dcr_tpu.data import duplication as D
from dcr_tpu.data.dataset import ObjectAttributeDataset


class Batch(dict):
    """dict with attribute access: pixel_values [B,H,W,3], input_ids [B,L],
    index [B]."""

    __getattr__ = dict.__getitem__


class TooManyBadSamples(RuntimeError):
    """The epoch's quarantine budget (fault.max_bad_sample_frac) is spent."""


def sampling_plan(dataset: ObjectAttributeDataset, *, epoch: int,
                  seed: int) -> np.ndarray:
    """Global epoch order. Under dup_both/dup_image: weighted WITH replacement
    (the duplication mechanism itself — reference diff_train.py:470-479);
    otherwise a plain shuffle."""
    n = len(dataset)
    if dataset.cfg.duplication in ("dup_both", "dup_image"):
        weights = np.asarray(dataset.sampling_weights)[dataset.active_indices]
        return D.weighted_sample_indices(weights, n, seed, epoch)
    return D.shuffled_indices(n, seed, epoch)


class DataLoader:
    def __init__(self, dataset: ObjectAttributeDataset, *, batch_size: int,
                 num_workers: int = 8, seed: int = 0,
                 process_index: int = 0, process_count: int = 1,
                 drop_last: bool = True, prefetch: int = 4,
                 fault: Optional[FaultToleranceConfig] = None,
                 quarantine: Optional[R.QuarantineManifest] = None,
                 defer_budget_abort: bool = False):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.dataset = dataset
        self.global_batch_size = batch_size * process_count
        self.batch_size = batch_size
        self.num_workers = max(1, num_workers)
        self.seed = seed
        self.process_index = process_index
        self.process_count = process_count
        self.drop_last = drop_last
        self.prefetch = prefetch
        # fault=None (or max_bad_sample_frac=0) keeps the seed's fail-fast
        # contract: the first bad sample kills the epoch
        self.fault = fault
        self.quarantine = quarantine
        self.bad_samples = 0  # run-total, surfaced as faults/bad_samples
        self._bad_lock = threading.Lock()
        self._epoch_bad = [0]  # rebound per epoch(); read via epoch_bad_count
        # multi-host: a loader worker must NOT raise TooManyBadSamples
        # unilaterally — the budget is pod-global, and one host unwinding
        # while peers enter the next agreement round hangs the pod. The
        # trainer sets this on sliced multi-host loaders and aborts through
        # the fault-agreement word instead (bounded by one log window).
        self.defer_budget_abort = defer_budget_abort
        if len(dataset) < self.global_batch_size and drop_last:
            raise ValueError(
                f"dataset of {len(dataset)} samples can't fill one global batch "
                f"of {self.global_batch_size}")

    def steps_per_epoch(self) -> int:
        return len(self.dataset) // self.global_batch_size

    @property
    def epoch_bad_count(self) -> int:
        """Bad samples quarantined by THIS process in the current epoch —
        the local contribution to the pod-global budget agreement
        (core/coordination.py)."""
        return self._epoch_bad[0]

    def epoch_bad_budget(self) -> int:
        """The epoch's quarantine budget in samples, over the GLOBAL epoch
        (multi-host: hosts compare the summed count against this at agreement
        boundaries — per-host counts can each look fine while the pod as a
        whole is past the line)."""
        budget_frac = self.fault.max_bad_sample_frac if self.fault else 0.0
        return int(budget_frac * self.steps_per_epoch() * self.global_batch_size)

    def epoch(self, epoch: int, start_step: int = 0) -> Iterator[Batch]:
        """Yield this process's local batches for one epoch.

        Bad samples (decode failures after the dataset's own retries, or
        injected ``decode_error`` faults) are quarantined when
        ``fault.max_bad_sample_frac > 0``: the occurrence is replaced by a
        deterministic redraw from the same epoch plan (the next plan slot that
        decodes — the example another step would legitimately produce there,
        so the substitution is reproducible across restarts and processes),
        recorded in the quarantine manifest, and counted against the epoch's
        budget. Past the budget — or with the default budget of 0 — the error
        propagates to the consumer exactly as in the seed.
        """
        plan = sampling_plan(self.dataset, epoch=epoch, seed=self.seed)
        steps = self.steps_per_epoch()
        out_q: "queue.Queue[tuple[int, Optional[Batch], Optional[BaseException]]]" = (
            queue.Queue(maxsize=self.prefetch))
        stop = threading.Event()
        budget_frac = self.fault.max_bad_sample_frac if self.fault else 0.0
        epoch_budget = self.epoch_bad_budget()
        epoch_bad = [0]  # shared across workers, guarded by _bad_lock
        self._epoch_bad = epoch_bad  # published for the global-budget agreement

        def fetch(step: int, slot: int):
            from dcr_tpu.utils import faults

            position = int(plan[slot])
            # the `index` coordinate is the DATASET index — the same value the
            # quarantine manifest records for this occurrence
            if faults.fire("decode_error", step=step, slot=slot,
                           index=int(self.dataset.active_indices[position]),
                           epoch=epoch):
                raise faults.InjectedFault(
                    f"decode_error at epoch={epoch} step={step} slot={slot}")
            return self.dataset.get(position, epoch=epoch, slot=slot)

        def fetch_or_replace(step: int, slot: int):
            try:
                return fetch(step, slot)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as err:
                return self._replace(err, plan=plan, epoch=epoch, step=step,
                                     slot=slot, fetch=fetch,
                                     epoch_bad=epoch_bad,
                                     epoch_budget=epoch_budget,
                                     budget_frac=budget_frac)

        def make_batch(step: int) -> Batch:
            # one span per decoded batch, on the worker thread that built it:
            # the trace separates decode/augment work (here) from the train
            # thread's wait (train/data_wait) — the pair answers "is the host
            # keeping the chip fed"
            base = step * self.global_batch_size + self.process_index * self.batch_size
            with tracing.span("data/batch", step=step, epoch=epoch):
                examples = [fetch_or_replace(step, base + j)
                            for j in range(self.batch_size)]
                return Batch(
                    pixel_values=np.stack([e.pixel_values for e in examples]),
                    input_ids=np.stack([e.input_ids for e in examples]),
                    index=np.asarray([e.index for e in examples], np.int64),
                )

        def safe_put(item) -> bool:
            # never block forever: re-check stop so consumer-side teardown can't
            # leave producers pinned in put() holding decoded batches
            while not stop.is_set():
                try:
                    out_q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker(worker_id: int) -> None:
            for step in range(start_step + worker_id, steps, self.num_workers):
                if stop.is_set():
                    return
                try:
                    if not safe_put((step, make_batch(step), None)):
                        return
                except BaseException as e:  # surface decode errors to the consumer
                    safe_put((step, None, e))
                    return

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(self.num_workers)]
        pending: dict[int, Batch] = {}

        def take(step: int) -> Batch:
            while step not in pending:
                got_step, batch, err = out_q.get()
                if err is not None:
                    raise err
                pending[got_step] = batch
            return pending.pop(step)

        # consumer-side spans, children of the caller's train/data_wait:
        # data/fill is the refill bubble every epoch opens with (workers'
        # start to the first batch in hand), data/wait each later wait on
        # the queue. Neither stays open across a yield: the generator hands
        # control back with the context variable as it found it.
        tracing.registry().gauge("data/workers").set(self.num_workers)
        try:
            if start_step < steps:
                with tracing.span("data/fill", epoch=epoch, step=start_step):
                    for t in threads:
                        t.start()
                    batch = take(start_step)
                yield batch
            for step in range(start_step + 1, steps):
                if step in pending:     # came out of order, already in hand
                    batch = pending.pop(step)
                else:
                    with tracing.span("data/wait", step=step, epoch=epoch):
                        batch = take(step)
                yield batch
        finally:
            stop.set()
            # drain until every worker has exited (safe_put re-checks stop, so
            # this terminates promptly)
            for t in threads:
                while t.is_alive():
                    try:
                        out_q.get_nowait()
                    except queue.Empty:
                        t.join(timeout=0.05)

    def _replace(self, err: BaseException, *, plan: np.ndarray, epoch: int,
                 step: int, slot: int, fetch, epoch_bad: list,
                 epoch_budget: int, budget_frac: float):
        """Quarantine a bad occurrence and return its deterministic
        replacement, or re-raise when recovery is disabled / budget is spent.
        Thread-safe: loader workers hit this concurrently."""
        ds = self.dataset
        bad_position = int(plan[slot])
        bad_index = int(ds.active_indices[bad_position])
        if budget_frac <= 0:
            raise err  # seed behavior: no quarantine budget configured
        with self._bad_lock:
            epoch_bad[0] += 1
            self.bad_samples += 1
            n_bad = epoch_bad[0]
        if n_bad > epoch_budget and not self.defer_budget_abort:
            raise TooManyBadSamples(
                f"epoch {epoch}: {n_bad} bad samples exceed the quarantine "
                f"budget of {epoch_budget} (max_bad_sample_frac={budget_frac} "
                f"of {len(plan)} samples); last failure: {err!r}") from err
        # deterministic redraw from the SAME epoch plan: walk forward to the
        # next slot whose sample decodes — (epoch, slot) fully determine the
        # example, so every process/restart substitutes identically
        last: BaseException = err
        for k in range(1, len(plan)):
            cand = (slot + k) % len(plan)
            try:
                example = fetch(step, cand)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as cand_err:
                last = cand_err
                continue
            if self.quarantine is not None:
                self.quarantine.record(
                    "bad_sample", epoch=epoch, step=step, slot=slot,
                    index=bad_index, path=ds.paths[bad_index],
                    replacement_slot=cand,
                    replacement_index=int(ds.active_indices[int(plan[cand])]),
                    error=repr(err))
            else:
                R.log_event("bad_sample_replaced", epoch=epoch, step=step,
                            slot=slot, index=bad_index, replacement_slot=cand,
                            error=repr(err))
            return example
        raise TooManyBadSamples(
            f"epoch {epoch}: no decodable replacement found in the entire "
            f"plan ({len(plan)} slots); last failure: {last!r}") from err
