"""Checkpointing: orbax-backed save/restore of params + optimizer + step + config.

Fills a genuine gap in the reference: its trainer saves model weights only
(rank-0 ``save_pretrained`` at diff_train.py:709-728) and **cannot resume** —
no optimizer/LR/step state is ever written (SURVEY.md §5.4). Here every
checkpoint carries the full train state, written asynchronously so the TPU never
idles on host I/O, which is what preemptible pods need (SURVEY.md §5.3).

Layout of <output_dir>:
  config.json                  full serialized TrainConfig
  checkpoints/<step>/          orbax composite: state (params/opt/step), ema
  checkpoints/manifests/<step>.json   content manifest (tree + checksums)
  checkpoints/quarantined/<step>/     corrupt steps moved aside, never retried
A separate exporter writes the HF-style directory-of-subfolders layout
(unet/, vae/, text_encoder/, scheduler/) for interop with the reference's
inference convention (diff_inference.py:83-88).

Integrity: every save writes a per-step content manifest (flattened tree key
-> crc32/shape/dtype of the host bytes) BEFORE the async orbax write begins,
so a torn/corrupt checkpoint is detectable on restore even when orbax itself
deserializes it without complaint. :meth:`restore_latest_valid` walks
``all_steps()`` newest-first, quarantines steps that fail to restore or fail
verification, and returns the newest valid one — preemptible-pod resume never
dies on a torn latest checkpoint (the seed raised instead).
"""

from __future__ import annotations

import json
import logging
import shutil
import zlib
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from dcr_tpu.core import dist
from dcr_tpu.core import fsio
from dcr_tpu.core import resilience as R
from dcr_tpu.core import tracing
from dcr_tpu.core.config import TEXT_TOWERS

log = logging.getLogger("dcr_tpu")

MANIFEST_FORMAT = 1


class CheckpointCorrupt(RuntimeError):
    """An explicitly-requested checkpoint failed integrity verification."""


def _leaf_key(path) -> str:
    return jax.tree_util.keystr(path)


def _host_view(leaf: Any) -> tuple[np.ndarray, tuple, str]:
    """(host bytes, GLOBAL shape, dtype) of a leaf for checksumming.

    Fully-addressable or fully-replicated arrays fetch whole. A multi-host
    sharded array contributes only this host's addressable shards,
    concatenated in device-placement order — deterministic for a fixed
    sharding, so the per-process manifest written at save time verifies the
    same host's restore (trainers shard state identically across a run)."""
    if (isinstance(leaf, jax.Array) and not leaf.is_fully_addressable
            and not leaf.is_fully_replicated):
        shards = sorted(leaf.addressable_shards,
                        key=lambda s: tuple(sl.start or 0 for sl in s.index))
        flat = np.concatenate([np.asarray(s.data).ravel() for s in shards])
        return flat, tuple(leaf.shape), str(leaf.dtype)
    arr = _fetch_uncached(leaf)
    return arr, tuple(arr.shape), str(arr.dtype)


def host_copy(tree: Any) -> Any:
    """The tree fetched to host numpy, leaf by leaf, with nothing left cached
    on the device arrays (see :func:`_fetch_uncached`)."""
    return jax.tree.map(_fetch_uncached, tree)


def _fetch_uncached(leaf: Any) -> np.ndarray:
    """Host copy of a fully-addressable leaf that does NOT stay cached on it.

    ``np.asarray(jax.Array)`` keeps the fetched copy alive on the array for
    as long as the array lives. Hashing a whole TrainState that way parks a
    second copy of the state in host RAM beside the one orbax's own
    device->host transfer makes: at SD-2.1 widths 12 GB + 12 GB on top of
    the TPU runtime's 14 GB, which ended the first save on a 40 GiB v5e host
    (PR 24; measured there: the copy is freed only with the array). A
    throwaway Array over the same device buffers takes its cache with it."""
    if not isinstance(leaf, jax.Array) or jax.dtypes.issubdtype(
            leaf.dtype, jax.dtypes.extended):
        return np.asarray(jax.device_get(leaf))
    view = jax.make_array_from_single_device_arrays(
        leaf.shape, leaf.sharding,
        [shard.data for shard in leaf.addressable_shards])
    return np.asarray(view)


def state_manifest(state: Any) -> dict:
    """Flattened-tree content manifest: per-leaf crc32 of the host bytes plus
    shape/dtype. crc32 is not cryptographic — the adversary is a torn write or
    bit rot, not tampering — and costs ~1GB/s on one core. Multi-host: each
    process manifests its own addressable view (see :func:`_host_view`) into
    its own per-process file, so no host ever touches non-addressable data."""
    leaves = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    for path, leaf in flat:
        arr, shape, dtype = _host_view(leaf)
        leaves[_leaf_key(path)] = {
            "crc32": zlib.crc32(np.ascontiguousarray(arr).tobytes()),
            "shape": list(shape),
            "dtype": dtype,
        }
    return {"format": MANIFEST_FORMAT, "leaves": leaves}


def verify_manifest(manifest: dict, state: Any) -> list[str]:
    """Mismatch descriptions ([] = valid) between a restored state and the
    manifest written at save time."""
    expected = manifest.get("leaves", {})
    problems: list[str] = []
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    seen = set()
    for path, leaf in flat:
        key = _leaf_key(path)
        seen.add(key)
        want = expected.get(key)
        if want is None:
            problems.append(f"{key}: leaf not in manifest")
            continue
        arr, shape, dtype = _host_view(leaf)
        if list(shape) != want["shape"] or dtype != want["dtype"]:
            problems.append(f"{key}: shape/dtype {shape}/{dtype} != "
                            f"{want['shape']}/{want['dtype']}")
        elif zlib.crc32(np.ascontiguousarray(arr).tobytes()) != want["crc32"]:
            problems.append(f"{key}: checksum mismatch")
    for key in set(expected) - seen:
        problems.append(f"{key}: missing from restored state")
    return problems


class CheckpointManager:
    """Checkpoint manager with per-step integrity manifests and
    quarantine-and-fall-back restore, over one of two storage backends:

    - **orbax** (TPU/GPU): async by default so the accelerator never idles on
      host I/O; sharded tensorstore writes (collective across processes).
    - **npz** (CPU, any process count): one ``<step>/state.npz`` per step,
      committed by atomic directory rename. The orbax/tensorstore native
      stack is memory-unsafe on the CPU backend in this environment
      (use-after-free heap aborts — glibc 'corrupted size vs. prev_size' —
      and checkpoints silently containing later-step bytes, both caught by
      the content manifests); CPU runs are tests/smoke only, so a plain
      numpy format loses nothing and removes every native thread from the
      path. Multi-process CPU (the coordination tests' regime): process 0
      writes the replicated state, every process joins a commit barrier, and
      restore rebuilds global arrays from the shared file. Both backends
      share the same manifest/quarantine semantics.

    Multi-host: pass a ``coordinator`` (core/coordination.py) and
    :meth:`restore_latest_valid` AGREES the fallback choice across hosts —
    each round proposes the newest local step, takes the pod-wide minimum,
    validates it everywhere, and only returns when every host restored the
    same step; a step any host rejects is quarantined pod-wide.
    """

    def __init__(self, directory: str | Path, *, max_to_keep: int = 3,
                 async_save: bool = True, verify: bool = True,
                 quarantine: Optional[R.QuarantineManifest] = None,
                 coordinator: Optional[Any] = None):
        self._dir = Path(directory).absolute()
        self._dir.mkdir(parents=True, exist_ok=True)
        self._npz = jax.default_backend() == "cpu"
        self._max_to_keep = max_to_keep
        self._coordinator = coordinator
        self._barrier_timeout = float(getattr(coordinator, "timeout_s", 0.0) or 0.0)
        if self._npz:
            self._mgr = None
        else:
            options = ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=async_save,
            )
            self._mgr = ocp.CheckpointManager(self._dir, options=options)
        self._verify = verify
        self._quarantine = quarantine
        self._manifest_dir = self._dir / "manifests"

    # -- npz backend (single-process CPU) ------------------------------------

    def _npz_steps(self) -> list[int]:
        return sorted(int(d.name) for d in self._dir.iterdir()
                      if d.is_dir() and d.name.isdigit()
                      and (d / "state.npz").exists())

    def _npz_save(self, step: int, state: Any) -> bool:
        # Barrier discipline on >1 process: every rank reaches the SAME
        # barriers in the SAME order no matter what the writer does, or the
        # pod deadlocks. Writer errors are deferred past the commit barrier.
        error: Optional[BaseException] = None
        if jax.process_index() == 0:
            try:
                flat, _ = jax.tree_util.tree_flatten_with_path(state)
                arrays = {}
                for path, leaf in flat:
                    if (isinstance(leaf, jax.Array)
                            and not leaf.is_fully_addressable
                            and not leaf.is_fully_replicated):
                        raise CheckpointCorrupt(
                            f"npz backend cannot save host-sharded leaf "
                            f"{_leaf_key(path)} (multi-process CPU requires "
                            "replicated state; use the orbax backend)")
                    arrays[_leaf_key(path)] = np.asarray(jax.device_get(leaf))
                tmp = self._dir / f"{step}.tmp-npz"
                if tmp.exists():
                    shutil.rmtree(tmp)
                tmp.mkdir(parents=True)
                np.savez(tmp / "state.npz", **arrays)
                # np.savez closed the file but its blocks may still be
                # page-cache-only: fsync file + dir before the atomic commit
                fsio.fsync_file(tmp / "state.npz")
                fsio.fsync_dir(tmp)
                tmp.replace(self._dir / str(step))  # atomic commit
                # retention, oldest first (matches orbax max_to_keep)
                steps = self._npz_steps()
                for old in steps[: max(0, len(steps) - self._max_to_keep)]:
                    shutil.rmtree(self._dir / str(old), ignore_errors=True)
            except BaseException as e:
                error = e
        if jax.process_count() > 1:
            # commit outcome agreement: peers must not report (or act on)
            # saved=True for a step the writer failed to commit — on the
            # preemption path that would exit EXIT_PREEMPTED claiming a final
            # checkpoint that does not exist. Doubles as the commit barrier:
            # no host proceeds before the write is visible on the shared fs.
            oks = dist.kv_allgather(str(int(error is None)),
                                    f"ckpt_save_ok:{step}",
                                    timeout_s=self._barrier_timeout)
            if oks[0] != "1":  # the writer (rank 0) reported failure
                if error is not None:
                    raise error
                raise CheckpointCorrupt(
                    f"step {step}: primary host failed to commit the npz "
                    f"checkpoint (see its log); refusing to report saved")
        elif error is not None:
            raise error
        return True

    def _npz_restore(self, step: int, state_like: Any) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(state_like)
        leaves = []
        multiproc = jax.process_count() > 1
        with np.load(self._dir / str(step) / "state.npz") as z:
            for path, like in flat:
                key = _leaf_key(path)
                if key not in z.files:
                    raise CheckpointCorrupt(
                        f"step {step}: leaf {key} missing from state.npz")
                arr = z[key]
                if tuple(arr.shape) != tuple(like.shape) or \
                        str(arr.dtype) != str(np.dtype(like.dtype)):
                    raise CheckpointCorrupt(
                        f"step {step}: leaf {key} is {arr.shape}/{arr.dtype}, "
                        f"expected {tuple(like.shape)}/{like.dtype}")
                sharding = getattr(like, "sharding", None)
                if sharding is not None and multiproc:
                    # global array spanning processes: every host read the
                    # shared file, each contributes its addressable pieces
                    leaves.append(jax.make_array_from_callback(
                        tuple(like.shape), sharding,
                        lambda idx, a=arr: a[idx]))
                elif sharding is not None:
                    leaves.append(jax.device_put(arr, sharding))
                else:
                    leaves.append(jnp.asarray(arr))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # -- manifests -----------------------------------------------------------

    def _manifest_path(self, step: int) -> Path:
        # one manifest file per process: each host checksums only its own
        # addressable view (see _host_view), and a shared filesystem never
        # sees two hosts racing writes to the same path
        if jax.process_count() == 1:
            return self._manifest_dir / f"{step}.json"
        return self._manifest_dir / f"{step}.p{jax.process_index()}.json"

    def _write_manifest(self, step: int, state: Any) -> None:
        # written synchronously BEFORE the async orbax save: a crash mid-save
        # leaves an orphan manifest (harmless), never an unverifiable step
        self._manifest_dir.mkdir(parents=True, exist_ok=True)
        manifest = {"step": step, **state_manifest(state)}
        tmp = self._manifest_path(step).with_suffix(".tmp")
        fsio.publish_durable(tmp, self._manifest_path(step),
                             json.dumps(manifest, sort_keys=True))

    def _load_manifest(self, step: int) -> Optional[dict]:
        path = self._manifest_path(step)
        if not path.exists():
            return None  # pre-manifest checkpoint: accepted, logged
        return json.loads(R.read_text_with_retry(path, name=f"manifest:{step}"))

    def _prune_manifests(self, keep: Optional[int] = None) -> None:
        if not self._manifest_dir.exists():
            return
        live = set(self.all_steps())
        if keep is not None:
            live.add(keep)  # the in-flight async save may not be listed yet
        for mf in self._manifest_dir.glob("*.json"):
            try:
                # stems are "<step>" or "<step>.p<rank>" (per-process)
                if int(mf.stem.split(".")[0]) not in live:
                    mf.unlink(missing_ok=True)  # peers prune concurrently
            except ValueError:
                continue

    # -- save/restore --------------------------------------------------------

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        if self._npz and jax.process_count() > 1:
            # align views BEFORE the existence check: without this, a rank
            # arriving after the primary's commit would take the idempotent
            # early return below while the primary waits alone at the commit
            # barrier inside _npz_save — a pod deadlock
            dist.barrier(f"ckpt_save_enter:{step}",
                         timeout_s=self._barrier_timeout)
        if step in self.all_steps():
            return False  # idempotent: final save may coincide with a periodic one
        # the span covers manifest hashing + the save *dispatch*; the orbax
        # backend writes asynchronously, so blocking time (what the train loop
        # actually lost) is exactly what this measures
        with tracing.span("ckpt/save", step=int(step)):
            if self._verify:
                self._write_manifest(step, state)
            if self._npz:
                saved = self._npz_save(step, state)
            else:
                saved = self._mgr.save(step, args=ocp.args.StandardSave(state),
                                       force=force)
        if saved:
            log.info("checkpoint saved at step %d -> %s", step, self._dir / str(step))
            self._prune_manifests(keep=step)
            from dcr_tpu.utils import faults

            if faults.fire("ckpt_corrupt", step=step):
                self.wait()
                _corrupt_step_dir(self._dir / str(step))
        return saved

    def _backend_restore(self, step: int, state_like: Any) -> Any:
        with tracing.span("ckpt/restore", step=int(step)):
            return self._backend_restore_impl(step, state_like)

    def _backend_restore_impl(self, step: int, state_like: Any) -> Any:
        if self._npz:
            state = self._npz_restore(step, state_like)
        else:
            state = self._mgr.restore(
                step, args=ocp.args.StandardRestore(state_like))
        if jax.default_backend() == "cpu":
            # device_put of host numpy on the CPU backend is ZERO-COPY: the
            # jax array aliases numpy-owned memory, and the train step's
            # donate_argnums then frees/reuses a buffer XLA does not own —
            # observed as glibc heap aborts and restored params scrambling
            # to NaN within a step or two. A jitted copy materializes the
            # tree into XLA-owned buffers (outputs never alias inputs
            # without donation), making the restored state donation-safe.
            state = _materialize(state)
        return state

    def restore(self, state_like: Any, step: Optional[int] = None) -> Any:
        """Restore one explicit step (or the latest), verifying its manifest
        when available. An explicitly-requested corrupt step raises
        :class:`CheckpointCorrupt` — only :meth:`restore_latest_valid` walks
        back silently."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self._dir}")
        state = self._backend_restore(step, state_like)
        if self._verify:
            manifest = self._load_manifest(step)
            if manifest is not None:
                problems = verify_manifest(manifest, state)
                if problems:
                    raise CheckpointCorrupt(
                        f"checkpoint step {step} failed verification "
                        f"({len(problems)} mismatches): {'; '.join(problems[:5])}")
        return state

    def _try_restore_verified(self, step: int, state_like: Any) -> tuple[bool, Any]:
        """(True, state) when ``step`` restores and passes its manifest;
        (False, reason) otherwise. Never raises on a bad step."""
        try:
            state = self._backend_restore(step, state_like)
            manifest = self._load_manifest(step) if self._verify else None
            if manifest is None:
                if self._verify:
                    log.info("checkpoint step %d has no manifest "
                             "(pre-manifest save): accepted unverified", step)
                return True, state
            problems = verify_manifest(manifest, state)
            if not problems:
                return True, state
            return False, f"verification failed: {'; '.join(problems[:3])}"
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # orbax raises many types on torn dirs
            return False, f"restore raised: {e!r}"

    def restore_latest_valid(self, state_like: Any) -> tuple[Any, int, list[tuple[int, str]]]:
        """(state, step, skipped): walk ``all_steps()`` newest-first to the
        newest checkpoint that restores AND verifies; quarantine every bad
        step on the way (moved to ``quarantined/<step>``, recorded, logged) so
        it is never retried. Raises FileNotFoundError only when no valid
        checkpoint exists at all.

        Multi-host (a coordinator was supplied): the choice is AGREED — see
        :meth:`_restore_latest_valid_coordinated` — so every host resumes from
        the identical step even when hosts observe different corruption."""
        self.wait()
        if (self._coordinator is not None
                and getattr(self._coordinator, "process_count", 1) > 1):
            return self._restore_latest_valid_coordinated(state_like)
        skipped: list[tuple[int, str]] = []
        while True:
            steps = sorted(self.all_steps(), reverse=True)
            if not steps:
                if skipped:
                    raise FileNotFoundError(
                        f"no valid checkpoint under {self._dir}: all "
                        f"{len(skipped)} steps quarantined ({skipped})")
                raise FileNotFoundError(f"no checkpoints under {self._dir}")
            step = steps[0]
            ok, payload = self._try_restore_verified(step, state_like)
            if ok:
                return payload, step, skipped
            self._quarantine_step(step, payload)
            skipped.append((step, payload))

    def _restore_latest_valid_coordinated(self, state_like: Any) -> tuple[Any, int, list[tuple[int, str]]]:
        """Pod-wide agreement loop: each round every host proposes its newest
        available step, the pod takes the minimum (the newest step EVERY host
        can see), every host validates that step, and a second agreement
        confirms all hosts succeeded. A step any host rejects is quarantined
        everywhere (concurrent moves on a shared filesystem are tolerated)
        and the loop re-proposes — so divergent local corruption can never
        make two hosts resume from different steps."""
        coord = self._coordinator
        skipped: list[tuple[int, str]] = []
        while True:
            steps = self.all_steps()
            candidate = max(steps) if steps else -1
            proposals = coord.agree_int(candidate, "ckpt_candidate")
            agreed = min(proposals)
            if agreed < 0:
                raise FileNotFoundError(
                    f"no checkpoint available on every host under {self._dir}: "
                    f"per-rank proposals {proposals}, skipped {skipped}")
            ok, payload = self._try_restore_verified(agreed, state_like)
            oks = coord.agree_int(int(ok), "ckpt_valid")
            if all(oks):
                return payload, agreed, skipped
            reason = (payload if not ok else
                      f"peer host failed validation of step {agreed} (oks={oks})")
            self._quarantine_step(agreed, reason)
            skipped.append((agreed, reason))

    def _quarantine_step(self, step: int, reason: str) -> None:
        src = self._dir / str(step)
        dst = self._dir / "quarantined" / str(step)
        dst.parent.mkdir(parents=True, exist_ok=True)
        if src.exists() and not dst.exists():
            try:
                shutil.move(str(src), str(dst))
            except OSError as e:  # a peer host on the shared fs moved it first
                log.info("quarantine move of step %d raced a peer: %r", step, e)
        if self._mgr is not None:
            self._mgr.reload()  # drop the moved step from orbax's cached list
        R.log_event("ckpt_quarantined", step=step, reason=reason,
                    moved_to=str(dst))
        if self._quarantine is not None:
            self._quarantine.record("bad_checkpoint", step=step, reason=reason,
                                    moved_to=str(dst))

    def latest_step(self) -> Optional[int]:
        if self._npz:
            steps = self._npz_steps()
            return steps[-1] if steps else None
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        if self._npz:
            return self._npz_steps()
        return list(self._mgr.all_steps())

    def wait(self) -> None:
        if self._mgr is not None:
            self._mgr.wait_until_finished()

    def close(self) -> None:
        if self._mgr is not None:
            self._mgr.wait_until_finished()
            self._mgr.close()


@jax.jit
def _materialize(tree: Any) -> Any:
    """Copy every leaf into fresh XLA-owned buffers (see _backend_restore)."""
    return jax.tree.map(jnp.copy, tree)


def _corrupt_step_dir(step_dir: Path) -> None:
    """Fault-injection helper: simulate a torn write by zero-filling every
    file in the step dir (tests also call this directly)."""
    for p in step_dir.rglob("*"):
        if p.is_file():
            p.write_bytes(b"\x00" * p.stat().st_size)


# ---------------------------------------------------------------------------
# HF-layout export/import (diffusers directory-of-subfolders convention)
# ---------------------------------------------------------------------------

def _diffusers_configs(mc: dict) -> dict[str, dict]:
    """Per-subfolder diffusers/transformers config.json contents derived from
    our ModelConfig dict (mirrors stabilityai/stable-diffusion-2-1's shipped
    configs at the default dims)."""
    ch = list(mc.get("block_out_channels", (320, 640, 1280, 1280)))
    # diffusers' (misnamed) attention_head_dim is the per-block HEAD COUNT:
    # SD-2.x configs list C // 64 per block; SD-1.x configs carry the scalar
    # fixed count (8) with conv projections (use_linear_projection false)
    num_heads = mc.get("attention_num_heads")
    head_dim = mc.get("attention_head_dim", 64)
    heads_cfg = num_heads if num_heads else [c // head_dim for c in ch]
    n = len(ch)
    unet = {
        "_class_name": "UNet2DConditionModel",
        "_diffusers_version": "0.14.0",
        "sample_size": mc.get("sample_size", 32),
        "in_channels": mc.get("in_channels", 4),
        "out_channels": mc.get("out_channels", 4),
        "down_block_types": ["CrossAttnDownBlock2D"] * (n - 1) + ["DownBlock2D"],
        "up_block_types": ["UpBlock2D"] + ["CrossAttnUpBlock2D"] * (n - 1),
        "block_out_channels": ch,
        "layers_per_block": mc.get("layers_per_block", 2),
        "cross_attention_dim": mc.get("cross_attention_dim", 1024),
        "attention_head_dim": heads_cfg,
        "use_linear_projection": bool(mc.get("use_linear_projection", True)),
        "norm_num_groups": mc.get("norm_num_groups", 32),
        "act_fn": "silu",
        "center_input_sample": False,
        "downsample_padding": 1,
        "flip_sin_to_cos": True,
        "freq_shift": 0,
        "mid_block_scale_factor": 1,
        "norm_eps": 1e-5,
    }
    vch = list(mc.get("vae_block_out_channels", (128, 256, 512, 512)))
    vae = {
        "_class_name": "AutoencoderKL",
        "_diffusers_version": "0.14.0",
        "sample_size": mc.get("sample_size", 32) * 8,
        "in_channels": 3,
        "out_channels": 3,
        "down_block_types": ["DownEncoderBlock2D"] * len(vch),
        "up_block_types": ["UpDecoderBlock2D"] * len(vch),
        "block_out_channels": vch,
        "latent_channels": mc.get("vae_latent_channels", 4),
        "layers_per_block": mc.get("vae_layers_per_block", 2),
        # mirror the model: groups never exceed the narrowest channel count
        "norm_num_groups": min(mc.get("norm_num_groups", 32), vch[0]),
        "act_fn": "silu",
        "scaling_factor": mc.get("vae_scaling_factor", 0.18215),
    }
    text = {
        "architectures": ["CLIPTextModel"],
        "model_type": "clip_text_model",
        "vocab_size": mc.get("text_vocab_size", 49408),
        "hidden_size": mc.get("text_hidden_size", 1024),
        "intermediate_size": 4 * mc.get("text_hidden_size", 1024),
        "num_hidden_layers": mc.get("text_layers", 23),
        "num_attention_heads": mc.get("text_heads", 16),
        "max_position_embeddings": mc.get("text_max_length", 77),
        "hidden_act": mc.get("text_act", "gelu"),
        "layer_norm_eps": 1e-5,
        "torch_dtype": "float32",
    }
    return {"unet": unet, "vae": vae, "text_encoder": text}


def export_hf_layout(out_dir: str | Path, *, unet=None, vae=None, text_encoder=None,
                     scheduler_config: Optional[dict] = None,
                     model_config: Optional[dict] = None) -> None:
    """Write checkpoint/<component>/ dirs mirroring the reference's pipeline
    save format (diff_train.py:709-716).

    Each subfolder gets BOTH:
      - params.npz — our Flax/NHWC tree, the fast internal path
        (import_hf_layout reads this back);
      - diffusion_pytorch_model.safetensors / model.safetensors — real torch
        layout under exact diffusers/transformers naming (models/export.py),
        plus a config.json, so diffusers' UNet2DConditionModel.from_pretrained
        / AutoencoderKL.from_pretrained / transformers'
        CLIPTextModel.from_pretrained load the export directly. Key sets are
        manifest-validated (tests/test_export.py).
    """
    from dcr_tpu.models import export as EX

    out = Path(out_dir)
    mc = dict(model_config or {})
    configs = _diffusers_configs(mc)
    n_blocks = len(mc.get("block_out_channels", (320, 640, 1280, 1280)))
    to_torch = {
        "unet": lambda p: EX.unet_to_diffusers(p, n_blocks=n_blocks),
        "vae": EX.vae_to_diffusers,
        "text_encoder": EX.text_to_transformers,
    }
    st_name = {"unet": "diffusion_pytorch_model.safetensors",
               "vae": "diffusion_pytorch_model.safetensors",
               "text_encoder": "model.safetensors"}
    tower = mc.get("text_tower", "clip")
    for name, params in (("unet", unet), ("vae", vae), ("text_encoder", text_encoder)):
        if params is None:
            continue
        sub = out / name
        sub.mkdir(parents=True, exist_ok=True)
        flat = _flatten(params)
        np.savez(sub / "params.npz", **_npz_encode(flat))
        if name == "text_encoder" and tower != "clip":
            # our own layout only: models/convert.py has no torch naming for
            # this tower until a published checkpoint is here to hold it to
            (sub / "config.json").write_text(json.dumps({
                "architectures": [tower], **mc.get(TEXT_TOWERS[tower].block, {}),
                "vocab_size": mc.get("text_vocab_size"),
                "max_position_embeddings": mc.get("text_max_length")}, indent=2))
            continue
        try:
            from safetensors.numpy import save_file
        except ImportError as e:  # pragma: no cover - safetensors is baked in
            log.warning("torch-layout export for %s skipped: %r", name, e)
            continue
        # conversion errors are NOT caught: a key/shape drift must fail the
        # export loudly, not ship a checkpoint that silently lost interop
        sd = to_torch[name](params)
        save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
                  str(sub / st_name[name]))
        (sub / "config.json").write_text(json.dumps(configs[name], indent=2))
    if scheduler_config is not None:
        sub = out / "scheduler"
        sub.mkdir(parents=True, exist_ok=True)
        sched = {
            "_class_name": "DPMSolverMultistepScheduler",
            "_diffusers_version": "0.14.0",
            "algorithm_type": "dpmsolver++",
            "solver_order": 2,
            "solver_type": "midpoint",
            "lower_order_final": True,
            "steps_offset": 1,
            "thresholding": False,
            "trained_betas": None,
            **scheduler_config,
        }
        (sub / "scheduler_config.json").write_text(json.dumps(sched, indent=2))
    if model_config is not None:
        index = {
            "_class_name": "StableDiffusionPipeline",
            "_diffusers_version": "0.14.0",
            "unet": ["diffusers", "UNet2DConditionModel"],
            "vae": ["diffusers", "AutoencoderKL"],
            "text_encoder": (["transformers", "CLIPTextModel"]
                             if tower == "clip" else ["dcr_tpu", tower]),
            "scheduler": ["diffusers", "DPMSolverMultistepScheduler"],
            "model_config": model_config,     # our native config, round-trips
        }
        (out / "model_index.json").write_text(json.dumps(index, indent=2))


_TORCH_WEIGHT_NAMES = ("diffusion_pytorch_model.safetensors", "model.safetensors",
                       "diffusion_pytorch_model.fp16.safetensors",
                       "model.fp16.safetensors",
                       "diffusion_pytorch_model.bin", "pytorch_model.bin",
                       "diffusion_pytorch_model.fp16.bin", "pytorch_model.fp16.bin")


def import_hf_layout(ckpt_dir: str | Path, component: str) -> dict:
    """Load one component's Flax params from an HF-layout checkpoint dir.

    Fast path: params.npz (our own exports). Fallback: a GENUINE
    diffusers/transformers checkpoint — torch-layout weights
    (safetensors/bin) + the subfolder's config.json, routed through
    models/convert.py. This makes a downloaded SD checkpoint directory
    (the reference's input format, diff_train.py:370-408) loadable with no
    manual conversion step."""
    sub_dir = Path(ckpt_dir) / component
    npz = sub_dir / "params.npz"
    if npz.exists():
        with np.load(npz) as z:
            flat = {k: z[k] for k in z.files}
        return _unflatten(_npz_decode(flat))

    weight_file = next((sub_dir / n for n in _TORCH_WEIGHT_NAMES
                        if (sub_dir / n).exists()), None)
    if weight_file is None:
        raise FileNotFoundError(
            f"no params.npz or torch weights ({'/'.join(_TORCH_WEIGHT_NAMES)}) "
            f"under {sub_dir}")
    from dcr_tpu.models import convert as CV

    sd = CV.load_torch_file(weight_file)
    cfg = json.loads((sub_dir / "config.json").read_text())
    if component == "unet":
        return CV.convert_unet(
            sd, block_out_channels=tuple(cfg["block_out_channels"]),
            layers_per_block=cfg.get("layers_per_block", 2),
            transformer_layers=_uniform_transformer_layers(cfg))
    if component == "vae":
        return CV.convert_vae(
            sd, block_out_channels=tuple(cfg["block_out_channels"]),
            layers_per_block=cfg.get("layers_per_block", 2))
    if component == "text_encoder":
        return CV.convert_clip_text(sd, layers=cfg["num_hidden_layers"],
                                    heads=cfg["num_attention_heads"])
    raise ValueError(f"unknown component {component!r}")


def _uniform_transformer_layers(unet_cfg: dict) -> int:
    """SD-1.x/2.x UNets use one transformer depth everywhere; SDXL-style
    per-block lists ([1, 2, 10]) are a different architecture — refuse loudly
    rather than silently building the wrong model from a weight subset."""
    tl = unet_cfg.get("transformer_layers_per_block", 1)
    if isinstance(tl, (list, tuple)):
        if len(set(tl)) != 1:
            raise ValueError(
                f"per-block transformer depths {tl} (SDXL-family?) are not "
                "supported by this UNet architecture")
        tl = tl[0]
    return int(tl)


def model_config_from_diffusers(ckpt_dir: str | Path) -> dict:
    """Infer our ModelConfig fields from a genuine diffusers checkpoint's
    per-subfolder config.json files (inverse of _diffusers_configs). Handles
    both head conventions: SD-2.x per-block head lists with a common head_dim,
    SD-1.x scalar fixed head count."""
    ckpt = Path(ckpt_dir)
    u = json.loads((ckpt / "unet" / "config.json").read_text())
    block_out = list(u["block_out_channels"])
    heads = u.get("attention_head_dim", 8)
    out: dict = {
        "sample_size": u.get("sample_size", 32),
        "in_channels": u.get("in_channels", 4),
        "out_channels": u.get("out_channels", 4),
        "block_out_channels": tuple(block_out),
        "layers_per_block": u.get("layers_per_block", 2),
        "cross_attention_dim": u.get("cross_attention_dim", 1024),
        "use_linear_projection": u.get("use_linear_projection", False),
        "norm_num_groups": u.get("norm_num_groups", 32),
    }
    out["transformer_layers"] = _uniform_transformer_layers(u)
    if isinstance(heads, (list, tuple)):
        head_dims = {c // h for c, h in zip(block_out, heads)}
        if len(head_dims) != 1:
            raise ValueError(
                f"per-block heads {heads} do not share one head_dim over "
                f"channels {block_out}; not expressible by ModelConfig")
        out["attention_head_dim"] = head_dims.pop()
    else:
        out["attention_num_heads"] = int(heads)
        out["attention_head_dim"] = 0
    vae_cfg = ckpt / "vae" / "config.json"
    if vae_cfg.exists():
        v = json.loads(vae_cfg.read_text())
        out.update(
            vae_block_out_channels=tuple(v["block_out_channels"]),
            vae_layers_per_block=v.get("layers_per_block", 2),
            vae_latent_channels=v.get("latent_channels", 4),
            vae_scaling_factor=v.get("scaling_factor", 0.18215))
    text_cfg = ckpt / "text_encoder" / "config.json"
    if text_cfg.exists():
        t = json.loads(text_cfg.read_text())
        out.update(
            text_vocab_size=t.get("vocab_size", 49408),
            text_hidden_size=t.get("hidden_size", 1024),
            text_layers=t.get("num_hidden_layers", 23),
            text_heads=t.get("num_attention_heads", 16),
            text_max_length=t.get("max_position_embeddings", 77),
            # transformers serializes configs as diffs from defaults, and
            # CLIPTextConfig's default is quick_gelu — an omitted key means
            # quick_gelu, not gelu
            text_act=t.get("hidden_act", "quick_gelu"))
    sched_cfg = ckpt / "scheduler" / "scheduler_config.json"
    if sched_cfg.exists():
        s = json.loads(sched_cfg.read_text())
        out.update(
            num_train_timesteps=s.get("num_train_timesteps", 1000),
            beta_schedule=s.get("beta_schedule", "scaled_linear"),
            beta_start=s.get("beta_start", 0.00085),
            beta_end=s.get("beta_end", 0.012),
            prediction_type=s.get("prediction_type", "epsilon"))
    return out


def _flatten(tree: Any, prefix: str = "") -> dict[str, np.ndarray]:
    out: dict[str, np.ndarray] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
    else:
        out[prefix[:-1]] = np.asarray(jax.device_get(tree))
    return out


#: npz has no bfloat16: such a leaf is stored as its 16 bits under this suffix
_BF16_SUFFIX = ":bf16"


def _npz_encode(flat: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    import ml_dtypes

    return {(k + _BF16_SUFFIX if v.dtype == ml_dtypes.bfloat16 else k):
            (v.view(np.uint16) if v.dtype == ml_dtypes.bfloat16 else v)
            for k, v in flat.items()}


def _npz_decode(flat: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    import ml_dtypes

    return {(k[:-len(_BF16_SUFFIX)] if k.endswith(_BF16_SUFFIX) else k):
            (v.view(ml_dtypes.bfloat16) if k.endswith(_BF16_SUFFIX) else v)
            for k, v in flat.items()}


def _unflatten(flat: dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for key, value in flat.items():
        parts = key.split("/")
        cur = tree
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = value
    return tree
