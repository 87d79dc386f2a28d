"""Device mesh + sharding helpers — the single parallelism substrate.

Every boundary that is process+NCCL in the reference (DDP grad sync
diff_train.py:656, eval all_gather utils_ret.py:756-779) becomes a jit boundary
over this mesh: GSPMD inserts the ICI collectives. Axes:

  data    batch sharding (DP) — gradient psum rides ICI
  fsdp    parameter/optimizer sharding (ZeRO-3 style, all-gather on use)
  tensor  reserved for intra-layer sharding of the UNet (off by default)

Axes of size 1 are kept in the mesh so the same partition specs serve a single
chip, a v4-8, or a multi-host pod without code changes.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dcr_tpu.core import tracing
from dcr_tpu.core.config import MeshConfig

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
TENSOR_AXIS = "tensor"
SEQ_AXIS = "seq"
AXES = (DATA_AXIS, FSDP_AXIS, TENSOR_AXIS, SEQ_AXIS)


def make_mesh(cfg: Optional[MeshConfig] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    d, f, t, s = cfg.axis_sizes(len(devices))
    arr = np.asarray(devices).reshape(d, f, t, s)
    return Mesh(arr, AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Global batch sharded over data (and fsdp, which also consumes batch)."""
    return NamedSharding(mesh, P((DATA_AXIS, FSDP_AXIS)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_parallel_size(mesh: Mesh) -> int:
    return mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]


def mesh_spans_processes(mesh: Mesh) -> bool:
    """True when the mesh's devices live on more than one process. Local
    meshes on a multi-process job (the lockstep-replica mode backends without
    cross-process XLA use) must take the single-host placement paths."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def shard_batch(mesh: Mesh, batch):
    """Place a host-global numpy batch onto the mesh, sharded on the batch axis.

    When the mesh spans processes each host passes its local shard;
    ``make_array_from_process_local_data`` assembles the global array. A
    local mesh (single process, or one replica of a multi-process CPU job)
    takes the plain device_put path.
    """
    sharding = batch_sharding(mesh)
    spans = mesh_spans_processes(mesh)

    def put(x):
        x = np.asarray(x)
        if spans:
            return jax.make_array_from_process_local_data(sharding, x)
        return jax.device_put(x, sharding)

    return jax.tree.map(put, batch)


def fsdp_spec(mesh: Mesh, shape: tuple[int, ...],
              min_size: int = 2 ** 16) -> PartitionSpec:
    """The FSDP rule: shard the largest evenly-divisible axis over `fsdp` when
    the tensor is big enough to be worth scattering, else replicate."""
    fsdp = mesh.shape[FSDP_AXIS]
    if fsdp > 1 and int(np.prod(shape, dtype=np.int64)) >= min_size:
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if shape[i] % fsdp == 0:
                spec = [None] * len(shape)
                spec[i] = FSDP_AXIS
                return P(*spec)
    return P()


def fsdp_sharding_for_params(mesh: Mesh, params, min_size: int = 2 ** 16):
    """Pytree of NamedSharding matching `params` (arrays or ShapeDtypeStructs)
    under the FSDP rule."""
    return jax.tree.map(
        lambda x: NamedSharding(mesh, fsdp_spec(mesh, tuple(x.shape), min_size)),
        params)


def to_host(x) -> np.ndarray:
    """Fetch a (possibly globally-sharded) device array to host numpy on every
    process. Single-process: plain device_get. Multi-host: the array's shards
    are not all addressable locally, so all-gather across processes first.

    Two spans split what a fetch costs: ``xfer/device_wait`` is the wait for
    the program that makes ``x`` (device time, nothing to win on the host),
    ``xfer/d2h`` the copy alone (the gather too, across hosts), during which
    a caller that fetches batch by batch has nothing in flight."""
    with tracing.span("xfer/device_wait"):
        jax.block_until_ready(x)
    with tracing.span("xfer/d2h"):
        if jax.process_count() == 1:
            return np.asarray(jax.device_get(x))
        from jax.experimental import multihost_utils

        from dcr_tpu.core import dist

        # bounded: a host that died mid-eval turns this into a BarrierTimeout
        # with a name, instead of every surviving rank hanging in the gather
        return np.asarray(dist.run_with_timeout(
            lambda: multihost_utils.process_allgather(x, tiled=True),
            dist.default_allgather_timeout_s(), name="to_host"))


@contextmanager
def use_mesh(mesh: Mesh):
    with jax.sharding.use_mesh(mesh):
        yield mesh
