"""Device mesh plan for CTR training.

The reference's process/device topology — one BoxPSWorker per GPU
(boxps_trainer.cc:53-73), NCCL ring per node, closed `boxps::MPICluster`
across nodes (box_wrapper.h:531) — collapses on TPU into one
`jax.sharding.Mesh` with a single `dp` axis:

- the minibatch is data-parallel over `dp` (one worker per chip parity);
- the pass working-set table is *sharded* over the same axis (the model-
  parallel dimension of a CTR model is the embedding table, which dwarfs the
  dense net — so dp and "table mp" share one axis and pull/push ride ICI
  all_to_all);
- dense grads are psum'd over `dp` (the NCCL allreduce / SyncDense path).

TP/PP/SP over the dense net are deliberately absent, matching the reference
(SURVEY.md §2.3: tensor/sequence parallelism ❌ absent — CTR dense towers are
tiny). The mesh axis spans both ICI and DCN when multi-host; XLA places the
collectives.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# the mesh-step makers and tests import these two names from here
shard_map = jax.shard_map
axis_size = jax.lax.axis_size


@dataclass(frozen=True)
class MeshPlan:
    """A mesh + the named shardings the train step uses."""

    mesh: Mesh
    axis: str = "dp"

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def sharded(self, *axes: Optional[str]) -> NamedSharding:
        """NamedSharding partitioning the given positional axes; e.g.
        ``plan.sharded(plan.axis)`` shards array axis 0 over dp."""
        return NamedSharding(self.mesh, P(*axes))

    @property
    def table_sharding(self) -> NamedSharding:
        """[n_shards, capacity, width] pass table: axis 0 over dp."""
        return self.sharded(self.axis)

    @property
    def batch_sharding(self) -> NamedSharding:
        """Per-device-leading batch arrays [n_dev, ...]: axis 0 over dp."""
        return self.sharded(self.axis)

    @property
    def replicated(self) -> NamedSharding:
        return self.sharded()


def make_mesh(
    n_devices: Optional[int] = None,
    axis: str = "dp",
    devices: Optional[Sequence[Any]] = None,
) -> MeshPlan:
    """Build the 1-D CTR mesh over the first ``n_devices`` devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(f"asked for {n_devices} devices, have {len(devices)}")
    mesh = Mesh(np.asarray(devices[:n_devices]), (axis,))
    return MeshPlan(mesh=mesh, axis=axis)


def make_mesh_2d(
    n_pp: int,
    n_dp: int,
    axes: Sequence[str] = ("pp", "dp"),
    devices: Optional[Sequence[Any]] = None,
) -> MeshPlan:
    """A 2-D (pipeline x data) mesh: pipeline stages along ``axes[0]``,
    data-parallel replicas of each stage along ``axes[1]``.

    The returned plan's ``axis`` is the dp axis (batch/table machinery
    keys off it); the pipeline step takes the pp axis via its spec. The
    reference composes pipeline sections with data parallelism the same
    way (PipelineTrainer sections x fleet DP ranks)."""
    if n_pp < 1 or n_dp < 1:
        raise ValueError(f"mesh needs n_pp >= 1 and n_dp >= 1, got ({n_pp}, {n_dp})")
    explicit = devices is not None
    if devices is None:
        devices = jax.devices()
    need = n_pp * n_dp
    if need > len(devices):
        raise ValueError(f"asked for {need} devices, have {len(devices)}")
    grid = None
    if not explicit and need == len(devices):
        # ICI-aware layout: on real hardware the ppermute hops of the pp
        # axis should ride nearest-neighbor links, which a raw enumeration
        # reshape does not guarantee
        try:
            from jax.experimental import mesh_utils

            grid = mesh_utils.create_device_mesh((n_pp, n_dp), devices=devices)
        except Exception:
            # the raw-enumeration fallback below is correct but loses the
            # ICI-aware layout — count it so a fleet silently training on
            # suboptimal pp hops is visible in the stats
            from paddlebox_tpu.utils.monitor import STAT_ADD

            STAT_ADD("mesh.device_mesh_fallbacks")
            grid = None
    if grid is None:
        grid = np.asarray(devices[:need]).reshape(n_pp, n_dp)
    mesh = Mesh(grid, tuple(axes))
    return MeshPlan(mesh=mesh, axis=axes[1])


def put_sharded(plan: MeshPlan, x: Any) -> jax.Array:
    """Host array -> device array sharded on axis 0 over the mesh.

    Multi-host aware: when the mesh spans processes, ``x`` may be either
    the GLOBAL array (each process contributes its own row block, assuming
    the 1-D mesh orders devices by process — jax.devices() order) or just
    this process's LOCAL block ``[n_local_dev, ...]`` (the shape a
    DistributedWorkingSet finalize returns); both assemble into one global
    jax.Array without any cross-host transfer of remote rows.
    """
    sh = plan.batch_sharding
    if jax.process_count() == 1:
        return jax.device_put(x, sh)
    n = plan.n_devices
    per = n // jax.process_count()

    def place(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            # already a global array (e.g. opt state carried across passes):
            # re-placing to the same sharding is a no-op, and np.asarray
            # would crash on its non-addressable shards
            return jax.device_put(leaf, sh)
        leaf = np.asarray(leaf)
        if leaf.shape[0] == n:
            local = leaf[jax.process_index() * per : (jax.process_index() + 1) * per]
        elif leaf.shape[0] == per:
            local = leaf
        else:
            raise ValueError(
                f"put_sharded: leading dim {leaf.shape[0]} is neither the "
                f"global device count {n} nor this host's local count {per}"
            )
        return jax.make_array_from_process_local_data(
            sh, np.ascontiguousarray(local), (n,) + leaf.shape[1:]
        )

    return jax.tree.map(place, x)


def put_per_device_copies(plan: MeshPlan, arr: np.ndarray) -> jax.Array:
    """THIS process's host array, copied onto each of its local devices, as
    a global ``[n_devices, *arr.shape]`` array sharded on the device axis.

    The multi-host resident feed's placement: each host's pass arrays
    (row stream, counts, labels) differ, so they cannot be replicated —
    instead every device carries its own host's copy and shard_map hands
    each device a ``[1, ...]`` block. All processes must pass arrays of
    the SAME (padded/locksteped) shape."""
    arr = np.ascontiguousarray(arr)
    sh = NamedSharding(plan.mesh, P(plan.axis, *([None] * arr.ndim)))
    pid = jax.process_index()
    local = [d for d in plan.mesh.devices.flat if d.process_index == pid]
    shards = [jax.device_put(arr[None], d) for d in local]
    return jax.make_array_from_single_device_arrays(
        (plan.n_devices,) + arr.shape, sh, shards
    )


def put_axis1_blocks(plan: MeshPlan, local: np.ndarray) -> jax.Array:
    """Local ``[K, n_local_dev, ...]`` blocks -> global ``[K, n_dev, ...]``
    sharded on axis 1 (the resident feed's per-chunk index blocks: the
    scan axis stays whole, devices split)."""
    sh = NamedSharding(
        plan.mesh, P(None, plan.axis, *([None] * (local.ndim - 2)))
    )
    if jax.process_count() == 1:
        return jax.device_put(local, sh)
    n = plan.n_devices
    per = n // jax.process_count()
    if local.shape[1] != per:
        raise ValueError(
            f"put_axis1_blocks: axis-1 dim {local.shape[1]} != this host's "
            f"local device count {per}"
        )
    return jax.make_array_from_process_local_data(
        sh,
        np.ascontiguousarray(local),
        (local.shape[0], n) + local.shape[2:],
    )


def put_replicated(plan: MeshPlan, tree: Any) -> Any:
    """Replicate a pytree (dense params, opt state) on every device.

    Multi-host: every process must pass the same values (they are placed
    as fully-replicated global arrays)."""
    return jax.device_put(tree, plan.replicated)


def local_slice(plan: MeshPlan, x: jax.Array) -> np.ndarray:
    """This process's addressable row block of an axis-0-sharded array —
    the inverse of ``put_sharded``'s local form. Single-process: the whole
    array."""
    if jax.process_count() == 1:
        return np.asarray(x)
    shards = sorted(
        x.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)
