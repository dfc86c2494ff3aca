"""Device meshes from placements, and the series-sharded compute mesh.

The cluster placement's shard->instance assignment (m3_tpu.cluster.placement)
is the same partitioning the device mesh uses: the 'shard' axis carries M3's
data-parallel virtual shards, and the 'replica' axis carries RF copies
(SURVEY.md §2.10). Collectives over these axes replace the reference's
host-side scatter-gather RPC (§2.11): psum over ICI for cross-shard rollups,
all_gather over 'replica' for divergence checks.

The COMPUTE mesh (PR 12, ROADMAP #1) is the 1-D ``("series",)`` mesh the
whole-query compiler and the device aggregation kernels serve on:
series-major arrays shard their row axis across it with
``NamedSharding``/``PartitionSpec`` and grouped reductions lower to
psums over the series axis. Mesh and sharding objects are built ONCE per
(devices, spec) through the lru_cache factories below — per-eval
construction is the jax-jit-per-call hazard m3lint flags (a fresh Mesh
defeats jit's C++ dispatch fast path and risks minting fresh executable
cache keys).
"""

from __future__ import annotations

import functools
import os

import numpy as np


def build_mesh(n_shard: int, n_replica: int = 1, devices=None):
    """(shard x replica) mesh over the first n_shard*n_replica devices.

    Setup-time factory (dry runs, tests, placement wiring) — the per-eval
    serving plane goes through the cached ``compute_mesh`` instead."""
    import jax
    from jax.sharding import Mesh

    devices = devices if devices is not None else jax.devices()
    need = n_shard * n_replica
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.array(devices[:need]).reshape(n_shard, n_replica)
    # m3lint: disable=jax-jit-per-call  (one-shot setup factory, not per-eval)
    return Mesh(grid, axis_names=("shard", "replica"))


# ---------------------------------------------------------------------------
# series-sharded compute mesh (the engine's serving plane)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def compute_mesh(n_devices: int):
    """The 1-D ``("series",)`` mesh over the first n_devices local devices
    — ONE Mesh object per device count for the life of the process, so
    every jit keyed on it reuses its executables."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    n = max(1, min(n_devices, len(devices)))
    return Mesh(np.array(devices[:n]), axis_names=("series",))


@functools.lru_cache(maxsize=None)
def row_sharding(mesh):
    """[S, T] series-major matrices: rows sharded, steps replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec("series", None))


@functools.lru_cache(maxsize=None)
def vec_sharding(mesh):
    """[S] per-series vectors (group ids, checksums): sharded."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec("series"))


@functools.lru_cache(maxsize=None)
def replicated_sharding(mesh):
    """Post-aggregation [G, T] outputs and small broadcast inputs."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def active_compute_mesh():
    """The compute mesh the serving paths should shard over, or None.

    ``M3_TPU_QUERY_SHARD`` is the operator hatch: ``0`` disables, an
    integer pins the device count (``1`` is a valid single-device mesh —
    the device-count-independence proof target), any other truthy value
    means all local devices. Unset, the mesh arms by itself when the
    default backend is an accelerator with more than one device, so
    single-device and CPU behavior is unchanged."""
    spec = os.environ.get("M3_TPU_QUERY_SHARD", "").strip()
    if spec == "0":
        return None
    import jax

    if spec:
        try:
            n = int(spec)
        except ValueError:
            n = len(jax.devices())
        return compute_mesh(n)
    from m3_tpu.utils import dispatch

    if not dispatch._accelerator_present():
        return None
    n = len(jax.devices())
    return compute_mesh(n) if n > 1 else None


def mesh_from_placement(placement, devices=None):
    """Mesh whose 'shard' axis size matches the placement's distinct shard
    groups: device i takes the shards of the i-th instance (sorted)."""
    n_instances = len(placement.instances)
    rf = placement.replica_factor
    # mirrored/replicated placements: shard groups = instances / RF
    n_shard_groups = max(n_instances // rf, 1)
    return build_mesh(n_shard_groups, max(rf, 1), devices)
