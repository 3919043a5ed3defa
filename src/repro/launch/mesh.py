"""Production meshes. Functions (not module constants) so importing never
touches jax device state.

Axis naming is unified on the engine's ("group", "data", "mp") canon:

  group  async compute groups (paper §IV-A round-robin staleness axis)
  data   synchronous data parallelism within a group
  mp     model parallelism within a worker (param/optimizer-state shards)

``sharding.rules`` reads the tensor/fsdp axis names *from the mesh*
(``rules.default_axes``), so the legacy production/dryrun meshes — which
keep their historical ("pod", "data", "model") naming as a compat shim for
the recorded dry-run artifacts — and the engine's group mesh consume the
same rule code.

Every mesh is built by ``make_mesh``, with every axis ``Auto``: the
steps here shard by ``with_sharding_constraint`` and ``shard_map``
specs, which refer to Auto axes only (``jax.make_mesh`` alone builds
Explicit axes).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

#: canonical engine mesh axes (model-parallel axis last)
GROUP_MESH_AXES = ("group", "data", "mp")


def make_mesh(shape, axes, devices=None) -> Mesh:
    """The one mesh constructor: ``shape`` over ``axes``, all axes Auto.
    ``devices`` (any array-like of ``prod(shape)`` devices) pins the
    device order; otherwise ``jax.make_mesh`` picks it from the visible
    pool."""
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)
    return Mesh(np.asarray(devices).reshape(shape), tuple(axes),
                axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16,16)=256 chips, ("data","model").
    Multi-pod: (2,16,16)=512 chips, ("pod","data","model").

    Compat shim: these keep the historical axis names the recorded dry-run
    artifacts were produced with; ``sharding.rules`` resolves axis roles
    from the mesh, so the naming difference is invisible to rule code.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires >= data*model host devices)."""
    return make_mesh((data, model), ("data", "model"))


def make_host_smoke_mesh(data: int = 4, mp: int = 2, groups: int = 1):
    """Forced-host-device mesh in the canonical ("group","data","mp")
    naming for the dryrun-smoke lane: ``groups`` compute groups of
    ``data`` fsdp-style shards times ``mp`` model shards (requires
    >= groups*data*mp host devices). Param rules replicate over "group"
    (``sharding.rules.default_axes``), mirroring the engine."""
    return make_mesh((groups, data, mp), GROUP_MESH_AXES)


def make_group_mesh(groups: int, data: int = 1, mp: int = 1):
    """Compute-group mesh for the execution engine: (g, k, mp) devices
    with axes ("group", "data", "mp") — g async compute groups of k
    synchronous data-parallel workers, each worker ``mp`` model-parallel
    devices holding one shard of the parameters and optimizer state
    (paper §IV-A for the group axis; the mp axis is the within-worker
    partitioning the planner's 2-D (g, mp) search allocates). Uses the
    first g*k*mp local devices, so it works on any prefix of the host/TPU
    device pool (CPU-testable via --xla_force_host_platform_device_count).
    """
    from repro.engine.spmd import group_mesh_devices
    return make_mesh((groups, data, mp), GROUP_MESH_AXES,
                     group_mesh_devices(groups, data, mp))
