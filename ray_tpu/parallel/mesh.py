"""Device meshes: the TPU-native resource fabric.

No reference analogue — this is the TPU design delta (SURVEY.md §7 delta 1
& 3): where the reference treats accelerators as an opaque count
(``num_gpus``), TPU scheduling is topology-first.  A ``MeshSpec`` names the
parallelism axes (dp/fsdp/tp/sp/ep/pp + a cross-slice DCN axis) and maps
them onto physical devices so XLA collectives ride ICI within a slice and
DCN across slices (cf. jax-ml.github.io/scaling-book recipe: pick a mesh,
annotate shardings, let XLA insert collectives).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis names used across ray_tpu.train / models:
#   dp    — data parallel (batch split, gradients psum)
#   fsdp  — fully-sharded data parallel (params sharded over this axis too)
#   tp    — tensor parallel (heads / mlp sharded)
#   sp    — sequence/context parallel (ring attention over this axis)
#   ep    — expert parallel (MoE experts)
#   pp    — pipeline parallel (layer stages)
#   dcn   — cross-slice data parallel over DCN (multi-pod)
AXIS_ORDER = ("dcn", "pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes; -1 on at most one axis = fill with all devices."""

    axes: dict[str, int] = field(default_factory=dict)

    def resolved(self, n_devices: int) -> dict[str, int]:
        axes = {k: v for k, v in self.axes.items() if v != 1 or k in ("dp",)}
        if not axes:
            axes = {"dp": -1}
        fills = [k for k, v in axes.items() if v == -1]
        if len(fills) > 1:
            raise ValueError(f"Only one axis may be -1, got {fills}")
        fixed = math.prod(v for v in axes.values() if v != -1)
        if fills:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed}")
            axes[fills[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"Mesh axes {axes} need {fixed} devices, have {n_devices}")
        # canonical order for predictable ICI layout
        return {k: axes[k] for k in AXIS_ORDER if k in axes} | {
            k: v for k, v in axes.items() if k not in AXIS_ORDER}


def create_mesh(axes: Optional[dict[str, int]] = None,
                devices: Optional[Sequence] = None,
                allow_split_physical_axes: bool = True) -> Mesh:
    """Build a ``jax.sharding.Mesh`` with named axes.

    ``mesh_utils.create_device_mesh`` lays devices out so that the
    innermost axes map to nearest ICI neighbors (reference capability
    being replaced: NCCL ring construction in ray.util.collective
    nccl_collective_group.py:127 — on TPU the topology mapping happens
    here, at mesh build time, and XLA emits the collectives).
    """
    devices = list(devices) if devices is not None else jax.devices()
    spec = MeshSpec(dict(axes) if axes else {"dp": -1})
    requested = math.prod(v for v in spec.axes.values() if v != -1)
    if (-1 not in spec.axes.values() and requested < len(devices)
            and len(devices) % requested == 0):
        # fewer devices asked for than exist (e.g. a dp=4 test mesh on an
        # 8-device host): use a prefix — the gang owns whole hosts, but a
        # mesh may be a sub-slice
        devices = devices[:requested]
    resolved = spec.resolved(len(devices))
    shape = tuple(resolved.values())
    try:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes)
    except (ValueError, AssertionError):
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names=tuple(resolved.keys()))


def create_hybrid_mesh(ici_axes: dict[str, int], dcn_size: int,
                       devices: Optional[Sequence] = None) -> Mesh:
    """Multi-slice mesh: `dcn` outermost over slices, ICI axes within
    (analogue of scaling DP over DCN while TP/SP stay inside a slice)."""
    devices = list(devices) if devices is not None else jax.devices()
    per_slice = len(devices) // dcn_size
    spec = MeshSpec(dict(ici_axes))
    resolved = spec.resolved(per_slice)
    try:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            tuple(resolved.values()),
            dcn_mesh_shape=(dcn_size,) + (1,) * (len(resolved) - 1),
            devices=devices)
    except (ValueError, AssertionError):
        # devices without a slice_index (virtual CPU devices, one real
        # slice) are what mesh_utils refuses; lay the slices out in
        # device order
        dev_array = np.asarray(devices).reshape((dcn_size,)
                                                + tuple(resolved.values()))
    return Mesh(dev_array, axis_names=("dcn",) + tuple(resolved.keys()))


def mesh_shape(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes over which the global batch is split."""
    return tuple(a for a in ("dcn", "dp", "fsdp") if a in mesh.axis_names)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [batch, ...] host data entering the mesh."""
    axes = data_axes(mesh)
    return NamedSharding(mesh, PartitionSpec(axes if axes else None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def local_device_count() -> int:
    return jax.local_device_count()
