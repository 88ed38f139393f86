"""Device mesh construction: dp / fsdp / tp / sp / ep axes over ICI + DCN.

The mesh IS the communicator: where the reference creates NCCL groups
(`collective_group/nccl_collective_group.py`) we build a
`jax.sharding.Mesh` whose axes map onto the physical topology — fast ICI
axes for tensor/sequence parallelism, the slower DCN axis for cross-slice
data parallelism (the "How to Scale Your Model" recipe).

Axis conventions (used by models/, train/, rllib/):
  dp    data parallel (pure replication of params, sharded batch)
  fsdp  fully-sharded data parallel (params sharded over this axis too)
  pp    pipeline parallel (layer stages; GPipe microbatch schedule)
  tp    tensor/model parallel (matmul contraction sharding)
  sp    sequence/context parallel (ring attention shards over this)
  ep    expert parallel (MoE experts)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "tp", "ep")


@dataclass
class MeshSpec:
    """Declarative mesh: axis name -> size; -1 on at most one axis = infer.

    `dcn_axes` marks axes that cross slice boundaries (multi-slice data
    parallelism over DCN); they are laid out as the slowest-varying mesh
    dims so XLA routes their collectives over DCN and keeps tp/sp on ICI.
    """

    axes: Dict[str, int] = field(default_factory=dict)
    dcn_axes: Tuple[str, ...] = ()

    def resolved(self, n_devices: int) -> Dict[str, int]:
        axes = {k: v for k, v in self.axes.items() if v != 1 or k in ("dp",)}
        axes = dict(self.axes)
        unknown = [k for k, v in axes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError("at most one axis may be -1")
        known = math.prod(v for v in axes.values() if v != -1)
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {axes}")
            axes[unknown[0]] = n_devices // known
        if math.prod(axes.values()) != n_devices:
            raise ValueError(
                f"mesh {axes} does not cover {n_devices} devices")
        return axes

    def axis_names(self) -> Tuple[str, ...]:
        ordered = [a for a in AXIS_ORDER if a in self.axes]
        extra = [a for a in self.axes if a not in AXIS_ORDER]
        return tuple(ordered + extra)

    @staticmethod
    def data_parallel() -> "MeshSpec":
        return MeshSpec({"dp": -1})

    @staticmethod
    def fsdp(tp: int = 1) -> "MeshSpec":
        return MeshSpec({"fsdp": -1, "tp": tp})

    @staticmethod
    def for_training(dp: int = 1, fsdp: int = -1, tp: int = 1, sp: int = 1
                     ) -> "MeshSpec":
        axes = {"dp": dp, "fsdp": fsdp, "tp": tp}
        if sp != 1:
            axes["sp"] = sp
        return MeshSpec(axes)


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None):
    """Build a `jax.sharding.Mesh` from a MeshSpec.

    Multi-slice layout: DCN-crossing axes are placed as the leading
    (slowest-varying) dims so that consecutive devices along ICI axes are
    physically adjacent. Uses `mesh_utils.create_device_mesh` when the
    topology is a real TPU slice (it knows the physical torus); falls back
    to a plain reshape on CPU/virtual platforms.
    """
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    axes = spec.resolved(len(devices))
    names = spec.axis_names()
    shape = tuple(axes[n] for n in names)
    # Order: DCN axes slowest. Reorder names so dcn axes come first.
    if spec.dcn_axes:
        dcn = [n for n in names if n in spec.dcn_axes]
        ici = [n for n in names if n not in spec.dcn_axes]
        names = tuple(dcn + ici)
        shape = tuple(axes[n] for n in names)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        if spec.dcn_axes:
            dcn_shape = tuple(axes[n] if n in spec.dcn_axes else 1 for n in names)
            ici_shape = tuple(1 if n in spec.dcn_axes else axes[n] for n in names)
            mesh_devices = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices)
        else:
            mesh_devices = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        mesh_devices = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(mesh_devices, names)


class MeshBound:
    """A jitted function traced and run with `mesh` as jax's context mesh.

    flax resolves `with_logical_constraint` against the context mesh at
    trace time, and `jax.shard_map` without a mesh argument maps over it;
    `jax.set_mesh` cannot be entered inside a trace, so it wraps the call.
    Without it every activation constraint in models/ compiles to nothing.
    `lower` is exposed for callers that inspect the compiled program."""

    def __init__(self, jitted, mesh):
        self._jitted = jitted
        self.mesh = mesh

    def __call__(self, *args, **kwargs):
        import jax

        with jax.set_mesh(self.mesh):
            return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        import jax

        with jax.set_mesh(self.mesh):
            return self._jitted.lower(*args, **kwargs)


def local_mesh(axis_name: str = "dp"):
    """Single-host mesh over all visible devices on one axis."""
    import jax

    return build_mesh(MeshSpec({axis_name: -1}))


def mesh_shape_summary(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def validate_divisibility(mesh, batch_size: int, seq_len: Optional[int] = None):
    """Fail fast on shapes XLA can't shard evenly (a silent perf cliff)."""
    shape = mesh_shape_summary(mesh)
    data_ways = shape.get("dp", 1) * shape.get("fsdp", 1)
    if batch_size % data_ways:
        raise ValueError(
            f"global batch {batch_size} not divisible by dp*fsdp={data_ways}")
    sp = shape.get("sp", 1)
    if seq_len is not None and seq_len % max(sp, 1):
        raise ValueError(f"sequence length {seq_len} not divisible by sp={sp}")
