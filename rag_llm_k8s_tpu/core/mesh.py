"""Device-mesh construction and sharding helpers.

The reference has no distributed substrate at all (survey §2c: no NCCL/MPI,
single process). The TPU-native equivalent is a ``jax.sharding.Mesh`` over the
slice's ICI links; all collectives (psum / all-gather / reduce-scatter /
ppermute) are emitted by XLA from sharding annotations — there is no
hand-written communication layer anywhere in this framework.

Axis convention (see :class:`~rag_llm_k8s_tpu.core.config.MeshConfig`):
  ``dp``  — data parallel (replicated weights, split batch)
  ``sp``  — sequence/context parallel (ring attention, long prompts)
  ``tp``  — tensor parallel (sharded weights; the main axis for 8B on v5e-8)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rag_llm_k8s_tpu.core.config import MeshConfig


@dataclass(frozen=True)
class MeshContext:
    """A mesh plus convenience sharding constructors."""

    mesh: Mesh

    # -- sharding constructors -------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def data_sharding(self) -> NamedSharding:
        """Batch dim split over dp; everything else replicated."""
        return self.sharding("dp")

    # -- axis sizes ------------------------------------------------------------
    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    @property
    def tp(self) -> int:
        return self.axis_size("tp")

    @property
    def dp(self) -> int:
        return self.axis_size("dp")

    @property
    def sp(self) -> int:
        return self.axis_size("sp")

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))


def make_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> MeshContext:
    """Build the (dp, sp, tp) mesh over available devices.

    On a real v5e-8 slice the devices come pre-ordered so that adjacent mesh
    coordinates are ICI neighbors (``jax.make_mesh`` consults device topology);
    TP shards therefore all-gather over ICI, never DCN. On CPU (tests) the
    virtual devices of ``--xla_force_host_platform_device_count`` are used.
    """
    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    dp, sp, tp = config.resolved(len(devices))
    # Force Auto axis types on every path: jax.make_mesh defaults to Explicit
    # sharding mode, under which plain indexing of sharded arrays raises
    # ShardingTypeError — this framework uses the Auto (NamedSharding
    # annotation) model throughout.
    axis_types = (jax.sharding.AxisType.Auto,) * 3
    if devices == list(jax.devices()):
        mesh = jax.make_mesh(
            (dp, sp, tp), config.axis_names, devices=devices, axis_types=axis_types
        )
    else:
        arr = np.asarray(devices).reshape(dp, sp, tp)
        mesh = Mesh(arr, config.axis_names, axis_types=axis_types)
    return MeshContext(mesh=mesh)


def single_device_mesh(device: Optional[jax.Device] = None) -> MeshContext:
    """1×1×1 mesh — lets all sharded code paths run unchanged on one chip."""
    device = device or jax.devices()[0]
    return make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=[device])


def serving_device_kind(mesh: Optional[MeshContext] = None) -> str:
    """``device_kind`` of the devices an engine serves on (its mesh's, or
    the default backend's) — what roofline peaks are keyed by."""
    device = mesh.mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return device.device_kind
