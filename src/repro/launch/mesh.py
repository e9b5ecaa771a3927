"""Production meshes. TPU v5e pod = 16×16 = 256 chips; multi-pod = 2 pods.

`make_production_mesh` is a FUNCTION (importing this module never touches
jax device state). Axis semantics:
  pod   — data parallel across pods (DCN); gradient all-reduce crosses it
  data  — FSDP + data parallel within a pod (ICI)
  model — tensor/expert parallel within a pod (ICI)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    """All our axes are Auto: the compiler is free to pick collectives."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests use small fake-device meshes)."""
    return _make_mesh(shape, axes)


def make_fleet_mesh(clients: int = 1, slabs: int = 1) -> jax.sharding.Mesh:
    """The cloud-serving mesh (repro.sharding.fleet). Axis semantics:
      clients — shards per-client service state on its leading slot axis
                (ServiceState / FleetState / stats / fallback frames)
      slabs   — shards the shared tree's slab attribute tables and the
                encode-once union codec rows
    clients*slabs must equal the available device count (multi-host CPU
    tests force it with --xla_force_host_platform_device_count)."""
    return _make_mesh((clients, slabs), ("clients", "slabs"))
