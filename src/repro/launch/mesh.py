"""Production mesh factory.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* importing
jax; everything else sees the real device count.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (used by tests and the mapping optimizer)."""
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: Optional[int] = None):
    """A mesh over whatever devices exist (CPU smoke tests: 1 device)."""
    n = jax.device_count()
    mp = model_parallel or 1
    assert n % mp == 0
    return jax.make_mesh((n // mp, mp), ("data", "model"))


def data_axes(mesh) -> Tuple[str, ...]:
    """The batch-sharding axes for this mesh ('pod' included when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def engine_mesh(devices: Sequence) -> Mesh:
    """The (1, len(devices)) ("data", "model") mesh of ONE serving engine:
    several devices shard its weights and KV heads over ``model`` (tensor
    parallelism); one device pins the engine, params and pool, there."""
    return Mesh(np.array(devices).reshape(1, -1), ("data", "model"))


def replica_meshes(n: int) -> List[Mesh]:
    """One single-device engine mesh per serving replica, dealt over the
    devices in order: replica i lives on device i mod device count."""
    devs = jax.devices()
    return [engine_mesh([devs[i % len(devs)]]) for i in range(n)]
