"""Production meshes (contract-specified shapes).

`make_production_mesh` is a FUNCTION so importing this module never touches
jax device state.  Axis meanings:
  pod   — across-pod axis (DP by default; pipeline stages when enabled)
  data  — in-pod data parallelism (+ FSDP shard axis for big archs)
  model — tensor/expert/context parallelism
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh over a prefix of jax.devices() (tests / small runs)."""
    n = int(np.prod(shape))
    devs = np.array(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, axes)


def single_device_mesh() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
