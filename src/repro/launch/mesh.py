"""Production mesh construction (functions only -- importing this module
never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    return _mk(shape, axes)


def make_replicated_mesh(replication: int, n_shards: int, model_parallel: int):
    """RDP mesh ("replica","shard","model") for a replication plan (B, r)."""
    return _mk((replication, n_shards, model_parallel), ("replica", "shard", "model"))
