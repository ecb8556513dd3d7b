"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax init,
and tests/benches must keep seeing the single real CPU device.

Every mesh has ``Auto`` axes: the code places arrays with explicit
``NamedSharding``s and ``shard_map``, and relies on plain indexing and
gathers of sharded arrays, which ``Explicit`` axes (the ``jax.make_mesh``
default in the installed jax) reject.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(devices=None):
    """Smallest honest mesh for local runs: (data=N, model=1)."""
    devices = devices if devices is not None else jax.devices()
    return make_mesh((len(devices), 1), ("data", "model"), devices)


def make_class_mesh(n_class_shards: int, n_data_shards: int = 1,
                    devices=None):
    """("data", "class") mesh for the sharded extreme-classification
    estimator (``repro.api.sharded``): profile/codebook rows shard over
    "class", fit examples optionally shard over "data".  Uses the first
    ``n_data_shards * n_class_shards`` devices."""
    devices = devices if devices is not None else jax.devices()
    need = int(n_data_shards) * int(n_class_shards)
    if need < 1 or len(devices) < need:
        raise ValueError(
            f"class mesh needs {n_data_shards} x {n_class_shards} = {need} "
            f"devices, have {len(devices)}")
    return make_mesh((int(n_data_shards), int(n_class_shards)),
                     ("data", "class"), devices[:need])
