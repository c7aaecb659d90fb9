"""Mesh construction and sharding helpers.

One logical axis — "keys" — shards the bucket-state arrays.  This is
the TPU-native analog of the reference's worker hash ring
(reference: gubernator_pool.go:128-148): each device owns a contiguous
slot range instead of each goroutine owning a hash arc.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — re-exported for the mesh tier
from jax.sharding import Mesh, NamedSharding, PartitionSpec

KEYS_AXIS = "keys"


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated placement (the psum-merged GLOBAL columns are
    identical on every device)."""
    return NamedSharding(mesh, PartitionSpec())


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D mesh over `devices` (default: all local devices)."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (KEYS_AXIS,))


def keys_sharding(mesh: Mesh) -> NamedSharding:
    """Shard a leading-axis array over the keys axis."""
    return NamedSharding(mesh, PartitionSpec(KEYS_AXIS))
