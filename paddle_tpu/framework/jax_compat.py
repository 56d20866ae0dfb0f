"""The single import point for the jax SPMD primitives the package uses,
so a jax upgrade that moves one of them is a one-file change."""
from __future__ import annotations

from jax import make_array_from_process_local_data, shard_map
from jax.lax import axis_size, pcast

__all__ = ["shard_map", "axis_size", "pcast",
           "make_array_from_process_local_data"]
