"""Async host->HBM prefetch: overlap the H2D hop with device compute.

The compiled step consumes batch N while a background thread already
issues the (PJRT-async) transfer for batch N+1 — the input/compute overlap
discipline that dominates step time once the step itself is fused. With a
``sharding`` (or mesh) the transfer lands each host's slice directly in
its GSPMD layout via ``make_array_from_process_local_data`` instead of a
replicated copy; without one it is a plain ``jax.device_put``.

Usage::

    it = prefetch_to_device(loader, depth=2)          # single device
    it = prefetch_to_device(loader, sharding=named)   # sharded landing
    for batch in it:
        loss = step(batch)
    it.close()   # also runs on exhaustion / GC

``it.stats()`` reports consumer stall seconds — the direct measure of an
input-bound pipeline.
"""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np

from .dataloader import _PrefetchIterator

__all__ = ["DevicePrefetchIterator", "prefetch_to_device"]


def _transfer_leaf(x, sharding, device):
    import jax

    arr = np.asarray(x)
    if sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from ..framework.jax_compat import make_array_from_process_local_data

        if (isinstance(sharding, NamedSharding)
                and arr.ndim < len(sharding.spec)):
            # lower-rank rider (e.g. the [B] validity mask next to
            # [B, S] data): clip the spec to the leaf's rank instead
            # of crashing on the rank mismatch
            sharding = NamedSharding(
                sharding.mesh, PartitionSpec(*sharding.spec[:arr.ndim]))
        return make_array_from_process_local_data(sharding, arr)
    if device is not None:
        return jax.device_put(arr, device)
    return jax.device_put(arr)


class DevicePrefetchIterator(_PrefetchIterator):
    """Double-buffered device prefetch over any host-batch iterable.

    ``depth`` bounds the number of batches resident in HBM ahead of the
    consumer (2 = classic double buffering). The transfer runs in the
    producer thread under a ``h2d_prefetch`` profiler span; ``close()``
    (also called on exhaustion, error delivery, and GC) unblocks and joins
    the thread.
    """

    def __init__(self, producer: Iterable, depth: int = 2, sharding=None,
                 mesh=None, device=None, spec=None):
        if sharding is None and mesh is not None:
            if spec is None:
                # no silent default: PartitionSpec() (replicated) would
                # assert each process's DIFFERENT local batch is the same
                # global array on multi-host — pass the batch-axis spec
                raise ValueError(
                    "DevicePrefetchIterator(mesh=...) needs spec= (e.g. "
                    "PartitionSpec('dp') for a batch-sharded landing); or "
                    "pass sharding= directly")
            from jax.sharding import NamedSharding

            sharding = NamedSharding(mesh, spec)
        self._sharding = sharding
        self._device = device

        # a plain closure, NOT a bound method: the producer thread must not
        # hold a reference to the iterator or GC-driven shutdown breaks
        # (see dataloader._PrefetchState)
        def to_device(batch):
            import jax

            from ..profiler import RecordEvent

            with RecordEvent("h2d_prefetch"):
                return jax.tree.map(
                    lambda x: _transfer_leaf(x, sharding, device), batch)

        super().__init__(producer, depth=depth, transform=to_device)


def prefetch_to_device(data: Iterable, depth: int = 2, sharding=None,
                       mesh=None, device=None,
                       spec=None) -> DevicePrefetchIterator:
    """Wrap an iterable of host batches in a :class:`DevicePrefetchIterator`."""
    return DevicePrefetchIterator(data, depth=depth, sharding=sharding,
                                  mesh=mesh, device=device, spec=spec)
