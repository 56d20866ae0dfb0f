"""Process bootstrap & environment.

Reference parity: ``python/paddle/distributed/parallel.py:98``
(``init_parallel_env`` — env-var rank discovery, TCPStore rendezvous at
``parallel.py:268``, NCCL comm init). TPU-native: JAX's distributed
coordination service *is* the TCPStore+comm-init bundle — one call wires every
host into a global runtime where ``jax.devices()`` spans the whole slice.
NCCL-ring bootstrap ops (``c_gen_nccl_id``/``c_comm_init``) have no analogue:
the mesh exists as soon as the runtime is up.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_initialized = False


def init_parallel_env(coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None) -> None:
    """Initialize multi-host execution. Single-process (one host, N chips)
    needs no initialization — SPMD covers all local devices. Multi-host reads
    either explicit args or the env contract:

    - ``PADDLE_MASTER`` / ``MASTER_ADDR:MASTER_PORT`` -> coordinator
    - ``PADDLE_TRAINERS_NUM`` / ``WORLD_SIZE``        -> process count
    - ``PADDLE_TRAINER_ID`` / ``RANK``                -> process id
    """
    global _initialized
    if _initialized:
        return
    coord = coordinator_address or os.environ.get("PADDLE_MASTER")
    if coord is None and os.environ.get("MASTER_ADDR"):
        coord = f"{os.environ['MASTER_ADDR']}:{os.environ.get('MASTER_PORT', '8701')}"
    nproc = num_processes or int(os.environ.get("PADDLE_TRAINERS_NUM",
                                                os.environ.get("WORLD_SIZE", "1")))
    pid = process_id if process_id is not None else int(
        os.environ.get("PADDLE_TRAINER_ID", os.environ.get("RANK", "0")))
    if coord is not None and nproc > 1:
        # NB: don't call jax.default_backend() here — it would initialise
        # the backends before jax.distributed.initialize gets to run
        if _cpu_platform_requested():
            # the CPU backend compiles cross-process collectives only when
            # a collectives layer is configured; without it every
            # multi-controller program (and even a replicated device_put,
            # which broadcasts to assert value equality) dies with
            # "Multiprocess computations aren't implemented on the CPU
            # backend" — the simulated-mesh test/CI path needs gloo
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            if _backends_initialized():
                # the config only shapes CpuClient CONSTRUCTION — a
                # backend built before this call has no collectives
                # layer, and the update above is silently inert
                import warnings

                warnings.warn(
                    "init_parallel_env: the CPU backend was already "
                    "initialized, so the gloo collectives config "
                    "cannot take effect — cross-process programs will "
                    "fail with 'Multiprocess computations aren't "
                    "implemented on the CPU backend'. Call "
                    "init_parallel_env before anything touches a jax "
                    "array.", RuntimeWarning)
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    _initialized = True


def _backends_initialized() -> bool:
    # no public spelling of this query exists in jax 0.9
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _cpu_platform_requested() -> bool:
    """True when the process is pinned to the CPU backend. Before a
    backend is live the declared platform decides —
    ``jax.default_backend()`` would initialise one."""
    if _backends_initialized():
        return jax.default_backend() == "cpu"
    return "cpu" in (jax.config.jax_platforms or "").split(",")


def get_rank() -> int:
    """Process (host) index — the unit of data loading and checkpoint IO."""
    return jax.process_index()


def get_world_size() -> int:
    return jax.process_count()


def device_count() -> int:
    return len(jax.devices())


def local_device_count() -> int:
    return len(jax.local_devices())


def is_initialized() -> bool:
    return _initialized or jax.process_count() > 1


def barrier(group=None):
    """Host barrier (reference: GlooWrapper barrier, ``gloo_wrapper.h:139``)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("paddle_tpu_barrier")
