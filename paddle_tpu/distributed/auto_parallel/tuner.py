"""Parallel-plan tuner: 5-axis search with measured cost calibration.

Reference parity: ``python/paddle/distributed/auto_parallel/tuner/``
(``parallel_tuner.py`` searching dist-attr assignments over process-mesh
shapes, ``profiler.py`` measured re-ranking, ``optimization_tuner.py``) and
``cost/`` (comp/comm cost model calibrated from a cluster description).

TPU-native reformulation: the search space is the GSPMD mesh itself —
(dp, sdp/ZeRO, mp, pp, sp) factorizations of the chip count — scored by a
roofline cost model whose constants come from MEASUREMENTS:

- achieved MFU from the recorded end-to-end bench (``bench.py`` JSON),
- ICI bandwidth from a live collective micro-bench (:func:`measure_ici`)
  when a mesh is available.

``ParallelTuner.tune()`` emits ranked candidates; ``validate()`` re-ranks
the top few by actually compiling + timing a scaled-down
DistributedTrainStep on a (possibly host-simulated) mesh — the
``profiler.py`` measured pass.
"""
from __future__ import annotations

import json
import math
import time
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .planner import ClusterSpec, ModelSpec

__all__ = ["ParallelTuner", "TunedPlan", "calibrate_cluster", "measure_ici"]


@dataclass
class TunedPlan:
    """One (dp, sdp, mp, pp, sp) candidate with modeled costs."""

    dp: int
    sdp: int
    mp: int
    pp: int
    sp: int
    step_time: float
    compute_time: float
    comm_time: float
    bubble_time: float
    mem_per_chip: float
    feasible: bool
    measured_time: Optional[float] = None

    @property
    def n_devices(self) -> int:
        return self.dp * self.sdp * self.mp * self.pp * self.sp

    @property
    def axes(self) -> Dict[str, int]:
        out = {}
        for name in ("dp", "sdp", "mp", "pp", "sp"):
            v = getattr(self, name)
            if v > 1:
                out[name] = v
        return out or {"dp": 1}

    def describe(self) -> str:
        t = self.step_time * 1e3
        return (f"{self.axes} step={t:.1f}ms (comp={self.compute_time*1e3:.1f}"
                f" comm={self.comm_time*1e3:.1f} bubble="
                f"{self.bubble_time*1e3:.1f}) mem={self.mem_per_chip/1e9:.1f}GB"
                f"{'' if self.feasible else ' INFEASIBLE'}")


def calibrate_cluster(bench_json: Optional[Any] = None,
                      base: Optional[ClusterSpec] = None,
                      ici_bandwidth: Optional[float] = None) -> ClusterSpec:
    """Build a :class:`ClusterSpec` from measurements instead of defaults.

    ``bench_json``: a path or dict in ``bench.py`` output shape — its
    ``extra.mfu`` replaces the default achievable-MFU guess (the single
    most load-bearing constant in the roofline). ``ici_bandwidth``: from
    :func:`measure_ici` when real chips are meshed.
    """
    spec = base or ClusterSpec()
    if bench_json is not None:
        if isinstance(bench_json, str):
            with open(bench_json) as f:
                bench_json = json.load(f)
        # accept both the raw bench line and the driver's BENCH_r{N} wrapper
        payload = bench_json.get("parsed", bench_json)
        mfu = payload.get("extra", {}).get("mfu")
        if mfu:
            spec = replace(spec, mfu=float(mfu))
    if ici_bandwidth:
        spec = replace(spec, ici_bandwidth=float(ici_bandwidth))
    return spec


def measure_ici(mesh=None, size_mb: float = 64.0, iters: int = 5) -> float:
    """Measured all-reduce bandwidth (bytes/s per chip) over the mesh's
    first axis — the collectives micro-bench feeding the cost model's
    ``ici_bandwidth``. Runs a psum inside shard_map and times it."""
    import jax
    import jax.numpy as jnp
    from ...framework.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        from ..mesh import current_mesh

        mesh = current_mesh()
    axis = mesh.axis_names[0]
    k = mesh.shape[axis]
    elems = int(size_mb * 1e6 / 4)
    # (k, elems) sharded over the ring axis: each chip holds ONE row of
    # size_mb (replicated across any other mesh axes)
    x = jnp.ones((k, elems), jnp.float32)

    @jax.jit
    def allreduce(v):
        return shard_map(lambda u: jax.lax.psum(u, axis), mesh=mesh,
                         in_specs=P(axis), out_specs=P(axis))(v)

    out = allreduce(x)
    float(np.asarray(out).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = allreduce(x)
    float(np.asarray(out).ravel()[0])
    dt = (time.perf_counter() - t0) / iters
    # ring all-reduce moves 2*(k-1)/k of each chip's LOCAL shard
    return (2 * (k - 1) / max(k, 1)) * (elems * 4) / dt


class ParallelTuner:
    """Search (dp, sdp, mp, pp, sp) factorizations of the device count,
    rank by a measured-calibrated roofline, optionally re-rank the top few
    by real compiled-step timings.
    """

    def __init__(self, model: ModelSpec, n_devices: int,
                 cluster: Optional[ClusterSpec] = None,
                 micro_batches: int = 8, num_heads: Optional[int] = None):
        self.model = model
        self.n_devices = int(n_devices)
        self.cluster = cluster or ClusterSpec()
        self.micro_batches = int(micro_batches)
        self.num_heads = num_heads

    # ------------------------------------------------------------- model
    def evaluate(self, dp: int, sdp: int, mp: int, pp: int,
                 sp: int) -> TunedPlan:
        m, c = self.model, self.cluster
        n_dev = dp * sdp * mp * pp * sp
        data_par = dp * sdp
        # tokens processed per (dp*sdp) replica group per step
        tokens_per_group = m.global_batch_tokens / data_par

        # ---- compute + pipeline bubble
        total_flops = m.flops_per_token * m.global_batch_tokens
        compute_time = total_flops / (n_dev * c.peak_flops * c.mfu)
        bubble_time = 0.0
        if pp > 1:
            # 1F1B bubble: (pp-1)/micro_batches of the pipeline's busy time
            bubble_time = compute_time * (pp - 1) / max(self.micro_batches, 1)

        # ---- comm over ICI
        comm_time = 0.0
        grad_bytes = m.n_params * m.bytes_per_param / (mp * pp)
        if data_par > 1:
            comm_time += 2 * (data_par - 1) / data_par * grad_bytes \
                / c.ici_bandwidth
        if sdp > 1:
            # ZeRO param all-gather once per step
            comm_time += grad_bytes / c.ici_bandwidth
        if mp > 1:
            # 2 activation all-reduces per layer fwd, 2 bwd
            act_bytes = tokens_per_group / sp * m.hidden_size \
                * m.bytes_per_param
            comm_time += m.n_layers * 4 * 2 * (mp - 1) / mp * act_bytes \
                / c.ici_bandwidth
        if sp > 1:
            # ring attention: KV blocks circulate the full ring per layer,
            # fwd + bwd (2x); each hop moves the local KV shard
            kv_bytes = tokens_per_group / sp * m.hidden_size * 2 \
                * m.bytes_per_param
            comm_time += m.n_layers * 2 * (sp - 1) * kv_bytes \
                / c.ici_bandwidth
        if pp > 1:
            # p2p activations at each stage boundary per micro-batch
            micro_act = tokens_per_group / max(self.micro_batches, 1) \
                * m.hidden_size * m.bytes_per_param / sp
            comm_time += 2 * (pp - 1) * self.micro_batches * micro_act \
                / c.ici_bandwidth

        # ---- memory per chip
        param_bytes = m.n_params * m.bytes_per_param
        state_bytes = param_bytes * m.optim_state_mult
        zero_shard = sdp if sdp > 1 else 1
        mem = (param_bytes + state_bytes) / (mp * pp) / zero_shard
        act_factor = 2.0 if m.remat else 14.0
        act = tokens_per_group / sp * m.hidden_size \
            * (m.n_layers / pp) * act_factor / mp
        if pp > 1:
            # 1F1B holds up to pp in-flight micro-batches of activations
            act = act / max(self.micro_batches, 1) * min(pp, self.micro_batches)
        mem_per_chip = mem + act

        return TunedPlan(
            dp=dp, sdp=sdp, mp=mp, pp=pp, sp=sp,
            step_time=compute_time + comm_time + bubble_time,
            compute_time=compute_time, comm_time=comm_time,
            bubble_time=bubble_time, mem_per_chip=mem_per_chip,
            feasible=mem_per_chip <= c.hbm_per_chip)

    # ------------------------------------------------------------ search
    def _valid_axes(self, dp, sdp, mp, pp, sp) -> bool:
        m = self.model
        if m.hidden_size % mp:
            return False
        if self.num_heads and self.num_heads % (mp * sp):
            return False
        if m.n_layers % pp:
            return False
        if m.seq_len % sp or (sp > 1 and m.seq_len // sp < 128):
            return False
        # batch must split over the data axes
        if (m.global_batch_tokens / m.seq_len) % (dp * sdp):
            return False
        return True

    def candidates(self) -> List[TunedPlan]:
        n = self.n_devices
        seen = set()
        out = []
        for mp in _divisors(n):
            for pp in _divisors(n // mp):
                for sp in _divisors(n // (mp * pp)):
                    rest = n // (mp * pp * sp)
                    for sdp in _divisors(rest):
                        dp = rest // sdp
                        key = (dp, sdp, mp, pp, sp)
                        if key in seen:
                            continue
                        seen.add(key)
                        if not self._valid_axes(*key):
                            continue
                        out.append(self.evaluate(*key))
        return sorted(out, key=lambda c: (not c.feasible, c.step_time))

    def tune(self, top_k: Optional[int] = None) -> List[TunedPlan]:
        cands = self.candidates()
        if not cands:
            raise ValueError(
                f"no valid plan for {self.n_devices} devices and this model")
        return cands[:top_k] if top_k else cands

    def best(self) -> TunedPlan:
        best = self.tune()[0]
        if not best.feasible:
            raise ValueError(
                f"no feasible plan fits HBM; closest: {best.describe()}")
        return best

    # ---------------------------------------------------------- measured
    def validate(self, plans: Sequence[TunedPlan],
                 step_builder: Callable[[TunedPlan], Callable[[], Any]],
                 steps: int = 3) -> List[TunedPlan]:
        """Measured re-rank (the reference tuner's ``profiler.py`` pass):
        ``step_builder(plan)`` returns a zero-arg callable running ONE
        training step under that plan's mesh; each plan is timed after a
        warmup step and returned sorted by measured time."""
        measured = []
        for plan in plans:
            run = step_builder(plan)
            run()  # compile + warm
            t0 = time.perf_counter()
            for _ in range(steps):
                out = run()
            _materialize(out)
            measured.append(replace(
                plan, measured_time=(time.perf_counter() - t0) / steps))
        return sorted(measured, key=lambda c: c.measured_time)


def _materialize(out) -> None:
    import jax

    leaves = jax.tree.leaves(out)
    if leaves:
        np.asarray(leaves[0])


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]
