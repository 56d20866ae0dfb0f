"""From a profiler trace (``.xplane.pb``) to what the metrics read: the
device's programs and operations with their device durations, the union
of the time an operation ran, and the idle gaps named by the programs on
either side. Read with ``jax.profiler.ProfileData``, nothing else.

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA
Modules`` holds one event a program run (``jit__step(<hash>)``,
``jit__decode_fn(<hash>)``) and whose line ``XLA Ops`` holds every HLO
operation, kernels among them as custom calls named after their jitted
wrapper (``..._flash_fwd_impl...``). Times are on the device's clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def short_module(name: str) -> str:
    """``jit__decode_fn(123)`` -> ``decode``; ``jit__step(9)`` -> ``step``."""
    name = name.split("(", 1)[0]
    name = name[4:] if name.startswith("jit_") else name
    name = name.strip("_")
    return name[:-3] if name.endswith("_fn") else name


_RESULT_AND_OPCODE = re.compile(r"^(\([^()]*\)|\S+)\s+([\w\-]+)\(")


def op_key(name: str) -> str:
    """Group the instances of one operation: drop the numbering of the
    instruction (``%fusion.12`` -> ``%fusion``), the memory layouts
    (``{1,0:T(8,128)S(1)}``) and the operands, which all differ between
    instances of one kernel; keep the result's shape and the opcode."""
    head, sep, rest = name.partition(" = ")
    head = re.sub(r"[.\d]+$", "", head)
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    m = _RESULT_AND_OPCODE.match(rest)
    if m:
        rest = f"{m.group(1)} {m.group(2)}"
    return (head + sep + rest)[:160]


def _union(starts: np.ndarray, ends: np.ndarray):
    """Busy nanoseconds of the union of ``[start, end)`` intervals and
    the gaps between its pieces, as two arrays (gap start, gap end)."""
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    reach = np.maximum.accumulate(ends)
    is_gap = starts[1:] > reach[:-1]
    g0, g1 = reach[:-1][is_gap], starts[1:][is_gap]
    busy = float(reach[-1] - starts[0] - np.sum(g1 - g0))
    return busy, g0, g1


def _reduce_plane(plane) -> dict:
    modules, op_start, op_end = [], [], []
    ops = defaultdict(lambda: [0, 0.0])
    for line in plane.lines:
        if line.name == MODULES_LINE:
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events)
        elif line.name == OPS_LINE:
            for e in line.events:
                op_start.append(e.start_ns)
                op_end.append(e.start_ns + e.duration_ns)
                slot = ops[e.name]
                slot[0] += 1
                slot[1] += e.duration_ns
    if not op_start:
        return {"name": plane.name, "modules": [], "ops": {}, "busy_ns": 0.0,
                "t0_ns": 0.0, "t1_ns": 0.0, "gaps": {}}
    starts, ends = np.asarray(op_start, float), np.asarray(op_end, float)
    busy, g0, g1 = _union(starts, ends)
    # name each gap by the programs around it
    gaps = defaultdict(float)
    if modules:
        m0 = np.asarray([m[0] for m in modules])
        m1 = np.asarray([m[1] for m in modules])
        short = [short_module(m[2]) for m in modules]
        # code 0: before the first program; 2i + 1: after program i and
        # outside it; 2i + 2: inside program i
        at = np.searchsorted(m0, g0, side="right") - 1
        inside = g1 <= m1[np.maximum(at, 0)]
        code = np.where(at < 0, 0, 2 * at + 1 + inside)
        for c, ns in enumerate(np.bincount(code, weights=g1 - g0)):
            i = (c - 1) // 2
            if ns == 0:
                continue
            if c == 0:
                label = "before first"
            elif c % 2 == 0:
                label = f"in {short[i]}"
            elif i + 1 < len(modules):
                label = f"between {short[i]} and {short[i + 1]}"
            else:
                label = f"after last {short[i]}"
            gaps[label] += float(ns)
    return {"name": plane.name,
            "modules": [(n, s, e - s) for s, e, n in modules],
            "ops": {k: tuple(v) for k, v in ops.items()},
            "busy_ns": busy, "t0_ns": float(starts.min()),
            "t1_ns": float(ends.max()), "gaps": dict(gaps)}


def reduce_trace(path: str) -> dict:
    """``{"devices": [...], "busy_s", "window_s", "idle_share"}``.

    The window is the stretch from the first device operation of the
    traced slice to the end of the last, per chip; busy is the union of
    the operations inside it; both are averaged over the chips that ran
    anything. A slice in which nothing ran on any device has no window:
    ``busy_s`` and ``window_s`` are 0 and the caller must refuse it."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices = [_reduce_plane(p) for p in data.planes
               if _DEVICE_PLANE.match(p.name)]
    ran = [d for d in devices if d["busy_ns"] > 0]
    if not ran:
        return {"devices": devices, "busy_s": 0.0, "window_s": 0.0,
                "idle_share": None}
    busy = float(np.mean([d["busy_ns"] for d in ran])) * 1e-9
    window = float(np.mean([d["t1_ns"] - d["t0_ns"] for d in ran])) * 1e-9
    return {"devices": devices, "busy_s": busy, "window_s": window,
            "idle_share": 1.0 - busy / window}


def module_durations_s(reduced: dict, substring: str) -> list:
    """Device durations, in seconds, of the program runs whose name holds
    ``substring``, over all chips."""
    return [d * 1e-9 for dev in reduced["devices"]
            for name, _, d in dev["modules"] if substring in name]


def median_module_ms(reduced: dict, substring: str):
    """Median device duration in ms of those program runs, None if none."""
    runs = module_durations_s(reduced, substring)
    return 1e3 * float(np.median(runs)) if runs else None


def op_seconds(reduced: dict, substring: str):
    """(events, summed device seconds) of the operations whose name holds
    ``substring``, over all chips."""
    n, total = 0, 0.0
    for dev in reduced["devices"]:
        for name, (count, ns) in dev["ops"].items():
            if substring in name:
                n += count
                total += ns * 1e-9
    return n, total


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time (instances of one operation grouped, see :func:`op_key`; an
    operation that encloses others, such as a ``while``, is listed with
    what it encloses) and the idle gaps by the programs around them."""
    ops, gaps = defaultdict(float), defaultdict(float)
    chips = max(1, sum(1 for d in reduced["devices"] if d["busy_ns"] > 0))
    for dev in reduced["devices"]:
        for name, (_, ns) in dev["ops"].items():
            ops[op_key(name)] += ns * 1e-9 / chips
        for label, ns in dev["gaps"].items():
            gaps[label] += ns * 1e-9 / chips

    def biggest(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": biggest(ops), "idle_gaps": biggest(gaps)}
