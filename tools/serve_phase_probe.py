#!/usr/bin/env python
"""One traced run of a serving cell of the benchmark, with what
``benchmarks/run.py --trace 1`` throws away kept: the profiler's
``.xplane.pb`` of the traced slice and the serve loop's spans of the same
stretch. Run by hand, on the chip, when the serve loop's host turn is in
question (PERF.md section 6, "PR 25"):

    chiprun -- python tools/serve_phase_probe.py --workload \\
        gpt3-xl.serve-batch --seed 7 --seconds 50 --out chiprun_out/probe_xl

It drives the benchmark's own harness (``benchmarks/harness/serve.py``:
the same server, traffic, window and slice) and changes two things from
outside: the traced slice writes under ``--out`` instead of a temporary
directory, and ``--host-tracer 1`` turns on the profiler's host tracer at
level 1 (the ``TraceAnnotation`` of every span; the benchmark records
device events only). It prints

- the end-to-end metrics and the loop's phase counters over the window,
  with their sum against the window (the phases must cover the thread),
- ``trace_view --xplane`` on the slice: the causality check, the device's
  idle gaps by host phase and by neighbouring programs,
- the phase instances that took long, each with the loop thread's CPU
  time in it (a stall with no CPU time is a wait: the interpreter lock,
  the machine; one with CPU time is work),
- with ``--host-tracer 1``, how far the ``TraceAnnotation`` events on the
  trace's ``/host:CPU`` plane lie from the ring's spans of the same name.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT,
                os.path.join(ROOT, "tools")]

HOST_PHASES = ("schedule", "admit_host", "decode_dispatch", "emit")
LONG_HOST_NS, LONG_WAIT_NS = 10e6, 80e6


def _install(out_dir: str, host_tracer: int, kept: dict) -> None:
    """The two changes from outside the harness, and the log of long
    phase instances."""
    import jax

    from harness import common, trace_reduce
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving.metrics import ServingMetrics

    def start_trace(log_dir):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = host_tracer
        jax.profiler.start_trace(log_dir, profiler_options=options)

    @contextlib.contextmanager
    def traced_slice(holder):
        d = os.path.join(out_dir, "trace")
        start_trace(d)
        t0 = time.time()
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        kept["spans"] = [s for s in tracing.spans() if s["t1"] >= t0 - 1.0]
        kept["xplane"] = trace_reduce.newest_xplane(d)
        holder["trace"] = trace_reduce.reduce_trace(kept["xplane"])

    common._start_trace = start_trace      # warm_up_profiler() uses it too
    common.traced_slice = traced_slice

    book = ServingMetrics.loop_phase
    kept["long"] = []

    def logged(self, phase, wall_ns, cpu_ns):
        limit = LONG_HOST_NS if phase in HOST_PHASES else LONG_WAIT_NS
        if wall_ns >= limit and phase != "idle":
            kept["long"].append((time.time(), phase, wall_ns, cpu_ns))
        book(self, phase, wall_ns, cpu_ns)

    ServingMetrics.loop_phase = logged


def _print_counters(res: dict) -> None:
    a, b = res["ctx"]["serving"]["open"], res["ctx"]["serving"]["close"]
    window = b["elapsed_s"] - a["elapsed_s"]
    steps = b["decode_steps"] - a["decode_steps"]
    print(f"window {window:.3f} s between the two snapshots, {steps} decode "
          f"steps, {b['prefills'] - a['prefills']} admissions")
    print("phase            count    wall_s     cpu_s  wall_ms/step")
    total = 0.0
    for p, v in b["loop"].items():
        n = v["count"] - a["loop"][p]["count"]
        wall = v["wall_s"] - a["loop"][p]["wall_s"]
        cpu = v["cpu_s"] - a["loop"][p]["cpu_s"]
        total += wall
        print(f"{p:16s}{n:6d}{wall:10.4f}{cpu:10.4f}"
              f"{1e3 * wall / max(steps, 1):12.4f}")
    print(f"phases sum to {total:.4f} s = {100 * total / window:.3f} % of "
          f"the window")


def _print_long(kept: dict, t_open_wall: float) -> None:
    print(f"phase instances over {LONG_HOST_NS / 1e6:.0f} ms (host) or "
          f"{LONG_WAIT_NS / 1e6:.0f} ms (waits), ended at s of the window:")
    for t, phase, wall, cpu in kept["long"]:
        if t >= t_open_wall:
            print(f"  {t - t_open_wall:8.3f}  {phase:16s} wall "
                  f"{wall / 1e6:8.2f} ms  cpu {cpu / 1e6:8.2f} ms")


def _compare_annotations(kept: dict, start_s: float) -> None:
    """Ring spans against the TraceAnnotation events of the same name on
    the trace's host plane (which starts at ``start_s``)."""
    import jax
    import numpy as np

    data = jax.profiler.ProfileData.from_file(kept["xplane"])
    host = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns))
    if not host:
        print("no serve.* TraceAnnotation on /host:CPU")
        return
    print("TraceAnnotation on /host:CPU against the ring's span, us "
          "(start: annotation less span; duration likewise):")
    for name, evs in sorted(host.items()):
        evs = np.asarray(sorted(evs), float)
        ring = np.asarray(sorted(
            ((s["t0"] - start_s) * 1e9, (s["t1"] - s["t0"]) * 1e9)
            for s in kept["spans"] if s["name"] == name), float)
        if not len(ring):
            continue
        # each annotation against the ring span that starts nearest
        j = np.abs(evs[:, :1] - ring[None, :, 0]).argmin(axis=1)
        d_start = (evs[:, 0] - ring[j, 0]) * 1e-3
        d_dur = (evs[:, 1] - ring[j, 1]) * 1e-3
        print(f"  {name:24s} n {len(evs):5d} (ring {len(ring):5d})  start "
              f"median {np.median(d_start):8.2f} worst "
              f"{d_start[np.abs(d_start).argmax()]:9.2f}  duration median "
              f"{np.median(d_dur):8.2f} worst "
              f"{d_dur[np.abs(d_dur).argmax()]:9.2f}")


def _print_step_latencies(spans: list, xp: dict, program: str) -> None:
    """Per decode step of the slice, from the merged timeline: how long
    after the dispatch call returned the device began (the launch), and
    how long after the device ended the read-back returned."""
    import numpy as np

    steps = {}
    for s in spans:
        if s["name"] in ("serve.decode.dispatch", "serve.decode.wait"):
            steps.setdefault(s["tags"].get("step"), {})[s["name"]] = s
    rel = lambda t: (t - xp["start_s"]) * 1e9          # noqa: E731
    # the loop is one step ahead: a step's wait begins a launch later
    # than its own dispatch ends
    win = sorted((rel(v["serve.decode.dispatch"]["t0"]),
                  rel(v["serve.decode.dispatch"]["t1"]),
                  rel(v["serve.decode.wait"]["t1"]))
                 for v in steps.values() if len(v) == 2)
    runs = sorted((s, s + d) for dev in xp["devices"]
                  for n, s, d in dev["modules"] if program in n)
    if not win or not runs:
        return
    lo = np.asarray([w[0] for w in win])
    rows = []
    for start, end in runs:
        i = int(np.searchsorted(lo, start, side="right")) - 1
        if i >= 0 and end <= win[i][2] + 1e3:
            t_disp, t_ret, t_back = win[i]
            rows.append((t_ret - t_disp, start - t_ret, end - start,
                         t_back - end))
    if not rows:
        return
    rows = np.asarray(rows) * 1e-6
    print(f"per step of the slice ({len(rows)} steps), ms, median / p95 / "
          f"worst:")
    for k, label in enumerate(("dispatch call", "call returned -> device "
                               "begins", "device runs", "device ends -> "
                               "read-back returns")):
        c = rows[:, k]
        print(f"  {label:38s}{np.median(c):8.3f}{np.percentile(c, 95):8.3f}"
              f"{c[np.abs(c).argmax()]:9.3f}")


def probe(cell: dict, config: dict, args) -> int:
    """Run the cell's harness with the probe installed and print what
    the module's docstring lists."""
    import jax

    import trace_view
    from harness import common

    kept: dict = {}
    _install(args.out, args.host_tracer, kept)
    # perf_counter and time.time() read together: the harness stamps its
    # window with the first, the spans are on the second
    pc0, wall0 = time.perf_counter(), time.time()
    res = common.resolve(cell["harness"])(cell, config, args.seed,
                                          args.seconds, True)
    print(f"device {jax.devices()[0].device_kind}; host tracer level "
          f"{args.host_tracer}; correct {res['correct']}; attempted "
          f"{res['attempted']} failed {res['failed']}")
    print("end-to-end " + json.dumps(
        {k: v for k, (v, _) in res["end_to_end"].items()}))
    tr = res["ctx"]["trace"]
    print(f"traced slice: window {tr['window_s']:.4f} s busy "
          f"{tr['busy_s']:.4f} s idle {tr['window_s'] - tr['busy_s']:.4f} s")
    _print_counters(res)
    _print_long(kept, wall0 + (res["t_window_open"] - pc0))

    spans_path = os.path.join(args.out, "spans.json")
    with open(spans_path, "w") as f:
        json.dump(kept["spans"], f)
    print(f"{len(kept['spans'])} spans of the slice in {spans_path}")
    xp = trace_view.read_xplane(kept["xplane"])
    rc = trace_view.report_xplane(kept["spans"], xp, "decode")
    print(f"{sum('decode' in m[0] for d in xp['devices'] for m in d['modules'])}"
          f" decode programs in the slice")
    _print_step_latencies(kept["spans"], xp, "decode")
    if args.host_tracer:
        _compare_annotations(kept, xp["start_s"])
    if args.keep_xplane:
        print(f"kept {kept['xplane']}")
    else:
        shutil.rmtree(os.path.join(args.out, "trace"))
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--host-tracer", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep-xplane", action="store_true",
                    help="leave the .xplane.pb under --out (tens of MB)")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    import run as bench_run

    _, cell, config = bench_run.load_cell(args.workload)
    if not cell["regime"].startswith("serve"):
        sys.exit("a serving cell, please")
    os.environ["JAX_PLATFORMS"] = "tpu"   # a chip or nothing, as run.py

    from paddle_tpu.framework import compile_cache

    compile_cache.enable_persistent_cache()
    return probe(cell, config, args)


if __name__ == "__main__":
    sys.exit(main())
