#!/usr/bin/env python
"""Merge flight-recorder / trace dumps into one chrome://tracing JSON,
keyed by correlation id.

A fleet request's telemetry is scattered: the router's span buffer in
one process, each replica's spans (and crash-time flight dumps) in
others. This CLI reads any mix of

- flight-recorder dumps (``{"format": "flight_recorder", "spans": [...],
  "events": [...]}`` — what ``observability.flight.dump()`` writes),
- raw span lists (``[{"name", "corr", "t0", "t1", "tags"}, ...]`` — what
  ``observability.tracing.spans()`` serializes to),
- chrome traces (``{"traceEvents": [...]}`` — what
  ``export_chrome_trace`` writes),

and merges every span into ONE chrome trace where each correlation id is
a single named lane, regardless of which process recorded which piece.
Wall-clock timestamps make the cross-process merge line up.

Flight dumps are hostname-prefixed (``flight_<host>_<pid>_...``), so
many hosts can share one dump dir (NFS); ``--list`` groups its summary
by recording host when more than one contributed.

    python tools/trace_view.py flight_records/*.json -o merged.json
    python tools/trace_view.py --list flight_records/*.json
    python tools/trace_view.py --corr req-1f03ab-000004 dumps/*.json \\
        -o one_request.json

``--xplane <file.xplane.pb>`` lays the spans over a ``jax.profiler``
trace of the same process. The trace stamps its start on the clock the
spans are stamped with (``profile_start_time`` on the ``Task
Environment`` plane, nanoseconds since the epoch; every event of the
trace is an offset from it), so nothing is calibrated: the device's
programs join the merged chrome trace as a lane of their own, and the
device's idle gaps are printed by the serve-loop span that covers them
(``serve.schedule``, ``serve.decode.dispatch``, ...; a parent span
counts only where no child covers) beside the by-neighbouring-programs
table of ``benchmarks/harness/trace_reduce.py``. Before it attributes
anything it checks causality: every run of ``--program`` (default the
decode program) must start and end on the device inside one step's
``serve.decode.dispatch`` .. ``serve.decode.wait`` (the two share the
step's tags; the loop launches a step ahead, so the next step's dispatch
lies between them); if over 1 % do not,
the clocks disagree, and it says by how much and stops.

    python tools/trace_view.py spans.json --xplane t.xplane.pb -o m.json

``--scopes <S.json>`` (with ``--xplane``, spans optional) prints the
device's time by program and named scope: ``S.json`` is what
``compile_cache.export_program_scopes(path)`` wrote in the process that
was profiled (which ``jax.named_scope`` each instruction of its compiled
programs belongs to), joined with the trace's device events by
``benchmarks/harness/scope_time.py``, the code the benchmark's
``model.*_share`` metrics read.

    python tools/trace_view.py --xplane t.xplane.pb --scopes S.json

Exit codes: 0 ok; 2 no spans found / unreadable input; 3 the spans and
the trace are not on one clock.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional, Tuple


def _spans_from_chrome(obj: dict, label: str) -> List[dict]:
    out = []
    for ev in obj.get("traceEvents", []):
        if ev.get("ph") not in ("X", "i"):
            continue
        t0 = float(ev.get("ts", 0.0)) / 1e6
        t1 = t0 + float(ev.get("dur", 0.0)) / 1e6
        args = dict(ev.get("args") or {})
        corr = args.pop("correlation_id", None)
        out.append({"name": ev.get("name", "?"), "corr": corr,
                    "t0": t0, "t1": t1, "tags": args, "src": label})
    return out


def _events_as_spans(events: List[dict], label: str) -> List[dict]:
    """Flight-recorder ring events become instant spans so a dump's
    engine_reset/compile markers land on the merged timeline too."""
    out = []
    for ev in events:
        if not isinstance(ev, dict) or "t" not in ev:
            continue
        tags = {k: v for k, v in ev.items()
                if k not in ("t", "kind", "corr")
                and isinstance(v, (str, int, float, bool))}
        out.append({"name": f"event:{ev.get('kind', '?')}",
                    "corr": ev.get("corr"), "t0": float(ev["t"]),
                    "t1": float(ev["t"]), "tags": tags, "src": label})
    return out


def load_spans(path: str) -> Tuple[List[dict], str]:
    """(spans, kind) from one input file; raises on unreadable input."""
    with open(path) as f:
        obj = json.load(f)
    label = os.path.basename(path)
    if isinstance(obj, dict) and obj.get("format") == "flight_recorder":
        label = f"{label}:pid{obj.get('pid', '?')}"
        host = obj.get("host")
        spans = []
        for rec in obj.get("spans", []):
            rec = dict(rec)
            rec["src"] = label
            if host:
                rec.setdefault("host", host)
            spans.append(rec)
        for rec in _events_as_spans(obj.get("events", []), label):
            if host:
                rec.setdefault("host", host)
            spans.append(rec)
        return spans, "flight"
    if isinstance(obj, dict) and "traceEvents" in obj:
        return _spans_from_chrome(obj, label), "chrome"
    if isinstance(obj, list):
        out = []
        for rec in obj:
            if isinstance(rec, dict) and "t0" in rec and "t1" in rec:
                rec = dict(rec)
                rec["src"] = label
                out.append(rec)
        return out, "spans"
    raise ValueError(f"{path}: not a flight dump, span list, or "
                     f"chrome trace")


def _migrated_corrs(spans: List[dict]) -> set:
    """Correlation ids whose KV blocks moved between replicas: the
    prefill side records ``kv_migrate:send``, the decode side
    ``kv_migrate:recv``, under the SAME corr id — seeing both halves
    (usually from different hosts' dumps) marks the request migrated."""
    sends, recvs = set(), set()
    for s in spans:
        c = s.get("corr")
        if c is None:
            continue
        if s.get("name") == "kv_migrate:send":
            sends.add(c)
        elif s.get("name") == "kv_migrate:recv":
            recvs.add(c)
    return sends & recvs


def merge_chrome(spans: List[dict], corr: Optional[str] = None) -> dict:
    """One merged chrome trace: pid 1 = the merged view, one tid lane
    per correlation id (sorted by first-span time so lanes read in
    arrival order), lane 0 for uncorrelated spans. A migrated request
    (kv_migrate:send + recv under one corr) keeps a SINGLE lane even
    though its halves were recorded on different hosts — the lane name
    carries a ``[migrated]`` marker."""
    spans = [s for s in spans
             if corr is None or (s.get("corr") or "").find(corr) >= 0]
    first_seen = {}
    for s in sorted(spans, key=lambda s: s["t0"]):
        c = s.get("corr")
        if c is not None and c not in first_seen:
            first_seen[c] = s["t0"]
    lanes = {c: i + 1 for i, c in enumerate(
        sorted(first_seen, key=first_seen.get))}
    events = [{"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
               "args": {"name": "merged fleet trace"}},
              {"ph": "M", "name": "thread_name", "pid": 1, "tid": 0,
               "args": {"name": "untraced"}}]
    migrated = _migrated_corrs(spans)
    for c, tid in lanes.items():
        lane_name = f"{c} [migrated]" if c in migrated else c
        events.append({"ph": "M", "name": "thread_name", "pid": 1,
                       "tid": tid, "args": {"name": lane_name}})
        events.append({"ph": "M", "name": "thread_sort_index", "pid": 1,
                       "tid": tid, "args": {"sort_index": tid}})
    for s in spans:
        tid = lanes.get(s.get("corr"), 0)
        args = dict(s.get("tags") or {})
        if s.get("corr") is not None:
            args["correlation_id"] = s["corr"]
        if s.get("src"):
            args["source"] = s["src"]
        if s.get("host"):
            args["host"] = s["host"]
        t0, t1 = float(s["t0"]), float(s["t1"])
        ev = {"name": s.get("name", "?"), "pid": 1, "tid": tid,
              "ts": t0 * 1e6, "args": args}
        if t1 > t0:
            ev.update(ph="X", dur=(t1 - t0) * 1e6)
        else:
            ev.update(ph="i", s="t")
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def list_correlations(spans: List[dict]) -> List[dict]:
    migrated = _migrated_corrs(spans)
    by_corr = {}
    for s in spans:
        c = s.get("corr")
        if c is None:
            continue
        e = by_corr.setdefault(c, {"corr": c, "spans": 0,
                                   "t0": s["t0"], "t1": s["t1"],
                                   "names": [], "sources": set(),
                                   "hosts": set()})
        e["spans"] += 1
        e["t0"] = min(e["t0"], s["t0"])
        e["t1"] = max(e["t1"], s["t1"])
        if s.get("name") not in e["names"]:
            e["names"].append(s.get("name"))
        if s.get("src"):
            e["sources"].add(s["src"])
        if s.get("host"):
            e["hosts"].add(s["host"])
    out = []
    for e in sorted(by_corr.values(), key=lambda e: e["t0"]):
        e["duration_ms"] = round((e["t1"] - e["t0"]) * 1e3, 3)
        e["sources"] = sorted(e["sources"])
        e["hosts"] = sorted(e["hosts"])
        e["migrated"] = e["corr"] in migrated
        out.append(e)
    return out


def group_by_host(spans: List[dict]) -> dict:
    """``{host: sorted source labels}`` — dumps from many hosts sharing
    one flight dir (NFS) group under their recording host; spans with
    no host annotation book under ``"local"``."""
    by_host: dict = {}
    for s in spans:
        h = s.get("host") or "local"
        by_host.setdefault(h, set()).add(s.get("src") or "?")
    return {h: sorted(srcs) for h, srcs in sorted(by_host.items())}


# ------------------------------------------------- spans over a trace
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_SPAN_PREFIX = "serve."        # what the serve loop's thread records
STEP_SPANS = ("serve.decode.dispatch", "serve.decode.wait")
UNCOVERED = "(no span)"


def _harness(module: str):
    """The device side of a trace is the benchmark's reduction, not a
    copy of it."""
    import importlib

    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module("harness." + module)


def _trace_reduce():
    return _harness("trace_reduce")


def scope_lines(reduced: dict, maps: dict) -> List[str]:
    """The table of device time by program and named scope, as the
    benchmark logs it: ``reduced`` a trace as ``reduce_trace`` gives it,
    ``maps`` what ``compile_cache.export_program_scopes`` wrote."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from paddle_tpu.observability import scopes

    st = _harness("scope_time")
    return st.lines(st.join(reduced["devices"], maps, scopes),
                    reduced["busy_s"], _trace_reduce().op_key)


def read_xplane(path: str) -> dict:
    """``{"start_s", "devices": [{"name", "modules", "gap0", "gap1"}],
    "reduced"}``: the trace's start in seconds since the epoch, and a
    chip's program runs ``(name, start_ns, duration_ns)`` and idle gaps
    (two arrays, ns) as offsets from it."""
    import jax
    import numpy as np

    tr = _trace_reduce()
    reduced = tr.reduce_trace(path)
    programs = {d["name"]: d["modules"] for d in reduced["devices"]}
    start_ns = None
    devices = []
    # the reduction keeps a chip's gaps only as sums by label: the gaps
    # themselves come from the operations' intervals, by its own union
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats).get("profile_start_time")
        if not programs.get(plane.name):
            continue
        ops = [(e.start_ns, e.start_ns + e.duration_ns)
               for line in plane.lines if line.name == tr.OPS_LINE
               for e in line.events]
        _, g0, g1 = tr._union(*np.asarray(ops, float).T)
        devices.append({"name": plane.name, "gap0": g0, "gap1": g1,
                        "modules": programs[plane.name]})
    if start_ns is None:
        raise ValueError(f"{path}: no profile_start_time on a 'Task "
                         f"Environment' plane")
    return {"start_s": int(start_ns) * 1e-9, "devices": devices,
            "reduced": reduced}


def innermost_segments(spans: List[dict]) -> List[Tuple[float, float, str]]:
    """Disjoint ``(t0, t1, name)`` pieces of one thread's spans, each
    named by the innermost span over it: a parent keeps only what no
    child covers."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []      # (t1, name) of open spans
    at = None

    def close_until(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            t1, name = stack.pop()
            if t1 > at:
                out.append((at, t1, name))
                at = t1

    for s in sorted(spans, key=lambda s: (s["t0"], -s["t1"])):
        t0, t1 = float(s["t0"]), float(s["t1"])
        if t1 <= t0:
            continue
        close_until(t0)
        if stack and t0 > at:
            out.append((at, t0, stack[-1][1]))
        at = t0
        stack.append((t1, s["name"]))
    close_until(float("inf"))
    return out


def check_causality(spans: List[dict], xp: dict, program: str) -> dict:
    """Does every device run of ``program`` lie inside one step's
    ``serve.decode.dispatch`` .. ``serve.decode.wait``: started after the
    host began to dispatch it, finished before its read-back returned?
    Runs outside the stretch the step spans cover are not judged. A slip
    smaller than the slack around a run (the dispatch and the read-back's
    latency) cannot be seen this way; ``offset_ms``, the median of device
    start less the start of the last dispatch span begun before it (over
    the runs that fit; over all when the clocks disagree), is what to
    read then."""
    import numpy as np

    by_step: dict = {}
    for s in spans:
        if s.get("name") in STEP_SPANS:
            step = (s.get("tags") or {}).get("step")
            w = by_step.setdefault(step, [float("inf"), float("-inf")])
            w[0], w[1] = min(w[0], s["t0"]), max(w[1], s["t1"])
    if not by_step:
        return {"judged": 0, "outside": 0, "ok": False, "offset_ms": None,
                "why": "no serve.decode.* span among the inputs"}
    win = np.asarray(sorted(by_step.values()))
    lo = (win[:, 0] - xp["start_s"]) * 1e9
    hi = (win[:, 1] - xp["start_s"]) * 1e9
    runs = np.asarray([(s, s + d) for dev in xp["devices"]
                       for n, s, d in dev["modules"] if program in n],
                      float).reshape(-1, 2)
    runs = runs[(runs[:, 0] >= lo[0]) & (runs[:, 0] <= hi[-1])]
    if not len(runs):
        return {"judged": 0, "outside": 0, "ok": False, "offset_ms": None,
                "why": f"no run of a program named *{program}* inside the "
                       f"stretch the step spans cover"}
    start, end = runs[:, 0], runs[:, 1]
    i = np.clip(np.searchsorted(lo, start, side="right") - 1, 0, len(lo) - 1)
    slack = 1e3     # a float of epoch seconds resolves 0.24 us
    fits = (start >= lo[i] - slack) & (end <= hi[i] + slack)
    outside = int((~fits).sum())
    ok = outside <= 0.01 * len(runs)
    off = (start - lo[i])[fits if ok else slice(None)]
    return {"judged": len(runs), "outside": outside, "ok": bool(ok),
            "offset_ms": float(np.median(off)) * 1e-6,
            "worst_ms": float(off[np.abs(off).argmax()]) * 1e-6}


def idle_by_phase(spans: List[dict], xp: dict) -> dict:
    """Seconds of device idle gaps under each serve-loop span (innermost
    first), and under none, averaged over the chips that ran."""
    import numpy as np

    loop = [s for s in spans
            if str(s.get("name", "")).startswith(LOOP_SPAN_PREFIX)]
    segs = innermost_segments(loop)
    out: dict = {}
    chips = max(1, len(xp["devices"]))
    for dev in xp["devices"]:
        g0, g1 = dev["gap0"], dev["gap1"]
        cum = np.concatenate([[0.0], np.cumsum(g1 - g0)])

        def gap_before(t):
            """Idle ns before ``t`` (an array): the whole gaps that
            began before it, the last of them only as far as ``t``."""
            k = np.searchsorted(g0, t, side="right") - 1   # last gap begun
            last = np.maximum(k, 0)
            return np.where(k < 0, 0.0, cum[last]
                            + np.minimum(t, g1[last]) - g0[last])

        total = float(cum[-1])
        covered = 0.0
        if segs and len(g0):
            a = (np.asarray([s[0] for s in segs]) - xp["start_s"]) * 1e9
            b = (np.asarray([s[1] for s in segs]) - xp["start_s"]) * 1e9
            under = gap_before(b) - gap_before(a)
            for (_, _, name), ns in zip(segs, under):
                out[name] = out.get(name, 0.0) + float(ns) * 1e-9 / chips
            covered = float(under.sum())
        out[UNCOVERED] = out.get(UNCOVERED, 0.0) + \
            (total - covered) * 1e-9 / chips
    return out


def device_lane_events(xp: dict, pid: int = 2) -> List[dict]:
    """The device's program runs as chrome events on the spans' clock."""
    events = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
               "args": {"name": "device (jax.profiler trace)"}}]
    for tid, dev in enumerate(xp["devices"]):
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": dev["name"]}})
        for name, start_ns, dur_ns in dev["modules"]:
            events.append({"name": name, "ph": "X", "pid": pid, "tid": tid,
                           "ts": xp["start_s"] * 1e6 + start_ns * 1e-3,
                           "dur": dur_ns * 1e-3, "args": {}})
    return events


def report_xplane(spans: List[dict], xp: dict, program: str) -> int:
    """Print the causality check and, if it holds, the device's idle by
    host phase beside the by-neighbouring-programs table; 0 or 3."""
    tr = _trace_reduce()
    red = xp["reduced"]
    print(f"trace starts at {xp['start_s']:.6f} s since the epoch; window "
          f"{red['window_s']:.4f} s, busy {red['busy_s']:.4f} s, idle "
          f"{red['window_s'] - red['busy_s']:.4f} s")
    c = check_causality(spans, xp, program)
    if c["judged"] == 0:
        print(f"causality: not checked: {c['why']}")
        return 3
    print(f"causality: {c['judged'] - c['outside']} of {c['judged']} runs "
          f"of *{program}* lie on the device inside their step's "
          f"dispatch..wait spans")
    if not c["ok"]:
        print(f"the clocks disagree: a run starts a median "
              f"{c['offset_ms']:.3f} ms (worst {c['worst_ms']:.3f} ms) "
              f"after the last dispatch span begun before it, and "
              f"{c['outside']} do not end inside that step; nothing "
              f"attributed")
        return 3
    print(f"device start less dispatch start: median {c['offset_ms']:.3f} "
          f"ms, worst {c['worst_ms']:.3f} ms")
    print("device idle by the host span that covers it, s:")
    for name, sec in sorted(idle_by_phase(spans, xp).items(),
                            key=lambda kv: -kv[1]):
        print(f"  {sec:9.4f}  {name}")
    print("device idle by the programs around it, s:")
    for label, sec in tr.breakdown(red)["idle_gaps"]:
        print(f"  {sec:9.4f}  {label}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("inputs", nargs="*",
                    help="flight dumps / span lists / chrome traces")
    ap.add_argument("-o", "--output", default=None,
                    help="merged chrome-trace JSON path")
    ap.add_argument("--corr", default=None,
                    help="keep only correlation ids containing this "
                         "substring")
    ap.add_argument("--list", action="store_true",
                    help="print one line per correlation id instead of "
                         "writing a trace")
    ap.add_argument("--xplane", default=None, metavar="FILE.xplane.pb",
                    help="a jax.profiler trace of the process that "
                         "recorded the spans: check the clocks, print the "
                         "device's idle gaps by host phase, add the "
                         "device's programs to the merged trace")
    ap.add_argument("--program", default="decode",
                    help="with --xplane: the device program whose runs "
                         "the causality check places (default: decode)")
    ap.add_argument("--scopes", default=None, metavar="S.json",
                    help="with --xplane: what compile_cache."
                         "export_program_scopes() wrote in the profiled "
                         "process; print device time by program and "
                         "named scope (needs no spans)")
    args = ap.parse_args(argv)

    if args.scopes:
        if not args.xplane:
            ap.error("--scopes needs --xplane")
        try:
            with open(args.scopes) as f:
                maps = json.load(f)
            reduced = _trace_reduce().reduce_trace(args.xplane)
        except Exception as e:
            print(f"trace_view: {type(e).__name__}: {e}", file=sys.stderr)
            return 2
        print("\n".join(scope_lines(reduced, maps)))
        if not args.inputs:
            return 0

    spans: List[dict] = []
    for path in args.inputs:
        try:
            got, kind = load_spans(path)
        except Exception as e:
            print(f"trace_view: {path}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 2
        print(f"[trace_view] {path}: {len(got)} span(s) ({kind})",
              file=sys.stderr)
        spans.extend(got)
    if not spans:
        print("trace_view: no spans in any input", file=sys.stderr)
        return 2

    if args.list:
        groups = group_by_host(spans)
        if len(groups) > 1:
            # multi-host flight dir (hostname-prefixed dumps): lead with
            # a per-host roll-up so an operator sees which machines
            # contributed; '#' lines keep per-corr output line-JSON
            for host, sources in groups.items():
                print(f"# host {host}: {len(sources)} source(s): "
                      f"{', '.join(sources)}")
        for e in list_correlations(spans):
            if args.corr and args.corr not in e["corr"]:
                continue
            print(json.dumps(e))
        return 0

    trace = merge_chrome(spans, corr=args.corr)
    n = sum(1 for ev in trace["traceEvents"] if ev["ph"] in ("X", "i"))
    if args.xplane:
        try:
            xp = read_xplane(args.xplane)
        except Exception as e:
            print(f"trace_view: {args.xplane}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 2
        rc = report_xplane(spans, xp, args.program)
        if rc:
            return rc
        trace["traceEvents"].extend(device_lane_events(xp))
        if not args.output:
            return 0
    if args.output:
        with open(args.output, "w") as f:
            json.dump(trace, f)
        print(f"[trace_view] wrote {args.output}: {n} event(s), "
              f"{len({e['tid'] for e in trace['traceEvents']}) - 1} "
              f"lane(s) — open in chrome://tracing", file=sys.stderr)
    else:
        print(json.dumps(trace))
    return 0 if n else 2


if __name__ == "__main__":
    sys.exit(main())
