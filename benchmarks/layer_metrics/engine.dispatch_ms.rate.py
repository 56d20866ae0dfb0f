"""Host time to launch one decode step: wall time of the serve loop's
``decode_dispatch`` phase (from the step's entry until the call of the
compiled decode program has returned, its eight host vectors handed
over) over the phase's count, as differences of the server's phase
counters (``ServingMetrics.snapshot()["loop"]``) between the window's
two readings. A read-back one step behind can hide the rest of the host
turn behind the device, not this. A program without the counters reports
nothing."""
META = {"name": "engine.dispatch_ms.rate", "unit": "ms",
        "layer": "serving engine", "moves": "itl_p95_ms",
        "regimes": ["serve_rate"]}


def read(ctx):
    a, b = (ctx["serving"][k].get("loop") for k in ("open", "close"))
    if a is None or b is None:
        return None
    a, b = a["decode_dispatch"], b["decode_dispatch"]
    n = b["count"] - a["count"]
    return 1e3 * (b["wall_s"] - a["wall_s"]) / n if n > 0 else None
