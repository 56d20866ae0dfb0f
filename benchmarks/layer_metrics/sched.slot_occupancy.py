"""Share of slot-seconds of the measured window in which a slot held a
live request: the server's time-weighted occupancy integral
(``ServingMetrics.snapshot()``), as the difference of its two readings at
the window's ends."""
META = {"name": "sched.slot_occupancy", "unit": "%",
        "layer": "serving scheduler", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}


def read(ctx):
    a, b = ctx["serving"]["open"], ctx["serving"]["close"]
    slot_s = (b["slot_occupancy"] * b["elapsed_s"]
              - a["slot_occupancy"] * a["elapsed_s"])
    return 100.0 * slot_s / (b["elapsed_s"] - a["elapsed_s"])
