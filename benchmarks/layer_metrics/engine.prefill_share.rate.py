"""Share of the measured window the serve loop spent admitting requests:
wall time of its ``admit_host`` and ``admit_wait`` phases (an admission
from its entry to its return: adapter and prefix planning, padding, the
key-split, unstack and prefill programs, the first token's read-back)
over the time between the window's two readings, from the server's phase
counters (``ServingMetrics.snapshot()["loop"]``). Every decode step of
every live request waits while an admission runs: this is prefill's
share of a serving step. A program without the counters reports
nothing."""
META = {"name": "engine.prefill_share.rate", "unit": "%",
        "layer": "serving engine", "moves": "itl_p95_ms",
        "regimes": ["serve_rate"]}


def read(ctx):
    a, b = ctx["serving"]["open"], ctx["serving"]["close"]
    window_s = b["elapsed_s"] - a["elapsed_s"]
    if "loop" not in a or "loop" not in b or window_s <= 0:
        return None
    admit_s = sum(b["loop"][p]["wall_s"] - a["loop"][p]["wall_s"]
                  for p in ("admit_host", "admit_wait"))
    return 100.0 * admit_s / window_s
