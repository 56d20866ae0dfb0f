"""Host time of the serve loop a decode step: wall time the loop's thread
spent in its four host phases (``schedule``, ``admit_host``,
``decode_dispatch``, ``emit``: everything but waiting for the device or
for work) over the decode steps it ran, both as differences of the
server's phase counters (``ServingMetrics.snapshot()["loop"]``) between
the window's two readings. With every slot live this is what the
device waits out between two decode programs, less the runtime's own
launch and read-back latency. A program without the counters reports
nothing."""
META = {"name": "loop.host_turn_ms.sat", "unit": "ms",
        "layer": "serving scheduler", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}
HOST = ("schedule", "admit_host", "decode_dispatch", "emit")


def read(ctx):
    a, b = ctx["serving"]["open"], ctx["serving"]["close"]
    steps = b["decode_steps"] - a["decode_steps"]
    if "loop" not in a or "loop" not in b or steps <= 0:
        return None
    host_s = sum(b["loop"][p]["wall_s"] - a["loop"][p]["wall_s"]
                 for p in HOST)
    return 1e3 * host_s / steps
