"""Share of the traced slice in which no operation ran on the device: 1
minus the union of the device's operation intervals over the stretch from
the first operation to the end of the last (harness/trace_reduce.py),
averaged over the chips."""
META = {"name": "device.idle_share.rate", "unit": "%", "layer": "device",
        "moves": "itl_p95_ms", "regimes": ["serve_rate"]}


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
