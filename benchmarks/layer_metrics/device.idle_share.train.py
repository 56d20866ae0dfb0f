"""Share of the traced slice in which no operation ran on the device: 1
minus the union of the device's operation intervals over the stretch from
the first operation to the end of the last (harness/trace_reduce.py),
averaged over the chips."""
META = {"name": "device.idle_share.train", "unit": "%", "layer": "device",
        "moves": "train_tokens_per_s", "regimes": ["train"]}


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
