"""Host time of one ``step(batch)`` call, before any wait for the device:
the median over the measured window's steps. The device runs a step
behind the host, so this is hidden as long as it stays under the step's
device time."""
META = {"name": "step.dispatch_ms", "unit": "ms", "layer": "train step",
        "moves": "train_tokens_per_s", "regimes": ["train"]}


def read(ctx):
    import statistics

    return 1e3 * statistics.median(ctx["measured"]["dispatch_s"])
