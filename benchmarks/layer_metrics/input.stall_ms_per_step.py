"""Time the training loop waited for its next batch, a step: the device
prefetcher's ``stats()["consumer_stall_s"]`` over the batches it handed
out, both taken as differences across the measured window."""
META = {"name": "input.stall_ms_per_step", "unit": "ms",
        "layer": "input pipeline", "moves": "train_tokens_per_s",
        "regimes": ["train"]}


def read(ctx):
    return 1e3 * ctx["measured"]["input_stall_s_per_batch"]
