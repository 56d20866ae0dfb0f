"""Share of a train step's device time in attention, forward and backward:
the bucket ``attention`` (the projections, and the Pallas flash kernels
or XLA's attention, whichever the sequence length takes).
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.attention_share.train", "unit": "%", "layer": "model",
        "moves": "train_tokens_per_s", "regimes": ["train"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("attention",), kind="train")
