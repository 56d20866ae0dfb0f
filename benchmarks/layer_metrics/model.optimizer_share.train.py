"""Share of a train step's device time in the optimizer's update: the
bucket ``optimizer`` (``TrainStep._step`` opens it around
``optimizer.update``: AdamW over the float32 masters).
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.optimizer_share.train", "unit": "%", "layer": "train step",
        "moves": "train_tokens_per_s", "regimes": ["train"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("optimizer",), kind="train")
