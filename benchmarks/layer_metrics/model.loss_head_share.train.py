"""Share of a train step's device time in the head and the loss: the
buckets ``loss_head`` (the chunked projection and cross entropy, forward
and backward) and ``lm_head``.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.loss_head_share.train", "unit": "%", "layer": "model",
        "moves": "train_tokens_per_s", "regimes": ["train"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("loss_head", "lm_head"), kind="train")
