"""Share of the device's time that the decode program spends in dense
FFNs: the bucket ``mlp`` (an expert layer's shared expert stays with
``moe``). At a small batch this is the streaming of the FFN's weights.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.mlp_share.rate", "unit": "%", "layer": "model",
        "moves": "itl_p95_ms", "regimes": ["serve_rate"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("mlp",), kind="decode")
