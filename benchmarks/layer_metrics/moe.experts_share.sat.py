"""Share of the device's time that the decode program spends in expert
FFNs: the bucket ``moe`` (``nn/layers/expert_ffn.py``: router, dispatch,
the grouped matmuls, combine, the shared expert; the log shows each as a
sub-row). Nothing for a model without the scope.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "moe.experts_share.sat", "unit": "%", "layer": "expert FFN",
        "moves": "serve_tokens_per_s", "regimes": ["serve_saturated"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("moe",), kind="decode")
