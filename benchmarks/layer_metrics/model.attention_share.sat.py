"""Share of the device's time that the decode program spends in attention
outside its cache parts: the bucket ``attention`` (projections, rotary
angles, the absorbed latent query and output), which ``cache_write`` and
``cache_read`` inside it are taken out of.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.attention_share.sat", "unit": "%", "layer": "model",
        "moves": "serve_tokens_per_s", "regimes": ["serve_saturated"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("attention",), kind="decode")
