"""Share of the device's time that the decode program spends reading the
cache for attention: the bucket ``cache_read``, opened in
``models/kv_cache.py`` around ``cached_attention`` (the kernel that reads
by position, or XLA's whole-leaf einsums) and ``latent_attention``.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.cache_read_share.sat", "unit": "%", "layer": "serving engine",
        "moves": "serve_tokens_per_s", "regimes": ["serve_saturated"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("cache_read",), kind="decode")
