"""Share of the device's time that the decode program spends writing a
step's keys and values (or latent pair) into the cache: the bucket
``cache_write``, opened in ``models/kv_cache.py:update_kv_cache`` around
whatever implements the write (the merge kernel, the scatter's loop).
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.cache_write_share.rate", "unit": "%", "layer": "serving engine",
        "moves": "itl_p95_ms", "regimes": ["serve_rate"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("cache_write",), kind="decode")
