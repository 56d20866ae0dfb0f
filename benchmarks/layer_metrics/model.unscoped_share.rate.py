"""Share of the device's time in instructions of the watched programs that
lie in no bucket of the vocabulary
(``paddle_tpu/observability/scopes.py``): how much of the table of time
by scope is not accounted for. The log lists those over 0.5 %.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "model.unscoped_share.rate", "unit": "%", "layer": "model",
        "moves": "itl_p95_ms", "regimes": ["serve_rate"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("unscoped",), absent=0.0)
