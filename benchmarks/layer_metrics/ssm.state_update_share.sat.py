"""Share of the device's time that the decode program spends advancing
the recurrent state by one token: the bucket ``state_update``
(``lm_utils.scan_with_state``: the fused update of every slot's state
and the write of state and window back into the cache, with whatever
copy the compiler books behind it under that scope). Nothing for a
model without the scope.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "ssm.state_update_share.sat", "unit": "%", "layer": "recurrent state",
        "moves": "serve_tokens_per_s", "regimes": ["serve_saturated"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, buckets=("state_update",), kind="decode")
