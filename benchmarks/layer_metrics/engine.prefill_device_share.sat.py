"""Share of the device's time that goes to anything but the decode
program: every leaf of the prefill programs, and the little programs an
admission runs beside them (a key split, an unstack), which no map
holds. A 2 s slice of a saturated cell may hold no admission: 0 then.
Leaf device time of the traced slice, joined by
``harness/scope_time.py`` with the program's own map of its executables
(``compile_cache.program_scopes()``); nothing where the program keeps no
map or over 1 % of the slice is found in none."""
META = {"name": "engine.prefill_device_share.sat", "unit": "%", "layer": "serving engine",
        "moves": "serve_tokens_per_s", "regimes": ["serve_saturated"]}


def read(ctx):
    from harness import scope_time

    return scope_time.share(ctx, other_than_kind="decode", absent=0.0)
