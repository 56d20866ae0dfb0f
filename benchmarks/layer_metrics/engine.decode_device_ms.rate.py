"""Device time of one run of the decode program (jit__decode_fn on the
trace's XLA Modules line): the median over the traced slice."""
META = {"name": "engine.decode_device_ms.rate", "unit": "ms",
        "layer": "serving engine", "moves": "itl_p95_ms", "regimes": ["serve_rate"]}


def read(ctx):
    return ctx["trace_reduce"].median_module_ms(ctx["trace"], "jit__decode_fn")
