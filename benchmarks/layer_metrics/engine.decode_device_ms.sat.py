"""Device time of one run of the decode program (jit__decode_fn on the
trace's XLA Modules line): the median over the traced slice."""
META = {"name": "engine.decode_device_ms.sat", "unit": "ms",
        "layer": "serving engine", "moves": "serve_tokens_per_s", "regimes": ["serve_saturated"]}


def read(ctx):
    return ctx["trace_reduce"].median_module_ms(ctx["trace"], "jit__decode_fn")
