"""Share of the memory roofline that the decode program reaches: the
least time the chip's memory bandwidth allows for the bytes one decode
step must move (``harness/decode_bytes.py``: the weights the step
applies, and the cached keys and values of the positions its live slots
attend over), over the median device time of ``jit__decode_fn`` in the
traced slice. The load is the window's mean, from the program's counters
(``ServingMetrics.snapshot()["decode"]``: steps, live slots and live
positions summed over the steps, as differences between the window's two
readings); the bytes come from the configuration's shapes through the
function the configuration names (``decode_least_bytes``), so the share
reads the same work whatever program does it. A configuration that names
no function, a program without the counters, or a slice without a decode
step reports nothing."""
META = {"name": "engine.decode_hbm_roofline.sat", "unit": "%",
        "layer": "serving engine", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx):
    spec = ctx["config"].get("decode_least_bytes")
    a, b = (ctx["serving"][k].get("decode") for k in ("open", "close"))
    if spec is None or a is None or b is None:
        return None
    ms = ctx["trace_reduce"].median_module_ms(ctx["trace"], "jit__decode_fn")
    steps = b["steps"] - a["steps"]
    if ms is None or steps <= 0:
        return None
    slots = (b["live_slot_steps"] - a["live_slot_steps"]) / steps
    positions = (b["live_position_steps"] - a["live_position_steps"]) / steps
    nbytes = ctx["resolve"](spec)(
        ctx["config"]["config"], ITEMSIZE[ctx["config"]["run"]["dtype"]],
        slots, positions)
    least_ms = 1e3 * nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"](f"decode step: {slots:.2f} live slots over {positions:.1f} "
               f"positions; least {nbytes / 1e9:.3f} GB = {least_ms:.2f} ms "
               f"at the memory's peak; device time {ms:.2f} ms")
    return 100.0 * least_ms / ms
