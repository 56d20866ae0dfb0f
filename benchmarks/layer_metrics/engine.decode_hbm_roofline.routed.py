"""Share of the memory roofline that the decode program of a SPARSE model
reaches: the least time the chip's memory bandwidth allows for the bytes
one decode step must move (``harness/decode_bytes_routed.py``: the weights
outside the routed experts, the experts that the step's live tokens were
routed to and no others, the cached entries of the positions its live
slots attend over), over the median device time of ``jit__decode_fn`` in
the traced slice: the cell's share of the whole step. The load is the
window's mean, from the program's counters as differences between the
window's two readings: ``snapshot()["decode"]`` (steps, live slots, live
positions) and ``snapshot()["moe"]`` (steps, experts touched a layer).
A configuration that names no ``decode_least_bytes_routed``, a program
without the ``moe`` counters, or a slice without a decode step reports
nothing."""
META = {"name": "engine.decode_hbm_roofline.routed", "unit": "%",
        "layer": "serving engine", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx):
    spec = ctx["config"].get("decode_least_bytes_routed")
    a, b = ctx["serving"]["open"], ctx["serving"]["close"]
    if spec is None or not all(k in s for s in (a, b)
                               for k in ("decode", "moe")):
        return None
    ms = ctx["trace_reduce"].median_module_ms(ctx["trace"], "jit__decode_fn")
    steps = b["decode"]["steps"] - a["decode"]["steps"]
    moe_steps = b["moe"]["steps"] - a["moe"]["steps"]
    if ms is None or steps <= 0 or moe_steps <= 0:
        return None
    slots = (b["decode"]["live_slot_steps"]
             - a["decode"]["live_slot_steps"]) / steps
    positions = (b["decode"]["live_position_steps"]
                 - a["decode"]["live_position_steps"]) / steps
    touched = [y - x for x, y in zip(a["moe"]["experts_touched_steps"],
                                     b["moe"]["experts_touched_steps"])]
    experts = sum(touched) / (len(touched) * moe_steps)
    nbytes = ctx["resolve"](spec)(
        ctx["config"]["config"], ITEMSIZE[ctx["config"]["run"]["dtype"]],
        slots, positions, experts)
    least_ms = 1e3 * nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"](f"decode step: {slots:.2f} live slots over {positions:.1f} "
               f"positions, {experts:.2f} experts touched a layer; least "
               f"{nbytes / 1e9:.3f} GB = {least_ms:.2f} ms at the memory's "
               f"peak; device time {ms:.2f} ms")
    return 100.0 * least_ms / ms
