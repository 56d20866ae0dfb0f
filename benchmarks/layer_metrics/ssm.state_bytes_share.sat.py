"""Share of a decode step's least bytes that is recurrent state: what the
live slots' state entries cost a step, read and written once each (``2 x
state_bytes_per_slot`` as the ENGINE ALLOCATED it, from
``snapshot()["state"]``, times the window's mean live slots from
``snapshot()["decode"]``), over the least bytes of the whole step
(``harness/decode_bytes_hybrid.py``, through the function the
configuration names as ``decode_least_bytes``, at the same load). The
denominator counts the state from the configuration's shapes and the
numerator from the leaves: a state that is padded, widened or laid out
with its small axis on the lanes shows as a larger share. Lower at the
same traffic means fewer bytes a token. A program without the ``state``
block (a model with no recurrent layer), a configuration that names no
function, or a window without a decode step reports nothing."""
META = {"name": "ssm.state_bytes_share.sat", "unit": "%",
        "layer": "recurrent state", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx):
    spec = ctx["config"].get("decode_least_bytes")
    a, b = ctx["serving"]["open"], ctx["serving"]["close"]
    if spec is None or not all(k in s for s in (a, b)
                               for k in ("decode", "state")):
        return None
    steps = b["decode"]["steps"] - a["decode"]["steps"]
    if steps <= 0:
        return None
    slots = (b["decode"]["live_slot_steps"]
             - a["decode"]["live_slot_steps"]) / steps
    positions = (b["decode"]["live_position_steps"]
                 - a["decode"]["live_position_steps"]) / steps
    least = ctx["resolve"](spec)(
        ctx["config"]["config"], ITEMSIZE[ctx["config"]["run"]["dtype"]],
        slots, positions)
    state = 2.0 * b["state"]["state_bytes_per_slot"] * slots
    ctx["log"](f"recurrent state: {b['state']['state_bytes_per_slot']} B a "
               f"slot as allocated, {slots:.2f} live slots: "
               f"{state / 1e9:.3f} GB of the step's least "
               f"{least / 1e9:.3f} GB")
    return 100.0 * state / least
