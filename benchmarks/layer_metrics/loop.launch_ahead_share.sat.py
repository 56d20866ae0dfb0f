"""Share of the window's decode steps that the serve loop handed to the
device while the step before still ran (``engine.launch`` returned that it
was made ahead: behind a launch the host had not read back nor seen end
through an admission's read-back), over all decode steps, both as
differences of the server's counters
(``ServingMetrics.snapshot()["decode"]`` = ``{steps, live_slot_steps,
live_position_steps, launched_ahead_steps}``, booked by the loop thread
at each launch, no device read) between the window's two readings. The
host's turn behind such a step costs no device time; the others are the
first launch after an idle stretch and the one behind each admission.
With every slot live only an admission empties the pipeline. A
program without the counter reports nothing."""
META = {"name": "loop.launch_ahead_share.sat", "unit": "%",
        "layer": "serving scheduler", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}


def read(ctx):
    a, b = (ctx["serving"][k].get("decode") for k in ("open", "close"))
    if a is None or b is None or "launched_ahead_steps" not in a \
            or "launched_ahead_steps" not in b:
        return None
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    return 100.0 * (b["launched_ahead_steps"]
                    - a["launched_ahead_steps"]) / steps
