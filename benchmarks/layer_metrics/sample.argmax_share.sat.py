"""Share of the window's decode steps whose sampler took the argmax alone:
steps in which every live slot was greedy, so the program ran no
temperature divide, no sort over the vocabulary, no softmax and drew no
random bits (branch 0 of ``generation.sample_logits_rows``), over all
decode steps, both as differences of the server's counters
(``ServingMetrics.snapshot()["sample"]`` = ``{argmax_steps,
categorical_steps, nucleus_steps}``, booked by the loop thread from the
vectors each step was given) between the window's two readings. With
every slot live one sampling caller among them holds the whole batch off
this branch. A program without the counters reports nothing."""
META = {"name": "sample.argmax_share.sat", "unit": "%",
        "layer": "serving engine", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}


def read(ctx):
    a, b = (ctx["serving"][k].get("sample") for k in ("open", "close"))
    if a is None or b is None:
        return None
    steps = sum(b.values()) - sum(a.values())
    if steps <= 0:
        return None
    return 100.0 * (b["argmax_steps"] - a["argmax_steps"]) / steps
