"""Share of the experts that a decode step touches: the experts that at
least one live slot's token was routed to, over the experts the layer
routes over, mean over the expert layers and the window's decode steps
(``ServingMetrics.snapshot()["moe"]``, as differences between the
window's two readings). It is what a step must stream of the expert
weights: lower at the same traffic means fewer bytes a token. A program
without the counters (a model without experts) reports nothing."""
META = {"name": "moe.experts_touched_share.sat", "unit": "%",
        "layer": "expert FFN", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}


def read(ctx):
    a, b = (ctx["serving"][k].get("moe") for k in ("open", "close"))
    if a is None or b is None or b["steps"] <= a["steps"]:
        return None
    touched = [y - x for x, y in zip(a["experts_touched_steps"],
                                     b["experts_touched_steps"])]
    experts = len(b["tokens_per_expert"][0])
    return 100.0 * sum(touched) / (len(touched) * (b["steps"] - a["steps"])
                                   * experts)
