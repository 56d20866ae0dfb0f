"""How unevenly the router loads the experts: the tokens the most loaded
expert of a layer received over the mean of the layer's experts, live
slots' tokens over the window's decode steps
(``ServingMetrics.snapshot()["moe"]["tokens_per_expert"]``, as
differences between the window's two readings), the worst layer. 1 is a
perfectly even router; a grouped matmul's longest group, and with
experts on several chips the slowest chip, follow this number. A program
without the counters (a model without experts) reports nothing."""
META = {"name": "moe.load_max_over_mean.sat", "unit": "ratio",
        "layer": "expert FFN", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}


def read(ctx):
    a, b = (ctx["serving"][k].get("moe") for k in ("open", "close"))
    if a is None or b is None or b["steps"] <= a["steps"]:
        return None
    worst = None
    for before, after in zip(a["tokens_per_expert"], b["tokens_per_expert"]):
        load = [y - x for x, y in zip(before, after)]
        if sum(load) <= 0:
            return None
        ratio = max(load) * len(load) / sum(load)
        worst = ratio if worst is None else max(worst, ratio)
    return worst
