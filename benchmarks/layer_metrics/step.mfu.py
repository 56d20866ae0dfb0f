"""Model FLOP/s utilization of the train step: tokens per second of the
measured window times the operations a token needs (forward and backward,
the configuration's ``train_flops_per_token`` function under
``harness/flops.py``, formula stated there), over chips times the peak."""
META = {"name": "step.mfu", "unit": "%", "layer": "train step",
        "moves": "train_tokens_per_s", "regimes": ["train"]}


def read(ctx):
    per_token = ctx["resolve"](ctx["config"]["train_flops_per_token"])(
        ctx["config"]["config"], ctx["measured"]["seq"])
    peak = ctx["cell"]["chips"] * ctx["peaks"]["flops_per_s"]
    return 100.0 * ctx["measured"]["tokens_per_s"] * per_token / peak
