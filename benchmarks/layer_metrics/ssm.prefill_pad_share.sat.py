"""Share of the prefill scans' positions that are padding: ``1 - prompt
tokens / bucket tokens`` over the window's admissions
(``ServingMetrics.snapshot()["state"]``, as differences between the
window's two readings). A recurrent mixer walks every position of the
bucket a prompt was padded to, and the pads must not move the state: this
is the share of that walk spent on them. Lower at the same traffic means
buckets that fit the prompts better. A program without the ``state``
block (a model with no recurrent layer) or a window without an admission
reports nothing."""
META = {"name": "ssm.prefill_pad_share.sat", "unit": "%",
        "layer": "recurrent state", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}


def read(ctx):
    a, b = (ctx["serving"][k].get("state") for k in ("open", "close"))
    if a is None or b is None:
        return None
    bucket = b["bucket_tokens"] - a["bucket_tokens"]
    if bucket <= 0:
        return None
    return 100.0 * (1.0 - (b["prompt_tokens"] - a["prompt_tokens"]) / bucket)
