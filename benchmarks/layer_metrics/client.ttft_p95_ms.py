"""95th percentile, over the requests due in the window before the
profiler started, of first token received minus the time the request was
due (a refused, failed or late request counts as the time limit). What a
chat user feels first, and not an end-to-end metric only because 240
requests cannot pin it: each first token waits out the decode step in
progress, a uniform 0-39 ms, and the 95th percentile of 240 such draws
spreads by 13-16 % between runs of one code, over the 10 % a bound may
be (PERF.md, PR 24). Admitting a prefill sooner shortens this and
lengthens the token gap it interrupts."""
META = {"name": "client.ttft_p95_ms", "unit": "ms",
        "layer": "serving scheduler", "moves": "itl_p95_ms",
        "regimes": ["serve_rate"]}


def read(ctx):
    import numpy as np

    ttft = ctx["measured"]["ttft_ms"]
    return float(np.percentile(ttft, 95)) if len(ttft) else None
