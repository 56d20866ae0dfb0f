"""95th percentile of the time a request waited between ``submit`` and
its admission to a slot, from the server's own ``ServingMetrics``
queue-wait histogram, reset when the window opened and read just before
the profiler starts (the start stalls the process and would fill the
queue): the requests due in the window up to the traced slice, and no
others. Admission is what the scheduler trades against the token gap: a
prefill admitted between two decode steps is the 95th-percentile gap."""
META = {"name": "sched.queue_wait_p95_ms", "unit": "ms",
        "layer": "serving scheduler", "moves": "itl_p95_ms",
        "regimes": ["serve_rate"]}


def read(ctx):
    p95 = ctx["serving"]["queue_wait_p95_s"]
    return None if p95 is None else 1e3 * p95
