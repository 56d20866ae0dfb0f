"""Share of the serve loop's host time in which its thread was not on a
CPU: 1 - CPU time / wall time over the four host phases (``schedule``,
``admit_host``, ``decode_dispatch``, ``emit``), both from the server's
phase counters (``ServingMetrics.snapshot()["loop"]``; the CPU time is
the loop thread's own, ``time.thread_time_ns``) as differences between
the window's two readings. In a host phase the thread waits for no
device, so what it did not run it spent runnable or blocked: the
interpreter lock held by a client thread, another lock, a core taken by
someone else. A program without the counters reports nothing."""
META = {"name": "loop.offcpu_share.sat", "unit": "%",
        "layer": "serving scheduler", "moves": "serve_tokens_per_s",
        "regimes": ["serve_saturated"]}
HOST = ("schedule", "admit_host", "decode_dispatch", "emit")


def read(ctx):
    a, b = (ctx["serving"][k].get("loop") for k in ("open", "close"))
    if a is None or b is None:
        return None
    wall = sum(b[p]["wall_s"] - a[p]["wall_s"] for p in HOST)
    cpu = sum(b[p]["cpu_s"] - a[p]["cpu_s"] for p in HOST)
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None
