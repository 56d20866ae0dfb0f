"""Regimes ``serve_rate`` and ``serve_saturated``: the program's
``InferenceServer`` under traffic from :mod:`traffic`, measured from the
client's side.

``serve_rate`` is an open loop at the cell's fixed rate, under the knee:
every request is submitted when it is due, whatever the server is doing,
and its latencies count from the time it was *due*. End-to-end metrics:
``ttft_p95_ms`` and ``itl_p95_ms`` over all requests due in the window
(a refused, failed or late request counts as the time limit).

``serve_saturated`` is a closed loop of clients that each send their next
request when the last is answered. End-to-end metric:
``serve_tokens_per_s`` = output tokens that reached a client inside the
window, over the window.

One thread a request in flight consumes ``RequestHandle.stream()`` and
stamps each token as it arrives; the dispatcher is the main thread.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from . import common, traffic as traffic_mod
from .common import log

# A sampled token may fall short of the reference's best logit by at most
# this share of the standard deviation of the reference's logits at that
# position. Reason: the system computes in bfloat16 (8 bits of mantissa)
# what the reference computes in float32, so logits differ by a few
# hundredths of their spread and near-ties flip (on random weights the
# top two logits are often closer than that); a token picked from the
# wrong position, slot or cache row is a random one and falls short by
# about four standard deviations (the maximum of 50k draws). 0.25 sits
# five times above the largest shortfall seen on the chip and sixteen
# times under a wrong token.
SHORTFALL_TOLERANCE = 0.25
CHECKED_REQUESTS = 4


class _Rec:
    """What the client saw of one request."""
    __slots__ = ("req", "due", "sent", "times", "tokens", "error", "ended")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = None
        self.times, self.tokens = [], []
        self.error, self.ended = None, None


def _consume(handle, rec: _Rec) -> None:
    try:
        for tok in handle.stream():
            rec.times.append(time.perf_counter())
            rec.tokens.append(tok)
    except Exception as e:   # the request's own failure, kept for the count
        rec.error = e
    rec.ended = time.perf_counter()


def _submit(server, rec: _Rec, kwargs: dict):
    rec.sent = time.perf_counter()
    try:
        return server.submit(rec.req.prompt,
                             max_new_tokens=rec.req.max_new_tokens, **kwargs)
    except Exception as e:   # QueueFull, Overloaded, ValueError: a miss
        rec.error, rec.ended = e, time.perf_counter()
        return None


def _tracer(holder: dict, start_at: float, stop_at: float,
            before) -> threading.Thread:
    """Profile ``[start_at, stop_at]`` (perf_counter times) from a thread
    of its own. Starting the profiler stalls the whole process (1.8 s on
    the chip the first time, generator and server alike), so ``before()``
    runs first: it reads the program's counters while they are still
    clean, and client-side numbers of a traced run count only what was
    due before ``holder["trace_started"]``."""
    def work():
        time.sleep(max(0.0, start_at - time.perf_counter()))
        before()
        holder["trace_started"] = time.perf_counter()
        with common.traced_slice(holder):
            time.sleep(max(0.0, stop_at - time.perf_counter()))

    t = threading.Thread(target=work, name="bench-tracer")
    t.start()
    return t


def _open_loop(server, reqs, seconds, limit_s, submit_kw, trace, slice_s,
               holder, before_trace):
    """Returns (records, threads, t_open, t_close, tracer)."""
    recs, threads = [], []
    t_open = time.perf_counter() + 0.05
    tracer = (_tracer(holder, t_open + seconds - slice_s, t_open + seconds,
                      before_trace) if trace else None)
    for r in reqs:
        rec = _Rec(r, t_open + r.due_s)
        recs.append(rec)
        wait = rec.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        h = _submit(server, rec, dict(submit_kw, deadline=limit_s))
        if h is not None:
            th = threading.Thread(target=_consume, args=(h, rec))
            th.start()
            threads.append(th)
    t_close = t_open + seconds
    time.sleep(max(0.0, t_close - time.perf_counter()))
    return recs, threads, t_open, t_close, tracer


def _client(server, plan, out: list, stop: threading.Event, submit_kw):
    i = 0
    while not stop.is_set():
        rec = _Rec(plan[i % len(plan)], time.perf_counter())
        i += 1
        out.append(rec)
        h = _submit(server, rec, submit_kw)
        if h is None:
            if not stop.is_set():
                time.sleep(0.05)   # refused: back off, do not spin
            continue
        _consume(h, rec)


def _wait_all_slots_live(server, slots: int, settle_s: float, limit_s: float):
    t0 = time.perf_counter()
    while server.snapshot()["active_slots"] < slots:
        if time.perf_counter() - t0 > limit_s:
            raise RuntimeError(f"slots not all live after {limit_s} s")
        time.sleep(0.05)
    log(f"all {slots} slots live after {time.perf_counter() - t0:.1f} s; "
        f"settling {settle_s} s")
    time.sleep(settle_s)


def _check_against_reference(recs, model, config: dict, pad_to: int) -> dict:
    """Replay a few requests through the plain reference: the prompt and
    the tokens the system emitted go through one full float32 forward
    pass (padded to one length, so it compiles once; the mask is causal,
    so padding changes nothing before it), and each emitted token must be
    the reference's argmax at its position or fall short of it by less
    than ``SHORTFALL_TOLERANCE`` of the logits' standard deviation."""
    from paddle_tpu.framework.jit import param_state

    reference = common.resolve(config["reference"])
    have = sorted((r for r in recs if r.tokens),
                  key=lambda r: len(r.req.prompt))
    if not have:
        return {"checked": 0, "worst_shortfall": None, "ok": False}
    idx = sorted({round(i * (len(have) - 1) / (CHECKED_REQUESTS - 1))
                  for i in range(CHECKED_REQUESTS)})
    params = param_state(model)
    worst, tokens, exact = 0.0, 0, 0
    for i in idx:
        rec = have[i]
        p, out = len(rec.req.prompt), np.asarray(rec.tokens, np.int32)
        ids = np.zeros(pad_to, np.int32)
        ids[:p] = rec.req.prompt
        ids[p:p + len(out)] = out
        # logits at position t predict token t + 1: the emitted tokens are
        # predicted at p - 1 ... p + len(out) - 2
        lg = np.asarray(reference.logits(params, config["config"],
                                         ids[None])[0, p - 1:p - 1 + len(out)])
        short = (lg.max(axis=-1) - lg[np.arange(len(out)), out]) \
            / lg.std(axis=-1)
        worst = max(worst, float(short.max()))
        tokens += len(out)
        exact += int((short == 0).sum())
    log(f"reference check: {len(idx)} requests (prompts "
        f"{[len(have[i].req.prompt) for i in idx]}), {tokens} tokens, "
        f"{exact} the reference's argmax, worst shortfall {worst:.4f} of the "
        f"logits' std (tolerance {SHORTFALL_TOLERANCE})")
    return {"checked": len(idx), "worst_shortfall": worst,
            "ok": worst <= SHORTFALL_TOLERANCE}


def run(cell: dict, config: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    from paddle_tpu import amp
    from paddle_tpu.serving import InferenceServer

    spec, tr = cell["server"], cell["traffic"]
    saturated = cell["regime"] == "serve_saturated"
    limit_s = float(tr["time_limit_s"])
    submit_kw = dict(tr["sampling"])

    t0 = time.perf_counter()
    model = common.build_model(config, spec.get("model_overrides"), seed)
    model = amp.decorate(model, **spec["amp"])
    model.eval()
    log(f"model built and cast in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    server = InferenceServer(model, **spec["args"])
    warm = server.engine.warmup()
    server.start()
    # one request through the front door, so that whatever submit() and
    # the loop trace lazily is traced before the window
    server.submit(np.arange(1, 17, dtype=np.int32), max_new_tokens=2,
                  **submit_kw).result(timeout=600)
    log(f"server built and warmed in {time.perf_counter() - t0:.1f} s: {warm}")

    vocab = config["config"]["vocab_size"]
    slice_s = float(cell["trace_slice_s"])
    holder = {"trace": None, "trace_started": None}

    def read_queue_wait():
        holder["queue_wait_p95_s"] = (
            server.metrics.queue_wait.percentile(95)
            if server.metrics.queue_wait.count else None)

    if trace:
        common.warm_up_profiler()
    stop = threading.Event()
    threads, recs, tracer = [], [], None
    try:
        if saturated:
            plans = traffic_mod.closed_loop(tr, seed, vocab)
            per_client = [[] for _ in plans]
            threads = [threading.Thread(
                target=_client, args=(server, plan, out, stop, submit_kw))
                for plan, out in zip(plans, per_client)]
            for th in threads:
                th.start()
            _wait_all_slots_live(server, spec["args"]["slots"],
                                 float(tr["settle_s"]), limit_s)
            with common.GcWatch() as gcw:
                opened, snap_open = common.program_counters(), server.snapshot()
                t_open = time.perf_counter()
                t_close = t_open + seconds
                tracer = (_tracer(holder, t_close - slice_s, t_close,
                                  read_queue_wait) if trace else None)
                time.sleep(seconds)
                t_close = time.perf_counter()
                closed, snap_close = common.program_counters(), server.snapshot()
            stop.set()
        else:
            reqs = traffic_mod.open_loop(tr, seconds, seed, vocab)
            server.metrics.reset()   # idle server: counters start at the window
            with common.GcWatch() as gcw:
                opened, snap_open = common.program_counters(), server.snapshot()
                recs, threads, t_open, t_close, tracer = _open_loop(
                    server, reqs, seconds, limit_s, submit_kw, trace,
                    slice_s, holder, read_queue_wait)
                closed, snap_close = common.program_counters(), server.snapshot()
            # drain: the requests due near the close still owe their tokens
            end_by = time.perf_counter() + float(tr["drain_limit_s"])
            for th in threads:
                th.join(max(0.0, end_by - time.perf_counter()))
    finally:
        stop.set()
        server.shutdown(drain=False, timeout=60)
        for th in threads:
            th.join(60)
    if tracer is not None:
        tracer.join()
    else:
        read_queue_wait()
    if saturated:
        recs = [r for out in per_client for r in out]
    alive = sum(th.is_alive() for th in threads)
    log(f"window {t_close - t_open:.3f} s; gc passes over 20 ms: "
        f"{gcw.pauses_ms}; client threads left: {alive}")

    compiled = common.compiled_inside(opened, closed)
    memory_peak = common.memory_peak_bytes(int(cell["chips"]))
    check = _check_against_reference(
        recs, model, config, int(spec["args"]["max_length"]))
    ctx = {
        "trace": holder["trace"],
        "counters": {"open": opened, "close": closed},
        "serving": {"open": snap_open, "close": snap_close,
                    "queue_wait_p95_s": holder["queue_wait_p95_s"]},
        "measured": {"window_s": t_close - t_open},
    }

    if saturated:
        # a request cut off by the shutdown at the end is not a failure
        failed = [r for r in recs if r.error is not None
                  and r.ended is not None and r.ended < t_close]
        tokens = sum(1 for r in recs for t in r.times if t_open <= t < t_close)
        rate = tokens / (t_close - t_open)
        done = sum(1 for r in recs if r.error is None
                   and len(r.tokens) == r.req.max_new_tokens)
        log(f"{len(recs)} requests sent, {done} answered in full, "
            f"{len(failed)} failed; {tokens} tokens in the window, "
            f"{rate:.1f} tokens/s")
        short = [r for r in recs if r.error is None
                 and len(r.tokens) != r.req.max_new_tokens]
        end_to_end = {"serve_tokens_per_s":
                      (rate / int(cell["chips"]), "tokens/s/chip")}
    else:
        late = np.asarray([r.sent - r.due for r in recs]) * 1e3
        log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} p95 "
            f"{np.percentile(late, 95):.3f} worst three "
            f"{np.sort(late)[-3:].round(3).tolist()}")
        ttft, gaps, failed, short = [], [], [], []
        for r in recs:
            first = (r.times[0] - r.due) if r.times else limit_s
            ttft.append(min(first, limit_s))
            gaps.extend(np.diff(r.times))
            if r.error is not None or first > limit_s:
                failed.append(r)
            elif len(r.tokens) != r.req.max_new_tokens:
                short.append(r)
        ttft_ms, gaps_ms = np.asarray(ttft) * 1e3, np.asarray(gaps) * 1e3
        log(f"{len(recs)} requests due, {len(failed)} failed or late, "
            f"{len(gaps_ms)} gaps; ttft ms p50 {np.percentile(ttft_ms, 50):.2f}"
            f" p95 {np.percentile(ttft_ms, 95):.2f} max {ttft_ms.max():.2f}; "
            f"itl ms p50 {np.percentile(gaps_ms, 50):.2f} p95 "
            f"{np.percentile(gaps_ms, 95):.2f} max {gaps_ms.max():.2f}; "
            f"queue depth at close {snap_close['queue_depth']}")
        end_to_end = {
            "ttft_p95_ms": (float(np.percentile(ttft_ms, 95)), "ms"),
            "ttft_mean_ms": (float(ttft_ms.mean()), "ms"),
            "itl_p95_ms": (float(np.percentile(gaps_ms, 95)), "ms"),
        }
        clean = np.asarray([holder["trace_started"] is None
                            or r.due < holder["trace_started"] for r in recs])
        ctx["measured"].update(ttft_ms=ttft_ms[clean], gaps_ms=gaps_ms)

    checks = {
        "sampled tokens agree with the reference": check["ok"],
        "every answered request has the length asked": not short,
        "nothing compiled or traced in the window": compiled == 0,
        "no client thread left": alive == 0,
    }
    for r in failed[:3]:
        log(f"failed request: prompt {len(r.req.prompt)} tokens, "
            f"{len(r.tokens)} received, error {r.error!r}")
    log(f"compiled in window {compiled}; checks {checks}")
    return {"correct": all(checks.values()), "attempted": len(recs),
            "failed": len(failed), "t_window_open": t_open,
            "memory_peak_bytes": memory_peak,
            "end_to_end": end_to_end, "ctx": ctx}
