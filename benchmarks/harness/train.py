"""Regime ``train``: the program's ``TrainStep`` on fresh seeded token
batches through its device prefetcher, measured for a fixed time.

End-to-end metric: ``train_tokens_per_s`` = tokens of every step
dispatched after the window opened, over the time until a host read of
the last loss returned (so all of them completed inside it). All the work
and all the time of the window count.
"""
from __future__ import annotations

import collections
import itertools
import math
import time

import numpy as np

from . import common
from .common import log


# The first step's loss may differ from the float32 reference's by this
# much. Reason: the step computes activations and logits in bfloat16 (8
# bits of mantissa, relative error 2**-9 a rounding) where the reference
# keeps float32; on a loss near ln(vocab) = 10.8 that has measured 0.002
# to 0.003 apart on the chip. 0.02 is under 0.2 % of the loss: a dropped
# layer, a wrong shift of the labels or an 8-bit activation moves the
# loss by more.
LOSS_TOLERANCE = 0.02


def _batches(vocab_size: int, batch: int, seq: int, seed: int):
    """Fresh uniform token ids for every step, from the seed. The labels
    are the ids; the model shifts them."""
    rng = common.seeded_rng(seed, 3)
    while True:
        ids = rng.integers(0, vocab_size, (batch, seq), dtype=np.int32)
        yield ids, ids


def run(cell: dict, config: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    from paddle_tpu import amp
    from paddle_tpu.framework.jit import TrainStep, param_state
    from paddle_tpu.io.device_prefetch import prefetch_to_device

    job = cell["job"]
    batch, seq = int(job["batch"]), int(job["seq"])
    t0 = time.perf_counter()
    model = common.build_model(config, job.get("model_overrides"), seed)
    opt = common.resolve(job["optimizer"]["class"])(**job["optimizer"]["args"])
    model, opt = amp.decorate(model, opt, **job["amp"])
    log(f"model built and decorated in {time.perf_counter() - t0:.1f} s")

    gen = _batches(config["config"]["vocab_size"], batch, seq, seed)
    first = next(gen)

    t0 = time.perf_counter()
    step = TrainStep(model, opt, loss_fn=None)
    log(f"TrainStep built in {time.perf_counter() - t0:.1f} s")

    pf = prefetch_to_device(itertools.chain([first], gen),
                            depth=int(job["prefetch_depth"]))
    try:
        it = iter(pf)
        t0 = time.perf_counter()
        first_loss = float(np.asarray(step(next(it))))
        log(f"first step (compile or cache load) {time.perf_counter() - t0:.1f}"
            f" s, loss {first_loss:.5f}")
        for _ in range(int(job["warmup_steps"]) - 1):
            float(np.asarray(step(next(it))))

        inflight_max = int(job["steps_in_flight"])
        inflight, losses, dispatch_s = collections.deque(), [], []
        opened = common.program_counters()
        stall_open = pf.stats()
        with common.GcWatch() as gcw:
            t_open = time.perf_counter()
            while True:
                b = next(it)
                t0 = time.perf_counter()
                loss = step(b)
                dispatch_s.append(time.perf_counter() - t0)
                inflight.append(loss)
                if len(inflight) > inflight_max:
                    losses.append(float(np.asarray(inflight.popleft())))
                if time.perf_counter() - t_open >= seconds:
                    break
            while inflight:   # the host read that closes the window
                losses.append(float(np.asarray(inflight.popleft())))
            t_close = time.perf_counter()
        closed = common.program_counters()
        stall_close = pf.stats()

        window_s = t_close - t_open
        steps = len(losses)
        tokens_per_s = steps * batch * seq / window_s
        log(f"window {window_s:.3f} s, {steps} steps, "
            f"{window_s / steps * 1e3:.2f} ms a step, "
            f"{tokens_per_s:.1f} tokens/s; gc pauses over 20 ms: "
            f"{gcw.pauses_ms}")

        holder = {"trace": None}
        if trace:
            # three more steps of the same steady state, profiled, after
            # the window has closed: the tracer costs the window nothing
            with common.traced_slice(holder):
                for _ in range(int(job["trace_steps"])):
                    loss = step(next(it))
                float(np.asarray(loss))
        memory_peak = common.memory_peak_bytes(int(cell["chips"]))
    finally:
        pf.close()

    # correctness, after the window: the plain float32 reference on the
    # weights the step started from (the Layer keeps them; the step trains
    # its own donated copy) and on the batch it saw first
    t0 = time.perf_counter()
    reference = common.resolve(config["reference"])
    ref_loss = reference.loss(param_state(model), config["config"], *first)
    log(f"reference loss {ref_loss:.5f} in {time.perf_counter() - t0:.1f} s")

    bad = sum(1 for x in losses if not math.isfinite(x))
    compiled = common.compiled_inside(opened, closed)
    step_compiles = step.cache_stats()["compiles"]
    tol = LOSS_TOLERANCE
    checks = {
        "first loss within tolerance of the reference":
            abs(first_loss - ref_loss) <= tol,
        "every loss finite": bad == 0 and math.isfinite(first_loss),
        "step traced once": step_compiles == 1,
        "nothing compiled in the window": compiled == 0,
    }
    log(f"first loss {first_loss:.5f} vs reference {ref_loss:.5f} "
        f"(apart {abs(first_loss - ref_loss):.5f}, tolerance {tol}); "
        f"step traces {step_compiles}; compiled in window {compiled}; "
        f"checks {checks}")
    batches = max(1, stall_close["batches"] - stall_open["batches"])
    return {
        "correct": all(checks.values()),
        "attempted": steps,
        "failed": bad,
        "t_window_open": t_open,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {
            "train_tokens_per_s": (tokens_per_s / int(cell["chips"]),
                                   "tokens/s/chip"),
        },
        "ctx": {
            "trace": holder["trace"],
            "measured": {
                "tokens_per_s": tokens_per_s, "window_s": window_s,
                "steps": steps, "batch": batch, "seq": seq,
                "dispatch_s": dispatch_s,
                "input_stall_s_per_batch":
                    (stall_close["consumer_stall_s"]
                     - stall_open["consumer_stall_s"]) / batches,
            },
            "counters": {"open": opened, "close": closed},
        },
    }
