"""The serve loop's host turn, measured where it happens: the phase
counters of ``ServingMetrics.snapshot()["loop"]`` and the ``serve.*``
spans of ``InferenceServer``'s loop thread (serving/metrics.py:LoopClock).
"""
import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.observability import tracing
from paddle_tpu.serving import InferenceServer
from paddle_tpu.serving.metrics import LOOP_PHASES, LoopClock, ServingMetrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS = 3


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(11)
    m = GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                attention_dropout_prob=0.0,
                                use_flash_attention=False))
    m.eval()
    return m


@pytest.fixture()
def server(model):
    srv = InferenceServer(model, slots=SLOTS, max_length=64,
                          prefill_buckets=(16,), max_prefills_per_step=SLOTS)
    srv.engine.warmup()       # main thread, the engine's own clock
    yield srv
    srv.shutdown(drain=False, timeout=30)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 200, (n,)).astype(np.int32)


def _loop_wall(snap):
    return sum(v["wall_s"] for v in snap["loop"].values())


def test_phase_counters_of_a_served_batch(server):
    t_start = time.time()
    server.start()
    hs = [server.submit(_prompt(5 + i, i), max_new_tokens=6)
          for i in range(5)]
    for h in hs:
        h.result(timeout=300)
    a = server.snapshot()
    loop = a["loop"]
    assert tuple(loop) == LOOP_PHASES and len(LOOP_PHASES) == 7
    # warmup ran on another thread's clock: nothing of it is in here
    assert (loop["decode_dispatch"]["count"] == loop["decode_wait"]["count"]
            == a["decode_steps"] > 0)
    assert loop["admit_wait"]["count"] == a["prefills"] == 5
    assert loop["admit_host"]["count"] == 2 * 5     # before and after it
    assert loop["emit"]["count"] in (a["decode_steps"],
                                     a["decode_steps"] - 1)
    for v in loop.values():
        assert v["wall_s"] >= 0 and 0 <= v["cpu_s"]
    # waiting for the device or for work burns no CPU to speak of; the
    # host phases run (this is a test machine: only a loose bound)
    for p in ("schedule", "admit_host", "decode_dispatch", "emit"):
        assert loop[p]["cpu_s"] <= loop[p]["wall_s"] + 5e-3
    # monotone across two snapshots, a further request between them
    server.submit(_prompt(7, 9), max_new_tokens=4).result(timeout=300)
    b = server.snapshot()
    for p in LOOP_PHASES:
        for k in ("count", "wall_s", "cpu_s"):
            assert b["loop"][p][k] >= a["loop"][p][k], (p, k)
    assert b["loop"]["decode_wait"]["count"] == b["decode_steps"]
    # disjoint and complete: once the thread has ended, the phases' wall
    # time is its whole life (start() to the join), to a few per cent
    server.shutdown(drain=True, timeout=60)
    life = time.time() - t_start
    total = _loop_wall(server.snapshot())
    assert total <= life
    assert total == pytest.approx(life, rel=0.05, abs=0.02)
    server.metrics.reset()
    assert _loop_wall(server.snapshot()) == 0
    assert all(v["count"] == 0
               for v in server.snapshot()["loop"].values())


def test_idle_is_booked_while_it_lasts(server):
    """A snapshot of an idle server lacks one wait of it at most."""
    server.start()
    server.submit(_prompt(4), max_new_tokens=2).result(timeout=300)
    time.sleep(0.35)
    a = server.snapshot()["loop"]["idle"]
    time.sleep(0.5)
    b = server.snapshot()["loop"]["idle"]
    assert b["count"] > a["count"]
    assert 0.3 <= b["wall_s"] - a["wall_s"] <= 0.7


def _step_records(server, live, tokens=12):
    """Span records per decode step, over steps in which nothing is
    admitted and nothing finishes, with ``live`` slots decoding."""
    hs = [server.submit(_prompt(6, i), max_new_tokens=tokens)
          for i in range(live)]
    for h in hs:
        h.result(timeout=300)
    corrs = {h.correlation_id for h in hs}
    t_last_admit = max(s["t1"] for s in tracing.spans(name="serve.admit")
                       if s["corr"] in corrs)
    t_first_end = min(s["t0"] for s in tracing.spans(name="stream_end")
                      if s["corr"] in corrs)
    mid = [s for s in tracing.spans()
           if t_last_admit <= s["t0"] and s["t1"] < t_first_end]
    steps = {s["tags"]["step"] for s in mid
             if s["name"] == "serve.decode.dispatch"}
    assert len(steps) >= tokens - 3
    assert all(s["tags"].get("live", live) == live for s in mid)
    return len(mid) / len(steps), {s["name"] for s in mid}


def test_span_records_a_step_do_not_grow_with_the_live_slots(server):
    tracing.clear()
    server.start()
    one, names = _step_records(server, 1)
    full, names_full = _step_records(server, SLOTS)
    assert names == names_full == {"serve.schedule", "serve.decode.dispatch",
                                   "serve.decode.wait", "serve.emit"}
    # schedule, dispatch, wait, emit; the window's ends may cut a step
    assert 3.5 <= one <= 4.5 and 3.5 <= full <= 4.5
    # with the request-lane records of a step that ends a request
    # (decode, stream_end) the most is 6, whatever the slots hold
    assert max(one, full) + 2 <= 6.5


def test_clock_books_every_nanosecond_once():
    m = ServingMetrics(2)
    clock = LoopClock(m.loop_phase)
    t0 = clock.t
    for phase in ("schedule", "decode_dispatch", "decode_wait", "emit",
                  "schedule", "idle"):
        clock.enter(phase)
        time.sleep(0.002)
    clock.enter("idle")
    loop = m.snapshot()["loop"]
    booked = sum(v["wall_s"] for v in loop.values())
    assert booked == pytest.approx((clock.t - t0) * 1e-9, abs=1e-12)
    assert loop["schedule"]["count"] == 2 and loop["idle"]["count"] == 2
    assert loop["emit"]["wall_s"] >= 0.002
    # a clock stepped backwards books no negative time
    clock.t += 10 ** 9
    clock.enter("emit")
    assert m.snapshot()["loop"]["idle"]["wall_s"] == loop["idle"]["wall_s"]


def test_clock_spans_share_the_boundary_and_end_with_the_phase():
    tracing.clear()
    clock = LoopClock()           # books nothing: the engine's own kind
    tags = {"step": 1, "live": 2}
    clock.enter("decode_dispatch", "serve.decode.dispatch", tags=tags)
    clock.enter("decode_wait", "serve.decode.wait", tags=clock.tags)
    clock.enter("emit")           # no span of its own
    clock.enter("schedule", "serve.schedule")
    got = tracing.spans()
    assert [s["name"] for s in got] == ["serve.decode.dispatch",
                                        "serve.decode.wait"]
    assert got[0]["t1"] == got[1]["t0"] and got[0]["tags"] == tags
    # the ring off: counters still, no span, no annotation
    tracing.enable(False)
    try:
        m = ServingMetrics(1)
        c2 = LoopClock(m.loop_phase)
        c2.enter("emit", "serve.emit")
        c2.enter("idle")
        assert c2._open is None
        assert m.snapshot()["loop"]["emit"]["count"] == 1
    finally:
        tracing.enable(True)
    assert len(tracing.spans()) == 2


def test_begin_and_end_record_one_span_under_a_trace_annotation():
    tracing.clear()
    opened = tracing.begin("phase.x", t0=100.0)
    name, t0, ann = opened
    assert (name, t0) == ("phase.x", 100.0)
    assert type(ann).__name__ == "TraceAnnotation"
    tracing.end(opened, t1=100.5, corr="c", tags={"k": 1})
    with tracing.span("phase.y", corr=None, k=2):
        pass
    got = tracing.spans()
    assert [(s["name"], s["corr"], s["tags"]) for s in got] == [
        ("phase.x", "c", {"k": 1}), ("phase.y", None, {"k": 2})]
    assert (got[0]["t0"], got[0]["t1"]) == (100.0, 100.5)
    tracing.end(None)             # what begin() gives with the ring off


def test_no_serving_block_is_bracketed_twice():
    """One span system in serving/: no ``RecordEvent`` left beside the
    tracing spans, no per-token span."""
    for path in glob.glob(os.path.join(ROOT, "paddle_tpu", "serving",
                                       "*.py")):
        text = open(path).read()
        assert "RecordEvent(" not in text, path
        assert "import RecordEvent" not in text, path
    server_py = open(os.path.join(ROOT, "paddle_tpu", "serving",
                                  "server.py")).read()
    assert "_last_token_wall" not in server_py
    assert "_submit_wall" not in server_py


def test_clients_do_not_disturb_the_books(server):
    """snapshot() and reset() from other threads while the loop books, at
    a switch interval that interleaves them as often as can be: a reset
    swaps the table, so no count is ever torn, and once the resets stop
    the counters agree with the steps again."""
    server.start()
    stop = threading.Event()

    def poke():
        while not stop.is_set():
            loop = server.snapshot()["loop"]
            assert all(v["wall_s"] >= 0 and v["count"] >= 0
                       for v in loop.values())
            server.metrics.reset()

    threads = [threading.Thread(target=poke) for _ in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        out = [server.submit(_prompt(5, i), max_new_tokens=8)
               .result(timeout=300) for i in range(3)]
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert all(len(o) == 8 for o in out)
    time.sleep(0.15)          # the loop is idle: nothing left to book
    a = server.snapshot()
    server.submit(_prompt(6, 7), max_new_tokens=5).result(timeout=300)
    b = server.snapshot()
    steps = b["decode_steps"] - a["decode_steps"]
    assert steps == 4
    for p in ("decode_dispatch", "decode_wait"):
        assert b["loop"][p]["count"] - a["loop"][p]["count"] == steps
