"""The serve loop one step ahead of what it has read (``engine.launch`` /
``engine.collect``; serving/engine.py, serving/server.py).

The order of the work changes and nothing else: every stream the server
serves is token for token what the same engine serves driven by the
synchronous ``step()`` and what a solo ``generate()`` returns at the same
seed, on a dense GPT, a grouped-query Llama and the hybrid whose cache
holds a recurrent state (nothing masks a state: a reused slot must start
from the prompt's, not from what a filler step left). What the host
learns one launch late (an eos) reaches no client; what it must not learn
late (a length) it does not.
"""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.resilience import FaultPlan
from paddle_tpu.serving import InferenceServer
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.scheduler import Request

GEO = dict(max_length=64, prefill_buckets=(8, 16))
FAMILIES = ("gpt", "llama", "hybrid")


def _build(family):
    if family == "gpt":
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

        pt.seed(7)
        cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                       use_flash_attention=False)
        model = GPTForCausalLM(cfg)
    elif family == "llama":
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        pt.seed(8)
        cfg = llama_tiny(use_flash_attention=False)
        model = LlamaForCausalLM(cfg)
    else:
        from paddle_tpu.models.jamba import JambaForCausalLM, jamba_tiny

        pt.seed(11)
        # 0.2: the branches must outweigh the tied head's copy of the
        # embedding for a state to show in a token (tests/test_jamba.py)
        cfg = jamba_tiny(initializer_range=0.2)
        model = JambaForCausalLM(cfg)
    model.eval()
    return model, cfg


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(family):
        if family not in built:
            built[family] = _build(family)
        return built[family]

    return get


@pytest.fixture(scope="module")
def gpt_server(models):
    model, cfg = models("gpt")
    srv = InferenceServer(model, slots=2, max_queue_depth=16,
                          max_request_retries=1, **GEO)
    yield srv, model, cfg
    srv.shutdown(drain=False, timeout=30)


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, (n,)).astype(np.int32)


def _fresh_token(stream, at_least):
    """(index, token) of the first token at or after ``at_least`` that the
    stream has not held before: as an eos it ends the stream right there."""
    stream = [int(t) for t in stream]
    return next((i, t) for i, t in enumerate(stream)
                if i >= at_least and t not in stream[:i])


def _mix(model, cfg):
    """Seven requests for two slots: greedy and sampled (one under a
    nucleus), ends by length and by eos, an eos out of the prefill."""
    kinds = [dict(), dict(do_sample=True, temperature=0.8, seed=5),
             dict(do_sample=True, temperature=1.3, top_p=0.7, seed=9),
             dict(), dict(do_sample=True, temperature=0.9, top_p=0.9, seed=3),
             dict(), dict()]
    lengths = [(9, 10), (12, 7), (5, 12), (3, 9), (14, 6), (7, 8), (6, 5)]
    eos_at = {2: 4, 3: 2, 5: 0}     # request -> index of the token it ends on
    reqs = []
    for i, ((n, new), kw) in enumerate(zip(lengths, kinds)):
        p = _prompt(cfg, n, 20 + i)
        kw = dict(kw, max_new_tokens=new)
        if i in eos_at:
            probe = model.generate(p[None], **kw, **GEO)[0]
            kw["eos_token_id"] = int(probe[eos_at[i]])
        reqs.append((p, kw))
    return reqs


def _drive_synchronously(engine, reqs):
    """The plain loop over the same engine: admit in order into free
    slots, ``step()``, hand out, release on eos or length."""
    streams = [[] for _ in reqs]
    todo = list(range(len(reqs)))
    slot_of = {}

    def push(i, slot, tok, done):
        streams[i].append(tok)
        if done or len(streams[i]) >= reqs[i][1]["max_new_tokens"]:
            engine.release(slot)
            del slot_of[slot]

    while todo or slot_of:
        for slot in engine.free_slots():
            if not todo:
                break
            i = todo.pop(0)
            p, kw = reqs[i]
            req = Request(prompt=p, max_new_tokens=kw["max_new_tokens"],
                          greedy=not kw.get("do_sample", False),
                          temperature=kw.get("temperature", 1.0),
                          top_p=kw.get("top_p", 1.0),
                          eos_token_id=kw.get("eos_token_id"),
                          seed=kw.get("seed"))
            first, fin, _ = engine.admit(req, slot)
            slot_of[slot] = i
            push(i, slot, first, fin)
        if slot_of:
            for ev in engine.step():
                push(slot_of[ev.slot], ev.slot, ev.token, ev.done)
    return streams


# ------------------------------------------------- (a) the same tokens
@pytest.mark.parametrize("family", FAMILIES)
def test_streams_are_the_synchronous_engines_and_generates(models, family):
    model, cfg = models(family)
    reqs = _mix(model, cfg)
    solo = [model.generate(p[None], **kw, **GEO)[0].tolist()
            for p, kw in reqs]
    ends = [(len(s), kw["max_new_tokens"]) for s, (_, kw) in zip(solo, reqs)]
    # by length, on an eos out of a decode step, on one out of the prefill
    assert {(n == new, 1 < n < new, n == 1) for n, new in ends} == {
        (True, False, False), (False, True, False), (False, False, True)}
    srv = InferenceServer(model, slots=2, max_queue_depth=16, **GEO)
    try:
        hs = [srv.submit(p, **kw) for p, kw in reqs]
        served = [list(h.stream()) for h in hs]
        srv.shutdown(drain=True, timeout=120)
        snap = srv.snapshot()
    finally:
        srv.shutdown(drain=False, timeout=30)
    assert served == solo
    assert snap["tokens_emitted"] == sum(map(len, solo))
    assert snap["prefills"] == len(reqs) > srv.engine.slots
    assert snap["decode"]["launched_ahead_steps"] > 0
    # the same engine, its loop stopped, by the synchronous step()
    srv.engine.reset()
    assert _drive_synchronously(srv.engine, reqs) == solo
    cc = srv.engine.cache_stats()
    assert cc["decode"]["compiles"] == 1
    assert cc["prefill"]["compiles"] <= len(GEO["prefill_buckets"])


# ------------------------------------------------------------ (b) an eos
@pytest.mark.parametrize("eos_at", [0, 1, 3])
def test_nothing_behind_an_eos_reaches_anyone(models, eos_at):
    """One slot, so the request behind lands where the eos was: the step
    launched ahead of the eos's read-back decoded filler there, and its
    token is neither the first request's nor the second's."""
    model, cfg = models("gpt")
    a, b = _prompt(cfg, 8, 4), _prompt(cfg, 11, 5)
    hot = dict(do_sample=True, temperature=3.0, seed=1)     # no repeats
    probe = model.generate(a[None], max_new_tokens=8, **hot, **GEO)[0]
    at, eos = _fresh_token(probe, eos_at)
    solo_a = model.generate(a[None], max_new_tokens=16, eos_token_id=eos,
                            **hot, **GEO)[0].tolist()
    solo_b = model.generate(b[None], max_new_tokens=6, do_sample=True,
                            temperature=0.9, seed=2, **GEO)[0].tolist()
    assert at == eos_at and solo_a[-1] == eos and len(solo_a) == eos_at + 1
    srv = InferenceServer(model, slots=1, **GEO)
    try:
        ha = srv.submit(a, max_new_tokens=16, eos_token_id=eos, **hot)
        hb = srv.submit(b, max_new_tokens=6, do_sample=True,
                        temperature=0.9, seed=2)
        got_a, got_b = list(ha.stream()), list(hb.stream())
        srv.shutdown(drain=True, timeout=120)
        snap = srv.snapshot()
    finally:
        srv.shutdown(drain=False, timeout=30)
    assert got_a == solo_a and got_b == solo_b
    assert snap["tokens_emitted"] == len(got_a) + len(got_b)
    assert snap["requests_completed"] == 2
    # an eos out of a decode step was read one launch late: that launch
    # counted the slot live and its event was dropped
    late = 1 if eos_at else 0
    assert snap["decode_steps"] == eos_at + late + len(got_b) - 1
    assert srv.engine.in_flight == 0


# ------------------------------------------- (c) the two halves by hand
@pytest.fixture(scope="module")
def hand_engine(models):
    model, cfg = models("gpt")
    return ContinuousBatchingEngine(model, slots=2, **GEO), cfg


def _admit(engine, cfg, slot, new=12, seed=0, **kw):
    req = Request(prompt=_prompt(cfg, 6 + slot, 60 + seed),
                  max_new_tokens=new, **{"greedy": True, "seed": 0, **kw})
    return req, engine.admit(req, slot)[0]


def test_one_launch_ahead_and_no_more(hand_engine):
    engine, cfg = hand_engine
    engine.reset()
    _admit(engine, cfg, 0)
    assert engine.launch() is False         # nothing before it
    assert engine.launch() is True          # ahead of the first
    assert engine.in_flight == 2
    with pytest.raises(RuntimeError, match="two launches are in flight"):
        engine.launch()
    assert engine.in_flight == 2 and engine._positions[0] == 6 + 2
    assert [e.slot for e in engine.collect()] == [0]
    assert engine.launch() is True
    engine.collect(), engine.collect()
    assert engine.in_flight == 0


def test_step_refuses_an_engine_with_a_launch_in_flight(hand_engine):
    engine, cfg = hand_engine
    engine.reset()
    _admit(engine, cfg, 1)
    engine.launch()
    with pytest.raises(RuntimeError, match="a launch is in flight"):
        engine.step()
    (ev,) = engine.collect()
    assert ev.slot == 1
    assert [e.slot for e in engine.step()] == [1]


def test_reset_drops_the_launches_not_read_back(hand_engine):
    engine, cfg = hand_engine
    engine.reset()
    _admit(engine, cfg, 0)
    engine.launch(), engine.launch()
    engine.reset()
    assert engine.in_flight == 0 and engine.live_count == 0
    assert engine._override.all() and engine._done.all()
    _, first = _admit(engine, cfg, 0)
    toks = [first] + [engine.step()[0].token for _ in range(4)]
    fresh = ContinuousBatchingEngine(engine.model, slots=2, **GEO)
    _, first_f = _admit(fresh, cfg, 0)
    assert toks == [first_f] + [fresh.step()[0].token for _ in range(4)]


@pytest.mark.parametrize("how", ["released", "replaced", "eos"])
def test_collect_drops_what_is_no_longer_the_slots(hand_engine, how):
    """A launch counted a slot live; before its read-back the request
    was released, replaced, or found to have ended on eos a launch
    earlier: no event."""
    engine, cfg = hand_engine
    engine.reset()
    if how == "eos":
        hot = dict(greedy=False, temperature=3.0, seed=1)   # no repeats
        _, first = _admit(engine, cfg, 0, **hot)
        at, eos = _fresh_token(
            [first] + [engine.step()[0].token for _ in range(6)], 1)
        assert at == 1      # the first decode step's token, not the prefill's
        engine.reset()
        _admit(engine, cfg, 0, eos_token_id=eos, **hot)
        _admit(engine, cfg, 1)
        engine.launch(), engine.launch()
        first_read = engine.collect()
        assert [(e.slot, e.token, e.done) for e in first_read
                if e.slot == 0] == [(0, eos, True)]
        assert [e.slot for e in engine.collect()] == [1]
        assert [e.slot for e in engine.step()] == [1]   # stays done
        return
    _admit(engine, cfg, 0)
    _admit(engine, cfg, 1)
    engine.launch()
    engine.release(0)
    if how == "replaced":
        _admit(engine, cfg, 0, seed=3)
    assert [e.slot for e in engine.collect()] == [1]
    # and the slot's next launch is the new request's, from its own token
    live = [e.slot for e in engine.step()]
    assert live == ([0, 1] if how == "replaced" else [1])


@pytest.mark.parametrize("new", [1, 2, 5])
def test_a_length_is_known_ahead(hand_engine, new):
    """A request complete with the launches in flight is not decoded for
    again: the counters a roofline reads stay exact."""
    engine, cfg = hand_engine
    engine.reset()
    _admit(engine, cfg, 0, new=new)
    _admit(engine, cfg, 1, new=9)
    loads = []
    for _ in range(6):
        engine.launch()
        loads.append(engine.step_load[0])
        engine.collect()
    assert loads == [2 if k < new - 1 else 1 for k in range(6)]
    assert engine._positions[0] == 6 + new - 1
    assert engine.live_count == 1


# ---------------------------------------------------------- (d) faults
@pytest.mark.parametrize("where", ["serve.step", "collect"])
def test_a_fault_with_a_step_in_flight_requeues_everyone(gpt_server,
                                                         monkeypatch, where):
    srv, model, cfg = gpt_server
    ps = [_prompt(cfg, 7 + i, 80 + i) for i in range(3)]
    kws = [dict(max_new_tokens=9), dict(max_new_tokens=7, do_sample=True,
                                        temperature=0.9, seed=11),
           dict(max_new_tokens=6)]
    solo = [model.generate(p[None], **kw, **GEO)[0].tolist()
            for p, kw in zip(ps, kws)]
    srv.submit(ps[0], max_new_tokens=2).result(timeout=300)     # compiled
    requeued0 = srv.metrics.requests_requeued
    seen = {"in_flight": None, "calls": 0}
    recover = srv._recover

    def recording(exc, extra=()):
        seen["in_flight"] = srv.engine.in_flight
        return recover(exc, extra=extra)

    monkeypatch.setattr(srv, "_recover", recording)
    if where == "collect":
        collect = srv.engine.collect

        def failing():
            seen["calls"] += 1
            if seen["calls"] == 3:
                raise RuntimeError("read-back failed")
            return collect()

        monkeypatch.setattr(srv.engine, "collect", failing)
        plan = FaultPlan([])
    else:
        plan = FaultPlan([{"site": "serve.step", "kind": "drop", "times": 1,
                           "after": 3}], seed=3)
    with plan, pytest.warns(RuntimeWarning, match="serve loop fault"):
        hs = [srv.submit(p, **kw) for p, kw in zip(ps, kws)]
        outs = [h.result(timeout=300).tolist() for h in hs]
    if where == "serve.step":
        assert plan.fired[0] == 1
    # the fault struck with a launch not read back (in collect(): the
    # one it was to read and the one made ahead of it)
    assert seen["in_flight"] == (1 if where == "serve.step" else 2)
    assert outs == solo
    assert srv.metrics.requests_requeued >= requeued0 + 1
    assert srv.engine.in_flight == 0
    # a restarted stream is re-emitted from the beginning, whole
    assert all(h.done and h.error is None for h in hs)
    assert srv.engine.cache_stats()["decode"]["compiles"] == 1


# ------------------------------------------- (e) drained before it rests
@pytest.mark.parametrize("n,ending", [(1, "length"), (3, "length"),
                                      (2, "eos")])
def test_the_loop_reads_the_last_launch_before_it_rests(models, n, ending):
    model, cfg = models("gpt")
    ps = [_prompt(cfg, 6 + i, 90 + i) for i in range(n)]
    kw = dict(max_new_tokens=8)
    if ending == "eos":
        kw.update(do_sample=True, temperature=3.0, seed=1)  # no repeats
        # both requests end on the same decode step's token: the slots
        # empty with a launch ahead of the read in flight
        kws = [dict(kw, eos_token_id=_fresh_token(
            model.generate(p[None], **kw, **GEO)[0], 2)[1]) for p in ps]
    else:
        kws = [kw] * n
    srv = InferenceServer(model, slots=3, max_prefills_per_step=3, **GEO)
    try:
        srv.engine.warmup()
        hs = [srv.submit(p, **k) for p, k in zip(ps, kws)]
        lens = [len(h.result(timeout=300)) for h in hs]
        t0 = time.monotonic()
        while srv.engine.in_flight and time.monotonic() - t0 < 30:
            time.sleep(0.01)
        assert srv.engine.in_flight == 0
        time.sleep(0.25)        # idle: every phase that ended is booked
        a = srv.snapshot()
        loop, d = a["loop"], a["decode"]
        assert (loop["decode_dispatch"]["count"]
                == loop["decode_wait"]["count"] == a["decode_steps"]
                == d["steps"] > 0)
        assert 0 < d["launched_ahead_steps"] <= d["steps"]
        if ending == "length":
            assert lens == [8] * n and d["live_slot_steps"] == 7 * n
            # every step is handed over while the one before it runs,
            # but the first and the one behind each later admission
            assert 7 <= d["steps"] <= 7 + n - 1
            assert d["steps"] - n <= d["launched_ahead_steps"] < d["steps"]
            if n == 1:
                assert (d["steps"], d["launched_ahead_steps"]) == (7, 6)
        else:
            assert all(2 < length < 8 for length in lens)
            assert a["tokens_emitted"] == sum(lens)
        srv.shutdown(drain=True, timeout=60)
        b = srv.snapshot()
        assert b["decode"] == d and srv.engine.in_flight == 0
    finally:
        srv.shutdown(drain=False, timeout=30)


def test_shutdown_drains_a_launch_in_flight(models):
    """``shutdown(drain=True)`` called while requests decode: the loop
    ends only after the last launch was read back and emitted."""
    model, cfg = models("gpt")
    srv = InferenceServer(model, slots=2, **GEO)
    try:
        hs = [srv.submit(_prompt(cfg, 5 + i, i), max_new_tokens=12)
              for i in range(3)]
        srv.shutdown(drain=True, timeout=120)
        assert [len(h.result(timeout=1)) for h in hs] == [12] * 3
        snap = srv.snapshot()
        assert srv.engine.in_flight == 0
        assert (snap["loop"]["decode_wait"]["count"]
                == snap["loop"]["decode_dispatch"]["count"]
                == snap["decode_steps"])
    finally:
        srv.shutdown(drain=False, timeout=30)
