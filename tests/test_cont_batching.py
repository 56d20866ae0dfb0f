"""Continuous-batching serving subsystem (paddle_tpu/serving/).

The acceptance contract:

1. **Correctness under interleaving** — requests submitted at staggered
   times, admitted into slots while other requests are mid-decode, all
   complete with EXACTLY the tokens a solo batch-1 ``generate()`` with
   the same seed produces (slot placement and batch companions must not
   leak into results);
2. **Compile discipline** — after warmup the serving loop holds at
   ``#prefill_buckets + 1`` compiled programs (``cache_stats()``), no
   matter how many requests flow through;
3. **Admission control** — a full queue rejects with retryable
   backpressure; queue-expired deadlines fail with ``TimeoutError``;
4. **Crash safety** — an injected worker fault requeues in-flight
   requests and the recovered run returns identical tokens, without
   recompiling.

Tier-1 budget discipline: ONE module-scoped server (ONE bucket, so two
serving programs total) is shared by every integration test; scheduler/
metrics tests are device-free. The open-loop load bench runs under the
``slow`` marker only. NOTE: the drain-shutdown test must run LAST in
this file — it retires the shared server.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.resilience import (Deadline, FaultPlan,
                                               RetryPolicy)
from paddle_tpu.serving import (FifoScheduler, InferenceServer, QueueFull,
                                Request, SchedulerClosed)
from paddle_tpu.serving.metrics import LatencyHistogram, ServingMetrics

GEO = dict(max_length=64, prefill_buckets=(16,))


@pytest.fixture(scope="module")
def lm():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(7)
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model, cfg


@pytest.fixture(scope="module")
def server(lm):
    model, _ = lm
    srv = InferenceServer(model, slots=2, max_queue_depth=8,
                          max_request_retries=1, **GEO)
    yield srv
    try:
        srv.shutdown(drain=False, timeout=30)
    except Exception:
        pass


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n,)).astype(np.int32)


# ---------------------------------------------------------------- tentpole
def test_continuous_batching_matches_solo_generate(lm, server):
    """THE acceptance test: three staggered requests (greedy + seeded
    sampling, different lengths/budgets) admitted into a 2-slot live
    batch — every result equals its solo batch-1 generate()."""
    model, cfg = lm
    p0, p1, p2 = (_prompt(cfg, 9, 1), _prompt(cfg, 12, 2),
                  _prompt(cfg, 6, 3))
    solo0 = model.generate(p0[None], max_new_tokens=10, **GEO)[0]
    solo1 = model.generate(p1[None], max_new_tokens=7, do_sample=True,
                           temperature=0.8, seed=5, **GEO)[0]
    solo2 = model.generate(p2[None], max_new_tokens=5, **GEO)[0]

    h0 = server.submit(p0, max_new_tokens=10)
    time.sleep(0.15)  # h1/h2 arrive while h0 is mid-decode
    h1 = server.submit(p1, max_new_tokens=7, do_sample=True,
                       temperature=0.8, seed=5)
    time.sleep(0.1)
    h2 = server.submit(p2, max_new_tokens=5)
    np.testing.assert_array_equal(h0.result(timeout=300), solo0)
    np.testing.assert_array_equal(h1.result(timeout=300), solo1)
    np.testing.assert_array_equal(h2.result(timeout=300), solo2)
    assert h0.ttft_s is not None and h0.ttft_s > 0


def test_steady_state_holds_at_buckets_plus_one(lm, server):
    """After warmup (previous test), more traffic — mixed sampling knobs,
    every free-slot reuse pattern — adds ZERO compiled programs: exactly
    #prefill_buckets prefill + 1 decode."""
    from paddle_tpu.framework import compile_cache

    model, cfg = lm
    cc = server.engine.cache_stats()
    assert cc["prefill"]["compiles"] == len(server.engine.prefill_buckets)
    assert cc["decode"]["compiles"] == 1
    with compile_cache.retrace_guard(max_compiles=0, label="serving"):
        hs = [server.submit(_prompt(cfg, 4 + i, seed=10 + i),
                            max_new_tokens=3 + i, do_sample=bool(i % 2),
                            temperature=0.5 + 0.1 * i, top_p=0.9,
                            seed=i) for i in range(5)]
        for h in hs:
            assert h.result(timeout=300).shape[0] == h.request.max_new_tokens
    cc2 = server.engine.cache_stats()
    assert cc2["prefill"]["compiles"] == cc["prefill"]["compiles"]
    assert cc2["decode"]["compiles"] == 1
    total = cc2["prefill"]["compiles"] + cc2["decode"]["compiles"]
    assert total == len(server.engine.prefill_buckets) + 1


def test_streaming_iterator_and_eos(lm, server):
    """stream() yields tokens incrementally; eos finishes the request
    early and the stream ends cleanly."""
    model, cfg = lm
    p = _prompt(cfg, 8, 4)
    probe = model.generate(p[None], max_new_tokens=2, **GEO)[0]
    eos = int(probe[1])  # greedy token at step 2 -> finishes there
    solo = model.generate(p[None], max_new_tokens=16, eos_token_id=eos,
                          **GEO)[0]
    h = server.submit(p, max_new_tokens=16, eos_token_id=eos)
    got = list(h.stream())
    np.testing.assert_array_equal(np.asarray(got, np.int32), solo)
    assert got[-1] == eos and len(got) < 16


def test_worker_fault_requeues_and_result_is_identical(lm, server):
    """An injected fault mid-serve (FaultPlan at the serve.step site)
    resets the engine, requeues the in-flight request, and the retried
    run — same seed — returns the same tokens, with NO recompile."""
    model, cfg = lm
    p = _prompt(cfg, 10, 6)
    solo = model.generate(p[None], max_new_tokens=6, do_sample=True,
                          temperature=0.9, seed=11, **GEO)[0]
    before = server.engine.cache_stats()
    requeued0 = server.metrics.requests_requeued
    plan = FaultPlan([{"site": "serve.step", "kind": "drop", "times": 1}],
                     seed=3)
    with plan, pytest.warns(RuntimeWarning, match="serve loop fault"):
        h = server.submit(p, max_new_tokens=6, do_sample=True,
                          temperature=0.9, seed=11)
        out = h.result(timeout=300)
    assert plan.fired[0] == 1  # the fault actually hit the serve loop
    np.testing.assert_array_equal(out, solo)
    assert server.metrics.requests_requeued == requeued0 + 1
    after = server.engine.cache_stats()
    assert after["prefill"]["compiles"] == before["prefill"]["compiles"]
    assert after["decode"]["compiles"] == before["decode"]["compiles"]


def test_admit_fault_requeues_whole_admission_batch(lm, server):
    """A fault during ADMISSION must not drop the other requests popped
    in the same admission batch — every client completes (the handles
    would otherwise hang forever)."""
    model, cfg = lm
    solos = [model.generate(_prompt(cfg, 5 + i, 30 + i)[None],
                            max_new_tokens=4, **GEO)[0] for i in range(3)]
    plan = FaultPlan([{"site": "serve.admit", "kind": "drop", "times": 1}],
                     seed=5)
    with plan, pytest.warns(RuntimeWarning, match="serve loop fault"):
        hs = [server.submit(_prompt(cfg, 5 + i, 30 + i), max_new_tokens=4)
              for i in range(3)]
        outs = [h.result(timeout=300) for h in hs]
    assert plan.fired[0] == 1
    for out, solo in zip(outs, solos):
        np.testing.assert_array_equal(out, solo)


def test_request_deadline_expires_in_queue(lm, server):
    model, cfg = lm
    h = server.submit(_prompt(cfg, 5, 7), max_new_tokens=4, deadline=0.0)
    with pytest.raises(TimeoutError, match="expired in queue"):
        h.result(timeout=60)
    assert server.metrics.requests_expired >= 1


def test_result_timeout_and_overlong_reject(lm, server):
    model, cfg = lm
    with pytest.raises(ValueError, match="max_length"):
        server.submit(_prompt(cfg, 8), max_new_tokens=1000)
    h = server.submit(_prompt(cfg, 5, 8), max_new_tokens=4)
    with pytest.raises(TimeoutError):
        h.result(timeout=0.0)
    h.result(timeout=300)  # then completes fine


def test_unseeded_sampled_requests_draw_fresh_randomness(lm, server):
    """Two unseeded sampled requests with the SAME prompt must not
    return identical streams (solo generate(seed=None) semantics — the
    serving layer must not pin a default seed)."""
    model, cfg = lm
    p = _prompt(cfg, 7, 40)
    kw = dict(max_new_tokens=8, do_sample=True, temperature=8.0)
    a = server.submit(p, **kw).result(timeout=300)
    b = server.submit(p, **kw).result(timeout=300)
    assert not np.array_equal(a, b)


def test_metrics_snapshot_shape(server):
    snap = server.snapshot()
    for k in ("slot_occupancy", "tokens_per_sec", "requests_per_sec",
              "queue_depth", "active_slots", "compile_stats"):
        assert k in snap
    for h in ("ttft", "inter_token", "queue_wait"):
        assert {"count", "p50_ms", "p99_ms"} <= set(snap[h])
    assert snap["requests_completed"] >= 9
    assert 0.0 <= snap["slot_occupancy"] <= 1.0


@pytest.mark.slow
def test_llama_gqa_continuous_batching():
    """The GQA+RoPE path under per-slot positions: two staggered llama
    requests in a 2-slot batch both equal their solo runs (rotary tables
    and the grouped-KV cache index per ROW, not per batch). Slow: pays a
    second model family's serving compiles; the tier-1 vector-position
    coverage for llama is the eager equivalence test in
    test_generation.py."""
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(7)
    cfg = llama_tiny(use_flash_attention=False)
    assert cfg.num_kv_heads < cfg.num_heads  # GQA, not MHA
    model = LlamaForCausalLM(cfg)
    model.eval()
    p0, p1 = _prompt(cfg, 9, 20), _prompt(cfg, 6, 21)
    solo0 = model.generate(p0[None], max_new_tokens=6, **GEO)[0]
    solo1 = model.generate(p1[None], max_new_tokens=4, do_sample=True,
                           temperature=0.8, seed=3, **GEO)[0]
    srv = InferenceServer(model, slots=2, **GEO)
    try:
        h0 = srv.submit(p0, max_new_tokens=6)
        time.sleep(0.1)  # h1 lands while h0 decodes
        h1 = srv.submit(p1, max_new_tokens=4, do_sample=True,
                        temperature=0.8, seed=3)
        np.testing.assert_array_equal(h0.result(timeout=300), solo0)
        np.testing.assert_array_equal(h1.result(timeout=300), solo1)
    finally:
        srv.shutdown(drain=True, timeout=60)


def test_hapi_model_serve(lm):
    """Model.serve() surface: tiny 1-slot server, result == generate."""
    from paddle_tpu.hapi import Model
    import paddle_tpu.nn as nn

    model, cfg = lm
    m = Model(model)
    p = _prompt(cfg, 7, 9)
    solo = model.generate(p[None], max_new_tokens=3, **GEO)[0]
    srv = m.serve(slots=1, **GEO)
    try:
        np.testing.assert_array_equal(
            srv.submit(p, max_new_tokens=3).result(timeout=300), solo)
    finally:
        srv.shutdown(drain=True, timeout=60)
    with pytest.raises(TypeError, match="cache_spec"):
        Model(nn.Linear(4, 4)).serve()


# NOTE: keep this LAST among the tests using the shared server — it
# retires it (graceful drain, then closed-for-business semantics).
def test_shutdown_drains_inflight_then_refuses(lm, server):
    model, cfg = lm
    solo = model.generate(_prompt(cfg, 8, 12)[None], max_new_tokens=8,
                          **GEO)[0]
    h = server.submit(_prompt(cfg, 8, 12), max_new_tokens=8)
    server.shutdown(drain=True, timeout=120)
    np.testing.assert_array_equal(h.result(timeout=1), solo)
    with pytest.raises(SchedulerClosed):
        server.submit(_prompt(cfg, 4), max_new_tokens=2)


# ------------------------------------------------------- device-free units
def test_scheduler_fifo_order_and_admission_rate():
    s = FifoScheduler(max_queue_depth=8, max_prefills_per_step=2)
    reqs = [Request(prompt=[1], id=i) for i in range(5)]
    for r in reqs:
        s.submit(r)
    admit, expired = s.take(free_slots=4)
    assert [r.id for r in admit] == [0, 1]  # K=2 caps the admission rate
    assert not expired
    admit2, _ = s.take(free_slots=1)        # free slots cap it too
    assert [r.id for r in admit2] == [2]
    s.requeue(admit[0])                     # crash recovery: head, not tail
    admit3, _ = s.take(free_slots=4)
    assert [r.id for r in admit3] == [0, 3]


def test_scheduler_backpressure_is_retryable():
    """QueueFull rides the stack's RetryPolicy like any transport
    failure: a client retrying with backoff gets in once depth frees."""
    s = FifoScheduler(max_queue_depth=1)
    s.submit(Request(prompt=[1]))
    with pytest.raises(QueueFull):
        s.submit(Request(prompt=[2]))
    calls = {"n": 0}

    def drain_then_submit():
        calls["n"] += 1
        if calls["n"] == 2:  # depth freed between attempts
            s.take(free_slots=1)
        s.submit(Request(prompt=[3]))
        return True

    assert RetryPolicy(max_attempts=4, base_delay=0.01).call(
        drain_then_submit)
    assert calls["n"] >= 2


def test_scheduler_deadline_sweep_and_seal():
    s = FifoScheduler(max_queue_depth=8)
    alive = Request(prompt=[1], deadline=Deadline(60))
    dead = Request(prompt=[2], deadline=Deadline(0.0))
    s.submit(alive)
    s.submit(dead)
    expired = s.pop_expired()
    assert [r is dead for r in expired] == [True]
    s.seal()
    with pytest.raises(SchedulerClosed):
        s.submit(Request(prompt=[3]))
    admit, _ = s.take(free_slots=2)  # sealed still drains
    assert admit == [alive]
    assert s.close() == []


def test_scatter_slice_cache_rows_roundtrip():
    """The slot-scatter primitive (kv_cache.py): write a single-slot
    cache into the live batch at a traced index, read the row back —
    bit-identical, other rows untouched. Eager: no compile cost."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.kv_cache import scatter_cache_rows

    rng = np.random.default_rng(0)
    live = tuple((jnp.asarray(rng.normal(size=(3, 5, 2, 4)), jnp.float32),
                  jnp.asarray(rng.normal(size=(3, 5, 2, 4)), jnp.float32))
                 for _ in range(2))
    row = tuple((jnp.asarray(rng.normal(size=(1, 5, 2, 4)), jnp.float32),
                 jnp.asarray(rng.normal(size=(1, 5, 2, 4)), jnp.float32))
                for _ in range(2))
    out = scatter_cache_rows(live, row, jnp.int32(1))
    back = jax.tree.map(lambda x: x[1:2], out)
    for (bk, bv), (rk, rv) in zip(back, row):
        np.testing.assert_array_equal(np.asarray(bk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(bv), np.asarray(rv))
    for li, (lk, _) in enumerate(live):  # rows 0/2 untouched
        np.testing.assert_array_equal(np.asarray(out[li][0])[0],
                                      np.asarray(lk)[0])
        np.testing.assert_array_equal(np.asarray(out[li][0])[2],
                                      np.asarray(lk)[2])


def test_latency_histogram_reservoir_percentiles():
    h = LatencyHistogram(max_samples=64, seed=0)
    for v in range(1, 101):
        h.observe(v / 1000.0)
    s = h.summary()
    assert s["count"] == 100
    assert 0.020 <= s["p50_ms"] / 1000.0 <= 0.080  # sampled median ~0.05
    assert s["p99_ms"] >= s["p50_ms"]
    assert s["max_ms"] == pytest.approx(100.0)


def test_serving_metrics_occupancy_integral():
    m = ServingMetrics(slots=4)
    m.set_active_slots(4)
    time.sleep(0.05)
    m.set_active_slots(0)
    snap = m.snapshot()
    assert snap["slot_occupancy"] > 0.0
    m.inc("tokens_emitted", 10)
    assert m.snapshot()["tokens_per_sec"] > 0


def test_concurrent_submitters_thread_safety():
    """Many client threads submitting at once: scheduler stays
    consistent (device-free — a standalone scheduler, not the shared
    server, so this can run after shutdown)."""
    s = FifoScheduler(max_queue_depth=64, max_prefills_per_step=64)
    errs = []

    def client(i):
        try:
            s.submit(Request(prompt=[i], id=i))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=client, args=(i,)) for i in range(32)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errs and s.depth == 32
    seen = []
    while True:
        got, _ = s.take(free_slots=8)
        if not got:
            break
        seen.extend(r.id for r in got)
    assert sorted(seen) == list(range(32))


# ------------------------------------------------------------------- slow
@pytest.mark.slow
def test_serve_bench_cli_emits_percentile_json():
    """tools/serve_bench.py --check end-to-end on CPU: p50/p99 TTFT and
    inter-token latency, goodput, occupancy — and exit 0 (zero
    steady-state recompiles)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "serve_bench.py"),
         "--check"],
        capture_output=True, text=True, timeout=540, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(
        [l for l in proc.stdout.splitlines() if l.startswith('{"')][-1])
    assert rec["metric"] == "gpt_serve_requests_per_sec"
    assert rec["value"] > 0
    ex = rec["extra"]
    assert ex["goodput"] > 0
    assert ex["ttft_p99_ms"] >= ex["ttft_p50_ms"] > 0
    assert ex["inter_token_p99_ms"] >= ex["inter_token_p50_ms"] > 0
    assert 0.0 <= ex["slot_occupancy"] <= 1.0
    assert ex["decode_compiles"] == 1
    assert ex["steady_state_recompiles"] == 0
