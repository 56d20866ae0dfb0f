"""Fleet serving: paged prefix/KV block pool + multi-replica router.

The acceptance contract on top of PR 4's continuous batching:

1. **Prefix reuse is invisible in the tokens** — a request whose prompt
   prefix is warm in the block pool admits by copying matched blocks
   in-program and prefilling only the novel suffix, and its stream is
   token-identical to a cold solo ``generate()`` with the same seed;
2. **Compile discipline survives pooling** — hit admits, miss admits and
   block stores all ride ONE program family per suffix bucket, so a
   pooled replica still holds at ``#prefill_buckets + 1`` programs;
3. **The router is load- and affinity-aware** — shared-prefix traffic
   lands where its blocks are warm, occupancy/queue skew pushes traffic
   away, ``QueueFull`` fails over before propagating, drains re-route;
4. **A replica crash loses nothing** — in-flight requests reroute to
   survivors and replay identical tokens (router-assigned seeds).

Tier-1 budget discipline: ONE module-scoped two-replica fleet (ONE
bucket each) is shared by every integration test; router/pool logic is
otherwise exercised on device-free stubs. NOTE: the crash test kills
replica "b" and must stay LAST among the fleet-fixture tests.
"""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.resilience import RetryPolicy
from paddle_tpu.serving import (BlockPool, InferenceServer,
                                NoReplicasAvailable, QueueFull,
                                ReplicaRouter, Request, SchedulerClosed,
                                ServingMetrics)
from paddle_tpu.serving.server import RequestHandle

GEO = dict(max_length=64, prefill_buckets=(32,))
POOL = dict(block_tokens=8, max_bytes=1 << 20)


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
def fleet(lm):
    model, _ = lm
    a = InferenceServer(model, slots=2, prefix_cache=dict(POOL), **GEO)
    b = InferenceServer(model, slots=2, prefix_cache=dict(POOL), **GEO)
    router = ReplicaRouter()
    router.add_replica(a, "a")
    router.add_replica(b, "b")
    yield router, a, b
    for srv in (a, b):
        try:
            srv.shutdown(drain=False, timeout=30)
        except Exception:
            pass


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n,)).astype(np.int32)


# ---------------------------------------------------------------- tentpole
def test_prefix_hit_stream_matches_cold_solo(lm, fleet):
    """THE acceptance test: a cold admit populates the pool; two
    follow-ups sharing its 16-token prefix admit as hits (blocks copied
    in-program, only the suffix prefilled) and both equal their cold
    solo generate() — greedy and seeded-sampled."""
    model, cfg = lm
    router, a, b = fleet
    prefix = _prompt(cfg, 16, 100)
    p1 = np.concatenate([prefix, _prompt(cfg, 5, 101)])
    p2 = np.concatenate([prefix, _prompt(cfg, 6, 102)])
    p3 = np.concatenate([prefix, _prompt(cfg, 4, 103)])
    solo1 = model.generate(p1[None], max_new_tokens=6, **GEO)[0]
    solo2 = model.generate(p2[None], max_new_tokens=5, **GEO)[0]
    solo3 = model.generate(p3[None], max_new_tokens=6, do_sample=True,
                           temperature=0.8, seed=9, **GEO)[0]

    h1 = router.submit(p1, max_new_tokens=6, prefer="a")
    np.testing.assert_array_equal(h1.result(timeout=300), solo1)
    assert h1.cache_hit_tokens == 0          # cold: the pool was empty

    h2 = router.submit(p2, max_new_tokens=5, prefer="a")
    h3 = router.submit(p3, max_new_tokens=6, do_sample=True,
                       temperature=0.8, seed=9, prefer="a")
    np.testing.assert_array_equal(h2.result(timeout=300), solo2)
    np.testing.assert_array_equal(h3.result(timeout=300), solo3)
    assert h2.cache_hit_tokens == 16         # both full prefix blocks
    assert h3.cache_hit_tokens == 16
    snap = a.snapshot()
    assert snap["prefix_hit_tokens"] >= 32
    assert snap["prefix_cache"]["blocks_in_use"] >= 2
    assert snap["prefix_cache"]["hit_rate"] > 0


def test_pooled_engine_holds_compile_budget(lm, fleet):
    """Hits, misses and block stores all rode ONE prefill program: the
    pooled replica sits exactly at #buckets + 1 compiled programs after
    the traffic above."""
    router, a, b = fleet
    cc = a.engine.cache_stats()
    assert cc["prefill"]["compiles"] == len(a.engine.prefill_buckets) == 1
    assert cc["decode"]["compiles"] == 1
    assert len(cc["prefill"]["signatures"]) == 1   # one shape, reused


def test_router_affinity_places_warm_replica(lm, fleet):
    """Equal load, warm blocks on "a": the shared-prefix request must
    land on "a" (prefix-affinity scoring), and a disjoint prompt on the
    emptier scorer without error."""
    model, cfg = lm
    router, a, b = fleet
    prefix = _prompt(cfg, 16, 100)           # warm on a from the test above
    p = np.concatenate([prefix, _prompt(cfg, 5, 104)])
    assert a.engine.pool.match(p) == 16 and b.engine.pool.match(p) == 0
    h = router.submit(p, max_new_tokens=2)
    h.result(timeout=300)
    assert h.replica == "a"
    assert h.cache_hit_tokens == 16


def test_fleet_crash_reroutes_and_tokens_identical(lm, fleet):
    """LAST fleet test (kills "b"): a seeded in-flight request whose
    replica dies mid-stream reroutes to the survivor and produces the
    EXACT solo tokens; the survivor does not recompile."""
    model, cfg = lm
    router, a, b = fleet
    p = _prompt(cfg, 12, 110)
    solo = model.generate(p[None], max_new_tokens=20, do_sample=True,
                          temperature=0.9, seed=77, **GEO)[0]
    before = a.engine.cache_stats()
    h = router.submit(p, max_new_tokens=20, do_sample=True,
                      temperature=0.9, seed=77, prefer="b")
    # hard kill, no drain: whatever b held must reroute, not drop
    b.shutdown(drain=False, timeout=60)
    out = h.result(timeout=300)
    np.testing.assert_array_equal(out, solo)
    assert h.reroutes >= 1 and h.replica == "a"
    assert router.replicas()["b"] == "dead"
    assert router.snapshot()["requests_rerouted"] >= 1
    after = a.engine.cache_stats()
    assert after["prefill"]["compiles"] == before["prefill"]["compiles"]
    assert after["decode"]["compiles"] == before["decode"]["compiles"]
    # dead replica out of rotation: placement still works
    out2 = router.submit(p, max_new_tokens=3).result(timeout=300)
    assert out2.shape[0] == 3


# ------------------------------------------------------- device-free units
class _StubPool:
    block_tokens = 4

    def __init__(self, matched=0):
        self.matched = matched

    def match(self, prompt):
        return min(self.matched, len(prompt))

    def match_digests(self, digests):
        return min(self.matched, len(digests) * self.block_tokens)


class _StubEngine:
    def __init__(self, active, slots, pool):
        self.active_count = active
        self.slots = slots
        self.pool = pool


class _StubScheduler:
    def __init__(self, depth, cap):
        self.depth = depth
        self.max_queue_depth = cap


class _StubHandle:
    def __init__(self, outcome):
        self.outcome = outcome  # np array to return, or exception to raise
        self.cache_hit_tokens = 0
        self.ttft_s = 0.001

    def result(self, timeout=None):
        if isinstance(self.outcome, BaseException):
            raise self.outcome
        return self.outcome

    def stream(self):
        for t in self.result():
            yield int(t)


class _StubServer:
    """Just enough surface for ReplicaRouter: live load fields +
    submit()/start()/shutdown()."""

    def __init__(self, active=0, depth=0, slots=4, cap=8, matched=0,
                 submit_error=None, outcomes=None):
        self.engine = _StubEngine(active, slots, _StubPool(matched))
        self.scheduler = _StubScheduler(depth, cap)
        self.submit_error = submit_error
        self.outcomes = list(outcomes or [])
        self.submitted = []
        self.shutdowns = []

    def start(self):
        return self

    def submit(self, **kw):
        if self.submit_error is not None:
            raise self.submit_error
        self.submitted.append(kw)
        out = (self.outcomes.pop(0) if self.outcomes
               else np.zeros(1, np.int32))
        return _StubHandle(out)

    def shutdown(self, drain=True, timeout=None):
        self.shutdowns.append(drain)

    def snapshot(self):
        return {"requests_completed": len(self.submitted),
                "tokens_emitted": 0, "prefix_hit_tokens": 0,
                "prefix_miss_tokens": 0}


def test_router_places_on_least_loaded():
    busy = _StubServer(active=4, slots=4, depth=6)
    idle = _StubServer(active=0, slots=4, depth=0)
    r = ReplicaRouter()
    r.add_replica(busy, "busy")
    r.add_replica(idle, "idle")
    h = r.submit(np.arange(4), max_new_tokens=2)
    assert h.replica == "idle" and len(idle.submitted) == 1


def test_router_affinity_outweighs_mild_load_skew():
    warm = _StubServer(active=1, slots=4, matched=8)
    cold = _StubServer(active=0, slots=4, matched=0)
    r = ReplicaRouter(affinity_weight=0.75)
    r.add_replica(warm, "warm")
    r.add_replica(cold, "cold")
    h = r.submit(np.arange(8), max_new_tokens=2)   # fully warm prompt
    assert h.replica == "warm"
    # ...but a hot replica's queue eventually outweighs its warm cache
    warm.engine.active_count = 4
    warm.scheduler.depth = 8
    h2 = r.submit(np.arange(8), max_new_tokens=2)
    assert h2.replica == "cold"


def test_router_queuefull_fails_over_then_propagates():
    full_a = _StubServer(submit_error=QueueFull("a full"))
    ok_b = _StubServer()
    r = ReplicaRouter()
    r.add_replica(full_a, "a")
    r.add_replica(ok_b, "b")
    assert r.submit(np.arange(4), max_new_tokens=2).replica == "b"
    ok_b.submit_error = QueueFull("b full")
    with pytest.raises(QueueFull):           # every replica at depth
        r.submit(np.arange(4), max_new_tokens=2)
    # ...and QueueFull stays a ConnectionError: RetryPolicy retries it
    calls = {"n": 0}

    def submit_retry():
        calls["n"] += 1
        if calls["n"] == 2:
            ok_b.submit_error = None
        return r.submit(np.arange(4), max_new_tokens=2)

    h = RetryPolicy(max_attempts=4, base_delay=0.01).call(submit_retry)
    assert h.replica == "b" and calls["n"] >= 2


def test_router_drain_reroutes_new_traffic():
    a = _StubServer()
    b = _StubServer()
    r = ReplicaRouter()
    r.add_replica(a, "a")
    r.add_replica(b, "b")
    assert r.submit(np.arange(4), max_new_tokens=2).replica == "a"
    r.drain("a", timeout=10)
    assert a.shutdowns == [True]             # graceful: backlog finishes
    assert r.replicas()["a"] == "dead"
    for _ in range(3):                       # placement never returns to a
        assert r.submit(np.arange(4), max_new_tokens=2).replica == "b"
    r.drain("b", timeout=10)
    with pytest.raises(NoReplicasAvailable):
        r.submit(np.arange(4), max_new_tokens=2)


def test_router_dead_replica_resubmits_to_survivor():
    tokens = np.asarray([5, 6, 7], np.int32)
    dying = _StubServer(outcomes=[SchedulerClosed("crashed")])
    healthy = _StubServer(outcomes=[tokens])
    r = ReplicaRouter()
    r.add_replica(dying, "dying")
    r.add_replica(healthy, "healthy")
    h = r.submit(np.arange(4), max_new_tokens=3, prefer="dying")
    np.testing.assert_array_equal(h.result(timeout=5), tokens)
    assert h.reroutes == 1 and h.replica == "healthy"
    assert r.replicas()["dying"] == "dead"
    # reroute budget bounds the loop: a fleet of corpses raises
    r2 = ReplicaRouter(max_reroutes=1)
    r2.add_replica(_StubServer(
        outcomes=[SchedulerClosed("x"), SchedulerClosed("x")]), "only")
    h2 = r2.submit(np.arange(4), max_new_tokens=3)
    with pytest.raises(SchedulerClosed):
        h2.result(timeout=5)


def test_router_reroute_is_single_flight_across_consumers():
    """Two threads blocked on one RouterHandle observing the same dead
    inner handle must trigger exactly ONE resubmission (the loser waits
    for the winner's placement and picks up its handle)."""
    import threading

    tokens = np.asarray([3, 4], np.int32)
    dying = _StubServer(outcomes=[SchedulerClosed("crashed")])
    healthy = _StubServer(outcomes=[tokens, tokens])
    r = ReplicaRouter()
    r.add_replica(dying, "dying")
    r.add_replica(healthy, "healthy")
    h = r.submit(np.arange(4), max_new_tokens=2, prefer="dying")
    got, errs = [], []

    def consume():
        try:
            got.append(h.result(timeout=10))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=consume) for _ in range(2)]
    [t.start() for t in ts]
    [t.join(timeout=30) for t in ts]
    assert not errs and len(got) == 2
    np.testing.assert_array_equal(got[0], tokens)
    np.testing.assert_array_equal(got[1], tokens)
    assert len(healthy.submitted) == 1       # ONE resubmission, not two
    assert h.reroutes == 1


def test_router_all_replicas_closed_raises_retryable():
    """Every candidate rejecting with SchedulerClosed (a fleet-wide
    shutdown race) must surface as retryable NoReplicasAvailable — not
    the non-retryable SchedulerClosed — and mark the corpses DEAD."""
    r = ReplicaRouter()
    r.add_replica(_StubServer(submit_error=SchedulerClosed("gone")), "x")
    r.add_replica(_StubServer(submit_error=SchedulerClosed("gone")), "y")
    with pytest.raises(NoReplicasAvailable):
        r.submit(np.arange(4), max_new_tokens=2)
    assert set(r.replicas().values()) == {"dead"}


def test_prefix_cache_zero_budget_means_off(lm):
    """A 0-byte budget spells "disabled" (config convention), never a
    one-block pool on the slower pooled program."""
    model, _ = lm
    srv = InferenceServer(model, slots=1, prefix_cache=0, **GEO)
    assert srv.engine.pool is None
    srv2 = InferenceServer(model, slots=1, prefix_cache=0.0, **GEO)
    assert srv2.engine.pool is None


def test_router_assigns_seed_to_unseeded_sampled():
    """The reroute-replay guarantee: an unseeded sampled request gets a
    concrete seed at the front door, so a resubmission reuses it."""
    a = _StubServer()
    r = ReplicaRouter()
    r.add_replica(a, "a")
    r.submit(np.arange(4), max_new_tokens=2, do_sample=True)
    assert a.submitted[0]["seed"] is not None
    r.submit(np.arange(4), max_new_tokens=2)          # greedy: no seed
    assert a.submitted[1]["seed"] is None


# ----------------------------------------------------------- block pool
class _SpecModel:
    def cache_spec(self):
        return {"num_layers": 2, "num_kv_heads": 2, "head_dim": 4,
                "max_length": 64, "dtype": "float32"}


def _commit_tokens(pool, toks, matched=None):
    """Host-side store of a prompt's full blocks (the engine does this
    around its fused dispatch)."""
    hit = pool.lookup(toks)
    m = hit.tokens if matched is None else matched
    if m != hit.tokens:
        hit = pool.trim(hit, m)
    plan = pool.plan_store(toks, m)
    pool.commit(hit, plan, pool.tensors)
    return hit, plan


def test_block_pool_hash_chain_match():
    pool = BlockPool(_SpecModel(), block_tokens=4, max_bytes=1 << 20)
    toks = np.arange(14, dtype=np.int32)     # 3 full blocks + tail of 2
    assert pool.match(toks) == 0
    _commit_tokens(pool, toks)
    assert pool.match(toks) == 12
    # same prefix, divergent third block: chain stops at 2 blocks
    other = toks.copy()
    other[9] = 99
    assert pool.match(other) == 8
    # the WHOLE prompt never matches: the last token must be recomputed
    exact = np.arange(12, dtype=np.int32)
    assert pool.match(exact) == 8
    # a matched read plan points the padded tail at the dump row 0
    hit = pool.lookup(toks)
    assert hit.tokens == 12
    assert (hit.read_idx[:3] > 0).all() and (hit.read_idx[3:] == 0).all()
    plan = pool.plan_store(toks, hit.tokens)
    assert not plan.pending                  # nothing new to store
    pool.commit(hit, plan, pool.tensors)
    s = pool.stats()
    assert s["blocks_in_use"] == 3 and s["hit_tokens"] >= 12
    assert 0 < s["occupancy"] <= 1 and s["hit_rate"] > 0


def test_block_pool_lru_eviction_and_pinning():
    spec = _SpecModel()
    probe = BlockPool(spec, block_tokens=4, max_bytes=1 << 20)
    pool = BlockPool(spec, block_tokens=4,
                     max_bytes=4 * probe.block_bytes)   # 4 usable rows
    assert pool.num_blocks == 5              # + reserved dump row
    a = np.arange(0, 9, dtype=np.int32)      # 2 full blocks
    b = np.arange(100, 109, dtype=np.int32)  # 2 full blocks
    _commit_tokens(pool, a)
    _commit_tokens(pool, b)
    assert pool.stats()["blocks_in_use"] == 4            # pool full
    c = np.arange(200, 209, dtype=np.int32)
    _commit_tokens(pool, c)                  # forces eviction, LRU = a
    s = pool.stats()
    assert s["blocks_evicted"] == 2 and s["blocks_in_use"] == 4
    assert pool.match(a) == 0 and pool.match(b) == 8 and pool.match(c) == 8
    # pinned entries survive eviction pressure: hold b, push d through
    hit_b = pool.lookup(b)
    d = np.arange(300, 309, dtype=np.int32)
    hit_d = pool.lookup(d)                   # miss (0 matched), no pins
    plan_d = pool.plan_store(d, 0)
    assert len(plan_d.pending) <= 2          # c's rows (LRU, unpinned)...
    pool.commit(hit_d, plan_d, pool.tensors)
    assert pool.match(b) == 8                # ...never b's (pinned)
    pool.commit(hit_b, pool.plan_store(b, hit_b.tokens), pool.tensors)


def test_block_pool_child_blocks_protect_parents():
    """A chain's middle link never evicts from under its descendants:
    eviction takes leaves first (children == 0)."""
    spec = _SpecModel()
    probe = BlockPool(spec, block_tokens=4, max_bytes=1 << 20)
    pool = BlockPool(spec, block_tokens=4,
                     max_bytes=3 * probe.block_bytes)
    chain = np.arange(13, dtype=np.int32)    # 3 full blocks, one chain
    _commit_tokens(pool, chain)
    assert pool.stats()["blocks_in_use"] == 3
    x = np.arange(500, 505, dtype=np.int32)  # 1 block, needs 1 eviction
    _commit_tokens(pool, x)
    # the leaf (block 3 of the chain) went; the chain still matches 8
    assert pool.match(chain) == 8
    assert pool.match(x) == 4


def test_block_pool_reset_and_abort():
    pool = BlockPool(_SpecModel(), block_tokens=4, max_bytes=1 << 20)
    toks = np.arange(9, dtype=np.int32)
    hit = pool.lookup(toks)
    plan = pool.plan_store(toks, 0)
    assert len(plan.pending) == 2
    free_before = len(pool._free)
    pool.abort(hit, plan)                    # dispatch failed: rows back
    assert len(pool._free) == free_before + 2
    assert pool.match(toks) == 0
    _commit_tokens(pool, toks)
    assert pool.match(toks) == 8
    pool.reset()                             # crash recovery wipes blocks
    assert pool.match(toks) == 0
    assert pool.stats()["blocks_in_use"] == 0
    assert pool.stats()["blocks_stored"] == 2   # cumulative survives


def test_block_pool_abort_without_plan_releases_pins():
    """tpu_lint R9 regression: a failure between lookup and plan_store
    has pins but no plan yet — abort(hit) alone must release them so
    the blocks stay evictable."""
    pool = BlockPool(_SpecModel(), block_tokens=4, max_bytes=1 << 20)
    toks = np.arange(14, dtype=np.int32)
    _commit_tokens(pool, toks)
    hit = pool.lookup(toks)
    assert hit.tokens == 12
    assert pool.stats()["blocks_pinned"] == 3
    pool.abort(hit)                          # no plan: pins only
    assert pool.stats()["blocks_pinned"] == 0


def test_plan_hit_failure_path_releases_pins(monkeypatch):
    """tpu_lint R9 regression (the self-application fix): a raise out
    of plan_store inside `_plan_hit` must abort the lookup's pins —
    pre-fix they leaked forever, making the pool unevictable."""
    from types import SimpleNamespace

    from paddle_tpu.serving.engine import ContinuousBatchingEngine

    pool = BlockPool(_SpecModel(), block_tokens=4, max_bytes=1 << 20)
    toks = np.arange(14, dtype=np.int32)
    _commit_tokens(pool, toks)

    def boom(*a, **k):
        raise RuntimeError("planner down")

    monkeypatch.setattr(pool, "plan_store", boom)
    fake = SimpleNamespace(pool=pool, max_length=64,
                           bucket_for_prompt=lambda n: 32)
    with pytest.raises(RuntimeError, match="planner down"):
        ContinuousBatchingEngine._plan_hit(fake, toks,
                                           int(toks.shape[0]))
    assert pool.stats()["blocks_pinned"] == 0


def test_gather_scatter_cache_blocks_roundtrip():
    """The paged-pool primitives (generation.py): scatter a cache row
    into pool blocks, gather it back at the same indices — identical;
    dump-row writes never corrupt real blocks. Eager: no compile."""
    import jax.numpy as jnp
    from paddle_tpu.models.kv_cache import (gather_cache_blocks,
                                            scatter_cache_blocks)

    rng = np.random.default_rng(0)
    pool = tuple((jnp.asarray(rng.normal(size=(6, 4, 2, 3)), jnp.float32),
                  jnp.asarray(rng.normal(size=(6, 4, 2, 3)), jnp.float32))
                 for _ in range(2))
    row = tuple((jnp.asarray(rng.normal(size=(1, 16, 2, 3)), jnp.float32),
                 jnp.asarray(rng.normal(size=(1, 16, 2, 3)), jnp.float32))
                for _ in range(2))
    idx = jnp.asarray([2, 5, 0, 0], jnp.int32)   # blocks 3/4 -> dump row
    stored = scatter_cache_blocks(pool, row, idx)
    back = gather_cache_blocks(stored, idx, 16)
    for (bk, bv), (rk, rv) in zip(back, row):
        np.testing.assert_array_equal(np.asarray(bk)[0, :8],
                                      np.asarray(rk)[0, :8])
    for li in (0, 1):                        # untouched rows keep values
        for j in (1, 3, 4):
            np.testing.assert_array_equal(np.asarray(stored[li][0])[j],
                                          np.asarray(pool[li][0])[j])
    short = gather_cache_blocks(stored, idx, 20)  # padded past n*bs
    assert np.asarray(short[0][0]).shape == (1, 20, 2, 3)
    assert (np.asarray(short[0][0])[0, 16:] == 0).all()


def test_metrics_snapshot_prefix_fields():
    m = ServingMetrics(slots=2)
    m.inc("prefix_hit_tokens", 30)
    m.inc("prefix_miss_tokens", 10)
    snap = m.snapshot(prefix_cache={"blocks_in_use": 3, "occupancy": 0.5})
    assert snap["prefix_hit_tokens"] == 30
    assert snap["prefix_miss_tokens"] == 10
    assert snap["prefix_hit_rate"] == 0.75
    assert snap["prefix_cache"]["blocks_in_use"] == 3
    assert "prefix_cache" not in ServingMetrics(slots=1).snapshot()


# ------------------------------------------- scheduler expiry regression
def test_shutdown_tail_counts_queued_expiry_as_expired(lm):
    """Regression (satellite): a request whose deadline lapsed while
    QUEUED, caught by a non-drain shutdown racing the expiry sweep, must
    expire (TimeoutError + requests_expired) — not vanish into
    requests_failed as a generic SchedulerClosed."""
    from paddle_tpu.distributed.resilience import Deadline

    model, _ = lm
    srv = InferenceServer(model, slots=1, **GEO)   # worker never started
    expired_req = Request(prompt=np.arange(4), deadline=Deadline(0.0))
    expired_req.handle = RequestHandle(expired_req)
    live_req = Request(prompt=np.arange(4), deadline=None)
    live_req.handle = RequestHandle(live_req)
    srv.scheduler.submit(expired_req)
    srv.scheduler.submit(live_req)
    time.sleep(0.005)
    srv._fail_backlog()
    assert srv.metrics.requests_expired == 1
    assert srv.metrics.requests_failed == 1
    with pytest.raises(TimeoutError, match="expired in queue"):
        expired_req.handle.result(timeout=1)
    with pytest.raises(SchedulerClosed):
        live_req.handle.result(timeout=1)


def test_queued_expiry_still_counted_in_live_loop(lm):
    """The pre-existing live path keeps working: deadline expiry during
    normal service produces TimeoutError + the expired counter."""
    model, cfg = lm
    srv = InferenceServer(model, slots=1, **GEO)
    # pretend every slot is busy so nothing admits and the queued
    # request can only expire (device-free: no dispatch, no compile)
    srv.engine.free_slots = lambda: []
    h = srv.submit(_prompt(cfg, 4), max_new_tokens=2, deadline=0.01)
    with pytest.raises(TimeoutError, match="expired in queue"):
        h.result(timeout=30)
    assert srv.metrics.requests_expired == 1
    srv.shutdown(drain=False, timeout=30)


# ------------------------------------------------------------------- slow
@pytest.mark.slow
def test_serve_bench_fleet_crash_cli():
    """The robustness_gate --fleet command end-to-end: 2 replicas,
    prefix-heavy trace, one hard-killed mid-window — exit 0 (all
    requests recovered, token parity held, zero steady recompiles)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "serve_bench.py"),
         "--check", "--replicas", "2", "--prefix-cache-mb", "4",
         "--prefix-tokens", "24", "--crash-replica", "--verify", "3"],
        capture_output=True, text=True, timeout=540, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(
        [l for l in proc.stdout.splitlines() if l.startswith('{"')][-1])
    ex = rec["extra"]
    assert ex["failed"] == 0
    assert ex["verify_failures"] == 0
    assert ex["cache_hit_rate"] > 0
    assert ex["steady_state_recompiles"] == 0
    assert ex["crashed_replica"] == "r1" and ex["live_replicas"] == 1
