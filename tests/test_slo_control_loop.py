"""The SLO control loop (PR 16): burn-rate-driven autoscaler,
per-tenant token-bucket admission + deficit-round-robin fair queueing,
and the abuse-proofing contract (rate-limit rejects book ZERO tenant
failures, so an abusive tenant cannot buy fleet capacity).

Everything here runs on stubs — no model build, no rpc world, injected
clocks throughout — so the suite stays inside the tier-1 time budget;
the real 2-process adversarial trace is ``tools/serve_bench.py
--fairness`` (robustness_gate --fairness).
"""
import json
import os
import sys
import threading

import numpy as np
import pytest

from paddle_tpu.observability import flight
from paddle_tpu.serving import (Autoscaler, Backpressure, FifoScheduler,
                                InferenceServer, Overloaded, QueueFull,
                                RateLimited, ReplicaRouter, Request,
                                TokenBucket)
from paddle_tpu.serving.scheduler import BASE_TENANT

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


@pytest.fixture(autouse=True)
def _restore_flight_dir():
    rec = flight.flight_recorder()
    saved = rec.dump_dir
    yield
    flight.configure(dump_dir=saved)


def _req(tenant=None, deadline=None, n=4):
    return Request(prompt=np.zeros(n, np.int32), max_new_tokens=4,
                   adapter_id=tenant, deadline=deadline)


# ------------------------------------------------------------ TokenBucket
def test_token_bucket_burst_then_rate():
    b = TokenBucket(rate=2.0, burst=3.0)
    now = 100.0
    assert all(b.try_take(now) for _ in range(3))   # burst capacity
    assert not b.try_take(now)                      # empty
    assert b.retry_after() == pytest.approx(0.5)    # 1 token at 2/s
    assert b.try_take(now + 0.5)                    # refilled exactly 1
    assert not b.try_take(now + 0.5)
    # refill caps at burst: a long quiet period doesn't bank credit
    assert b.level(now + 1000.0) == pytest.approx(3.0)


def test_rate_limited_is_retryable_backpressure():
    e = RateLimited("over", tenant="t1", retry_after=0.25)
    assert isinstance(e, Backpressure)
    assert isinstance(e, ConnectionError)   # RetryPolicy-visible
    assert e.tenant == "t1" and e.retry_after == pytest.approx(0.25)


# ----------------------------------------------- scheduler rate limiting
def test_scheduler_defaults_off_no_buckets():
    s = FifoScheduler(max_queue_depth=4)
    for _ in range(4):
        s.submit(_req(tenant="loud"))   # unlimited without knobs
    assert s.bucket_levels() == {}
    with pytest.raises(QueueFull):      # depth cap still the only gate
        s.submit(_req(tenant="loud"))


def test_scheduler_per_tenant_bucket_rejects_and_refills():
    clock = [0.0]
    s = FifoScheduler(max_queue_depth=64, tenant_rate=1.0,
                      tenant_burst=2.0, clock=lambda: clock[0])
    s.submit(_req(tenant="t"))
    s.submit(_req(tenant="t"))
    with pytest.raises(RateLimited) as ei:
        s.submit(_req(tenant="t"))
    assert ei.value.tenant == "t"
    assert ei.value.retry_after == pytest.approx(1.0)
    s.submit(_req(tenant="other"))      # other tenants: own buckets
    clock[0] = 1.0
    s.submit(_req(tenant="t"))          # refilled
    levels = s.bucket_levels()
    assert levels["t"]["rate"] == 1.0 and levels["t"]["burst"] == 2.0
    assert levels["t"]["tokens"] == pytest.approx(0.0)


def test_scheduler_tenant_limits_override_and_base_tenant():
    clock = [0.0]
    s = FifoScheduler(max_queue_depth=64,
                      tenant_limits={"abuser": (1.0, 1.0)},
                      clock=lambda: clock[0])
    s.submit(_req(tenant="abuser"))
    with pytest.raises(RateLimited):
        s.submit(_req(tenant="abuser"))
    for _ in range(8):
        s.submit(_req())                # base/unlisted: unlimited
    assert BASE_TENANT not in s.bucket_levels()


def test_requeue_bypasses_the_bucket():
    """A crash-recovery requeue re-admits work the tenant ALREADY paid
    admission for — charging the bucket again would double-bill."""
    clock = [0.0]
    s = FifoScheduler(max_queue_depth=64,
                      tenant_limits={"t": (1.0, 1.0)},
                      clock=lambda: clock[0])
    r = _req(tenant="t")
    s.submit(r)
    taken, _ = s.take(1)
    assert taken == [r]
    s.requeue(r)                        # no RateLimited despite empty
    assert s.take(1)[0] == [r]          # bucket


# --------------------------------------------------- DRR fair queueing
def test_fair_take_round_robins_under_10x_tenant():
    s = FifoScheduler(max_queue_depth=64, max_prefills_per_step=8,
                      fair_queueing=True)
    flood = [_req(tenant="abuser") for _ in range(20)]
    quiet = [_req(tenant="alice"), _req(tenant="bob")]
    for r in flood[:10]:
        s.submit(r)
    for r in quiet:
        s.submit(r)
    for r in flood[10:]:
        s.submit(r)
    got, _ = s.take(4)
    # one service quantum per tenant per round: both quiet tenants are
    # served in the FIRST budget despite 20 queued abuser requests
    # (identity checks: Request.__eq__ compares numpy prompt fields)
    assert any(r is quiet[0] for r in got)
    assert any(r is quiet[1] for r in got)
    assert [r for r in got if r.adapter_id == "abuser"] == flood[:2]


def test_fair_take_fifo_within_tenant_and_drains():
    s = FifoScheduler(max_queue_depth=64, max_prefills_per_step=4,
                      fair_queueing=True)
    a = [_req(tenant="a") for _ in range(3)]
    b = [_req(tenant="b") for _ in range(1)]
    for r in a[:2]:
        s.submit(r)
    for r in b:
        s.submit(r)
    s.submit(a[2])
    assert s.take(4)[0] == [a[0], b[0], a[1], a[2]]
    assert s.depth == 0


def test_fair_weights_bias_the_quantum():
    s = FifoScheduler(max_queue_depth=64, max_prefills_per_step=6,
                      fair_queueing=True,
                      fair_weights={"gold": 2.0, "bronze": 1.0})
    gold = [_req(tenant="gold") for _ in range(4)]
    bronze = [_req(tenant="bronze") for _ in range(4)]
    for g, b in zip(gold, bronze):
        s.submit(g)
        s.submit(b)
    got, _ = s.take(6)
    assert len([r for r in got if r.adapter_id == "gold"]) == 4
    assert len([r for r in got if r.adapter_id == "bronze"]) == 2


def test_fair_take_skips_expired_without_spending_deficit():
    clock = [0.0]
    s = FifoScheduler(max_queue_depth=64, max_prefills_per_step=4,
                      fair_queueing=True)
    from paddle_tpu.distributed.resilience import Deadline

    dead = _req(tenant="a", deadline=Deadline(0.0))
    live = _req(tenant="a")
    other = _req(tenant="b")
    s.submit(dead)
    s.submit(live)
    s.submit(other)
    got, exp = s.take(3)
    assert all(r is not dead for r in got)
    assert any(r is live for r in got) and any(r is other for r in got)
    assert len(exp) == 1 and exp[0] is dead     # handed back to fail


def test_fair_off_is_strict_fifo():
    """Defaults-off bit-identical: without fair_queueing the take order
    is EXACTLY the PR 15 FIFO regardless of tenant mix."""
    s = FifoScheduler(max_queue_depth=64, max_prefills_per_step=8)
    reqs = [_req(tenant=t) for t in
            ("a", "a", "a", "b", "a", None, "a", "b")]
    for r in reqs:
        s.submit(r)
    assert s.take(8)[0] == reqs


# -------------------------------------- server path (stubbed, no model)
class _KnownStore:
    """Submit-path validation stub: every adapter name is registered."""

    def known(self, name):
        return True

    def resident(self, name):
        return False    # no adapter-affinity bonus in scoring


class _StubEngine:
    active_count = 0
    slots = 4
    pool = None
    store = None

    def validate(self, n, m):
        pass


def _stub_server(**sched_kw):
    """A real InferenceServer instance driving a REAL FifoScheduler
    through the real ``submit()`` path — engine and start() stubbed so
    no model is built and no loop thread spawns."""
    srv = object.__new__(InferenceServer)
    from paddle_tpu.serving.metrics import ServingMetrics

    srv.engine = _StubEngine()
    srv.engine.store = _KnownStore()
    srv.scheduler = FifoScheduler(**sched_kw)
    srv.metrics = ServingMetrics(slots=4)
    srv._cv = threading.Condition()
    srv.start = lambda: srv
    return srv


def test_server_submit_rate_limited_counts_not_tenant_failure(tmp_path):
    """The abuse-proofing contract end to end at the server door: a
    RateLimited reject increments its own counter, notes a
    tenant-labeled flight event, and books NO per-tenant failure — so
    the SLO tracker sees zero burn from throttled abuse."""
    flight.configure(dump_dir=str(tmp_path))
    clock = [0.0]
    srv = _stub_server(max_queue_depth=8,
                       tenant_limits={"abuser": (1.0, 1.0)},
                       clock=lambda: clock[0])
    srv.submit(np.zeros(4, np.int32), max_new_tokens=4,
               adapter_id="abuser")
    with pytest.raises(RateLimited):
        srv.submit(np.zeros(4, np.int32), max_new_tokens=4,
                   adapter_id="abuser")
    snap = srv.metrics.snapshot()
    assert snap["requests_rate_limited"] == 1
    assert snap["requests_shed"] == 0
    # NO failure booked against the tenant (shed/expired would book)
    assert snap.get("per_adapter", {}).get("abuser", {}) \
                                      .get("failures", 0) == 0
    ev = [e for e in flight.flight_recorder().events()
          if e.get("kind") == "rate_limited"]
    assert ev and ev[-1]["tenant"] == "abuser"
    assert ev[-1].get("corr")       # listable as a trace lane
    # the statusz token_buckets block reads straight from here
    assert srv.scheduler.bucket_levels()["abuser"]["rate"] == 1.0


def test_rate_limited_flight_event_lists_in_trace_view(tmp_path):
    flight.configure(dump_dir=str(tmp_path))
    clock = [0.0]
    srv = _stub_server(max_queue_depth=8,
                       tenant_limits={"abuser": (1.0, 1.0)},
                       clock=lambda: clock[0])
    srv.submit(np.zeros(4, np.int32), max_new_tokens=4,
               adapter_id="abuser")
    with pytest.raises(RateLimited):
        srv.submit(np.zeros(4, np.int32), max_new_tokens=4,
                   adapter_id="abuser")
    path = flight.dump("test_rate_limit_dump")
    from trace_view import list_correlations, load_spans

    spans, _ = load_spans(path)
    rl = [s for s in spans if s["name"] == "event:rate_limited"]
    assert rl and rl[0]["tags"]["tenant"] == "abuser"
    corrs = {e["corr"] for e in list_correlations(spans)}
    assert rl[0]["corr"] in corrs


# ----------------------------------------------------------- autoscaler
class _StubSched:
    depth = 0
    max_queue_depth = 8

    def __init__(self, buckets=None):
        self._buckets = buckets or {}

    def bucket_levels(self):
        return dict(self._buckets)


class _StubServer:
    def __init__(self, buckets=None):
        self.engine = _StubEngine()
        self.scheduler = _StubSched(buckets)
        self.started = False
        self.shutdowns = []

    def start(self):
        self.started = True
        return self

    def shutdown(self, drain=True, timeout=None):
        self.shutdowns.append(drain)

    def snapshot(self):
        return {"requests_completed": 0, "tokens_emitted": 0,
                "prefix_hit_tokens": 0, "prefix_miss_tokens": 0}

    def statusz(self):
        return {}


def _burning(tenant="spike", burn=5.0):
    return {"tenants": {tenant: {
        "burn_slow": burn, "burn_fast": 2 * burn, "slow_breached": True,
        "fast_breached": False, "alerting": False,
        "window_slow": {"total": 10}, "window_fast": {"total": 10}}}}


def _quiet():
    return {"tenants": {"spike": {
        "burn_slow": 0.0, "burn_fast": 0.0, "slow_breached": False,
        "fast_breached": False, "alerting": False,
        "window_slow": {"total": 10}, "window_fast": {"total": 10}}}}


def _fleet(n=1, spawn_log=None, **kw):
    router = ReplicaRouter([_StubServer() for _ in range(n)])
    clock = [0.0]

    def spawn(name):
        if spawn_log is not None:
            spawn_log.append(name)
        return _StubServer()

    kw.setdefault("sustain_ticks", 2)
    kw.setdefault("cooldown_s", 60.0)
    auto = Autoscaler(router, spawn, clock=lambda: clock[0], **kw)
    return router, auto, clock


def test_scale_out_is_edge_triggered_on_sustained_burn():
    spawned = []
    router, auto, clock = _fleet(1, spawn_log=spawned, max_replicas=3)
    router.slo_report = _burning
    assert auto.tick() is None          # 1 hot tick: sustaining, no act
    d = auto.tick()
    assert d["action"] == "scale_out" and d["tenant"] == "spike"
    assert d["burn_slow"] == pytest.approx(5.0)
    assert spawned == ["auto-1"]
    assert router.replicas()["auto-1"] == "active"
    assert auto.scale_outs == 1


def test_one_window_spike_does_not_scale():
    """Hysteresis: burn must SUSTAIN for sustain_ticks consecutive
    evaluations — a single hot window resets on the next quiet one."""
    router, auto, clock = _fleet(1, max_replicas=3)
    reports = [_burning(), _quiet(), _burning(), _quiet()]
    router.slo_report = lambda: reports.pop(0)
    for _ in range(4):
        assert auto.tick() is None
    assert auto.scale_outs == 0


def test_cooldown_suppresses_flap():
    router, auto, clock = _fleet(1, max_replicas=4, cooldown_s=60.0)
    router.slo_report = _burning
    auto.tick()
    assert auto.tick()["action"] == "scale_out"
    clock[0] = 59.0                     # still cooling: burn keeps
    for _ in range(5):                  # sustaining but nothing fires
        assert auto.tick() is None
    assert auto.scale_outs == 1
    clock[0] = 121.0                    # cooldown over: the sustain
    d = auto.tick()                     # banked while cooling fires at
    assert d["action"] == "scale_out"   # once
    assert auto.scale_outs == 2


def test_max_replicas_bounds_scale_out():
    router, auto, clock = _fleet(2, max_replicas=2)
    router.slo_report = _burning
    for _ in range(6):
        assert auto.tick() is None
    assert auto.scale_outs == 0


def test_scale_in_drains_never_kills():
    spawned = []
    router, auto, clock = _fleet(1, spawn_log=spawned, max_replicas=2,
                                 scale_in_load=0.5)
    router.slo_report = _burning
    auto.tick()
    auto.tick()
    grown = router._replicas["auto-1"].server
    router.slo_report = _quiet
    clock[0] = 100.0
    assert auto.tick() is None          # sustained headroom required
    d = auto.tick()
    assert d["action"] == "scale_in" and d["replica"] == "auto-1"
    # the LIFO victim is the autoscaler's own spawn, and it was
    # DRAINED (drain=True), never killed
    assert grown.shutdowns == [True]
    assert router.replicas()["auto-1"] == "dead"
    assert auto.scale_ins == 1


def test_min_replicas_bounds_scale_in():
    router, auto, clock = _fleet(1, min_replicas=1, max_replicas=2,
                                 scale_in_load=0.5)
    router.slo_report = _quiet
    for _ in range(6):
        assert auto.tick() is None
    assert auto.scale_ins == 0


def test_spawn_failure_is_counted_not_fatal():
    router = ReplicaRouter([_StubServer()])

    def bad_spawn(name):
        raise RuntimeError("boom")

    clock = [0.0]
    auto = Autoscaler(router, bad_spawn, sustain_ticks=1,
                      cooldown_s=0.0, max_replicas=2,
                      clock=lambda: clock[0])
    router.slo_report = _burning
    d = auto.tick()
    assert d["action"] == "scale_out_failed" and "boom" in d["error"]
    assert auto.spawn_failures == 1 and auto.scale_outs == 0
    assert list(router.replicas()) == ["replica-%d" % (
        int(list(router.replicas())[0].split("-")[1]))]  # no new member


def test_statusz_autoscaler_block_and_token_buckets():
    router = ReplicaRouter(
        [_StubServer(buckets={"abuser": {"tokens": 0.5, "rate": 1.0,
                                         "burst": 2.0}})])
    clock = [0.0]
    auto = Autoscaler(router, lambda name: _StubServer(),
                      sustain_ticks=1, cooldown_s=60.0, max_replicas=2,
                      clock=lambda: clock[0])
    router.slo_report = _burning
    auto.tick()
    block = router.statusz()["autoscaler"]
    assert block["state"] == "manual"       # no interval -> no thread
    assert block["scale_outs"] == 1
    assert block["last_decision"]["tenant"] == "spike"
    assert block["cooldown_remaining_s"] == pytest.approx(60.0)
    assert block["config"]["max_replicas"] == 2
    name = next(iter(router.replicas()))
    assert block["token_buckets"][name]["abuser"]["tokens"] == 0.5


def test_statusz_has_no_autoscaler_block_by_default():
    router = ReplicaRouter([_StubServer()])
    assert "autoscaler" not in router.statusz()


def test_router_shutdown_stops_autoscaler_thread():
    router = ReplicaRouter([_StubServer()])
    auto = Autoscaler(router, lambda name: _StubServer(),
                      interval=30.0)
    auto.start()
    assert auto._thread is not None and auto._thread.is_alive()
    router.shutdown()
    assert not auto._thread.is_alive()


def test_scale_out_dump_lists_in_trace_view(tmp_path):
    flight.configure(dump_dir=str(tmp_path))
    router, auto, clock = _fleet(1, max_replicas=2, sustain_ticks=1)
    router.slo_report = _burning
    d = auto.tick()
    assert d["action"] == "scale_out"
    dumps = [f for f in os.listdir(tmp_path) if "scale_out" in f]
    assert dumps
    from trace_view import list_correlations, load_spans

    spans, _ = load_spans(os.path.join(str(tmp_path), dumps[0]))
    lanes = {e["corr"]: e for e in list_correlations(spans)}
    assert d["corr"] in lanes           # visible in --list
    ev = [s for s in spans if s["name"] == "event:scale_out"
          and s["corr"] == d["corr"]]
    assert ev and ev[0]["tags"]["tenant"] == "spike"
    with open(os.path.join(str(tmp_path), dumps[0])) as f:
        extra = json.load(f)["extra"]
    assert extra["tenant"] == "spike"   # burn evidence rides the dump
    assert extra["burn_slow"] == pytest.approx(5.0)


# ----------------------------------------- RateLimited through the router
class _RateLimitingServer(_StubServer):
    def __init__(self, exc):
        super().__init__()
        self.engine.store = _KnownStore()   # passes the adapter filter
        self.exc = exc

    def submit(self, **kw):
        raise self.exc


def test_router_propagates_rate_limited_when_all_replicas_throttle():
    router = ReplicaRouter([
        _RateLimitingServer(RateLimited("over", tenant="t",
                                        retry_after=0.5)),
        _RateLimitingServer(RateLimited("over", tenant="t",
                                        retry_after=0.7))])
    with pytest.raises(RateLimited) as ei:
        router.submit(np.zeros(4, np.int32), max_new_tokens=4,
                      adapter_id="t")
    assert ei.value.tenant == "t"       # tenant + retry_after intact


def test_router_mixed_rate_limit_and_full_raises_queue_full():
    router = ReplicaRouter([
        _RateLimitingServer(RateLimited("over", tenant="t")),
        _RateLimitingServer(QueueFull("full"))])
    with pytest.raises(QueueFull):
        router.submit(np.zeros(4, np.int32), max_new_tokens=4)


# ----------------------------------------------------- adapter hot-swap
class _SwapStore:
    def __init__(self, fail=False):
        self.fail = fail
        self.versions = {}

    def register(self, name, state):
        if self.fail:
            raise RuntimeError("load failed")
        self.versions[name] = self.versions.get(name, 0) + 1

    def known(self, name):
        return name in self.versions


def test_register_adapter_broadcasts_to_live_replicas():
    good, bad, storeless = _StubServer(), _StubServer(), _StubServer()
    good.engine.store = _SwapStore()
    bad.engine.store = _SwapStore(fail=True)
    router = ReplicaRouter()
    router.add_replica(good, "good")
    router.add_replica(bad, "bad")
    router.add_replica(storeless, "none")
    dead = _StubServer()
    dead.engine.store = _SwapStore()
    router.add_replica(dead, "dead")
    router._mark_dead("dead", cause="test")
    out = router.register_adapter("tenantA", {"w": 1})
    assert out == {"good": True, "bad": False, "none": False}
    assert good.engine.store.versions["tenantA"] == 1
    assert dead.engine.store.versions == {}     # dead replica skipped
    # re-register = hot swap: version bumps again on the live store
    router.register_adapter("tenantA", {"w": 2})
    assert good.engine.store.versions["tenantA"] == 2


def test_hot_swap_pins_old_rows_until_stream_end():
    """The PR 9 contract the router broadcast rides end to end: a
    re-register over a PINNED row orphans it — the live stream keeps
    its rows/salt to the end, new acquires get the new version and a
    DIFFERENT salt (so no cache can serve stale weights)."""
    from paddle_tpu import lora
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    cfg = gpt_tiny(hidden_size=32, num_layers=1, num_heads=2,
                   vocab_size=64, max_position_embeddings=32,
                   hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    lora.apply_lora(model, lora.LoraConfig(rank=1, alpha=2.0))
    zero = lora.lora_state(model)
    v1 = {k: np.full(np.shape(v), 0.01, np.float32)
          for k, v in zero.items()}
    v2 = {k: np.full(np.shape(v), 0.02, np.float32)
          for k, v in zero.items()}
    store = lora.AdapterStore(model, max_loaded=3)
    store.register("t", v1)
    slot_old, salt_old = store.acquire("t", with_salt=True)
    store.register("t", v2)             # hot swap mid-stream
    slot_new, salt_new = store.acquire("t", with_salt=True)
    assert salt_new != salt_old         # version salt split the caches
    assert slot_new != slot_old         # old row still pinned, intact
    store.release(slot_old)             # stream ends -> old row frees
    store.release(slot_new)
