"""Unified telemetry: metrics registry, request-scoped tracing, flight
recorder (paddle_tpu/observability/ + the wiring through serving,
profiler, supervisor and tools/trace_view.py).

The tentpole acceptance lives here: one served request yields a single
merged chrome-trace lane spanning router submit → queue wait → admission
(bucket/prefix tags; prefill dispatch and wait inside) → one decode span
→ stream end, keyed by its correlation id, with the decode steps behind
it in the untraced lane; and a crash drill (FaultPlan engine reset) emits a
flight-recorder dump carrying that id.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.observability import (MetricsRegistry, default_registry,
                                      flight, tracing)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

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
def fleet(lm):
    from paddle_tpu.serving import InferenceServer, ReplicaRouter

    model, _ = lm
    srv = InferenceServer(model, slots=2, max_queue_depth=8,
                          max_request_retries=1, **GEO)
    router = ReplicaRouter()
    router.add_replica(srv, "r0")
    yield router, srv
    try:
        router.shutdown(drain=False, timeout=30)
    except Exception:
        pass


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n,)).astype(np.int32)


@pytest.fixture(autouse=True)
def _restore_flight_dir():
    """Tests repoint the GLOBAL flight recorder at their tmp dirs;
    later test files must get the session default back."""
    rec = flight.flight_recorder()
    saved = rec.dump_dir
    yield
    flight.configure(dump_dir=saved)


# ------------------------------------------------------------- registry
def test_registry_counters_gauges_labels():
    r = MetricsRegistry()
    assert r.inc("req", 2) == 2
    assert r.inc("req", 3) == 5
    r.inc("req", 1, replica="a")
    r.set_gauge("depth", 7, replica="a")
    snap = r.snapshot()
    assert snap["counters"]["req"] == 5
    assert snap["counters"]['req{replica="a"}'] == 1
    assert snap["gauges"]['depth{replica="a"}'] == 7


def test_registry_histogram_percentiles():
    r = MetricsRegistry()
    for v in range(100):
        r.observe("lat", v / 1000.0)
    s = r.snapshot()["histograms"]["lat"]
    assert s["count"] == 100
    assert s["p50"] == pytest.approx(0.0495, abs=0.002)
    assert s["p99"] == pytest.approx(0.099, abs=0.002)
    assert s["max"] == pytest.approx(0.099)


def test_registry_collector_absorbs_and_flattens():
    r = MetricsRegistry()
    r.register_collector(
        lambda: {"gauges": {"pool": {"occupancy": 0.5, "name": "x"}},
                 "counters": {"hits": 3}},
        labels={"server": "s0"}, name="c")
    snap = r.snapshot()
    assert snap["gauges"]['pool.occupancy{server="s0"}'] == 0.5
    assert snap["counters"]['hits{server="s0"}'] == 3
    # non-numeric leaves are dropped from the scrape
    assert not any("pool.name" in k for k in snap["gauges"])
    assert r.unregister_collector("c") == 1
    assert 'hits{server="s0"}' not in r.snapshot()["counters"]


def test_registry_weak_collector_prunes_dead_owner():
    r = MetricsRegistry()

    class Owner:
        def collect(self):
            return {"gauges": {"alive": 1}}

    o = Owner()
    r.register_collector(o.collect, name="owner")
    assert r.snapshot()["gauges"].get("alive") == 1
    del o
    import gc

    gc.collect()
    assert "alive" not in r.snapshot()["gauges"]


def test_registry_prometheus_text_format():
    r = MetricsRegistry()
    r.inc("serving.requests_completed", 4, server="s0")
    r.set_gauge("queue-depth", 2)
    for v in (0.01, 0.02, 0.03):
        r.observe("ttft", v)
    text = r.prometheus_text()
    assert "# TYPE serving_requests_completed counter" in text
    assert 'serving_requests_completed{server="s0"} 4' in text
    assert "# TYPE queue_depth gauge" in text
    assert 'ttft{quantile="0.5"}' in text
    assert "ttft_count 3" in text
    # collector errors don't break the scrape
    r.register_collector(lambda: 1 / 0, name="boom")
    assert "queue_depth 2" in r.prometheus_text()
    assert r.collector_errors >= 1


def test_default_registry_absorbs_profiler_counters():
    from paddle_tpu import profiler

    profiler.bump_counter("obs.test_counter", 5)
    snap = default_registry().snapshot()
    assert snap["counters"]["obs.test_counter"] >= 5
    assert "compile_cache.compiles" in snap["gauges"]
    json.dumps(snap)   # the whole snapshot must be JSON-able


# -------------------------------------------------------------- tracing
def test_correlation_ids_unique_and_scoped():
    a, b = tracing.new_correlation_id(), tracing.new_correlation_id()
    assert a != b and a.startswith("req-")
    assert tracing.current() is None or isinstance(tracing.current(), str)
    with tracing.correlate("corr-x"):
        assert tracing.current() == "corr-x"
        with tracing.span("inner", tag=1):
            pass
    spans = tracing.spans(corr="corr-x", name="inner")
    assert len(spans) == 1 and spans[0]["tags"] == {"tag": 1}


def test_trace_buffer_bounded_counts_drops():
    from paddle_tpu.observability.tracing import _TraceBuffer

    buf = _TraceBuffer(capacity=4)
    # swap in a tiny buffer so the bound is testable without 65k appends
    saved = tracing._buf
    tracing._buf = buf
    try:
        for i in range(10):
            tracing.record_event(f"e{i}")
        st = tracing.stats()
        assert st["buffered"] == 4 and st["dropped"] == 6
        assert st["recorded"] == 10
        assert [s["name"] for s in tracing.spans()] == [
            "e6", "e7", "e8", "e9"]
    finally:
        tracing._buf = saved


def test_tracing_disabled_records_nothing():
    tracing.enable(False)
    try:
        before = tracing.stats()["recorded"]
        tracing.record_event("nope")
        with tracing.span("nope2"):
            pass
        assert tracing.stats()["recorded"] == before
    finally:
        tracing.enable(True)


def test_chrome_trace_one_lane_per_correlation():
    recs = [
        {"name": "a", "corr": "c1", "t0": 1.0, "t1": 2.0, "tags": {}},
        {"name": "b", "corr": "c1", "t0": 2.0, "t1": 2.0, "tags": {}},
        {"name": "c", "corr": "c2", "t0": 1.5, "t1": 1.8, "tags": {}},
        {"name": "d", "corr": None, "t0": 0.0, "t1": 0.5, "tags": {}},
    ]
    ct = tracing.chrome_trace(span_records=recs)
    data = [e for e in ct["traceEvents"] if e["ph"] in ("X", "i")]
    lanes = {e["args"].get("correlation_id", "untraced"): e["tid"]
             for e in data}
    assert lanes["c1"] != lanes["c2"] != lanes["untraced"]
    assert lanes["untraced"] == 0
    names = {e["args"]["name"] for e in ct["traceEvents"]
             if e.get("name") == "thread_name"}
    assert {"c1", "c2", "untraced"} <= names
    # durations in microseconds; instants use ph "i"
    a = next(e for e in data if e["name"] == "a")
    assert a["ph"] == "X" and a["dur"] == pytest.approx(1e6)
    b = next(e for e in data if e["name"] == "b")
    assert b["ph"] == "i"


def test_export_chrome_trace_writes_file(tmp_path):
    with tracing.correlate(tracing.new_correlation_id("exp")) as corr:
        with tracing.span("phase"):
            pass
    path = tracing.export_chrome_trace(
        str(tmp_path / "trace.json"), corr=corr)
    with open(path) as f:
        obj = json.load(f)
    assert any(e.get("name") == "phase" for e in obj["traceEvents"])


# ------------------------------------------------------------- profiler
def test_profiler_counts_dropped_spans_and_surfaces_them():
    from paddle_tpu import profiler
    from paddle_tpu.profiler import _HostEventRecorder

    saved = profiler._recorder
    rec = _HostEventRecorder(capacity=4)
    rec.enabled = True
    profiler._recorder = rec
    try:
        base = profiler.counter_values().get("profiler.spans_dropped", 0)
        for i in range(10):
            with profiler.RecordEvent("spin"):
                pass
        assert rec.dropped == 6
        got = profiler.counter_values()["profiler.spans_dropped"]
        assert got == base + 6
        rows = profiler.host_event_summary()
        assert rows["(dropped spans)"][0] == 6
    finally:
        profiler._recorder = saved


def test_host_event_summary_percentile_columns():
    from paddle_tpu import profiler
    from paddle_tpu.profiler import _HostEventRecorder

    saved = profiler._recorder
    rec = _HostEventRecorder()
    profiler._recorder = rec
    try:
        for i in range(1, 11):
            rec.record("op", 0.0, i / 100.0)   # 10ms..100ms
        rows = profiler.host_event_summary(percentiles=(50, 99))
        calls, total, avg, mx, p50, p99 = rows["op"]
        assert calls == 10 and mx == pytest.approx(0.10)
        assert p50 == pytest.approx(0.06, abs=0.011)
        assert p99 == pytest.approx(0.10, abs=0.011)
        # default stays the 4-tuple shape existing consumers unpack
        assert len(profiler.host_event_summary()["op"]) == 4
    finally:
        profiler._recorder = saved


# ------------------------------------------------------ flight recorder
def test_flight_recorder_ring_and_dump(tmp_path):
    from paddle_tpu.observability.flight import FlightRecorder

    rec = FlightRecorder(capacity=3, dump_dir=str(tmp_path))
    for i in range(5):
        rec.note("ev", corr=f"c{i}", detail=i)
    evs = rec.events()
    assert len(evs) == 3 and evs[0]["detail"] == 2  # oldest rolled off
    path = rec.dump("unit_test", corr="c4", extra={"k": "v"})
    with open(path) as f:
        dump = json.load(f)
    assert dump["format"] == "flight_recorder"
    assert dump["reason"] == "unit_test"
    assert dump["correlation_id"] == "c4"
    assert dump["extra"] == {"k": "v"}
    assert [e["corr"] for e in dump["events"]] == ["c2", "c3", "c4"]
    assert isinstance(dump["spans"], list)
    assert isinstance(dump["counters"], dict)
    assert rec.stats()["dumps_written"] == 1


def test_flight_recorder_dump_budget(tmp_path):
    from paddle_tpu.observability.flight import FlightRecorder

    rec = FlightRecorder(dump_dir=str(tmp_path), max_dumps=2)
    assert rec.dump("a") and rec.dump("b")
    assert rec.dump("c") is None
    st = rec.stats()
    assert st["dumps_written"] == 2 and st["dumps_skipped"] == 1


def test_hang_watchdog_dumps_flight_artifact(tmp_path):
    from paddle_tpu.framework.supervisor import HangWatchdog

    import warnings

    flight.configure(dump_dir=str(tmp_path))
    before = flight.flight_recorder().stats()["dumps_written"]
    wd = HangWatchdog(step_timeout=0.05, action="warn")
    with warnings.catch_warnings():
        # the watcher thread warns through the (global) filter state
        warnings.simplefilter("ignore", RuntimeWarning)
        wd.start()
        wd.beat()
        deadline = time.monotonic() + 5.0
        while wd.hangs_detected == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        wd.stop()
    assert wd.hangs_detected == 1
    rec = flight.flight_recorder()
    assert rec.stats()["dumps_written"] == before + 1
    with open(rec.stats()["last_dump_path"]) as f:
        dump = json.load(f)
    assert dump["reason"] == "hang"
    assert dump["extra"]["step_timeout_s"] == pytest.approx(0.05)


def test_hang_is_counted_once_its_dump_is_on_disk(tmp_path, monkeypatch):
    """The race behind the flaky test above, made certain: with a dump
    that takes 0.3 s to reach the disk, whoever sees ``hangs_detected``
    move must find the hang's artifact, not an earlier one."""
    from paddle_tpu.framework.supervisor import HangWatchdog

    import warnings

    flight.configure(dump_dir=str(tmp_path))
    flight.dump("earlier")
    real_fsync = os.fsync

    def slow_fsync(fd):
        time.sleep(0.3)
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", slow_fsync)
    wd = HangWatchdog(step_timeout=0.05, action="warn")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        wd.start()
        wd.beat()
        deadline = time.monotonic() + 5.0
        while wd.hangs_detected == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        path = flight.flight_recorder().stats()["last_dump_path"]
        wd.stop()
    assert wd.hangs_detected == 1
    with open(path) as f:
        assert json.load(f)["reason"] == "hang"
    assert not wd._thread.is_alive()      # stop() waited the watcher out


def test_supervisor_before_batch_stamps_train_corr(tmp_path):
    from paddle_tpu.framework.supervisor import (RecoveryPolicy,
                                                 TrainingSupervisor)

    class FakeStep:
        _count = 41

        def state_dict(self):
            return {}

    sup = TrainingSupervisor(
        FakeStep(), RecoveryPolicy(checkpoint_dir=str(tmp_path),
                                   preemption=False))
    prev = tracing.current()
    try:
        sup.before_batch()
        assert tracing.current() == f"train-{os.getpid():x}-s41"
    finally:
        tracing.set_current(prev)
        sup.stop()


# ------------------------------------------------- serving end-to-end
def test_served_request_yields_one_trace_lane(lm, fleet):
    """THE acceptance test: router submit → queue wait → admission (with
    bucket tag, its prefill dispatch and wait inside) → ONE decode span →
    stream end, one lane, one corr; the steps in the untraced lane."""
    model, cfg = lm
    router, srv = fleet
    p = _prompt(cfg, 9, seed=1)
    t_submit = time.time()
    h = router.submit(p, max_new_tokens=5)
    out = h.result(timeout=300)
    assert out.shape[0] == 5
    corr = h.correlation_id
    assert corr and corr == h._current().correlation_id
    spans = tracing.spans(corr=corr)
    names = [s["name"] for s in spans]
    for expected in ("submit", "router:submit", "queue_wait", "serve.admit",
                     "serve.prefill.dispatch", "serve.prefill.wait",
                     "decode", "stream_end"):
        assert expected in names, f"missing {expected} in {names}"
    # 5 tokens = the admission's first + 4 decoded, in ONE decode span
    assert names.count("decode") == 1
    decode = next(s for s in spans if s["name"] == "decode")
    assert decode["tags"]["tokens"] == 5 - 1
    admit = next(s for s in spans if s["name"] == "serve.admit")
    assert admit["tags"]["bucket"] == 16
    assert admit["tags"]["prompt_len"] == 9
    # the admission's parts lie inside it, the decode stretch after it
    for part in ("serve.prefill.dispatch", "serve.prefill.wait"):
        s = next(s for s in spans if s["name"] == part)
        assert admit["t0"] <= s["t0"] <= s["t1"] <= admit["t1"]
    assert admit["t0"] < decode["t0"] <= admit["t1"] < decode["t1"]
    ct = tracing.chrome_trace(corr=corr)
    lanes = {e["tid"] for e in ct["traceEvents"] if e["ph"] in ("X", "i")}
    assert len(lanes) == 1          # ONE merged lane for the request
    # the 4 decode steps: untraced lane, consecutive step numbers, each
    # a dispatch, a wait and an emit span; the loop is one step ahead, so
    # a step's wait begins where the NEXT step's dispatch ends, and the
    # last step's behind a pass that had nothing left to launch
    steps = {}
    for s in tracing.spans():
        if s["name"] in ("serve.decode.dispatch", "serve.decode.wait",
                         "serve.emit") and s["t0"] >= t_submit:
            assert s["corr"] is None
            steps.setdefault(s["tags"]["step"], {})[s["name"]] = s
    mine = sorted(steps)
    assert len(mine) == 4 and mine == list(range(mine[0], mine[0] + 4))
    for n in mine:
        d, w, e = (steps[n][k] for k in ("serve.decode.dispatch",
                                         "serve.decode.wait", "serve.emit"))
        assert d["t1"] <= w["t0"] and w["t1"] == e["t0"]
        assert d["tags"] == w["tags"] == e["tags"] == {"step": n, "live": 1}
        if n + 1 in steps:
            ahead = steps[n + 1]["serve.decode.dispatch"]
            assert d["t1"] <= ahead["t0"] and ahead["t1"] == w["t0"]
        else:
            before = max(s["t1"] for s in tracing.spans(name="serve.schedule")
                         if s["t1"] <= w["t0"])
            assert before == w["t0"] > steps[n - 1]["serve.emit"]["t1"]
    # a second request gets its own id and its own lane
    h2 = router.submit(_prompt(cfg, 6, seed=2), max_new_tokens=3)
    h2.result(timeout=300)
    assert h2.correlation_id != corr
    assert tracing.spans(corr=h2.correlation_id, name="stream_end")


def test_registry_scrape_carries_serving_and_introspection(lm, fleet):
    model, cfg = lm
    router, srv = fleet
    snap = default_registry().snapshot()
    completed = [v for k, v in snap["counters"].items()
                 if k.startswith("serving.requests_completed")]
    assert completed and max(completed) >= 1
    label = srv._obs_label
    assert snap["gauges"][f'serving.slots{{server="{label}"}}'] == 2
    text = srv.metrics_text()
    assert "# TYPE serving_requests_completed counter" in text
    assert f'server="{label}"' in text
    sz = srv.statusz()
    assert sz["queue_depth"] == 0
    assert sz["snapshot"]["requests_completed"] >= 1
    assert sz["trace"]["enabled"] is True
    rz = router.statusz()
    assert rz["replicas"] == {"r0": "active"}
    assert "requests_routed" in rz["snapshot"]


def test_crash_drill_dump_carries_failing_corr(lm, tmp_path):
    """Engine-reset drill (FaultPlan at serve.step): the flight dump
    must exist, be well formed, and carry the failing request's
    correlation id in its inflight list AND its span tail."""
    from flight_drill import run_drill

    model, _ = lm
    result = run_drill(str(tmp_path), new_tokens=5, model=model)
    assert result["fault_fired"], result
    assert result["ok"], result
    with open(result["dump_path"]) as f:
        dump = json.load(f)
    assert result["correlation_id"] in dump["extra"]["inflight"]
    kinds = [e["kind"] for e in dump["events"]]
    assert "engine_reset" in kinds


def test_trace_view_merges_replica_dumps_by_corr(tmp_path):
    """Two replica dumps sharing a correlation id merge into ONE lane."""
    from trace_view import list_correlations, load_spans, main

    corr = "req-merge-000042"
    for i, name in enumerate(("router", "replica")):
        dump = {"format": "flight_recorder", "version": 1,
                "reason": "test", "time": 0.0, "pid": 100 + i,
                "host": "h", "correlation_id": corr,
                "events": [{"t": 1.0 + i, "kind": "compile"}],
                "spans": [{"name": f"{name}:phase", "corr": corr,
                           "t0": 1.0 + i, "t1": 1.5 + i, "tags": {}},
                          {"name": "other", "corr": f"req-other-{i}",
                           "t0": 0.5, "t1": 0.6, "tags": {}}],
                "counters": {}, "metrics": None}
        with open(tmp_path / f"{name}.json", "w") as f:
            json.dump(dump, f)
    files = [str(tmp_path / "router.json"), str(tmp_path / "replica.json")]
    spans = []
    for p in files:
        got, kind = load_spans(p)
        assert kind == "flight"
        spans.extend(got)
    rows = {e["corr"]: e for e in list_correlations(spans)}
    assert rows[corr]["spans"] == 2
    assert sorted(rows[corr]["names"]) == ["replica:phase", "router:phase"]
    out = str(tmp_path / "merged.json")
    assert main(files + ["-o", out, "--corr", corr]) == 0
    with open(out) as f:
        merged = json.load(f)
    data = [e for e in merged["traceEvents"] if e["ph"] in ("X", "i")]
    # both replicas' spans, one lane; the other corrs filtered out
    assert {e["name"] for e in data} == {"router:phase", "replica:phase"}
    assert len({e["tid"] for e in data}) == 1


def test_compile_events_reach_flight_ring(lm, fleet):
    """compile_cache.record_trace lands compile events in the flight
    ring — the first thing a postmortem wants to rule out."""
    kinds = [e["kind"] for e in flight.flight_recorder().events()]
    assert "compile" in kinds     # the fleet fixture compiled programs


def test_serving_metrics_snapshot_keys_preserved(lm, fleet):
    """MIGRATION guarantee: the registry absorption did not change the
    ServingMetrics.snapshot() shape serve_bench/router roll-ups parse."""
    _, srv = fleet
    snap = srv.snapshot()
    for key in ("requests_submitted", "requests_completed",
                "tokens_emitted", "slot_occupancy", "ttft",
                "inter_token", "queue_wait", "prefix_hit_rate",
                "compile_stats"):
        assert key in snap, key
