"""Serving observability: gauges, counters, latency histograms.

The four signals a serving operator actually pages on:

- **queue depth / slot occupancy** (gauges + a time-weighted occupancy
  integral — "are we over/under-provisioned?"),
- **TTFT** (time to first token: queue wait + prefill),
- **inter-token latency** (the decode-loop heartbeat users feel),
- **goodput** (tokens/s, requests/s, and the reject/expire/requeue
  counts that explain the gap from offered load).

Histograms use reservoir sampling (bounded memory under unbounded
traffic) with exact counts/sums; ``snapshot()`` returns one plain dict —
the shape ``tools/serve_bench.py`` emits as JSON. Device-free and
import-light on purpose.

The serve loop's own time is here too: :class:`LoopClock` divides every
pass of the loop thread into the phases of :data:`LOOP_PHASES`, and at
each boundary one read of the clocks feeds both the always-on counters
(``snapshot()["loop"]``) and the phase's span in the tracing ring.
"""
from __future__ import annotations

import itertools
import random
import threading
import time
from typing import Dict, List, Optional

from ..observability import registry as _obs_registry
from ..observability import tracing as _tracing

__all__ = ["LOOP_PHASES", "SAMPLE_BRANCHES", "LatencyHistogram", "LoopClock",
           "ServingMetrics"]

#: What the serve loop's thread can be doing; disjoint, and together its
#: whole wall time. ``admit_wait`` and ``decode_wait`` wait for the
#: device (the read-back of an admission's first token, of a step's
#: tokens); ``idle`` waits for work; the other four are host work: wall
#: less CPU in one of those is time the thread was runnable or blocked
#: but not running (the interpreter lock, another lock, the machine).
#: The loop runs one step ahead: a pass's ``decode_dispatch`` launches
#: step N+1 (``engine.launch``), its ``decode_wait`` and ``emit`` belong
#: to step N (``engine.collect``), so each is still booked once a step
#: and the three counts agree once the loop has drained.
LOOP_PHASES = ("idle", "schedule", "admit_host", "admit_wait",
               "decode_dispatch", "decode_wait", "emit")

#: What a decode step's sampler ran, by ``generation.sample_branch``'s
#: number: an argmax alone (every live slot greedy), the categorical draw
#: without the nucleus filter, or the whole graph with its sort.
SAMPLE_BRANCHES = ("argmax_steps", "categorical_steps", "nucleus_steps")

_metrics_serial = itertools.count()


class LatencyHistogram:
    """Reservoir-sampled latency distribution with exact count/sum.

    Percentiles are computed over the reservoir (uniform sample of the
    stream — Vitter's algorithm R), so memory stays ``O(max_samples)``
    no matter how long the server runs."""

    def __init__(self, max_samples: int = 4096, seed: int = 0):
        self.max_samples = int(max_samples)
        self._rng = random.Random(seed)
        self._samples: List[float] = []
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def observe(self, seconds: float) -> None:
        s = float(seconds)
        self.count += 1
        self.total += s
        if s > self.max:
            self.max = s
        if len(self._samples) < self.max_samples:
            self._samples.append(s)
        else:
            j = self._rng.randrange(self.count)
            if j < self.max_samples:
                self._samples[j] = s

    def percentile(self, p: float) -> float:
        return _obs_registry.nearest_rank(sorted(self._samples), p)

    def summary(self) -> Dict[str, float]:
        mean = self.total / self.count if self.count else 0.0
        return {"count": self.count,
                "mean_ms": round(mean * 1e3, 3),
                "p50_ms": round(self.percentile(50) * 1e3, 3),
                "p99_ms": round(self.percentile(99) * 1e3, 3),
                "max_ms": round(self.max * 1e3, 3)}

    @classmethod
    def merge(cls, hists: List["LatencyHistogram"]) -> "LatencyHistogram":
        """Fleet roll-up: pool the replicas' reservoirs into one
        histogram (exact count/sum/max; percentiles over the combined
        sample — each replica's reservoir is a uniform sample of its
        stream, so the pool approximates the fleet distribution weighted
        by observed traffic)."""
        out = cls(max_samples=max([h.max_samples for h in hists] or [1]))
        for h in hists:
            out.count += h.count
            out.total += h.total
            out.max = max(out.max, h.max)
            out._samples.extend(h._samples)
        return out


class LoopClock:
    """Where the serve loop's thread is, and since when.

    The thread is always in exactly one phase. :meth:`enter` is a
    boundary: it reads the wall clock and the thread's CPU clock once,
    books the time since the last boundary to the phase that ends
    (``book(phase, wall_ns, cpu_ns)`` — ``ServingMetrics.loop_phase``)
    and closes that phase's span with the same reading; so the phases
    cannot overlap and leave nothing out. The wall clock is
    ``time.time_ns()``: what the span ring and a device trace's
    ``profile_start_time`` are stamped with. One thread owns a clock."""

    def __init__(self, book=None):
        self._book = book
        self.phase = "idle"
        self.t = time.time_ns()          # the last boundary, wall ns
        self._cpu = time.thread_time_ns()
        self._open = None
        self.corr = None                 # of the span that is open
        self.tags = None

    def enter(self, phase: str, name: Optional[str] = None, corr=None,
              tags: Optional[dict] = None) -> None:
        """The thread leaves its phase for ``phase``; ``name`` opens the
        new phase's span (:meth:`span`)."""
        t, cpu = time.time_ns(), time.thread_time_ns()
        if self._book is not None:
            self._book(self.phase, t - self.t, cpu - self._cpu)
        if self._open is not None:
            _tracing.end(self._open, t * 1e-9, corr=self.corr,
                         tags=self.tags)
            self._open = None
        self.phase, self.t, self._cpu = phase, t, cpu
        if name is not None:
            self.span(name, corr, tags)

    def span(self, name: str, corr=None, tags: Optional[dict] = None):
        """Open the running phase's span at the boundary just read (the
        next boundary closes it). Apart from :meth:`enter` so that a
        caller can open a parent span around it at the same instant."""
        self.corr, self.tags = corr, tags
        self._open = _tracing.begin(name, self.t * 1e-9)


class ServingMetrics:
    """Thread-safe counters/gauges/histograms for one serving loop."""

    def __init__(self, slots: int):
        self.slots = int(slots)
        self._lock = threading.Lock()
        self.reset()
        # absorbed into the unified observability registry behind this
        # class's unchanged API: a weak (bound-method) collector feeds
        # the counters/histograms into every snapshot()/prometheus_text
        # scrape, labeled per instance so co-hosted replicas stay apart
        self._obs_label = f"m{next(_metrics_serial)}"
        _obs_registry.default_registry().register_collector(
            self._obs_collect, labels={"metrics": self._obs_label},
            name=f"serving_metrics.{self._obs_label}")

    def _obs_collect(self) -> dict:
        with self._lock:
            counters = {
                "serving.requests_submitted": self.requests_submitted,
                "serving.requests_completed": self.requests_completed,
                "serving.requests_rejected": self.requests_rejected,
                "serving.requests_expired": self.requests_expired,
                "serving.requests_shed": self.requests_shed,
                "serving.requests_rate_limited": self.requests_rate_limited,
                "serving.requests_failed": self.requests_failed,
                "serving.requests_requeued": self.requests_requeued,
                "serving.tokens_emitted": self.tokens_emitted,
                "serving.prefills": self.prefills,
                "serving.decode_steps": self.decode_steps,
                "serving.prefix_hit_tokens": self.prefix_hit_tokens,
                "serving.prefix_miss_tokens": self.prefix_miss_tokens,
            }
            hists = {}
            for hname, h in (("serving.ttft_s", self.ttft),
                             ("serving.inter_token_s", self.inter_token),
                             ("serving.queue_wait_s", self.queue_wait)):
                hists[hname] = {"count": h.count,
                                "sum": round(h.total, 6),
                                "p50": round(h.percentile(50), 6),
                                "p99": round(h.percentile(99), 6),
                                "max": round(h.max, 6)}
            return {"counters": counters,
                    "gauges": {"serving.metrics_queue_depth":
                               self.queue_depth,
                               "serving.metrics_active_slots":
                               self.active_slots},
                    "histograms": hists}

    def reset(self) -> None:
        with self._lock:
            self._t0 = time.monotonic()
            self.requests_submitted = 0
            self.requests_completed = 0
            self.requests_rejected = 0
            self.requests_expired = 0
            # deadline-aware overload sheds (Overloaded, retryable) —
            # deliberately separate from requests_expired (deadline
            # actually lapsed: TimeoutError) and requests_failed
            # (non-retryable faults): a client backs off a shed, gives
            # up on an expiry, and pages on a failure
            self.requests_shed = 0
            # per-tenant token-bucket rejects (RateLimited, retryable):
            # separate from requests_shed — a shed says the FLEET is
            # over capacity, a rate-limit says one TENANT is over ITS
            # allowance while everyone else is fine
            self.requests_rate_limited = 0
            self.requests_failed = 0
            self.requests_requeued = 0
            self.tokens_emitted = 0
            self.prefills = 0
            self.decode_steps = 0
            # prefix-cache reuse: prompt tokens served from the block
            # pool vs prefilled from scratch (both 0 without a pool)
            self.prefix_hit_tokens = 0
            self.prefix_miss_tokens = 0
            self.queue_depth = 0
            self.active_slots = 0
            self._occ_integral = 0.0     # slot-seconds of occupancy
            self._occ_last_t = self._t0
            self.ttft = LatencyHistogram()
            self.inter_token = LatencyHistogram()
            self.queue_wait = LatencyHistogram()
            # per-tenant traffic (adapter id -> counters/ttft), recorded
            # only when the engine serves through an AdapterStore; the
            # base model's share books under "base"
            self._per_adapter: Dict[str, dict] = {}
            # [count, wall ns, CPU ns] a phase. One writer, the serve
            # loop, and no lock on its side: a reset swaps the table, so
            # a booking that races it lands in the old one
            self._loop = {p: [0, 0, 0] for p in LOOP_PHASES}
            # [steps, live slots, cached positions read, steps launched
            # ahead] summed over decode steps; the same writer, and no
            # lock for the same reason
            self._decode = [0, 0, 0, 0]
            # decode steps by the sampler's branch (SAMPLE_BRANCHES);
            # the same writer again
            self._sample = [0, 0, 0]
            # [admissions, prompt tokens, bucket tokens] of a model with
            # recurrent-state entries; the same writer again
            self._state = [0, 0, 0]

    # ------------------------------------------------------------ events
    def decode_step(self, live_slots: int, live_positions: int,
                    ahead: bool = False) -> None:
        """Book one decode step's load (``engine.step_load``): the slots
        live in it and the cached positions their queries read. What a
        step must move from memory follows from these and the model's
        shapes, whatever program ran it. ``ahead`` (what
        ``engine.launch`` returned): the step was handed to the device
        while the one before it still ran, so the host's turn between
        the two cost no device time."""
        d = self._decode
        d[0] += 1
        d[1] += live_slots
        d[2] += live_positions
        d[3] += ahead

    def sample_step(self, branch: int) -> None:
        """Book which branch of the sampler one decode step took
        (``engine.step_sample_branch``: the program's own test, made on
        the host vectors the step was given)."""
        self._sample[branch] += 1

    def state_admission(self, prompt_tokens: int, bucket_tokens: int) -> None:
        """Book one admission of a model whose cache holds a recurrent
        state: the prompt's real tokens and the bucket it was padded to
        (every position of which the prefill scan walks; the pads must
        not move the state)."""
        s = self._state
        s[0] += 1
        s[1] += prompt_tokens
        s[2] += bucket_tokens

    def loop_phase(self, phase: str, wall_ns: int, cpu_ns: int) -> None:
        """Book one ended instance of a serve-loop phase (the loop
        thread's :class:`LoopClock` calls this at every boundary). A
        clock stepped backwards books nothing rather than less."""
        c = self._loop[phase]
        c[0] += 1
        c[1] += max(wall_ns, 0)
        c[2] += cpu_ns

    def _advance_occupancy(self, now: float) -> None:
        self._occ_integral += self.active_slots * (now - self._occ_last_t)
        self._occ_last_t = now

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + by)

    def set_queue_depth(self, depth: int) -> None:
        with self._lock:
            self.queue_depth = int(depth)

    def set_active_slots(self, active: int) -> None:
        with self._lock:
            self._advance_occupancy(time.monotonic())
            self.active_slots = int(active)

    def observe_ttft(self, seconds: float) -> None:
        with self._lock:
            self.ttft.observe(seconds)

    def observe_inter_token(self, seconds: float) -> None:
        with self._lock:
            self.inter_token.observe(seconds)

    def observe_queue_wait(self, seconds: float) -> None:
        with self._lock:
            self.queue_wait.observe(seconds)

    # ------------------------------------------------------- per adapter
    def _adapter_locked(self, adapter_id) -> dict:
        name = "base" if adapter_id is None else str(adapter_id)
        e = self._per_adapter.get(name)
        if e is None:
            # smaller reservoir than the global histograms: one exists
            # per TENANT, and p50 stabilizes long before 4096 samples
            e = self._per_adapter[name] = {
                "requests": 0, "tokens": 0, "failures": 0,
                "ttft": LatencyHistogram(max_samples=512)}
        return e

    def adapter_request(self, adapter_id) -> None:
        with self._lock:
            self._adapter_locked(adapter_id)["requests"] += 1

    def adapter_failure(self, adapter_id, n: int = 1) -> None:
        """Book a failed/expired/shed request against its tenant — the
        per-tenant availability signal the SLO burn-rate tracker
        (``observability.slo``) diffs across scrapes."""
        with self._lock:
            self._adapter_locked(adapter_id)["failures"] += int(n)

    def adapter_tokens(self, adapter_id, n: int = 1) -> None:
        with self._lock:
            self._adapter_locked(adapter_id)["tokens"] += int(n)

    def observe_adapter_ttft(self, adapter_id, seconds: float) -> None:
        with self._lock:
            self._adapter_locked(adapter_id)["ttft"].observe(seconds)

    # ---------------------------------------------------------- snapshot
    def snapshot(self, compile_stats: Optional[dict] = None,
                 prefix_cache: Optional[dict] = None,
                 adapter_store: Optional[dict] = None,
                 moe: Optional[dict] = None,
                 state_bytes_per_slot: Optional[int] = None) -> dict:
        """One plain dict of everything — the serve_bench JSON shape.
        ``state_bytes_per_slot`` (``ContinuousBatchingEngine``'s: what a
        slot holds of recurrent state, None for a model with none) brings
        the ``"state"`` block: admissions, their prompt and bucket tokens.
        ``moe`` (``ContinuousBatchingEngine.expert_load()``: the decode
        steps' expert load, fetched from the device for this snapshot)
        rides along for a model with an expert FFN.
        ``prefix_cache`` (a ``BlockPool.stats()`` dict) and
        ``adapter_store`` (an ``AdapterStore.stats()`` dict) ride along
        under their own keys when the engine has them attached; the
        ``per_adapter`` block (requests / tokens / TTFT p50 per tenant)
        appears whenever adapter traffic was recorded — the observable
        inputs behind the router's adapter-affinity placement."""
        with self._lock:
            now = time.monotonic()
            self._advance_occupancy(now)
            elapsed = max(now - self._t0, 1e-9)
            seen = self.prefix_hit_tokens + self.prefix_miss_tokens
            return {
                "elapsed_s": round(elapsed, 3),
                "slots": self.slots,
                "queue_depth": self.queue_depth,
                "active_slots": self.active_slots,
                "slot_occupancy": round(
                    self._occ_integral / (elapsed * self.slots), 4),
                "requests_submitted": self.requests_submitted,
                "requests_completed": self.requests_completed,
                "requests_rejected": self.requests_rejected,
                "requests_expired": self.requests_expired,
                "requests_shed": self.requests_shed,
                "requests_rate_limited": self.requests_rate_limited,
                "requests_failed": self.requests_failed,
                "requests_requeued": self.requests_requeued,
                "tokens_emitted": self.tokens_emitted,
                "prefills": self.prefills,
                "decode_steps": self.decode_steps,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_miss_tokens": self.prefix_miss_tokens,
                "prefix_hit_rate": (round(self.prefix_hit_tokens / seen, 4)
                                    if seen else 0.0),
                "tokens_per_sec": round(self.tokens_emitted / elapsed, 2),
                "requests_per_sec": round(
                    self.requests_completed / elapsed, 3),
                "ttft": self.ttft.summary(),
                "inter_token": self.inter_token.summary(),
                "queue_wait": self.queue_wait.summary(),
                # a phase is booked when it ends: the one the loop is in
                # now is not in here yet (idle ends at every wake-up of
                # its wait, at most 0.1 s)
                "loop": {p: {"count": c[0], "wall_s": c[1] * 1e-9,
                             "cpu_s": c[2] * 1e-9}
                         for p, c in self._loop.items()},
                "decode": dict(zip(("steps", "live_slot_steps",
                                    "live_position_steps",
                                    "launched_ahead_steps"), self._decode)),
                "sample": dict(zip(SAMPLE_BRANCHES, self._sample)),
                **({"compile_stats": compile_stats}
                   if compile_stats is not None else {}),
                **({"prefix_cache": prefix_cache}
                   if prefix_cache is not None else {}),
                **({"adapter_store": adapter_store}
                   if adapter_store is not None else {}),
                **({"moe": moe} if moe is not None else {}),
                **({"state": dict(
                    zip(("admissions", "prompt_tokens", "bucket_tokens"),
                        self._state),
                    state_bytes_per_slot=state_bytes_per_slot)}
                   if state_bytes_per_slot is not None else {}),
                **({"per_adapter": {
                    name: {"requests": e["requests"],
                           "tokens": e["tokens"],
                           "failures": e["failures"],
                           "ttft_p50_ms": round(
                               e["ttft"].percentile(50) * 1e3, 3),
                           # exact count/sum so downstream SLO windows
                           # can diff an interval's mean TTFT across
                           # scrapes (reservoir percentiles can't diff)
                           "ttft_count": e["ttft"].count,
                           "ttft_sum_ms": round(
                               e["ttft"].total * 1e3, 3)}
                    for name, e in sorted(self._per_adapter.items())}}
                   if self._per_adapter else {}),
            }
