"""Threaded serving front end: submit / stream / drain / survive faults.

One daemon worker thread owns the engine (all device dispatch is
single-threaded by construction — no lock around jax); any number of
client threads ``submit()`` and consume per-request streams. The loop per
iteration: sweep deadline-expired queue entries, admit up to
``max_prefills_per_step`` requests into free slots (each one bucketed
prefill dispatch), then LAUNCH one decode step for the whole live batch,
and only then read back the step launched in the iteration before and
fan its tokens out to the request handles. Finished slots free
immediately — a new request admits into the hole while everyone else
keeps decoding.

The loop is always one step ahead of what it has read (``engine.launch``
/ ``engine.collect``): the device runs step N while the host hands it
step N+1, and runs N+1 while the host emits N's tokens and schedules, so
the host's turn costs device time only where it outlasts the program. A
step's input tokens never visit the host. What the host learns one
launch late is an eos (the slot decodes one masked filler step and frees
a step later; that step's token reaches nobody); a request's length it
knows ahead. An admission waits out the step in flight, then the
pipeline refills with the next launch. The loop reads the last launch
back before it sleeps or ends. A client can observe nothing of this but
timing: every stream is token for token what a synchronous loop serves.

Where the loop thread's time goes is measured where it happens: its
``LoopClock`` divides every pass into the phases of
``metrics.LOOP_PHASES`` (``idle``, ``schedule``, ``admit_host``,
``admit_wait``, ``decode_dispatch``, ``decode_wait``, ``emit``), counted
always (``snapshot()["loop"]``) and, while the span ring is on, recorded
as ``serve.schedule`` / ``serve.admit`` (request lane; children
``serve.prefill.dispatch`` and ``serve.prefill.wait``) /
``serve.decode.dispatch`` / ``serve.decode.wait`` / ``serve.emit``.

Failure story (``distributed/resilience`` conventions):

- **backpressure**: an over-depth queue rejects at ``submit`` with
  :class:`~paddle_tpu.serving.scheduler.QueueFull` (a ``ConnectionError``
  — wrap submit in a ``RetryPolicy`` to wait instead);
- **deadlines**: a per-request ``Deadline`` expires requests still in the
  queue (their handles raise ``TimeoutError``); ``handle.result(timeout)``
  bounds the client-side wait;
- **worker faults**: any exception in the serve loop (including
  ``fault_point("serve.admit")`` / ``("serve.step")`` injections from a
  ``FaultPlan``) resets the engine and requeues in-flight requests at the
  queue HEAD, up to ``max_request_retries`` re-admissions each; requests
  over budget fail with the original error. Regeneration restarts from
  the request's seed, so a recovered request's ``result()`` is identical
  — but a live ``stream()`` may re-emit its prefix (at-least-once).
- **graceful shutdown**: ``shutdown(drain=True)`` seals admission, lets
  the loop finish every accepted request, then joins the worker;
  ``drain=False`` fails the backlog fast with ``SchedulerClosed``.
"""
from __future__ import annotations

import itertools
import os
import queue
import threading
import time
import warnings
from typing import Iterator, Optional

import numpy as np

from ..distributed.resilience import Deadline, fault_point
from ..lora.store import AdapterError
from ..observability import flight as _flight
from ..observability import registry as _obs_registry
from ..observability import tracing as _tracing
from .engine import ContinuousBatchingEngine
from .metrics import LoopClock, ServingMetrics
from .scheduler import (FifoScheduler, Overloaded, QueueFull, RateLimited,
                        Request, SchedulerClosed)

__all__ = ["InferenceServer", "RequestHandle"]

_server_serial = itertools.count()


class RequestHandle:
    """Client-side view of one submitted request.

    ``stream()`` yields token ids as they are generated; ``result()``
    blocks for the full generated sequence. Thread-safe: the worker
    pushes, any client thread consumes."""

    def __init__(self, request: Request):
        self.request = request
        self._q: "queue.Queue" = queue.Queue()
        self._tokens = []
        self._lock = threading.Lock()
        self._done_evt = threading.Event()
        self.error: Optional[BaseException] = None
        self.ttft_s: Optional[float] = None
        #: prompt tokens served from the prefix cache at admission (0
        #: without a pool); clients read it off the handle to see reuse
        self.cache_hit_tokens: int = 0
        # monotonic; a span's wall-clock bounds are the recording
        # boundary's time.time() less a difference of these
        self._submit_t = time.monotonic()
        self._first_token_t: Optional[float] = None
        self._last_token_t: Optional[float] = None

    # ---- worker-side (single writer: the serve loop) ----
    def _push(self, tok: int) -> None:
        with self._lock:
            self._tokens.append(int(tok))
        self._q.put(("tok", int(tok)))

    def _restart(self) -> None:
        with self._lock:
            self._tokens = []
        self._last_token_t = None
        self._q.put(("restart", None))

    def _finish(self) -> None:
        self._done_evt.set()
        self._q.put(("end", None))

    def _fail(self, exc: BaseException) -> None:
        self.error = exc
        self._done_evt.set()
        self._q.put(("err", exc))

    def _count(self) -> int:
        with self._lock:
            return len(self._tokens)

    # ---- client-side ----
    @property
    def done(self) -> bool:
        return self._done_evt.is_set()

    @property
    def adapter_id(self):
        """The tenant adapter this request decodes under (None = base)."""
        return self.request.adapter_id

    @property
    def correlation_id(self) -> Optional[str]:
        """The request's tracing correlation id — the key into
        ``observability.tracing.spans()`` / flight-recorder dumps."""
        return self.request.corr_id

    def tokens(self) -> np.ndarray:
        """Tokens generated SO FAR (snapshot; may grow)."""
        with self._lock:
            return np.asarray(self._tokens, np.int32)

    def stream(self) -> Iterator[int]:
        """Yield token ids as the worker emits them; ends when the
        request finishes, raises its error if it failed. After a
        crash-recovery restart the regenerated stream is re-emitted from
        the beginning (at-least-once delivery)."""
        while True:
            kind, val = self._q.get()
            if kind == "tok":
                yield val
            elif kind == "restart":
                continue
            elif kind == "end":
                return
            else:
                raise val

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request completes; returns the generated ids
        ``[n]`` (``n <= max_new_tokens``). Raises ``TimeoutError`` after
        ``timeout`` seconds, or the request's failure."""
        if not self._done_evt.wait(timeout):
            raise TimeoutError(
                f"request {self.request.id} not finished within "
                f"{timeout}s ({self._count()} tokens so far)")
        if self.error is not None:
            raise self.error
        return self.tokens()


class InferenceServer:
    """Continuous-batching server around any causal-LM exposing
    ``cache_spec()``/the cached forward (GPT/Llama families).

    ``slots`` fixes the decode batch geometry (the ONE compiled decode
    program); ``top_k`` is a compile-time sampling static; every other
    sampling knob, ``top_p`` among them, is per-request. Construction is
    cheap — programs compile on first use, per prefill bucket.
    """

    def __init__(self, network, slots: int = 4,
                 max_length: Optional[int] = None,
                 prefill_buckets=None,
                 max_queue_depth: int = 64,
                 max_prefills_per_step: int = 2,
                 top_k: int = 0,
                 max_request_retries: int = 1,
                 prefix_cache=None, adapter_store=None,
                 shed_on_overload: bool = False,
                 tenant_rate: Optional[float] = None,
                 tenant_burst: Optional[float] = None,
                 tenant_limits=None,
                 fair_queueing: bool = False,
                 fair_weights=None, kv_dtype=None):
        self.engine = ContinuousBatchingEngine(
            network, slots=slots, max_length=max_length,
            prefill_buckets=prefill_buckets, top_k=top_k,
            prefix_cache=prefix_cache,
            adapter_store=adapter_store, kv_dtype=kv_dtype)
        self.scheduler = FifoScheduler(
            max_queue_depth=max_queue_depth,
            max_prefills_per_step=max_prefills_per_step,
            shed_on_overload=shed_on_overload,
            tenant_rate=tenant_rate, tenant_burst=tenant_burst,
            tenant_limits=tenant_limits, fair_queueing=fair_queueing,
            fair_weights=fair_weights)
        self.metrics = ServingMetrics(slots)
        self.max_request_retries = int(max_request_retries)
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._clock: Optional[LoopClock] = None   # the loop thread's own
        self._stop = False
        self._drain = True
        # absorb this server's live state into the process metrics
        # registry: queue depth, slot occupancy, compile counters, and
        # the pool/store occupancy blocks ride the scrape behind the
        # existing APIs. Weak (bound-method) collector: a GC'd server
        # drops out of the scrape instead of raising.
        self._obs_label = f"srv{next(_server_serial)}"
        _obs_registry.default_registry().register_collector(
            self._obs_collect, labels={"server": self._obs_label},
            name=f"serving.{self._obs_label}")

    # ------------------------------------------------------------ client
    def start(self) -> "InferenceServer":
        with self._cv:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, name="pt-serve", daemon=True)
                self._thread.start()
        return self

    def submit(self, prompt, max_new_tokens: int = 32,
               do_sample: bool = False, temperature: float = 1.0,
               top_p: float = 1.0, eos_token_id: Optional[int] = None,
               seed: Optional[int] = None,
               deadline: Optional[float] = None,
               adapter_id: Optional[str] = None,
               correlation_id: Optional[str] = None) -> RequestHandle:
        """Queue one generation request; returns immediately with a
        :class:`RequestHandle`. Raises ``ValueError`` on an impossible
        request (too long for the cache), :class:`QueueFull` when the
        admission queue is at depth (retryable backpressure), and
        :class:`SchedulerClosed` after shutdown.

        A ``seed`` makes the request's sampled stream deterministic and
        equal to a solo ``generate(..., seed=s)`` run; ``seed=None``
        draws fresh randomness per request (also the solo semantics).
        ``deadline`` (seconds) bounds QUEUE WAIT: requests that can't
        start in time expire with ``TimeoutError`` instead of occupying
        a slot nobody is waiting on.

        ``adapter_id`` decodes the request under that tenant's LoRA
        adapter (requires the server's engine to carry an
        ``adapter_store`` that knows the name; ``None`` = base model).
        Mixing adapters across the live batch is free — every slot
        gathers its own pages inside the one compiled decode program.

        ``correlation_id`` keys the request's trace lane (queue wait →
        admission → one decode span → stream end); ``None`` mints a fresh
        one. The router passes its own id through here so a rerouted
        request keeps ONE lane across replicas."""
        prompt = np.asarray(prompt, np.int32).ravel()
        self.engine.validate(int(prompt.shape[0]), int(max_new_tokens))
        from ..lora.store import normalize_adapter_id

        adapter_id = normalize_adapter_id(adapter_id)
        if adapter_id is not None:
            store = self.engine.store
            if store is None:
                raise ValueError(
                    f"request names adapter {adapter_id!r} but this "
                    f"server has no adapter_store; construct it with "
                    f"InferenceServer(..., adapter_store=AdapterStore("
                    f"model, ...))")
            if not store.known(adapter_id):
                raise ValueError(
                    f"unknown adapter {adapter_id!r}; AdapterStore."
                    f"register()/load() it before submitting")
        corr = correlation_id or _tracing.new_correlation_id()
        req = Request(
            prompt=prompt, max_new_tokens=int(max_new_tokens),
            greedy=not do_sample, temperature=float(temperature),
            top_p=float(top_p), eos_token_id=eos_token_id,
            seed=None if seed is None else int(seed),
            deadline=Deadline(deadline) if deadline is not None else None,
            adapter_id=adapter_id, corr_id=corr)
        handle = RequestHandle(req)
        req.handle = handle
        self.start()
        try:
            self.scheduler.submit(req)
        except Overloaded:
            # deadline-aware shed at the door: the fast-fail half of
            # overload control (the request learns NOW, within
            # microseconds of submit, not after its whole deadline)
            self.metrics.inc("requests_shed")
            self._adapter_fail(req)
            _tracing.record_event("shed", corr=corr,
                                  queue_depth=self.scheduler.depth)
            raise
        except RateLimited as e:
            # the tenant is over ITS admission rate — the system
            # working as designed, not an availability failure: no
            # _adapter_fail, so an abusive tenant's rejects cannot
            # burn an SLO window and buy fleet capacity through the
            # autoscaler. The flight note carries the tenant label
            # into every subsequent dump (trace_view --list).
            self.metrics.inc("requests_rate_limited")
            _tracing.record_event("rate_limited", corr=corr,
                                  tenant=e.tenant)
            _flight.note("rate_limited", corr=corr, tenant=e.tenant,
                         retry_after_s=round(e.retry_after, 3))
            raise
        except QueueFull:
            self.metrics.inc("requests_rejected")
            _tracing.record_event("rejected", corr=corr,
                                  queue_depth=self.scheduler.depth)
            raise
        self.metrics.inc("requests_submitted")
        _tracing.record_event("submit", corr=corr, request_id=req.id,
                              prompt_len=int(prompt.shape[0]))
        self.metrics.set_queue_depth(self.scheduler.depth)
        with self._cv:
            self._cv.notify_all()
        return handle

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the worker. ``drain=True`` finishes every accepted
        request first; ``drain=False`` fails the backlog with
        ``SchedulerClosed``. Idempotent. Raises ``TimeoutError`` if the
        drain doesn't finish in ``timeout`` seconds (the worker keeps
        draining; call again to keep waiting)."""
        self.scheduler.seal()
        with self._cv:
            self._stop = True
            self._drain = drain
            self._cv.notify_all()
            # read under the cv like every other _thread access — a
            # concurrent start() could otherwise publish the thread
            # between this read and the join (tpu_lint R5)
            t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout)
            if t.is_alive():
                raise TimeoutError(
                    f"serve loop still draining after {timeout}s "
                    f"({self.engine.active_count} active, "
                    f"{self.scheduler.depth} queued)")

    def __enter__(self) -> "InferenceServer":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.shutdown(drain=exc == (None, None, None))
        return False

    def snapshot(self) -> dict:
        """Metrics + compile-counter snapshot (see
        ``ServingMetrics.snapshot``), plus the block-pool occupancy/
        eviction numbers when a prefix cache is attached, the adapter
        registry residency/eviction numbers when an adapter store is, and
        the decode steps' expert load (``"moe"``: read from the device
        here, and only here) when the model has an expert FFN, and the
        admissions' prompt and bucket tokens (``"state"``) when its cache
        holds a recurrent state."""
        pool = self.engine.pool
        store = self.engine.store
        return self.metrics.snapshot(
            self.engine.cache_stats(),
            prefix_cache=None if pool is None else pool.stats(),
            adapter_store=None if store is None else store.stats(),
            moe=self.engine.expert_load(),
            state_bytes_per_slot=self.engine.state_bytes_per_slot)

    def metrics_text(self) -> str:
        """Prometheus text exposition of the process metrics registry —
        the ``/metrics`` handle (this server's gauges carry its
        ``server=<label>`` labels; co-hosted replicas and the training
        side share the same page)."""
        return _obs_registry.default_registry().prometheus_text()

    def statusz(self) -> dict:
        """Introspection snapshot — the ``/statusz`` handle: live
        engine/scheduler state, the full metrics snapshot, and the
        flight-recorder/trace-buffer health."""
        return {
            "time": round(time.time(), 3),
            "pid": os.getpid(),
            "server": self._obs_label,
            "active_slots": self.engine.active_count,
            "slots": self.engine.slots,
            "queue_depth": self.scheduler.depth,
            "prefill_buckets": list(self.engine.prefill_buckets),
            "snapshot": self.snapshot(),
            # per-tenant token-bucket fill (empty dict when rate
            # limiting is off or no tenant has submitted yet)
            "token_buckets": self.scheduler.bucket_levels(),
            "flight": _flight.flight_recorder().stats(),
            "trace": _tracing.stats(),
        }

    def probe(self) -> dict:
        """Cheap liveness/load probe — the payload the router's heartbeat
        failure detector polls. Host-side attribute reads only (no
        device sync, no histogram math), so a probe's latency measures
        the REPLICA's responsiveness, not this method's cost. The
        ``serve.probe`` fault site lets chaos drills fail or slow the
        probe path in isolation."""
        fault_point("serve.probe")
        depth = self.scheduler.depth
        return {
            "time": round(time.time(), 3),
            "pid": os.getpid(),
            "active": self.engine.active_count,
            "slots": self.engine.slots,
            "queue_depth": depth,
            "max_queue_depth": self.scheduler.max_queue_depth,
            # what a request arriving NOW should expect to wait (None
            # until the scheduler has cadence evidence) — the number an
            # admission-control-aware client sizes its deadline against
            "predicted_queue_wait": self.scheduler.predicted_wait(depth),
        }

    def _obs_collect(self) -> dict:
        """Registry collector: the occupancy/queue/compile numbers an
        autoscaler polls, read from live state (no histogram math)."""
        eng = self.engine
        cc = eng.cache_stats()
        gauges = {
            "serving.queue_depth": self.scheduler.depth,
            "serving.active_slots": eng.active_count,
            "serving.slots": eng.slots,
            "serving.prefill_compiles": cc["prefill"]["compiles"],
            "serving.decode_compiles": cc["decode"]["compiles"],
        }
        out = {"gauges": gauges}
        if eng.pool is not None:
            gauges["serving.prefix_cache"] = eng.pool.stats()
        if eng.store is not None:
            gauges["serving.adapter_store"] = eng.store.stats()
        return out

    # ------------------------------------------------------------ worker
    def _loop(self) -> None:
        # made here: the clock reads the CPU time of the thread it is on
        clock = self._clock = self.engine.clock = LoopClock(
            self.metrics.loop_phase)
        while True:
            with self._cv:
                # quiet: no slot occupied, nothing queued, and the last
                # launch read back
                while (not self._stop and self.engine.active_count == 0
                       and self.scheduler.depth == 0
                       and not self.engine.in_flight):
                    # entered anew at every wake-up, so that a snapshot
                    # lacks at most one wait of an idle stretch
                    clock.enter("idle")
                    self._cv.wait(0.1)
                if self._stop:
                    if not self._drain or (self.engine.active_count == 0
                                           and self.scheduler.depth == 0
                                           and not self.engine.in_flight):
                        break
            try:
                self._tick()
            except Exception as e:  # a fault must never kill the loop
                self._recover(e)
        self._fail_backlog()
        clock.enter("idle")     # book the last phase

    def _fail_backlog(self) -> None:
        """Shutdown tail: terminate whatever was not drained. Queued
        requests whose DEADLINE already lapsed are expired (TimeoutError
        + ``requests_expired``) exactly as a live tick would have done —
        a shutdown racing the expiry sweep must not reclassify a
        deadline miss as a generic failure (the client retry logic
        treats the two very differently). Everything else fails with
        ``SchedulerClosed``."""
        err = SchedulerClosed("server shut down before completion")
        for req in self.scheduler.close():
            if req.deadline is not None and req.deadline.expired():
                self._expire(req)
            else:
                self.metrics.inc("requests_failed")
                self._adapter_fail(req)
                req.handle._fail(err)
        for slot, req in enumerate(list(self.engine.requests)):
            if req is not None:
                self.engine.release(slot)
                self.metrics.inc("requests_failed")
                self._adapter_fail(req)
                req.handle._fail(err)
        self.engine.drop_flights()      # an undrained end: nobody's tokens
        self.metrics.set_active_slots(0)
        self.metrics.set_queue_depth(0)

    def _tick(self) -> None:
        clock = self._clock
        clock.enter("schedule", "serve.schedule")
        for req in self.scheduler.pop_expired():
            self._expire(req)
        for req in self.scheduler.pop_predicted_misses():
            self._shed(req)
        free = self.engine.free_slots()
        if free:
            admits, expired = self.scheduler.take(len(free))
            for req in expired:
                self._expire(req)
            for i, req in enumerate(admits):
                try:
                    self._admit(req, self.engine.free_slots()[0])
                except AdapterError as e:
                    # raised host-side BEFORE any device dispatch: the
                    # engine state is untouched, so only THIS request
                    # fails (unknown adapter / registry at pin capacity)
                    # — no reset, no requeue of innocents
                    self.metrics.inc("requests_failed")
                    self._adapter_fail(req)
                    req.handle._fail(e)
                except Exception as e:
                    # the failing request AND the rest of this admission
                    # batch (popped but not yet admitted) must all reach
                    # recovery — dropping them would hang their clients
                    self._recover(e, extra=admits[i:])
                    return
        engine = self.engine
        self.metrics.set_queue_depth(self.scheduler.depth)
        self.metrics.set_active_slots(engine.active_count)
        # the step launched in the tick before is read back behind this
        # tick's launch; with nobody owed a further token there is no
        # launch, and the read alone drains the pipeline
        behind, live = engine.in_flight, engine.live_count
        if not (behind or live):
            return
        fault_point("serve.step")
        if live:
            # the step's three spans share one tag dict: its number, as
            # snapshot()["decode_steps"] will count it, and the live slots
            clock.enter("decode_dispatch", "serve.decode.dispatch",
                        tags={"step": self.metrics.decode_steps + 1,
                              "live": live})
            ahead = engine.launch()
            self.metrics.inc("decode_steps")
            self.metrics.decode_step(*engine.step_load, ahead)
            self.metrics.sample_step(engine.step_sample_branch)
        if not behind:
            return
        events = engine.collect()       # leaves the clock in "emit"
        per_adapter = engine.store is not None
        now = time.monotonic()
        for ev in events:
            req = engine.requests[ev.slot]
            h = req.handle
            h._push(ev.token)
            self.metrics.inc("tokens_emitted")
            if per_adapter:
                self.metrics.adapter_tokens(req.adapter_id)
            if h._last_token_t is not None:
                self.metrics.observe_inter_token(now - h._last_token_t)
            h._last_token_t = now
            if ev.done or h._count() >= req.max_new_tokens:
                self._finish(req, ev.slot)

    def _admit(self, req: Request, slot: int) -> None:
        clock, h, corr = self._clock, req.handle, req.corr_id
        clock.enter("admit_host")
        # the admission's own span opens at that boundary, around the
        # spans of its parts; the engine divides it at the first token's
        # read-back, and what follows that is this span's own time
        t_admit = clock.t * 1e-9
        admission = _tracing.begin("serve.admit", t_admit)
        clock.span("serve.prefill.dispatch", corr)
        tags = {"slot": int(slot), "prompt_len": len(req.prompt)}
        try:
            req.attempts += 1   # count BEFORE any fault: a failed admission
            fault_point("serve.admit")  # spends retry budget, never loops
            wait = time.monotonic() - h._submit_t
            self.metrics.observe_queue_wait(wait)
            # the queue-wait lane slice: submit -> this admission (a
            # requeued request's later admissions re-enter the lane as
            # fresh queue_wait slices after the engine_reset marker)
            _tracing.record_span("queue_wait", t_admit - wait, t_admit,
                                 corr=corr, tags={"attempt": req.attempts})
            first, fin, hit_tokens = self.engine.admit(req, slot)
            tags["bucket"] = self.engine.bucket_for_prompt(
                len(req.prompt) - hit_tokens)
            if req.adapter_id is not None:
                tags["adapter"] = req.adapter_id
            self.metrics.inc("prefills")
            if self.engine.state_bytes_per_slot is not None:
                self.metrics.state_admission(len(req.prompt), tags["bucket"])
            if self.engine.pool is not None:
                tags["prefix_hit_tokens"] = int(hit_tokens)
                h.cache_hit_tokens = hit_tokens
                self.metrics.inc("prefix_hit_tokens", hit_tokens)
                self.metrics.inc("prefix_miss_tokens",
                                 len(req.prompt) - hit_tokens)
            h._push(first)
            self.metrics.inc("tokens_emitted")
            t1 = time.monotonic()
            if self.engine.store is not None:
                self.metrics.adapter_tokens(req.adapter_id)
            if h.ttft_s is None:  # a requeued request keeps its FIRST ttft
                h.ttft_s = t1 - h._submit_t
                self.metrics.observe_ttft(h.ttft_s)
                if self.engine.store is not None:
                    # under the first-admission guard, like TTFT: a crash-
                    # requeued request is ONE request, not one per attempt
                    # (requests_submitted counts it once; per_adapter must
                    # agree or per-tenant goodput skews)
                    self.metrics.adapter_request(req.adapter_id)
                    self.metrics.observe_adapter_ttft(req.adapter_id,
                                                      h.ttft_s)
            h._first_token_t = h._last_token_t = t1
            if fin or req.max_new_tokens == 1:
                # eos straight out of prefill: zero decode iterations
                self._finish(req, slot)
        finally:
            # a failed admission took the loop's time too: its span ends
            # with the tags it got to
            clock.enter("schedule")
            _tracing.end(admission, clock.t * 1e-9, corr=corr, tags=tags)
            clock.span("serve.schedule")

    def _finish(self, req: Request, slot: int) -> None:
        self.engine.release(slot)
        self.metrics.inc("requests_completed")
        self.metrics.set_active_slots(self.engine.active_count)
        h = req.handle
        n = h._count()
        if n > 1:
            # the request's decode stretch as ONE span, first token to
            # last; the steps behind it are the untraced lane's
            # serve.decode.* spans, the gaps are metrics.inter_token
            now = time.time()
            _tracing.record_span(
                "decode", now - (time.monotonic() - h._first_token_t), now,
                corr=req.corr_id, tags={"tokens": n - 1, "slot": int(slot)})
        _tracing.record_event("stream_end", corr=req.corr_id, tokens=n)
        h._finish()

    def _adapter_fail(self, req: Request) -> None:
        """Per-tenant failure accounting — the availability input the
        SLO burn-rate tracker diffs across scrapes. Recorded only when
        the engine serves through an adapter store, like every other
        per-tenant metric."""
        if self.engine.store is not None:
            self.metrics.adapter_failure(req.adapter_id)

    def _expire(self, req: Request) -> None:
        self.metrics.inc("requests_expired")
        self._adapter_fail(req)
        _tracing.record_event("expired", corr=req.corr_id)
        req.handle._fail(TimeoutError(
            f"request {req.id} expired in queue after "
            f"{req.deadline.total:.3f}s deadline"))

    def _shed(self, req: Request) -> None:
        """Post-admission shed: service degraded after this request was
        queued and its predicted wait now exceeds its deadline — fail it
        retryably NOW (Overloaded, a ``ConnectionError``) instead of
        letting it ride the queue into a guaranteed ``TimeoutError``."""
        self.metrics.inc("requests_shed")
        self._adapter_fail(req)
        _tracing.record_event("shed", corr=req.corr_id)
        req.handle._fail(Overloaded(
            f"request {req.id} shed from queue: predicted wait exceeds "
            f"its {req.deadline.total:.3f}s deadline; retry against "
            f"another replica"))

    def _recover(self, exc: BaseException, extra=()) -> None:
        """Crash-safe worker: reset the engine (donated buffers may be
        half-written mid-fault) and requeue every in-flight request at
        the queue head, bounded by ``max_request_retries`` re-admissions;
        over-budget requests fail with the fault."""
        inflight = [r for r in self.engine.requests if r is not None]
        inflight.extend(extra)
        warnings.warn(
            f"serve loop fault ({type(exc).__name__}: {exc}); resetting "
            f"engine, requeueing {len(inflight)} in-flight request(s)",
            RuntimeWarning)
        # crash artifact FIRST, while the ring still holds the lead-up:
        # the flight dump carries the failing requests' correlation ids,
        # their span tails, and the metric state at the moment of death
        corrs = [r.corr_id for r in inflight]
        for c in corrs:
            _tracing.record_event("engine_reset", corr=c)
        _flight.note("engine_reset", corr=corrs[0] if corrs else None,
                     error=f"{type(exc).__name__}: {exc}",
                     inflight=list(corrs))
        _flight.dump("engine_reset", corr=corrs[0] if corrs else None,
                     extra={"error": f"{type(exc).__name__}: {exc}",
                            "inflight": list(corrs),
                            "server": self._obs_label})
        try:
            self.engine.reset()
        except Exception as reset_exc:  # pragma: no cover
            for req in inflight:
                self.metrics.inc("requests_failed")
                self._adapter_fail(req)
                req.handle._fail(reset_exc)
            return
        # requeue newest-first via appendleft so the OLDEST submission
        # (lowest id) ends at the queue head — slot order is reuse order,
        # not admission order, so it can't be trusted for fairness
        for req in sorted(inflight, key=lambda r: r.id, reverse=True):
            if req.attempts > self.max_request_retries:
                self.metrics.inc("requests_failed")
                self._adapter_fail(req)
                req.handle._fail(exc)
            else:
                self.metrics.inc("requests_requeued")
                req.handle._restart()
                self.scheduler.requeue(req)
        self.metrics.set_active_slots(0)
        self.metrics.set_queue_depth(self.scheduler.depth)
