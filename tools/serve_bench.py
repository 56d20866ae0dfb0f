"""Latency-percentile load bench for the serving stack — solo or fleet.

Open-loop Poisson load (arrivals don't wait for completions — the honest
way to measure a server: closed-loop generators self-throttle and hide
queueing collapse) against ``paddle_tpu.serving``, reporting the serving
numbers that matter and the compile discipline. Prints ONE JSON line:

    {"metric": "gpt_serve_requests_per_sec", "value": N, "unit": "req/s",
     "extra": {"goodput": ..., "ttft_p50_ms": ..., "ttft_p99_ms": ...,
               "inter_token_p50_ms": ..., "inter_token_p99_ms": ...,
               "tokens_per_sec": ..., "slot_occupancy": ...,
               "cache_hit_rate": ..., "steady_state_recompiles": ...}}

Defaults reproduce the PR 4 single-replica bench byte-for-byte (the
``gpt_serve_requests_per_sec`` breadth metric ``bench.py`` probes).
Fleet knobs:

- ``--replicas N`` puts a load-aware ``ReplicaRouter`` in front of N
  ``InferenceServer`` replicas (prefix-affinity + occupancy placement);
- ``--prefix-cache-mb M`` attaches a paged prefix/KV block pool to every
  replica (``--block-tokens`` sets the page size);
- ``--prefix-tokens P`` switches the trace generator prefix-heavy: a
  ``--prefix-frac`` share of requests open with the SAME P-token system
  prefix (the millions-of-users shape), the rest stay uniform random;
- ``--crash-replica`` hard-kills one replica mid-window (no drain) —
  the router must requeue its requests onto survivors with no recompile
  and, for the ``--verify K`` seeded-greedy probes, no token divergence
  vs a solo ``generate`` (the fleet robustness gate).

Multi-tenant LoRA knobs:

- ``--adapters N`` registers N synthetic tenants (rank ``--adapter-rank``
  LoRA adapters on the attention+MLP projections) in a per-replica
  ``AdapterStore``; an ``--adapter-frac`` share of requests carries a
  tenant id drawn Zipf-style (skewed popularity — the realistic shape);
- ``--max-loaded`` caps device-resident adapters per replica (default:
  all N), so a smaller value exercises LRU load/evict churn under load —
  which must stay recompile-free;
- ``--verify`` probes with a tenant id are checked token-exact against a
  solo ``generate`` with that adapter's weights loaded.

The JSON gains a ``per_adapter`` block (offered/completed/tokens/TTFT
p50 per tenant) plus registry load/evict totals, and an ``slo_report``
block: per-tenant availability + multi-window burn rates over the
measured window against the ``--slo-ttft`` / ``--slo-availability``
targets (``observability.slo``).

Warmup touches every prefill bucket on every replica first; the
measured window must then hold at ``#buckets + 1`` programs per replica
— ANY steady-state recompile exits non-zero (the serving analogue of
``tools/retrace_report.py``), as does a verify mismatch or an
unrecovered crash casualty.

    python tools/serve_bench.py                  # CPU-safe tiny config
    python tools/serve_bench.py --check          # quick CI/bench probe
    python tools/serve_bench.py --preset serving --slots 8 --rate 4
    python tools/serve_bench.py --replicas 2 --prefix-cache-mb 8 \\
        --prefix-tokens 24 --crash-replica --verify 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def _pct(values, p):
    if not values:
        return 0.0
    return float(np.percentile(np.asarray(values, np.float64), p))


def _emit(record, json_out=None):
    """Print the one-line JSON record; mirror it to ``--json-out`` so
    the robustness gate can diff it against a checked-in baseline."""
    line = json.dumps(record)
    print(line)
    if json_out:
        with open(json_out, "w") as f:
            f.write(line + "\n")


def _kv_logit_error(model, prompt, steps, max_length):
    """Max relative logit error of an int8-quantized KV cache against
    full precision, over a teacher-forced decode (same token sequence
    through both caches, so every step compares like with like).
    Prefill attends over the un-quantized fresh block, so the error
    budget is spent exactly where the quantized path reads the cache:
    the decode steps."""
    import jax.numpy as jnp

    from paddle_tpu.models.kv_cache import init_cache
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     param_state)

    was_training = model.training
    model.eval()
    try:
        params, buffers = param_state(model), buffer_state(model)
        ids = jnp.asarray(prompt[None].astype(np.int32))
        seqs = {}
        for name, kv in (("full", None), ("int8", "int8")):
            cache = init_cache(model, 1, max_length, kv_dtype=kv)
            (lg, cache), _ = functional_call(
                model, params, buffers, ids, cache=cache,
                position_offset=0)
            per_step = [np.asarray(lg[:, -1], np.float32)]
            pos = int(prompt.shape[0])
            for s in range(steps):
                if name == "full":
                    tok = int(np.argmax(per_step[-1]))
                    seqs.setdefault("toks", []).append(tok)
                else:
                    tok = seqs["toks"][s]   # teacher-forced: same tokens
                (lg, cache), _ = functional_call(
                    model, params, buffers,
                    jnp.full((1, 1), tok, jnp.int32), cache=cache,
                    position_offset=pos + s)
                per_step.append(np.asarray(lg[:, -1], np.float32))
            seqs[name] = np.concatenate(per_step, axis=0)
    finally:
        if was_training:
            model.train()
    ref, quant = seqs["full"], seqs["int8"]
    scale = max(float(np.max(np.abs(ref))), 1e-9)
    return float(np.max(np.abs(ref - quant))) / scale


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", choices=("gpt", "llama"), default="gpt")
    ap.add_argument("--preset", choices=("tiny", "small", "serving"), default="tiny")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="offered load, requests/s (Poisson arrivals)")
    ap.add_argument("--requests", type=int, default=16,
                    help="measured requests after warmup")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--buckets", type=int, nargs="+", default=(16, 32))
    ap.add_argument("--max-queue-depth", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-request completion wait cap (s)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request queue-wait SLO (s): requests that "
                         "cannot start in time expire and count against "
                         "goodput — the number queueing collapse "
                         "actually destroys")
    ap.add_argument("--check", action="store_true",
                    help="small fixed workload for CI / bench.py probing")
    ap.add_argument("--kv-dtype", choices=("none", "int8"), default="none",
                    help="KV-cache storage dtype for every replica "
                         "(int8 = quantized slots + pool blocks)")
    ap.add_argument("--kv-logit-tol", type=float, default=0.05,
                    help="max relative logit error (vs full-precision "
                         "KV) the quantized --verify gate accepts")
    # ---- fleet knobs ----
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="per-replica paged KV block pool budget (0=off)")
    ap.add_argument("--block-tokens", type=int, default=8,
                    help="prefix-cache page size in tokens")
    ap.add_argument("--prefix-tokens", type=int, default=0,
                    help="shared system-prefix length for the "
                         "prefix-heavy trace (0=uniform random trace)")
    ap.add_argument("--prefix-frac", type=float, default=0.9,
                    help="share of requests carrying the shared prefix")
    ap.add_argument("--affinity-weight", type=float, default=0.75)
    ap.add_argument("--crash-replica", action="store_true",
                    help="hard-kill one replica mid-window (router must "
                         "reroute with no recompiles / no divergence)")
    ap.add_argument("--verify", type=int, default=0,
                    help="seeded-greedy probes checked token-exact "
                         "against a solo generate after the window")
    # ---- multi-tenant LoRA knobs ----
    ap.add_argument("--adapters", type=int, default=0,
                    help="register N synthetic LoRA tenants per replica "
                         "(0 = base-only trace)")
    ap.add_argument("--adapter-frac", type=float, default=0.7,
                    help="share of requests carrying a tenant id "
                         "(Zipf-skewed popularity over --adapters)")
    ap.add_argument("--adapter-rank", type=int, default=4)
    ap.add_argument("--max-loaded", type=int, default=0,
                    help="device-resident adapters per replica (0 = all "
                         "of --adapters; smaller exercises LRU churn)")
    # ---- SLO report knobs ----
    ap.add_argument("--slo-ttft", type=float, default=0.5,
                    help="per-tenant TTFT target (s) for the slo_report "
                         "block (window-mean judged)")
    ap.add_argument("--slo-availability", type=float, default=0.99,
                    help="per-tenant availability target for the "
                         "slo_report burn rates")
    # ---- adversarial fairness trace (SLO control loop, PR 16) ----
    ap.add_argument("--fairness", action="store_true",
                    help="adversarial SLO-control-loop trace: one "
                         "abusive tenant at 10x rate (token-bucket "
                         "throttled), a traffic spike that must force "
                         "a REAL burn-driven scale-out (child replica "
                         "over rpc), protected tenants' fast-window "
                         "burn must never edge-trigger, zero requests "
                         "lost across the scale events")
    ap.add_argument("--child-replica", action="store_true",
                    help="internal: host one replica for a --fairness "
                         "parent (rpc rank 1)")
    ap.add_argument("--endpoint", default=None,
                    help="internal: rpc master endpoint for "
                         "--child-replica")
    # ---- disaggregated prefill/decode fleet (PR 19) ----
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated fleet: dedicated prefill "
                         "replicas fill KV blocks and migrate them to "
                         "decode replicas over rpc (serving.disagg); "
                         "measures cold vs warm replica boot through "
                         "the persistent compile cache, per-pool "
                         "occupancy/goodput, and migration overhead")
    ap.add_argument("--prefill-ratio", type=float, default=0.5,
                    help="share of --replicas dedicated to the prefill "
                         "pool in --disagg mode (at least one replica "
                         "per pool; the PR 16 autoscaler scales each "
                         "pool on its own burn signal)")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="also write the one-line JSON record to PATH "
                         "(the regression-gate input)")
    ap.add_argument("--disagg-child", choices=("prefill", "decode"),
                    default=None,
                    help="internal: host one disagg replica of this "
                         "role for a --disagg parent")
    ap.add_argument("--rpc-name", default=None,
                    help="internal: rpc worker name for --disagg-child")
    ap.add_argument("--rank", type=int, default=None,
                    help="internal: rpc rank for --disagg-child")
    ap.add_argument("--world", type=int, default=None,
                    help="internal: rpc world size for --disagg-child")
    ap.add_argument("--wait-file", default=None,
                    help="internal: defer the model build until this "
                         "file exists (the warm-boot release gate)")
    args = ap.parse_args(argv)
    if args.child_replica:
        return _child_replica_main(args)
    if args.disagg_child:
        return _disagg_child_main(args)
    if args.fairness:
        return _fairness_main(args)
    if args.disagg:
        return _disagg_main(args)
    if args.check:
        args.requests = min(args.requests, 8)
        args.rate = min(args.rate, 4.0)
        args.new_tokens = min(args.new_tokens, 10)
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.crash_replica and args.replicas < 2:
        ap.error("--crash-replica needs --replicas >= 2 (someone must "
                 "survive)")

    import jax

    from decode_bench import build_model
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.serving import (InferenceServer, LatencyHistogram,
                                    QueueFull, ReplicaRouter)

    model, cfg = build_model(args.model, args.preset)
    prefix_pad = args.prefix_tokens + args.block_tokens
    max_length = min(cfg.max_position_embeddings,
                     max(args.buckets) + args.new_tokens + 8
                     + (prefix_pad if args.prefix_tokens else 0))
    if args.prefix_tokens and (args.prefix_tokens + args.block_tokens
                               + args.new_tokens > max_length):
        ap.error(
            f"--prefix-tokens {args.prefix_tokens} + --block-tokens "
            f"{args.block_tokens} + --new-tokens {args.new_tokens} "
            f"exceeds the model's cache length {max_length} "
            f"(max_position_embeddings={cfg.max_position_embeddings}); "
            f"shrink the prefix or pick a larger preset")
    if args.prefix_tokens and (args.prefix_tokens + args.block_tokens
                               > max(args.buckets)):
        # a cold shared-prefix prompt would overflow the top declared
        # bucket into the ladder — a legitimate warmup compile the
        # #buckets+1 budget check would then (correctly) reject
        ap.error(
            f"--prefix-tokens {args.prefix_tokens} + --block-tokens "
            f"{args.block_tokens} overflows the largest prefill bucket "
            f"{max(args.buckets)}; declare a bucket that fits the cold "
            f"prefix prompt (e.g. --buckets {min(args.buckets)} "
            f"{args.prefix_tokens + args.block_tokens})")
    prefix_cache = (int(args.prefix_cache_mb * (1 << 20))
                    if args.prefix_cache_mb > 0 else None)

    # ---- multi-tenant LoRA: N synthetic adapters, one store per replica
    tenant_names, tenant_trees, stores = [], {}, []
    if args.adapters > 0:
        from paddle_tpu.lora import (AdapterStore, LoraConfig, apply_lora,
                                     lora_state)

        lcfg = LoraConfig(rank=args.adapter_rank, alpha=2.0 * args.adapter_rank)
        apply_lora(model, lcfg)
        zero = lora_state(model)
        arng = np.random.default_rng(args.seed + 777)
        tenant_names = [f"tenant{k}" for k in range(args.adapters)]
        for name in tenant_names:
            tenant_trees[name] = {
                k: arng.normal(0.0, 0.02, v.shape).astype(np.float32)
                for k, v in zero.items()}
        max_loaded = args.max_loaded or args.adapters
        for _ in range(args.replicas):
            store = AdapterStore(model, lcfg, max_loaded=max_loaded)
            for name in tenant_names:
                store.register(name, tenant_trees[name])
            stores.append(store)
        # Zipf-ish popularity: a few hot tenants, a long cool tail
        zipf_w = np.array([1.0 / (k + 1) ** 1.1
                           for k in range(args.adapters)])
        zipf_w /= zipf_w.sum()
    kv_dtype = None if args.kv_dtype == "none" else args.kv_dtype
    servers = [
        InferenceServer(
            model, slots=args.slots, max_length=max_length,
            prefill_buckets=args.buckets,
            max_queue_depth=args.max_queue_depth,
            prefix_cache=(dict(max_bytes=prefix_cache,
                               block_tokens=args.block_tokens)
                          if prefix_cache else None),
            adapter_store=stores[i] if stores else None,
            kv_dtype=kv_dtype)
        for i in range(args.replicas)]
    fleet = args.replicas > 1
    router = None
    if fleet:
        router = ReplicaRouter(affinity_weight=args.affinity_weight)
        names = [router.add_replica(s, f"r{i}")
                 for i, s in enumerate(servers)]
    srv = servers[0]
    rng = np.random.default_rng(args.seed)
    lens = sorted(b - 2 for b in srv.engine.prefill_buckets)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, (int(n),)).astype(np.int32)

    shared_prefix = (prompt(args.prefix_tokens)
                     if args.prefix_tokens else None)

    def trace_prompt(i):
        """The measured trace: prefix-heavy when --prefix-tokens is
        set, PR 4's uniform-random lengths otherwise."""
        if shared_prefix is not None and rng.random() < args.prefix_frac:
            sfx = prompt(int(rng.integers(2, args.block_tokens + 1)))
            return np.concatenate([shared_prefix, sfx])
        return prompt(int(rng.integers(4, max(lens) + 1)))

    def trace_tenant(i):
        """Per-request tenant id: an --adapter-frac share of requests
        carries one, drawn Zipf-style over the registered adapters."""
        if not tenant_names or rng.random() >= args.adapter_frac:
            return None
        return tenant_names[int(rng.choice(args.adapters, p=zipf_w))]

    # ---- warmup: touch every bucket + the decode program, per replica ----
    t_warm = time.perf_counter()
    for s in servers:
        for L in lens:
            s.submit(prompt(L), max_new_tokens=4).result(
                timeout=args.timeout)
        s.submit(prompt(lens[0]), max_new_tokens=4, do_sample=True,
                 temperature=0.9, top_p=0.9, seed=1).result(
                     timeout=args.timeout)
        if shared_prefix is not None:
            # the suffix bucket a prefix hit lands in must be warm too
            s.submit(np.concatenate([shared_prefix, prompt(4)]),
                     max_new_tokens=4).result(timeout=args.timeout)
    warmup_s = time.perf_counter() - t_warm
    compiles_before = compile_cache.cache_stats()["compiles"]
    for s in servers:
        s.metrics.reset()

    # SLO burn-rate evaluation over the measured window: baseline
    # ingest here, final ingest after the window; the report block
    # rides the JSON (per-tenant availability + burn vs the --slo-*
    # targets). dump_on_burn off — a bench judging a historical window
    # must not write crash artifacts.
    from paddle_tpu.observability.slo import SloPolicy, SloTracker

    slo = SloTracker(
        SloPolicy(target_ttft_s=args.slo_ttft,
                  target_availability=args.slo_availability,
                  fast_window_s=60.0, slow_window_s=1800.0),
        dump_on_burn=False)

    def slo_snapshot():
        return router.snapshot() if fleet else srv.snapshot()

    slo.ingest(slo_snapshot())

    def submit(i, p, **kw):
        if fleet:
            return router.submit(p, **kw)
        return srv.submit(p, **kw)

    # ---- measured open-loop window ----
    interarrival = rng.exponential(1.0 / max(args.rate, 1e-6),
                                   args.requests)
    crash_at = args.requests // 2 if args.crash_replica else None
    crashed_replica = None
    # verify probes ride just below the crash point so the ones most
    # likely to be in flight when the replica dies are token-checked
    verify_idx = (set(range(max(0, crash_at - args.verify), crash_at))
                  if crash_at is not None
                  else set(range(args.verify)))
    verify_solo = {}
    tenant_of = {}
    handles, rejected = [], 0
    t0 = time.perf_counter()
    for i in range(args.requests):
        target = t0 + float(interarrival[:i + 1].sum())
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        if crash_at is not None and i == crash_at:
            # hard kill, no drain: queued + in-flight requests must be
            # rerouted by the router, not lost
            crashed_replica = names[-1]
            servers[-1].shutdown(drain=False, timeout=60.0)
        p = trace_prompt(i)
        tid = trace_tenant(i)
        tenant_of[i] = tid
        verify = i in verify_idx
        kw = dict(max_new_tokens=args.new_tokens, seed=args.seed + i,
                  deadline=args.deadline, adapter_id=tid)
        if verify:
            # correctness probes must not expire on the SLO — a queue-wait
            # miss would masquerade as token divergence
            kw["deadline"] = None
            verify_solo[i] = (p, tid)   # greedy + seeded: reproducible
        else:
            kw.update(do_sample=bool(i % 2), temperature=0.8, top_p=0.95)
        try:
            handles.append((i, submit(i, p, **kw)))
        except QueueFull:
            rejected += 1  # open loop: a reject is goodput lost, not a wait
    completed, failed, expired = 0, 0, 0
    results = {}
    for i, h in handles:
        try:
            results[i] = h.result(timeout=args.timeout)
            completed += 1
        except TimeoutError:
            if args.deadline is not None:
                expired += 1   # queue-wait SLO miss — goodput lost, not a bug
            else:
                failed += 1    # no SLO in play: a hung handle IS a lost
                               # request (the --crash-replica gate must see it)
        except Exception:
            failed += 1
    elapsed = time.perf_counter() - t0
    compiles_after = compile_cache.cache_stats()["compiles"]
    steady = compiles_after - compiles_before

    # ---- verify: seeded-greedy fleet streams == solo generate ----
    # divergence is judged only on probes that COMPLETED — a probe shed
    # by backpressure or lost to the crash is a capacity/loss event
    # (already visible in rejected/failed, and failed trips the crash
    # gate), not nondeterminism
    verify_failures = 0
    verify_compared = 0
    if verify_solo and stores:
        from paddle_tpu.lora import clear_adapter, set_adapter
    for i, (p, tid) in verify_solo.items():
        got = results.get(i)
        if got is None:
            continue
        verify_compared += 1
        if stores:
            # the tenant's solo reference runs with ITS adapter loaded
            # into the model's own leaves (engines hold their snapshot)
            if tid is None:
                clear_adapter(model)
            else:
                set_adapter(model, tenant_trees[tid])
        # the solo reference runs with the SAME kv storage dtype, so the
        # served stream stays token-EXACT even when quantized (fidelity
        # of quantization itself is the separate logit-error gate below)
        solo = model.generate(
            p[None], max_new_tokens=args.new_tokens,
            max_length=max_length, prefill_buckets=tuple(args.buckets),
            kv_dtype=kv_dtype)[0]
        if not np.array_equal(np.asarray(got), solo):
            verify_failures += 1
    if verify_solo and stores:
        clear_adapter(model)
    # quantized fidelity gate: the token-parity probes above prove the
    # served stream matches solo-with-int8; this bounds how far the
    # int8 cache's LOGITS drift from full precision (the bitwise gate's
    # replacement for a lossy representation)
    kv_logit_err = None
    if kv_dtype is not None and args.verify:
        probe = prompt(lens[0])
        kv_logit_err = _kv_logit_error(model, probe,
                                       steps=min(args.new_tokens, 8),
                                       max_length=max_length)
    # the solo engine above compiles its own programs; they are not
    # serving-loop recompiles
    live = [s for i, s in enumerate(servers)
            if not (crashed_replica is not None and i == len(servers) - 1)]
    snaps = [s.snapshot() for s in live]
    slo.ingest(slo_snapshot())
    slo_report = slo.report()
    # unified-registry scrape while every live server's collectors are
    # still registered: occupancy, hit-rate and compile counters land in
    # the BENCH artifact alongside the throughput numbers (the SLO
    # ingest above lands its burn gauges first)
    from paddle_tpu.observability import default_registry

    metrics_snap = default_registry().snapshot()
    for s in live:
        s.shutdown(drain=True, timeout=60.0)

    # ---- report ----
    ttfts = [h.ttft_s for _, h in handles
             if getattr(h, "ttft_s", None) is not None]
    inter = LatencyHistogram.merge(
        [s.metrics.inter_token for s in live]).summary()
    queue_wait = LatencyHistogram.merge(
        [s.metrics.queue_wait for s in live]).summary()
    hit = sum(sn["prefix_hit_tokens"] for sn in snaps)
    miss = sum(sn["prefix_miss_tokens"] for sn in snaps)
    tokens_emitted = sum(sn["tokens_emitted"] for sn in snaps)
    per_replica_compiles = [s.engine.cache_stats() for s in live]
    budget = len(srv.engine.prefill_buckets) + 1
    over_budget = [
        i for i, cc in enumerate(per_replica_compiles)
        if cc["prefill"]["compiles"] + cc["decode"]["compiles"] > budget]
    occ = (sum(sn["slot_occupancy"] for sn in snaps) / len(snaps)
           if snaps else 0.0)

    per_adapter = {}
    if stores:
        # offered/completed per tenant from the trace bookkeeping,
        # merged with the servers' per_adapter metric blocks
        for i, tid in tenant_of.items():
            name = tid or "base"
            e = per_adapter.setdefault(
                name, {"offered": 0, "completed": 0, "tokens": 0,
                       "ttft_p50_ms": 0.0})
            e["offered"] += 1
            if i in results:
                e["completed"] += 1
        for sn in snaps:
            for name, m in sn.get("per_adapter", {}).items():
                e = per_adapter.setdefault(
                    name, {"offered": 0, "completed": 0, "tokens": 0,
                           "ttft_p50_ms": 0.0})
                e["tokens"] += m["tokens"]
                e["ttft_p50_ms"] = max(e["ttft_p50_ms"], m["ttft_p50_ms"])
        adapter_loads = sum(st.stats()["loads"] for st in stores)
        adapter_evictions = sum(st.stats()["evictions"] for st in stores)

    record = {
        "metric": f"{args.model}_serve_requests_per_sec",
        "value": round(completed / max(elapsed, 1e-9), 3),
        "unit": "req/s",
        "extra": {
            "goodput": round(completed / max(args.requests, 1), 4),
            "offered_requests": args.requests,
            "completed": completed,
            "rejected": rejected,
            "expired": expired,
            "failed": failed,
            "deadline_s": args.deadline,
            "offered_rate_per_sec": args.rate,
            "elapsed_s": round(elapsed, 3),
            "tokens_per_sec": round(tokens_emitted / max(elapsed, 1e-9), 2),
            "ttft_p50_ms": round(_pct(ttfts, 50) * 1e3, 3),
            "ttft_p99_ms": round(_pct(ttfts, 99) * 1e3, 3),
            "inter_token_p50_ms": inter["p50_ms"],
            "inter_token_p99_ms": inter["p99_ms"],
            "queue_wait_p99_ms": queue_wait["p99_ms"],
            "slot_occupancy": round(occ, 4),
            "slots": args.slots,
            "new_tokens": args.new_tokens,
            "replicas": args.replicas,
            "live_replicas": len(live),
            "prefix_cache_mb": args.prefix_cache_mb,
            "prefix_tokens": args.prefix_tokens,
            "cache_hit_rate": round(hit / (hit + miss), 4)
            if (hit + miss) else 0.0,
            "prefix_hit_tokens": hit,
            "prefix_miss_tokens": miss,
            "prefill_compiles": sum(
                cc["prefill"]["compiles"] for cc in per_replica_compiles),
            "decode_compiles": sum(
                cc["decode"]["compiles"] for cc in per_replica_compiles),
            "compile_budget_per_replica": budget,
            "steady_state_recompiles": steady,
            "warmup_s": round(warmup_s, 2),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "preset": args.preset,
            "check": bool(args.check),
            "kv_dtype": args.kv_dtype,
            **({"kv_logit_err": round(kv_logit_err, 6),
                "kv_logit_tol": args.kv_logit_tol}
               if kv_logit_err is not None else {}),
            "metrics": metrics_snap,
            "slo_report": slo_report,
            **({"crashed_replica": crashed_replica,
                "rerouted": router.snapshot()["requests_rerouted"]}
               if crashed_replica is not None else {}),
            **({"verified": len(verify_solo),
                "verify_compared": verify_compared,
                "verify_failures": verify_failures}
               if args.verify else {}),
            **({"adapters": args.adapters,
                "adapter_frac": args.adapter_frac,
                "adapter_rank": args.adapter_rank,
                "max_loaded": args.max_loaded or args.adapters,
                "adapter_loads": adapter_loads,
                "adapter_evictions": adapter_evictions,
                "per_adapter": per_adapter}
               if stores else {}),
        },
    }
    _emit(record, args.json_out)
    rc = 0
    if steady:
        print(f"FAIL: {steady} recompile(s) during the measured window — "
              f"the serving loop is not shape-stable (see "
              f"compile_cache.cache_stats() signatures)", file=sys.stderr)
        rc = 1
    if over_budget:
        print(f"FAIL: replica(s) {over_budget} exceeded the "
              f"#buckets+1={budget} compile budget", file=sys.stderr)
        rc = 1
    if verify_failures:
        print(f"FAIL: {verify_failures}/{verify_compared} completed "
              f"seeded-greedy probes diverged from solo generate "
              f"(placement/reroute changed tokens)", file=sys.stderr)
        rc = 1
    if kv_logit_err is not None and kv_logit_err > args.kv_logit_tol:
        print(f"FAIL: int8 KV cache drifts logits by "
              f"{kv_logit_err:.4f} (rel) > tol {args.kv_logit_tol} — "
              f"quantization error is out of bounds", file=sys.stderr)
        rc = 1
    if args.crash_replica and failed:
        print(f"FAIL: {failed} request(s) lost to the replica crash — "
              f"the router did not requeue them onto survivors",
              file=sys.stderr)
        rc = 1
    return rc


# --------------------------------------------------------------------
# Adversarial fairness trace: the SLO control loop end to end.
#
# Topology: rank 0 (this process) runs the router over one local
# replica; the burn-driven scale-out spawns rank 1 ("auto-r1") as a
# CHILD serve_bench process hosting a second replica over the rpc
# fabric (remote.host_server). Four tenants: "alice"/"bob" (protected,
# unthrottled), "abuser" (10x offered rate, token-bucket limited), and
# "spike" (a mid-run burst with a tight queue-wait deadline whose
# expiries burn the slow window — the legitimate overload signal the
# autoscaler must answer). Gates: the scale-out really happened and
# was triggered by the spike/fleet burn (NEVER the abuser — rate-limit
# rejects book no tenant failures, so abuse can't buy capacity), the
# protected tenants' fast window never edge-triggered, zero requests
# were lost (failed == 0) across the scale event, and the #buckets+1
# compile budget held on BOTH replicas, the cold-started one included.

_FAIR_TENANTS = ("alice", "bob", "abuser", "spike")
_FAIR_PROTECTED = ("alice", "bob")
_FAIR_ABUSER_RATE = 1.0      # admitted req/s the abuser is entitled to
_FAIR_SPIKE_N = 48           # spike burst depth (~16 service times on
                             # default slots: the tail MUST miss the
                             # ~2-service-time deadline on any machine)


def _fair_geometry(args):
    return dict(slots=args.slots, prefill_buckets=tuple(args.buckets),
                max_queue_depth=args.max_queue_depth,
                tenant_limits={"abuser": (_FAIR_ABUSER_RATE, 2.0)},
                fair_queueing=True)


def _fair_server(args, model):
    """One replica with the PR 16 admission knobs on: per-tenant DRR
    fair queueing + the abuser's token bucket, plus the shared adapter
    registry (per-tenant metrics need adapter-id traffic)."""
    from paddle_tpu.lora import (AdapterStore, LoraConfig, apply_lora,
                                 lora_state)
    from paddle_tpu.serving import InferenceServer

    lcfg = LoraConfig(rank=2, alpha=4.0)
    apply_lora(model, lcfg)
    zero = lora_state(model)
    arng = np.random.default_rng(args.seed + 777)   # same seed both
    store = AdapterStore(model, lcfg,                # ranks: same trees
                         max_loaded=len(_FAIR_TENANTS))
    for name in _FAIR_TENANTS:
        store.register(name, {
            k: arng.normal(0.0, 0.02, v.shape).astype(np.float32)
            for k, v in zero.items()})
    cfg_max_len = max(args.buckets) + args.new_tokens + 8
    srv = InferenceServer(model, max_length=cfg_max_len,
                          adapter_store=store, **_fair_geometry(args))
    return srv


def _fair_warm(srv, args, rng, vocab):
    """Touch every prefill bucket + the decode program (greedy trace:
    the budget must close at #buckets+1)."""
    for b in srv.engine.prefill_buckets:
        p = rng.integers(0, vocab, (b - 2,)).astype(np.int32)
        srv.submit(p, max_new_tokens=4).result(timeout=args.timeout)


def _child_replica_main(args) -> int:
    """Rank 1 of the fairness drill: host one warmed replica and serve
    until the parent signals stop. Spawned mid-run by the autoscaler —
    everything from here to the first served token is the cold-start
    window the parent reports as ``cold_start_ttft_s``."""
    from decode_bench import build_model
    from paddle_tpu.distributed import rpc
    from paddle_tpu.serving import remote

    rpc.init_rpc(name="auto-r1", rank=1, world_size=2,
                 master_endpoint=args.endpoint)
    model, cfg = build_model(args.model, args.preset)
    srv = _fair_server(args, model)
    # warm BEFORE hosting: wait_ready green means placeable at full
    # speed, and the measured window stays recompile-free on this
    # replica too
    _fair_warm(srv, args, np.random.default_rng(args.seed + 1),
               cfg.vocab_size)
    remote.host_server(srv, name="default")
    remote.wait_for_stop(timeout=900.0)
    try:
        srv.shutdown(drain=False, timeout=20.0)
    except Exception:
        pass
    rpc.shutdown(timeout=6.0)
    return 0


def _fairness_main(args) -> int:
    import socket
    import subprocess

    import jax

    from decode_bench import build_model
    from paddle_tpu.distributed import rpc
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.observability.slo import SloPolicy
    from paddle_tpu.serving import (Autoscaler, ProcessReplicaSpawner,
                                    QueueFull, RateLimited,
                                    ReplicaRouter)
    from paddle_tpu.serving import remote as remote_mod

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        endpoint = f"127.0.0.1:{s.getsockname()[1]}"

    model, cfg = build_model(args.model, args.preset)
    local = _fair_server(args, model)
    policy = SloPolicy(
        # generous TTFT target: badness in this trace is AVAILABILITY
        # (spike expiries), so the burn evidence is machine-speed-proof
        target_ttft_s=30.0, target_availability=0.99,
        fast_window_s=15.0, slow_window_s=180.0)
    router = ReplicaRouter(slo_policy=policy)
    router.add_replica(local, "r0")

    child_argv = [
        sys.executable, os.path.abspath(__file__),
        "--child-replica", "--endpoint", endpoint,
        "--model", args.model, "--preset", args.preset,
        "--slots", str(args.slots),
        "--new-tokens", str(args.new_tokens),
        "--buckets", *[str(b) for b in args.buckets],
        "--max-queue-depth", str(args.max_queue_depth),
        "--seed", str(args.seed)]
    spawner = ProcessReplicaSpawner(
        child_argv, "auto-r1",
        init=lambda: rpc.init_rpc(name="bench", rank=0, world_size=2,
                                  master_endpoint=endpoint),
        rpc_timeout=30.0, connect_deadline=2.0, ready_timeout=600.0,
        env=dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu"))
    cold = {}
    rng = np.random.default_rng(args.seed)
    lens = sorted(b - 2 for b in local.engine.prefill_buckets)

    def prompt():
        n = int(rng.integers(4, max(lens) + 1))
        return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)

    def spawn(name):
        """The autoscaler's actuator, wrapped to time the warm-boot
        window: child process start -> rpc rendezvous -> model build +
        bucket warmup -> host_server -> first served token."""
        t0 = time.perf_counter()
        replica = spawner(name)
        t_ready = time.perf_counter()
        h = replica.submit(prompt=prompt(), max_new_tokens=4)
        h.result(timeout=args.timeout)
        cold["cold_start_ttft_s"] = round(
            (t_ready - t0) + (h.ttft_s or 0.0), 3)
        cold["probe_ttft_s"] = round(h.ttft_s or 0.0, 4)
        return replica

    auto = Autoscaler(
        router, spawn, min_replicas=1, max_replicas=2,
        sustain_ticks=2, cooldown_s=300.0, replica_prefix="auto-r")

    _fair_warm(local, args, rng, cfg.vocab_size)
    # one timed service round-trip calibrates the spike's queue-wait
    # deadline to THIS machine (~2 service times): the 48-deep burst
    # tail then misses it whatever the absolute hardware speed, so the
    # burn evidence is deterministic, not host-dependent
    t_cal = time.perf_counter()
    local.submit(prompt(), max_new_tokens=args.new_tokens).result(
        timeout=args.timeout)
    spike_deadline = max(0.05, 2.0 * (time.perf_counter() - t_cal))
    local.metrics.reset()
    compiles_before = compile_cache.cache_stats()["compiles"]

    # ---- the trace: per-tenant Poisson arrivals + one spike burst ----
    protected_rate = 1.5
    events = []        # (t, tenant, deadline)
    for name, rate, t_end in (("alice", protected_rate, 16.0),
                              ("bob", protected_rate, 16.0),
                              ("abuser", 10 * protected_rate, 8.0)):
        t = 0.0
        while True:
            t += float(rng.exponential(1.0 / rate))
            if t >= t_end:
                break
            events.append((t, name, None))
    spike_at = 6.0
    for k in range(_FAIR_SPIKE_N):   # the legitimate overload: a burst
        events.append((spike_at + 0.01 * k, "spike",   # too big for one
                       spike_deadline))               # replica to hold
    events.sort()

    handles, rate_limited, rejected = [], 0, 0
    protected_breached, abuser_breached = [], []
    trigger = None
    tick_every, next_tick = 1.0, 1.0
    t0 = time.perf_counter()
    for t_at, tenant, deadline in events:
        now = time.perf_counter() - t0
        if t_at > now:
            time.sleep(t_at - now)
        while time.perf_counter() - t0 >= next_tick:
            d = auto.tick()
            if d is not None and d["action"] == "scale_out":
                trigger = d
            rep = router.slo_report() or {}
            for name, ten in rep.get("tenants", {}).items():
                if name in _FAIR_PROTECTED and (ten["fast_breached"]
                                                or ten["alerting"]):
                    protected_breached.append(name)
                if name == "abuser" and (ten["fast_breached"]
                                         or ten["slow_breached"]):
                    abuser_breached.append(ten)
            next_tick += tick_every
        try:
            handles.append((tenant, deadline, router.submit(
                prompt(), max_new_tokens=args.new_tokens,
                adapter_id=tenant, deadline=deadline,
                seed=args.seed)))
        except RateLimited:
            rate_limited += 1        # retryable fast-fail by design
        except QueueFull:
            rejected += 1
    # a few ticks past the window so a just-sustained burn still fires
    for _ in range(4):
        if auto.scale_outs:
            break
        time.sleep(tick_every)
        d = auto.tick()
        if d is not None and d["action"] == "scale_out":
            trigger = d

    completed, expired, failed = 0, 0, 0
    per_tenant = {n: {"offered": 0, "completed": 0, "expired": 0}
                  for n in _FAIR_TENANTS}
    for tenant, deadline, h in handles:
        per_tenant[tenant]["offered"] += 1
        try:
            h.result(timeout=args.timeout)
            completed += 1
            per_tenant[tenant]["completed"] += 1
        except TimeoutError:
            if deadline is not None:
                expired += 1         # spike deadline lapsed: SLO miss,
                per_tenant[tenant]["expired"] += 1   # not a lost request
            else:
                failed += 1          # no deadline in play: a hung
                                     # handle IS a lost request
        except Exception:
            failed += 1              # THIS is a lost request
    # post-scale traffic: the grown fleet must serve cleanly too
    post = {"offered": 0, "completed": 0}
    for k in range(8):
        post["offered"] += 1
        try:
            router.submit(prompt(), max_new_tokens=args.new_tokens,
                          adapter_id=_FAIR_PROTECTED[k % 2],
                          seed=args.seed).result(timeout=args.timeout)
            post["completed"] += 1
        except Exception:
            failed += 1
    steady = compile_cache.cache_stats()["compiles"] - compiles_before
    auto.tick()
    slo_final = router.slo_report() or {}
    for name, ten in slo_final.get("tenants", {}).items():
        if name in _FAIR_PROTECTED and (ten["fast_breached"]
                                        or ten["alerting"]):
            protected_breached.append(name)
    statz = router.statusz()

    # ---- per-replica compile budget, spawned replica included ----
    budget = len(local.engine.prefill_buckets) + 1
    budgets = {}
    cc = local.engine.cache_stats()
    budgets["r0"] = cc["prefill"]["compiles"] + cc["decode"]["compiles"]
    remote_snap = None
    for rep_name, state in router.replicas().items():
        if rep_name == "r0" or state == "dead":
            continue
        try:
            remote_snap = router._replicas[rep_name].server.snapshot()
            ccr = remote_snap.get("compile_stats", {})
            budgets[rep_name] = (ccr.get("prefill", {}).get("compiles", 0)
                                 + ccr.get("decode", {}).get("compiles", 0))
        except Exception:
            budgets[rep_name] = -1
    over_budget = {n: c for n, c in budgets.items()
                   if c > budget or c < 0}

    # ---- teardown: stop the child host, then the local plane ----
    child_rcs = []
    if spawner.procs:
        try:
            rpc.rpc_sync("auto-r1", remote_mod._host_request_stop,
                         timeout=10.0, connect_deadline=2.0)
        except Exception:
            pass
    local.shutdown(drain=True, timeout=60.0)
    if spawner._init_done:
        try:
            rpc.shutdown(timeout=8.0)
        except Exception:
            pass
    for proc in spawner.procs:
        try:
            child_rcs.append(proc.wait(timeout=120))
        except Exception:
            proc.kill()
            child_rcs.append(-1)

    record = {
        "metric": f"{args.model}_serve_fairness_goodput",
        "value": round(
            sum(per_tenant[n]["completed"] for n in _FAIR_PROTECTED)
            / max(1, sum(per_tenant[n]["offered"]
                         for n in _FAIR_PROTECTED)), 4),
        "unit": "goodput",
        "extra": {
            "completed": completed, "expired": expired, "failed": failed,
            "rate_limited_at_submit": rate_limited,
            "rate_limited_counter":
                local.metrics.snapshot()["requests_rate_limited"],
            "rejected": rejected,
            "spike_deadline_s": round(spike_deadline, 4),
            "per_tenant": per_tenant,
            "post_scale": post,
            "scale_outs": auto.scale_outs,
            "scale_decision": trigger,
            **cold,
            "compile_budget_per_replica": budget,
            "per_replica_compiles": budgets,
            "steady_state_recompiles": steady,
            "protected_fast_breaches": sorted(set(protected_breached)),
            "abuser_breaches": len(abuser_breached),
            "slo_tenants": {
                n: {"burn_fast": t["burn_fast"],
                    "burn_slow": t["burn_slow"],
                    "alerting": t["alerting"]}
                for n, t in slo_final.get("tenants", {}).items()},
            "autoscaler": statz.get("autoscaler"),
            "child_rcs": child_rcs,
            "backend": jax.default_backend(),
        },
    }
    _emit(record, args.json_out)
    rc = 0
    if not auto.scale_outs or trigger is None:
        print("FAIL: the spike never forced a scale-out — the SLO "
              "control loop did not close", file=sys.stderr)
        rc = 1
    elif trigger.get("tenant") not in ("spike", "__fleet__"):
        print(f"FAIL: scale-out was triggered by "
              f"{trigger.get('tenant')!r} — an abusive/protected "
              f"tenant bought fleet capacity", file=sys.stderr)
        rc = 1
    if protected_breached:
        print(f"FAIL: protected tenant(s) "
              f"{sorted(set(protected_breached))} edge-triggered a "
              f"fast-window burn — fairness did not hold under the "
              f"abuser", file=sys.stderr)
        rc = 1
    if abuser_breached:
        print(f"FAIL: the abuser's burn windows breached "
              f"({len(abuser_breached)} ticks) — rate-limit rejects "
              f"leaked into its SLO accounting", file=sys.stderr)
        rc = 1
    if failed:
        print(f"FAIL: {failed} request(s) lost across the scale "
              f"events", file=sys.stderr)
        rc = 1
    if rate_limited == 0:
        print("FAIL: the 10x abuser was never rate-limited",
              file=sys.stderr)
        rc = 1
    if over_budget:
        print(f"FAIL: compile budget ({budget}) exceeded: "
              f"{over_budget}", file=sys.stderr)
        rc = 1
    if steady:
        print(f"FAIL: {steady} local recompile(s) during the measured "
              f"window", file=sys.stderr)
        rc = 1
    if any(c != 0 for c in child_rcs):
        print(f"FAIL: child replica exit codes {child_rcs}",
              file=sys.stderr)
        rc = 1
    return rc


# --------------------------------------------------------------------
# Disaggregated prefill/decode fleet (PR 19).
#
# Topology: rank 0 (this process) runs the DisaggClient; dedicated
# prefill replicas (ranks 1..P) fill KV blocks for max_new_tokens=1
# requests and export them over rpc; decode replicas import the blocks
# into their own pool and serve the stream through the normal
# pool-admit path. Every child process points its persistent XLA
# compile cache at a shared per-role directory (serving.disagg
# .warm_boot_env): the FIRST decode replica boots cold and pays every
# compile; the deferred warm-boot replica — released mid-window by a
# wait-file touch, the scale-out moment — deserializes them and must
# boot in a fraction of the cold window (PR 16 measured ~7.4s cold).
# The drill measures a cold boot on purpose, on the CPU: its cache
# directories are fresh temporaries, and JAX_COMPILATION_CACHE_DIR
# (which would outrank them) is removed from the children's environment.
#
# Gates: migrated-prefill streams token-identical to a solo generate
# (greedy + seeded), zero lost requests (fallback-to-local-recompute
# absorbs every failed migration leg), warm boot strictly faster than
# cold, at least one real migration, and the per-role compile budgets:
# #buckets prefill-only programs on a prefill replica (its decode
# program is never traced), #buckets+1 on a decode replica.

def _disagg_max_length(args, cfg):
    prefix_pad = args.prefix_tokens + args.block_tokens
    return min(cfg.max_position_embeddings,
               max(args.buckets) + args.new_tokens + 8
               + (prefix_pad if args.prefix_tokens else 0))


def _disagg_child_main(args) -> int:
    """One disagg replica host. Joins the rendezvous immediately (the
    fabric needs every rank), but a ``--wait-file`` child defers its
    model build + compile until the parent touches the file — the
    released-to-first-token window IS the warm-boot measurement."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.serving import remote

    rpc.init_rpc(name=args.rpc_name, rank=args.rank,
                 world_size=args.world, master_endpoint=args.endpoint)
    if args.wait_file:
        deadline = time.time() + 600.0
        while not os.path.exists(args.wait_file):
            if time.time() > deadline:
                return 3
            time.sleep(0.02)
    from decode_bench import build_model
    from paddle_tpu.serving import InferenceServer

    model, cfg = build_model(args.model, args.preset)
    srv = InferenceServer(
        model, slots=args.slots, max_length=_disagg_max_length(args, cfg),
        prefill_buckets=args.buckets,
        max_queue_depth=args.max_queue_depth,
        prefix_cache=dict(
            max_bytes=int(args.prefix_cache_mb * (1 << 20)),
            block_tokens=args.block_tokens),
        kv_dtype=None if args.kv_dtype == "none" else args.kv_dtype)
    # a prefill replica serves max_new_tokens=1 requests only — its
    # decode program is never traced, so the warmup must not trace it
    # either (#buckets programs, not #buckets+1)
    srv.engine.warmup(
        max_new_tokens=1 if args.disagg_child == "prefill" else 2)
    remote.host_server(srv, name="default")
    remote.wait_for_stop(timeout=900.0)
    try:
        srv.shutdown(drain=False, timeout=20.0)
    except Exception:
        pass
    rpc.shutdown(timeout=6.0)
    return 0


def _disagg_main(args) -> int:
    import socket
    import subprocess
    import tempfile
    import threading

    import jax

    from decode_bench import build_model
    from paddle_tpu.distributed import rpc
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.serving import remote as remote_mod
    from paddle_tpu.serving.disagg import (DisaggClient, PrefixIndex,
                                           warm_boot_env)
    from paddle_tpu.serving.remote import RemoteReplica

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        endpoint = f"127.0.0.1:{s.getsockname()[1]}"

    n_total = max(2, args.replicas)
    n_prefill = max(1, min(n_total - 1,
                           int(round(args.prefill_ratio * n_total))))
    n_decode = n_total - n_prefill
    # +1: the deferred warm-boot decode replica; +1: this parent
    world = 1 + n_prefill + n_decode + 1
    if args.prefix_cache_mb <= 0:
        args.prefix_cache_mb = 8.0     # both pools need KV blocks
    if args.prefix_tokens == 0:
        # prefix-heavy by default: migration needs prompts past one
        # full block, and the cold shared-prefix prompt must still fit
        # the largest declared bucket (the main-mode invariant)
        args.prefix_tokens = max(args.buckets) - args.block_tokens
    if args.check:
        args.requests = min(args.requests, 12)
        args.rate = min(args.rate, 4.0)
        args.new_tokens = min(args.new_tokens, 10)

    work = tempfile.mkdtemp(prefix="disagg-bench-")
    # per-role cache dirs: the prefill pool must not pre-populate the
    # decode programs, or the "cold" decode boot would silently warm
    decode_cache = os.path.join(work, "cache-decode")
    prefill_cache = os.path.join(work, "cache-prefill")
    wait_file = os.path.join(work, "warm.go")

    def child_argv(role, name, rank, deferred=False):
        argv = [sys.executable, os.path.abspath(__file__),
                "--disagg-child", role, "--rpc-name", name,
                "--rank", str(rank), "--world", str(world),
                "--endpoint", endpoint,
                "--model", args.model, "--preset", args.preset,
                "--slots", str(args.slots),
                "--new-tokens", str(args.new_tokens),
                "--buckets", *[str(b) for b in args.buckets],
                "--max-queue-depth", str(args.max_queue_depth),
                "--block-tokens", str(args.block_tokens),
                "--prefix-cache-mb", str(args.prefix_cache_mb),
                "--prefix-tokens", str(args.prefix_tokens),
                "--kv-dtype", args.kv_dtype,
                "--seed", str(args.seed)]
        if deferred:
            argv += ["--wait-file", wait_file]
        return argv

    def child_env(cache_dir):
        # children serve on host CPU (a real fleet maps each to its own
        # accelerator); the warm_boot_env flags point their persistent
        # compile cache at the shared per-role directory.
        # JAX_COMPILATION_CACHE_DIR outranks those flags, so it is
        # dropped: an ambient cache would make the "cold" boot warm
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
                   **warm_boot_env(cache_dir))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        return env

    plan = []      # (role, rpc name, rank, cache dir, deferred)
    rank = 1
    for i in range(n_prefill):
        plan.append(("prefill", f"pre{i}", rank, prefill_cache, False))
        rank += 1
    for i in range(n_decode):
        plan.append(("decode", f"dec{i}", rank, decode_cache, False))
        rank += 1
    plan.append(("decode", "dec-warm", rank, decode_cache, True))

    procs = []
    t_fleet0 = time.perf_counter()
    for role, name, r, cache, deferred in plan:
        procs.append(subprocess.Popen(
            child_argv(role, name, r, deferred=deferred),
            env=child_env(cache)))
    rpc.init_rpc(name="bench", rank=0, world_size=world,
                 master_endpoint=endpoint)
    reps = {name: RemoteReplica(name, rpc_timeout=60.0,
                                connect_deadline=2.0)
            for _, name, _, _, _ in plan}
    lens = sorted(b - 2 for b in args.buckets)
    # vocab-independent probe (any model's vocab covers ids 1..97), so
    # the cold measurement needs no local model build first
    probe_prompt = ((np.arange(lens[0]) % 97) + 1).astype(np.int32)

    # ---- cold boot: fleet spawn -> first token on the cold decode ----
    if not reps["dec0"].wait_ready(timeout=600.0):
        print("FAIL: cold decode replica never hosted", file=sys.stderr)
        return 1
    h = reps["dec0"].submit(prompt=probe_prompt, max_new_tokens=4)
    h.result(timeout=args.timeout)
    cold_s = round(time.perf_counter() - t_fleet0, 3)
    for role, name, _, _, deferred in plan:
        if not deferred and not reps[name].wait_ready(timeout=600.0):
            print(f"FAIL: replica {name} never hosted", file=sys.stderr)
            return 1

    rng = np.random.default_rng(args.seed)
    model, cfg = build_model(args.model, args.preset)
    max_length = _disagg_max_length(args, cfg)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, (int(n),)).astype(np.int32)

    index = PrefixIndex()
    client = DisaggClient(
        [reps[f"pre{i}"] for i in range(n_prefill)],
        [reps[f"dec{i}"] for i in range(n_decode)],
        block_tokens=args.block_tokens, index=index,
        prefill_timeout_s=min(args.timeout, 60.0))

    shared_prefix = prompt(args.prefix_tokens)

    def trace_prompt():
        if rng.random() < args.prefix_frac:
            sfx = prompt(int(rng.integers(2, args.block_tokens + 1)))
            return np.concatenate([shared_prefix, sfx])
        return prompt(int(rng.integers(4, max(lens) + 1)))

    # ---- warm boot: released on another thread mid-window, like a
    # burn-driven scale-out; the decode pool grows when it lands ----
    warm = {}

    def release_warm():
        with open(wait_file, "w") as f:
            f.write("go\n")
        t0 = time.perf_counter()
        if not reps["dec-warm"].wait_ready(timeout=600.0):
            warm["error"] = "never hosted"
            return
        hw = reps["dec-warm"].submit(prompt=probe_prompt,
                                     max_new_tokens=4)
        hw.result(timeout=args.timeout)
        warm["warm_boot_s"] = round(time.perf_counter() - t0, 3)
        warm["t_added"] = time.perf_counter()
        client.decode.append(reps["dec-warm"])

    warm_thread = threading.Thread(target=release_warm, daemon=True)

    # ---- measured open-loop window through the DisaggClient ----
    compiles_before = compile_cache.cache_stats()["compiles"]
    interarrival = rng.exponential(1.0 / max(args.rate, 1e-6),
                                   args.requests)
    release_at = args.requests // 3
    verify_idx = set(range(min(args.verify or 2, args.requests)))
    verify_solo = {}
    handles, failed = [], 0
    ttft_pre_add, ttft_post_add = [], []
    t0 = time.perf_counter()
    for i in range(args.requests):
        target = t0 + float(interarrival[:i + 1].sum())
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        if i == release_at:
            warm_thread.start()
        if i and i % 8 == 0:
            client.scrape_index()
        # verify probes always carry the shared prefix: they must take
        # the MIGRATED path to prove token identity end to end
        p = (np.concatenate([shared_prefix,
                             prompt(int(rng.integers(2,
                                        args.block_tokens + 1)))])
             if i in verify_idx else trace_prompt())
        kw = dict(max_new_tokens=args.new_tokens, seed=args.seed + i)
        if i in verify_idx:
            verify_solo[i] = p
        else:
            kw.update(do_sample=bool(i % 2), temperature=0.8, top_p=0.95)
        handles.append((i, time.perf_counter(), client.submit(p, **kw)))
    completed, results = 0, {}
    for i, sub_t, h in handles:
        try:
            results[i] = h.result(timeout=args.timeout)
            completed += 1
            if getattr(h, "ttft_s", None) is not None:
                # p99-spike gate input: requests submitted after the
                # warm replica joined vs before
                (ttft_post_add
                 if sub_t >= warm.get("t_added", float("inf"))
                 else ttft_pre_add).append(h.ttft_s)
        except Exception:
            failed += 1
    elapsed = time.perf_counter() - t0
    warm_thread.join(timeout=600.0)
    steady = compile_cache.cache_stats()["compiles"] - compiles_before
    warm_s = warm.get("warm_boot_s")

    # ---- verify: migrated streams == cold solo generate ----
    verify_failures = 0
    for i, p in verify_solo.items():
        got = results.get(i)
        if got is None:
            continue
        solo = model.generate(
            p[None], max_new_tokens=args.new_tokens,
            max_length=max_length, prefill_buckets=tuple(args.buckets),
            kv_dtype=None if args.kv_dtype == "none" else args.kv_dtype)[0]
        if not np.array_equal(np.asarray(got), solo):
            verify_failures += 1

    # ---- per-pool blocks + per-role compile budgets ----
    pools = {"prefill": {"replicas": [], "budget": len(args.buckets)},
             "decode": {"replicas": [], "budget": len(args.buckets) + 1}}
    over_budget = {}
    for role, name, _, _, deferred in plan:
        if deferred and warm_s is None:
            continue
        try:
            sn = reps[name].snapshot()
        except Exception:
            over_budget[name] = -1
            continue
        cc = sn.get("compile_stats", {})
        compiles = (cc.get("prefill", {}).get("compiles", 0)
                    + cc.get("decode", {}).get("compiles", 0))
        pools[role]["replicas"].append({
            "name": name,
            "slot_occupancy": round(sn.get("slot_occupancy", 0.0), 4),
            "tokens_emitted": sn.get("tokens_emitted", 0),
            "completed": sn.get("requests_completed", 0),
            "prefix_hit_tokens": sn.get("prefix_hit_tokens", 0),
            "compiles": compiles})
        if compiles > pools[role]["budget"]:
            over_budget[name] = compiles
    for role, blk in pools.items():
        rs = blk["replicas"]
        blk["occupancy"] = round(
            sum(r["slot_occupancy"] for r in rs) / max(1, len(rs)), 4)
        blk["tokens_per_sec"] = round(
            sum(r["tokens_emitted"] for r in rs) / max(elapsed, 1e-9), 2)
    mig = client.statusz()
    pools["prefill"]["goodput"] = round(
        mig["migrations"] / max(1, mig["migrations"] + mig["fallbacks"]),
        4)
    pools["decode"]["goodput"] = round(
        completed / max(1, args.requests), 4)

    # ---- teardown ----
    child_rcs = []
    for _, name, _, _, deferred in plan:
        try:
            rpc.rpc_sync(name, remote_mod._host_request_stop,
                         timeout=10.0, connect_deadline=2.0)
        except Exception:
            pass
    try:
        rpc.shutdown(timeout=8.0)
    except Exception:
        pass
    for proc in procs:
        try:
            child_rcs.append(proc.wait(timeout=120))
        except Exception:
            proc.kill()
            child_rcs.append(-1)

    record = {
        "metric": f"{args.model}_serve_disagg_requests_per_sec",
        "value": round(completed / max(elapsed, 1e-9), 3),
        "unit": "req/s",
        "extra": {
            "goodput": round(completed / max(args.requests, 1), 4),
            "offered_requests": args.requests,
            "completed": completed,
            "failed": failed,
            "elapsed_s": round(elapsed, 3),
            "prefill_replicas": n_prefill,
            "decode_replicas": n_decode,
            "prefill_ratio": args.prefill_ratio,
            "cold_start_ttft_s": {
                "cold": cold_s,
                "warm": warm_s,
                "reduction_frac": (round(1.0 - warm_s / cold_s, 4)
                                   if warm_s else None)},
            "ttft_p99_pre_add_ms": round(
                _pct(ttft_pre_add, 99) * 1e3, 3),
            "ttft_p99_post_add_ms": round(
                _pct(ttft_post_add, 99) * 1e3, 3),
            "pools": pools,
            "migration": {**mig,
                          "overhead_frac": round(
                              mig["migrate_s"] / max(elapsed, 1e-9), 4)},
            "verified": len(verify_solo),
            "verify_failures": verify_failures,
            "steady_state_recompiles": steady,
            "compile_budget": {r: pools[r]["budget"] for r in pools},
            "child_rcs": child_rcs,
            "backend": jax.default_backend(),
            "preset": args.preset,
            "check": bool(args.check),
        },
    }
    _emit(record, args.json_out)
    rc = 0
    if verify_failures:
        print(f"FAIL: {verify_failures} migrated stream(s) diverged "
              f"from solo generate — block migration changed tokens",
              file=sys.stderr)
        rc = 1
    if failed:
        print(f"FAIL: {failed} request(s) lost — migration fallback "
              f"must absorb every failed leg", file=sys.stderr)
        rc = 1
    if mig["migrations"] == 0:
        print("FAIL: no migration ever succeeded — the disagg path "
              "never ran", file=sys.stderr)
        rc = 1
    if over_budget:
        print(f"FAIL: per-role compile budget exceeded: {over_budget} "
              f"(prefill={len(args.buckets)}, "
              f"decode={len(args.buckets) + 1})", file=sys.stderr)
        rc = 1
    if warm_s is None:
        print(f"FAIL: warm-boot replica never served "
              f"({warm.get('error', 'unknown')})", file=sys.stderr)
        rc = 1
    elif warm_s >= cold_s:
        print(f"FAIL: warm boot ({warm_s}s) not faster than cold "
              f"({cold_s}s) — the persistent compile cache did not "
              f"deserialize", file=sys.stderr)
        rc = 1
    if steady:
        print(f"FAIL: {steady} parent-side recompile(s) during the "
              f"measured window", file=sys.stderr)
        rc = 1
    if any(c != 0 for c in child_rcs):
        print(f"FAIL: child replica exit codes {child_rcs}",
              file=sys.stderr)
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
