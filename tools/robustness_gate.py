#!/usr/bin/env python
"""Robustness gate: ONE command CI can block on for the fault-tolerance
story. Runs, in order:

0. ``tools/tpu_lint.py --json --changed-only --baseline
   .tpu_lint_baseline.json`` — the static trace-discipline analyzer
   (host syncs, retrace hazards, donation misuse, PRNG reuse, lock
   bypasses, lock-order/deadlock, blocking-under-lock, sharding
   discipline, resource-lifecycle leaks, SPMD collective divergence,
   rpc deadline/idempotence — R1–R11). The stage rides the
   ``.tpu_lint_cache/`` incremental engine by default (git diff +
   one-hop import closure; the tool falls back to — and refreshes —
   a full run whenever the cache is missing or the unchanged tree
   drifted); ``--full-lint`` forces the whole-repo run. One stage
   covers every package, replacing the per-subsystem scoped runs the
   ``--lora``/``--observability`` stages used to carry; it prints a
   per-package parse/lint timing roll-up from the ``--json`` timing
   block so lint-perf regressions are visible in CI logs. First because
   it is the cheapest stage by two orders of magnitude (seconds cold,
   milliseconds warm): a NEW unbaselined finding fails the gate before
   any soak spends minutes proving the same bug at runtime;
1. ``tools/chaos_soak.py --quick`` — the self-healing train loop under
   NaN batches, a step stall, and a kill-and-restart (fails on any
   unrecovered fault, loss divergence beyond tolerance, or a steady-state
   recompile — the soak children run under ``retrace_guard(0)``);
2. ``tools/fault_sweep.py`` — the distributed-primitive fault matrix
   (kv/rpc/checkpoint under drop/delay/crash);
3. with ``--elastic``, ``tools/chaos_soak.py --elastic --quick`` — the
   shrink/grow-on-preemption scenario: kill a run mid-training, resume on
   HALF the devices via reshard-restore, kill again, regrow to the full
   topology, and demand final-loss parity with an uninterrupted run
   (fails on any unrecovered shrink, a resize that never resharded, or
   loss divergence);
4. with ``--fleet``, ``tools/serve_bench.py --check --replicas 2
   --prefix-cache-mb 4 --prefix-tokens 24 --crash-replica --verify 3`` —
   the serving-fleet crash scenario: one replica is hard-killed
   mid-window under a prefix-heavy trace; the router must requeue its
   requests onto the survivor (zero lost), seeded-greedy probes must
   stay token-identical to a solo ``generate`` (no divergence across the
   reroute), and the survivor must hold its #buckets+1 compile budget
   with zero steady-state recompiles;
4a. with ``--fairness``, ``tools/serve_bench.py --fairness`` — the
   adversarial SLO-control-loop trace: one abusive tenant at 10x rate
   (token-bucket throttled, its rejects booking ZERO tenant failures so
   abuse cannot buy capacity) plus a traffic spike whose slow-window
   burn must force a REAL burn-driven scale-out (child replica spawned
   over the rpc fabric mid-run, its cold-start-to-first-token
   reported); protected tenants' fast-window burn must never
   edge-trigger, zero requests may be lost across the scale events, and
   the #buckets+1 compile budget must hold on every replica, the
   cold-started one included;
4b. with ``--fleet-chaos``, ``tools/fleet_chaos.py --quick`` — the
   CROSS-HOST fleet soak: rpc remote replicas in child processes under
   SIGKILL + network partition + slow-replica (``slow`` fault) +
   2x-overload faults. Zero lost requests, detector-driven reroutes
   (heartbeat misses -> DEAD -> abandoned handles fail over), hedge
   winners token-identical to solo generate, and overload sheds failing
   fast (< 10%% of their deadline) instead of timing out;
5. with ``--observability``, the telemetry gate in three parts:
   ``tools/flight_drill.py`` (an injected serve-loop crash must leave a
   well-formed flight-recorder dump carrying the failing request's
   correlation id, consumable by ``tools/trace_view.py``),
   ``tools/fleet_obs_drill.py`` (a 2-process rpc fleet: one
   ``fleet_metrics_text()`` scrape returns BOTH processes' serving
   metrics with per-replica labels; a replica partitioned mid-scrape
   degrades to a stale-marked partial roll-up, not an error; a remote
   request's stitched trace renders as one skew-aligned corr-id lane;
   an SLO burn on an induced stall flight-dumps with the right tenant
   label), and ``tools/decode_bench.py --trace-overhead`` (per-token
   span recording on the decode hot loop must cost <2% throughput,
   tracing-on vs tracing-off). The old scoped ``tpu_lint
   paddle_tpu/observability`` run folded into stage 0's whole-repo
   lint;
6. with ``--lora``, ``tools/lora_soak.py`` — the multi-tenant adapter
   lifecycle: fine-tune a tiny adapter 20 steps under the supervisor,
   hard-kill the process mid-checkpoint-save, resume from the newest
   complete checkpoint, finish, publish the adapter, then serve it
   mixed with base traffic — zero lost requests, zero steady-state
   recompiles, token parity vs solo generate. (Its old scoped
   ``tpu_lint paddle_tpu/lora`` companion folded into stage 0's
   whole-repo lint.)
7. with ``--overlap``, the step-schedule regression gate:
   ``tools/bench_profile.py --overlap --distributed`` measures the
   pre-PR serial schedule (stage 0: fused tail all-reduce + replicated
   weight update) against the bucketed overlap schedule
   (``overlap_grad_reduce=True`` + ZeRO sharded update) on the same
   model/batch; FAILS if the bucketed ``non_compute_frac`` regresses
   past the ``.overlap_baseline.json`` threshold or the serial->
   bucketed reduction drops below its floor. A scoped tpu_lint of the
   restructured step files (jit.py / shard.py / overlap.py /
   bench_profile.py) rides along so the R10 collective-divergence
   discipline is asserted even under ``--skip-lint``.
8. with ``--decode``, the raw-decode-speed regression gate:
   ``tools/decode_bench.py`` runs the ``small`` preset (compute-bound —
   the dispatch-bound ``tiny`` config hides model-level wins in launch
   overhead) with speculative decoding + int8 KV on, paired against the
   plain engine in the same process, and FAILS if the speedup drops
   below the ``.decode_baseline.json`` floor, the quantized cache stops
   halving, or the timed run recompiles. A ``--trace-overhead`` run
   rides the same baseline's threshold, and a scoped tpu_lint of the
   speculative/quantization files holds the R1/R9 line under
   ``--skip-lint``.
9. with ``--disagg``, the disaggregated prefill/decode gate:
   ``tools/fleet_chaos.py --disagg`` (KV-block migration parity — greedy
   and seeded-sampled migrated streams token-identical to solo generate
   — then SIGKILL the prefill replica MID-migration: the decode replica
   must fall back to local recompute with zero lost requests and the
   dead replica must drop from the fleet prefix index), followed by
   ``tools/serve_bench.py --disagg --check`` regression-gated against
   ``.disagg_baseline.json``: warm replica boot via the persistent
   compile cache must keep cutting cold TTFT by the stored floor, and
   migration overhead must stay under its ceiling.

10. with ``--sdc``, the silent-data-corruption drill:
   ``tools/sdc_drill.py --quick`` — a seeded one-bit flip on vote-axis
   rank 2's physical copies (logical value untouched, numerics watchdog
   blind) must be caught by the cross-replica fingerprint vote within
   one check interval with the right culprit named; the transient case
   must end at a deterministic replay (final loss bit-identical to
   fault-free), the sticky case must escalate to a conviction — durable
   quarantine record, flight dump, ``EXIT_EVICTED`` — and the next
   incarnation must resume on the surviving reduced topology via the
   elastic reshard path with loss parity. The integrity-ON clean run
   must be BIT-identical to the integrity-OFF reference (defaults off
   means defaults off). A scoped tpu_lint of the integrity/supervisor
   files rides along so the R1 (one batched fingerprint readback) and
   R9 (durable quarantine staging) lines hold under ``--skip-lint``.

Exit code is non-zero iff any stage fails. ``--skip-sweep`` /
``--skip-soak`` run a single stage (e.g. pre-merge quick signal vs the
nightly full matrix)::

    python tools/robustness_gate.py
    python tools/robustness_gate.py --skip-sweep   # lint + soak only
    python tools/robustness_gate.py --elastic      # + shrink/grow proof
    python tools/robustness_gate.py --fleet        # + serving-fleet crash
    python tools/robustness_gate.py --fairness     # + SLO control loop
    python tools/robustness_gate.py --fleet-chaos  # + cross-host rpc soak
    python tools/robustness_gate.py --lora         # + adapter lifecycle
    python tools/robustness_gate.py --observability  # + telemetry gate
    python tools/robustness_gate.py --overlap      # + step-schedule gate
    python tools/robustness_gate.py --decode       # + decode-speed gate
    python tools/robustness_gate.py --disagg       # + prefill/decode split
    python tools/robustness_gate.py --sdc          # + bit-flip defense
    python tools/robustness_gate.py --skip-lint    # runtime stages only
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _run(name: str, cmd: list) -> bool:
    print(f"[robustness_gate] === {name}: {' '.join(cmd[1:])}", flush=True)
    t0 = time.monotonic()
    env = dict(os.environ, PYTHONPATH=REPO)
    env.setdefault("JAX_PLATFORMS", "cpu")
    p = subprocess.run(cmd, env=env, timeout=2400)
    ok = p.returncode == 0
    print(f"[robustness_gate] === {name}: "
          f"{'PASS' if ok else f'FAIL (rc={p.returncode})'} "
          f"in {time.monotonic() - t0:.0f}s", flush=True)
    return ok


def _package_of(rel: str) -> str:
    """paddle_tpu/serving/server.py -> paddle_tpu/serving; tools/x.py ->
    tools — the roll-up grain of the lint timing table."""
    parts = rel.split("/")
    return "/".join(parts[:2]) if len(parts) > 2 else parts[0]


def _run_lint(full: bool = False) -> bool:
    """ONE tpu_lint run (R1–R11, baseline-gated) with a per-package
    parse/lint timing roll-up — the unified replacement for the scoped
    per-subsystem runs the --lora/--observability stages used to carry.

    Default is ``--changed-only``: the gate's lint step rides the
    ``.tpu_lint_cache/`` incremental engine (git diff + one-hop import
    closure) instead of re-linting every file — sub-second on a typical
    diff, and the tool itself falls back to a full run (refreshing the
    cache) whenever the cache is missing or the unchanged tree drifted.
    ``--full-lint`` forces the whole-repo run (the nightly/CI-trunk
    setting, and the one that refreshes the cache everyone else rides).
    """
    name = "tpu_lint"
    cmd = [sys.executable, os.path.join(TOOLS, "tpu_lint.py"), "--json",
           "--baseline", os.path.join(REPO, ".tpu_lint_baseline.json")]
    if not full:
        cmd.append("--changed-only")
    print(f"[robustness_gate] === {name}: {' '.join(cmd[1:])}", flush=True)
    t0 = time.monotonic()
    env = dict(os.environ, PYTHONPATH=REPO)
    env.setdefault("JAX_PLATFORMS", "cpu")
    p = subprocess.run(cmd, env=env, timeout=2400, capture_output=True,
                       text=True)
    ok = p.returncode == 0
    try:
        data = json.loads(p.stdout)
    except json.JSONDecodeError:
        data = {}
    timing = data.get("timing") or {}
    # a warm-cache run reports the cached analysis' timings under
    # "cached_run" — the per-package table must survive the fast path
    files_ms = (timing.get("files")
                or (timing.get("cached_run") or {}).get("files") or {})
    per_pkg: dict = {}
    for rel, t in files_ms.items():
        agg = per_pkg.setdefault(_package_of(rel),
                                 {"files": 0, "parse_ms": 0.0,
                                  "lint_ms": 0.0})
        agg["files"] += 1
        agg["parse_ms"] += t.get("parse_ms", 0.0)
        agg["lint_ms"] += t.get("lint_ms", 0.0)
    if per_pkg:
        print(f"[robustness_gate] {'package':32s} {'files':>5s} "
              f"{'parse_ms':>9s} {'lint_ms':>9s}")
        for pkg in sorted(per_pkg, key=lambda k: -per_pkg[k]["lint_ms"]):
            a = per_pkg[pkg]
            print(f"[robustness_gate] {pkg:32s} {a['files']:5d} "
                  f"{a['parse_ms']:9.1f} {a['lint_ms']:9.1f}")
    cache = data.get("cache") or {}
    stats = data.get("stats") or {}
    print(f"[robustness_gate] lint: {stats.get('files', '?')} files, "
          f"{len(data.get('new_findings', []))} NEW finding(s), "
          f"cache={'hit' if cache.get('hit') else cache.get('mode', '?')}",
          flush=True)
    for f in data.get("new_findings", []):
        print(f"[robustness_gate]   NEW {f['rule']} {f['path']}:"
              f"{f['line']} {f['message']}")
    if not ok and not data:
        sys.stdout.write(p.stdout[-2000:])
        sys.stderr.write(p.stderr[-2000:])
    print(f"[robustness_gate] === {name}: "
          f"{'PASS' if ok else f'FAIL (rc={p.returncode})'} "
          f"in {time.monotonic() - t0:.0f}s", flush=True)
    return ok


def _run_overlap_gate() -> bool:
    """``--overlap``: the step-schedule regression gate. Runs
    ``tools/bench_profile.py --overlap --distributed`` (pre-PR serial
    stage-0 schedule vs bucketed+ZeRO schedule, same model/batch) and
    fails if the bucketed schedule's ``non_compute_frac`` regresses past
    the stored ``.overlap_baseline.json`` threshold or the serial->
    bucketed reduction factor drops below its floor. Also scope-lints
    the restructured step files so ``--overlap --skip-lint`` still
    asserts the SPMD collective-divergence discipline (R10) on them."""
    name = "overlap"
    baseline_path = os.path.join(REPO, ".overlap_baseline.json")
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
    except OSError as e:
        print(f"[robustness_gate] === {name}: FAIL "
              f"(no {baseline_path}: {e})", flush=True)
        return False
    out = os.path.join(tempfile.gettempdir(),
                       f"overlap_gate_{os.getpid()}.json")
    ok = _run(name, [sys.executable,
                     os.path.join(TOOLS, "bench_profile.py"),
                     "--overlap", "--distributed", "--steps", "2",
                     "--json-out", out])
    if not ok:
        return False
    try:
        with open(out) as f:
            summary = json.load(f)
    finally:
        try:
            os.unlink(out)
        except OSError:
            pass
    frac = summary["bucketed"]["value"]
    reduction = summary["non_compute_frac_reduction"]
    max_frac = baseline["max_bucketed_non_compute_frac"]
    min_red = baseline["min_reduction"]
    ok = frac <= max_frac and reduction >= min_red
    print(f"[robustness_gate] === {name}: bucketed non_compute_frac="
          f"{frac:.4f} (max {max_frac}), reduction={reduction}x "
          f"(min {min_red}) -> {'PASS' if ok else 'FAIL'}", flush=True)
    if not ok:
        return False
    # scoped self-application: the restructured step files must carry
    # zero unbaselined findings (R1 host-sync, R10 collective divergence)
    return _run(f"{name}_lint",
                [sys.executable, os.path.join(TOOLS, "tpu_lint.py"),
                 "--baseline",
                 os.path.join(REPO, ".tpu_lint_baseline.json"),
                 os.path.join(REPO, "paddle_tpu/framework/jit.py"),
                 os.path.join(REPO, "paddle_tpu/distributed/shard.py"),
                 os.path.join(REPO, "paddle_tpu/distributed/overlap.py"),
                 os.path.join(REPO, "tools/bench_profile.py")])


def _run_decode_gate() -> bool:
    """``--decode``: the raw-decode-speed regression gate. Runs
    ``tools/decode_bench.py`` on the compute-bound ``small`` preset with
    the checked-in speculative/int8 config paired against the plain
    engine (same process, same box — the ratio is host-independent
    where absolute tokens/s is not) and fails if the speedup drops
    below the ``.decode_baseline.json`` floor or the quantized cache
    stops halving. The bench itself fails the stage on steady-state
    recompiles. A ``--trace-overhead`` run rides the same baseline's
    threshold, and the speculative/quantization files are scope-linted
    so R1 (host-sync in the round loop) and R9 stay asserted under
    ``--skip-lint``."""
    name = "decode"
    baseline_path = os.path.join(REPO, ".decode_baseline.json")
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
    except OSError as e:
        print(f"[robustness_gate] === {name}: FAIL "
              f"(no {baseline_path}: {e})", flush=True)
        return False
    bench = baseline["bench"]
    out = os.path.join(tempfile.gettempdir(),
                       f"decode_gate_{os.getpid()}.json")
    ok = _run(name, [sys.executable,
                     os.path.join(TOOLS, "decode_bench.py"),
                     "--preset", str(bench["preset"]),
                     "--batch", str(bench["batch"]),
                     "--new-tokens", str(bench["new_tokens"]),
                     "--speculative", str(bench["speculative_k"]),
                     "--draft-layers", str(bench["draft_layers"]),
                     "--kv-dtype", str(bench["kv_dtype"]),
                     "--json-out", out])
    if not ok:
        return False
    try:
        with open(out) as f:
            summary = json.load(f)
    finally:
        try:
            os.unlink(out)
        except OSError:
            pass
    speedup = summary["speedup"]
    min_speedup = baseline["min_speedup"]
    cache_frac = (summary["after"]["extra"]["cache_bytes"]
                  / max(summary["before"]["extra"]["cache_bytes"], 1))
    max_frac = baseline["max_cache_bytes_frac"]
    ok = speedup >= min_speedup and cache_frac <= max_frac
    print(f"[robustness_gate] === {name}: speedup={speedup}x "
          f"(min {min_speedup}), cache_frac={cache_frac:.3f} "
          f"(max {max_frac}), acceptance="
          f"{summary['after']['extra'].get('acceptance_rate')} -> "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    if not ok:
        return False
    # trace overhead on the SAME compute-bound preset: on tiny the span
    # recorder's fixed cost is a visible fraction of the ~launch-bound
    # step and the number is pure noise; on small it must stay inside
    # the baseline's budget (best-of-5 per mode filters box noise)
    if not _run(f"{name}_trace_overhead",
                [sys.executable, os.path.join(TOOLS, "decode_bench.py"),
                 "--preset", str(bench["preset"]),
                 "--batch", str(bench["batch"]),
                 "--trace-overhead", "5", "--trace-overhead-pct",
                 str(baseline["max_trace_overhead_pct"])]):
        return False
    # scoped self-application: the speculative round loop and the
    # quantize-on-write path must carry zero unbaselined findings
    return _run(f"{name}_lint",
                [sys.executable, os.path.join(TOOLS, "tpu_lint.py"),
                 "--baseline",
                 os.path.join(REPO, ".tpu_lint_baseline.json"),
                 os.path.join(REPO, "paddle_tpu/models/speculative.py"),
                 os.path.join(REPO, "paddle_tpu/models/generation.py"),
                 os.path.join(REPO, "paddle_tpu/models/kv_cache.py"),
                 os.path.join(REPO, "paddle_tpu/models/lm_utils.py"),
                 os.path.join(REPO, "paddle_tpu/quantization/__init__.py"),
                 os.path.join(REPO, "tools/decode_bench.py")])


def _run_disagg_gate() -> bool:
    """``--disagg``: the disaggregated prefill/decode gate, two stages.

    First ``tools/fleet_chaos.py --disagg`` — the migration fault drill:
    a dedicated prefill replica fills KV blocks and ships them to a
    decode replica over rpc; greedy AND seeded-sampled migrated streams
    must be token-identical to solo ``generate``, then the prefill
    replica is SIGKILLed MID-migration (a ``slow`` fault holds the
    export) and the decode replica must fall back to local recompute —
    zero lost requests, the fallback traced, the dead replica dropped
    from the fleet prefix index, and the prefill replica's #buckets
    (decode-free) compile budget held at exit.

    Then ``tools/serve_bench.py --disagg --check`` — the performance
    regression half: warm replica boot (persistent compile cache) and
    migration overhead are compared against the stored
    ``.disagg_baseline.json`` floors (warm boot must keep cutting cold
    TTFT by ``min_warm_boot_reduction_frac``; shipping prefilled blocks
    must stay under ``max_migration_overhead_frac`` of the window).
    The bench itself already fails the stage on lost requests, verify
    divergence, a post-scale-out p99 TTFT spike, steady-state
    recompiles, or a compile-budget breach on any replica."""
    name = "disagg"
    baseline_path = os.path.join(REPO, ".disagg_baseline.json")
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
    except OSError as e:
        print(f"[robustness_gate] === {name}: FAIL "
              f"(no {baseline_path}: {e})", flush=True)
        return False
    if not _run(f"{name}_chaos",
                [sys.executable, os.path.join(TOOLS, "fleet_chaos.py"),
                 "--disagg"]):
        return False
    bench = baseline["bench"]
    out = os.path.join(tempfile.gettempdir(),
                       f"disagg_gate_{os.getpid()}.json")
    ok = _run(name, [sys.executable,
                     os.path.join(TOOLS, "serve_bench.py"),
                     "--disagg", "--check",
                     "--requests", str(bench["requests"]),
                     "--prefill-ratio", str(bench["prefill_ratio"]),
                     "--verify", str(bench["verify"]),
                     "--json-out", out])
    if not ok:
        return False
    try:
        with open(out) as f:
            summary = json.load(f)
    finally:
        try:
            os.unlink(out)
        except OSError:
            pass
    extra = summary["extra"]
    red = extra["cold_start_ttft_s"]["reduction_frac"]
    min_red = baseline["min_warm_boot_reduction_frac"]
    overhead = extra["migration"]["overhead_frac"]
    max_overhead = baseline["max_migration_overhead_frac"]
    ok = red >= min_red and overhead <= max_overhead
    print(f"[robustness_gate] === {name}: warm-boot reduction_frac="
          f"{red:.4f} (min {min_red}), migration overhead_frac="
          f"{overhead:.4f} (max {max_overhead}) -> "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-soak", action="store_true")
    ap.add_argument("--skip-sweep", action="store_true")
    ap.add_argument("--full-soak", action="store_true",
                    help="run the soak without --quick")
    ap.add_argument("--elastic", action="store_true",
                    help="also run the shrink/grow-on-preemption scenario")
    ap.add_argument("--fleet", action="store_true",
                    help="also run the serving-fleet replica-crash "
                         "scenario (router reroute, token parity, "
                         "compile budget)")
    ap.add_argument("--fairness", action="store_true",
                    help="also run the adversarial SLO-control-loop "
                         "trace (10x abusive tenant + spike-driven "
                         "burn scale-out over rpc, "
                         "tools/serve_bench.py --fairness)")
    ap.add_argument("--fleet-chaos", action="store_true",
                    help="also run the cross-host rpc fleet soak "
                         "(SIGKILL + partition + slow replica + "
                         "overload shed, tools/fleet_chaos.py --quick)")
    ap.add_argument("--lora", action="store_true",
                    help="also run the multi-tenant LoRA lifecycle "
                         "(train, SIGKILL mid-save, resume, serve mixed "
                         "+ scoped tpu_lint of paddle_tpu/lora)")
    ap.add_argument("--observability", action="store_true",
                    help="also run the telemetry gate (flight-recorder "
                         "crash drill + 2-process fleet observability "
                         "drill [scrape/partition/SLO-burn/trace] + "
                         "<2%% decode tracing overhead)")
    ap.add_argument("--overlap", action="store_true",
                    help="also run the step-schedule regression gate "
                         "(bench_profile --overlap --distributed vs the "
                         ".overlap_baseline.json threshold + scoped "
                         "tpu_lint of the restructured step files)")
    ap.add_argument("--disagg", action="store_true",
                    help="also run the disaggregated prefill/decode "
                         "gate (fleet_chaos --disagg migration fault "
                         "drill + serve_bench --disagg warm-boot and "
                         "migration-overhead regression vs the "
                         ".disagg_baseline.json floors)")
    ap.add_argument("--decode", action="store_true",
                    help="also run the raw-decode-speed regression gate "
                         "(decode_bench small preset, speculative + int8 "
                         "KV vs plain engine, against the "
                         ".decode_baseline.json floor + scoped tpu_lint "
                         "of the speculative/quantization files)")
    ap.add_argument("--sdc", action="store_true",
                    help="also run the silent-data-corruption drill "
                         "(sdc_drill --quick: fingerprint-vote detection "
                         "of a seeded bit flip, replay-vs-convict ladder, "
                         "quarantine + eviction + reduced-topology resume "
                         "+ scoped tpu_lint of the integrity files)")
    ap.add_argument("--skip-lint", action="store_true",
                    help="skip the tpu_lint static-analysis stage")
    ap.add_argument("--full-lint", action="store_true",
                    help="force a whole-repo lint (default: "
                         "--changed-only riding the incremental cache; "
                         "the tool falls back to a full run on its own "
                         "when the cache is missing or stale)")
    args = ap.parse_args()

    results = {}
    if not args.skip_lint:
        results["tpu_lint"] = _run_lint(full=args.full_lint)
    elif args.lora or args.observability:
        # the scoped per-subsystem lints folded into stage 0; skipping
        # it now skips THEIR lint coverage too — say so loudly instead
        # of silently weakening the subsystem gates (MIGRATION.md)
        print("[robustness_gate] WARNING: --skip-lint also skips the "
              "lora/observability lint coverage that used to ride "
              "their stages (now part of the unified whole-repo lint)",
              flush=True)
    if not args.skip_soak:
        cmd = [sys.executable, os.path.join(TOOLS, "chaos_soak.py")]
        if not args.full_soak:
            cmd.append("--quick")
        results["chaos_soak"] = _run("chaos_soak", cmd)
    if args.elastic:
        cmd = [sys.executable, os.path.join(TOOLS, "chaos_soak.py"),
               "--elastic"]
        if not args.full_soak:
            cmd.append("--quick")
        results["elastic"] = _run("elastic", cmd)
    if args.fleet:
        results["fleet"] = _run(
            "fleet", [sys.executable, os.path.join(TOOLS, "serve_bench.py"),
                      "--check", "--replicas", "2", "--prefix-cache-mb",
                      "4", "--prefix-tokens", "24", "--crash-replica",
                      "--verify", "3"])
    if args.fairness:
        results["fairness"] = _run(
            "fairness", [sys.executable,
                         os.path.join(TOOLS, "serve_bench.py"),
                         "--fairness"])
    if args.fleet_chaos:
        results["fleet_chaos"] = _run(
            "fleet_chaos", [sys.executable,
                            os.path.join(TOOLS, "fleet_chaos.py"),
                            "--quick"])
    if args.observability:
        results["flight_drill"] = _run(
            "flight_drill", [sys.executable,
                             os.path.join(TOOLS, "flight_drill.py")])
        results["fleet_obs_drill"] = _run(
            "fleet_obs_drill", [sys.executable,
                                os.path.join(TOOLS,
                                             "fleet_obs_drill.py")])
        results["trace_overhead"] = _run(
            "trace_overhead", [sys.executable,
                               os.path.join(TOOLS, "decode_bench.py"),
                               "--trace-overhead", "3"])
    if args.lora:
        results["lora"] = _run(
            "lora", [sys.executable, os.path.join(TOOLS, "lora_soak.py")])
    if args.overlap:
        results["overlap"] = _run_overlap_gate()
    if args.disagg:
        results["disagg"] = _run_disagg_gate()
    if args.decode:
        results["decode"] = _run_decode_gate()
    if args.sdc:
        results["sdc"] = _run(
            "sdc", [sys.executable, os.path.join(TOOLS, "sdc_drill.py"),
                    "--quick"])
        if results["sdc"]:
            # scoped self-application: the fingerprint readback (R1
            # suppressed at exactly one reasoned sync point), the
            # monitor's lock discipline (R5/R7) and the quarantine
            # staging write (R9) must carry zero unbaselined findings
            results["sdc_lint"] = _run(
                "sdc_lint",
                [sys.executable, os.path.join(TOOLS, "tpu_lint.py"),
                 "--baseline",
                 os.path.join(REPO, ".tpu_lint_baseline.json"),
                 os.path.join(REPO, "paddle_tpu/distributed/integrity.py"),
                 os.path.join(REPO, "paddle_tpu/distributed/shard.py"),
                 os.path.join(REPO, "paddle_tpu/framework/supervisor.py"),
                 os.path.join(REPO, "tools/sdc_drill.py")])
    if not args.skip_sweep:
        results["fault_sweep"] = _run(
            "fault_sweep", [sys.executable,
                            os.path.join(TOOLS, "fault_sweep.py")])

    print()
    for name, ok in results.items():
        print(f"[robustness_gate] {name:12s} {'PASS' if ok else 'FAIL'}")
    if not results:
        print("[robustness_gate] nothing ran (both stages skipped)")
        return 2
    return 0 if all(results.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
