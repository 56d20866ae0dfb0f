"""Decode-throughput bench for the compiled KV-cache generation engine.

Measures the two serving numbers that matter — tokens/s and
time-to-first-token — for batched greedy decode through
``models.generation``, plus the compile discipline (prefill/decode
program counts must be ``#buckets_used + 1``). Prints ONE JSON line:

    {"metric": "gpt_decode_tokens_per_sec", "value": N, "unit":
     "tokens/s", "extra": {"ttft_ms": ..., "decode_tokens_per_sec": ...,
     "prefill_compiles": ..., "decode_compiles": ..., ...}}

Runs on any backend (tier-1 invokes it with JAX_PLATFORMS=cpu on the
tiny config; on TPU pass --preset serving for a 350M-class model).

Speculative decoding and int8 KV-cache quantization are measured with
the same harness: ``--speculative K`` swaps in
``models.speculative.SpeculativeEngine`` (weight-copied truncated
draft, ``--draft-layers`` deep) and the record grows acceptance-rate
and tokens-per-target-dispatch stats; ``--kv-dtype int8`` quantizes
the cache and the record reports cache bytes. ``--json-out`` runs the
plain engine first and writes a paired before/after artifact (same
shape as ``bench_profile.py --distributed``) so the speedup is
self-contained in one file.

    python tools/decode_bench.py
    python tools/decode_bench.py --model llama --batch 8 --new-tokens 128
    python tools/decode_bench.py --preset serving   # TPU-sized config
    python tools/decode_bench.py --preset small --speculative 4 \
        --kv-dtype int8 --json-out /tmp/decode.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_model(family: str, preset: str):
    import paddle_tpu as pt

    pt.seed(0)
    if family == "gpt":
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, gpt_tiny

        if preset == "serving":
            cfg = GPTConfig(vocab_size=50304, hidden_size=1024,
                            num_layers=24, num_heads=16,
                            max_position_embeddings=1024,
                            hidden_dropout_prob=0.0,
                            attention_dropout_prob=0.0, dtype="bfloat16")
        elif preset == "small":
            # CPU-runnable but COMPUTE-bound (tiny is dispatch-bound, so
            # prefill-vs-cache effects vanish in launch overhead) — the
            # config serve_bench's prefix-cache acceptance runs use
            cfg = GPTConfig(vocab_size=2048, hidden_size=256,
                            num_layers=4, num_heads=8,
                            max_position_embeddings=512,
                            hidden_dropout_prob=0.0,
                            attention_dropout_prob=0.0,
                            use_flash_attention=False)
        else:
            cfg = gpt_tiny(hidden_dropout_prob=0.0,
                           attention_dropout_prob=0.0,
                           use_flash_attention=False)
        return GPTForCausalLM(cfg), cfg
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         llama_tiny)

    if preset == "serving":
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, num_layers=24,
                          num_heads=16, num_kv_heads=4,
                          max_position_embeddings=1024, dtype="bfloat16")
    elif preset == "small":
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256, num_layers=4,
                          num_heads=8, num_kv_heads=4,
                          max_position_embeddings=512,
                          use_flash_attention=False)
    else:
        cfg = llama_tiny(use_flash_attention=False)
    return LlamaForCausalLM(cfg), cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", choices=("gpt", "llama"), default="gpt")
    ap.add_argument("--preset", choices=("tiny", "small", "serving"), default="tiny",
                    help="tiny: CPU-safe smoke config; serving: 350M-class")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    help="prefill length buckets (default: engine default)")
    ap.add_argument("--trace-overhead", type=int, nargs="?", const=3,
                    default=0, metavar="REPS",
                    help="measure tracing-on vs tracing-off decode "
                         "throughput (best of REPS runs each, default 3); "
                         "exits non-zero if the overhead exceeds "
                         "--trace-overhead-pct")
    ap.add_argument("--trace-overhead-pct", type=float, default=2.0,
                    help="max acceptable tracing overhead, percent")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="draft-model speculative decoding: propose K "
                         "tokens per round (0 = plain engine)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="layers kept in the weight-copied draft model")
    ap.add_argument("--kv-dtype", choices=("none", "int8"), default="none",
                    help="KV-cache storage dtype (int8 = quantized)")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="write a paired before/after summary (plain "
                         "engine vs the configured one) to PATH")
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu.framework import compile_cache
    from paddle_tpu.models.generation import GenerationEngine
    from paddle_tpu.models.kv_cache import (cache_nbytes, init_cache,
                                            normalize_kv_dtype)
    from paddle_tpu.observability import default_registry, tracing

    model, cfg = build_model(args.model, args.preset)
    model.eval()
    kv_dtype = normalize_kv_dtype(
        None if args.kv_dtype == "none" else args.kv_dtype)
    spec_k = max(0, args.speculative)
    max_length = min(cfg.max_position_embeddings,
                     args.prompt_len + args.new_tokens + 8 + spec_k)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size,
                       (args.batch, args.prompt_len)).astype(np.int32)

    def build_engine(k: int, kv):
        if k:
            from paddle_tpu.models.speculative import (SpeculativeEngine,
                                                       build_draft_model)
            draft = build_draft_model(model, num_layers=args.draft_layers)
            return SpeculativeEngine(model, draft, k=k,
                                     max_length=max_length,
                                     prefill_buckets=args.buckets,
                                     kv_dtype=kv, draft_kv_dtype=kv)
        return GenerationEngine(model, max_length=max_length,
                                prefill_buckets=args.buckets, kv_dtype=kv)

    def measure(k: int, kv):
        """Warm up (pays the compiles), then time one pure-dispatch run."""
        engine = build_engine(k, kv)
        t_warm = time.perf_counter()
        engine.generate(ids, max_new_tokens=args.new_tokens)
        warmup_s = time.perf_counter() - t_warm
        before = compile_cache.cache_stats()["compiles"]
        out, stats = engine.generate(ids, max_new_tokens=args.new_tokens,
                                     return_stats=True)
        after = compile_cache.cache_stats()["compiles"]
        extra = {
            "ttft_ms": round(stats["ttft_s"] * 1e3, 2),
            "decode_tokens_per_sec": round(stats["decode_tokens_per_sec"], 1),
            "new_tokens": int(out.shape[1]),
            "batch": args.batch,
            "prompt_len": args.prompt_len,
            "prefill_bucket": stats["prefill_bucket"],
            "steady_state_recompiles": after - before,
            "warmup_s": round(warmup_s, 2),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "preset": args.preset,
            "mode": "speculative" if k else "plain",
            "kv_dtype": kv or "full",
            "cache_bytes": cache_nbytes(
                init_cache(model, args.batch, max_length, kv_dtype=kv)),
        }
        for name, family in stats["compile_stats"].items():
            extra[f"{name}_compiles"] = family["compiles"]
        if k:
            extra.update(
                k=stats["k"],
                draft_layers=args.draft_layers,
                rounds=stats["rounds"],
                acceptance_rate=round(stats["acceptance_rate"], 4),
                tokens_per_target_dispatch=round(
                    stats["tokens_per_target_dispatch"], 3),
            )
        record = {
            "metric": f"{args.model}_decode_tokens_per_sec",
            "value": round(stats["tokens_per_sec"], 1),
            "unit": "tokens/s",
            "extra": extra,
        }
        return record, after - before

    if args.trace_overhead:
        # the observability gate: per-token span recording on the decode
        # hot loop must cost <--trace-overhead-pct of throughput.
        # Best-of-REPS per mode filters scheduler noise on shared boxes;
        # modes alternate so drift hits both equally.
        engine = build_engine(spec_k, kv_dtype)
        engine.generate(ids, max_new_tokens=args.new_tokens)  # pay compiles
        reps = max(1, int(args.trace_overhead))
        best = {True: 0.0, False: 0.0}
        was_enabled = tracing.enabled()
        try:
            for _ in range(reps):
                for mode in (False, True):
                    tracing.enable(mode)
                    _, stats = engine.generate(
                        ids, max_new_tokens=args.new_tokens,
                        return_stats=True)
                    best[mode] = max(best[mode],
                                     stats["decode_tokens_per_sec"])
        finally:
            tracing.enable(was_enabled)
        overhead_pct = 100.0 * (best[False] - best[True]) / max(
            best[False], 1e-9)
        record = {
            "metric": "decode_trace_overhead_pct",
            "value": round(overhead_pct, 3),
            "unit": "%",
            "extra": {
                "tokens_per_sec_tracing_off": round(best[False], 1),
                "tokens_per_sec_tracing_on": round(best[True], 1),
                "reps": reps,
                "threshold_pct": args.trace_overhead_pct,
                "batch": args.batch,
                "new_tokens": args.new_tokens,
                "preset": args.preset,
                "backend": jax.default_backend(),
            },
        }
        print(json.dumps(record))
        if overhead_pct > args.trace_overhead_pct:
            print(f"FAIL: tracing costs {overhead_pct:.2f}% decode "
                  f"throughput (> {args.trace_overhead_pct}% budget) — "
                  f"the span recorder is on the wrong side of a "
                  f"dispatch point", file=sys.stderr)
            return 1
        return 0

    baseline_record = None
    if args.json_out and (spec_k or kv_dtype):
        baseline_record, _ = measure(0, None)

    record, recompiles = measure(spec_k, kv_dtype)
    # unified-registry snapshot: compile counters (and whatever else this
    # process absorbed) ride the bench artifact
    record["extra"]["metrics"] = default_registry().snapshot()
    print(json.dumps(record))

    if args.json_out:
        summary = {
            "bench": "decode_bench",
            "model": args.model,
            "preset": args.preset,
            "batch": args.batch,
            "prompt_len": args.prompt_len,
            "new_tokens": args.new_tokens,
            "before": baseline_record or record,
            "after": record,
            "speedup": round(
                record["value"]
                / max((baseline_record or record)["value"], 1e-9), 3),
        }
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json_out}", file=sys.stderr)

    if recompiles:
        print(f"FAIL: timed run recompiled ({recompiles} new programs) — "
              f"the decode step is not shape-stable", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
