"""Break down the b8 bench step: fwd / fwd+bwd / full step, flash variants
— plus the per-step collective-overlap breakdown (``--overlap``) that
ROADMAP item 1 (overlap-scheduled distributed training) gates on.

``--overlap`` runs N instrumented train steps under the profiler's host
span recorder and splits each step's wall time into:

- **compute** — the measured fwd+bwd program time (the part overlap
  scheduling cannot shrink);
- **collective** — host spans whose names mark collective work
  (``allreduce``/``psum``/``all_gather``/... — today's serial schedule
  runs them inside the one compiled program, so this column reads 0
  until bucketed/async collectives land and register their own spans);
- **host_stall** — input-pipeline / H2D spans (``h2d_prefetch`` et al.)
  overlapping the step;
- **non_compute residual** — step wall minus all of the above
  (optimizer + dispatch + the collective time hiding inside the fused
  program). The overlap work drives THIS number toward zero per step;
  the table + JSON line make the trajectory visible per run.

Printed as a table and emitted as one bench-style JSON line
(``<model>_step_overlap_breakdown``), so ``bench_sweep``-style tooling
can archive it next to the MFU numbers.

With ``--distributed`` the ``--overlap`` mode runs the REAL target of
the work — ``DistributedTrainStep`` on the multi-device mesh — twice on
the same config: once with the serial schedule (knobs off) and once
with ``overlap_grad_reduce=True`` (bucketed reverse-backward reduction
+ ZeRO weight-update sharding under ``--stage >= 1``). Each run emits
its own ``gpt_step_overlap_breakdown`` record tagged
``schedule: serial|bucketed``; per-bucket collective spans (named
``allreduce/bucketNN``, cost measured in isolation via a shard_map psum
of the bucket's payload and attributed into each step window) make the
bucketed schedule visible in the table. ``--buckets N`` sweeps bucket
count; ``--json-out`` archives the paired records + reduction factor as
one artifact for ``bench_sweep``-style diffing (and for
``robustness_gate --overlap``, which fails on a non_compute_frac
regression).

Run: python -m tools.bench_profile            # classic fwd/bwd/step timings
     python -m tools.bench_profile --overlap  # per-step breakdown table
     python -m tools.bench_profile --overlap --distributed \
         [--stage 1] [--buckets N] [--bucket-mb MB] [--json-out PATH]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def timeit(fn, *args, n=10, warmup=2):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    # tpu-lint: disable=R1(benchmark warmup fence — the timed region must start with nothing in flight)
    jax.tree.map(lambda x: x.block_until_ready()
                 if hasattr(x, "block_until_ready") else x, out)
    # a one-element host read: waits for the last dispatch like
    # block_until_ready, and also proves the result can be fetched
    leaf = jax.tree.leaves(out)[0]
    float(np.asarray(leaf).reshape(-1)[0])
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    leaf = jax.tree.leaves(out)[0]
    float(np.asarray(leaf).reshape(-1)[0])
    return (time.perf_counter() - t0) / n


# --------------------------------------------- overlap breakdown (pure)
#: span-name classification for the breakdown — "existing profiler
#: events" in, buckets out. Collective names cover the wrappers
#: distributed/collective.py and future bucketed-allreduce spans will
#: register; host-stall covers the input pipeline's spans.
_COLLECTIVE_KEYS = ("allreduce", "all_reduce", "psum", "pmean",
                    "all_gather", "allgather", "reduce_scatter",
                    "all_to_all", "a2a", "collective", "ppermute")
_HOST_STALL_KEYS = ("h2d", "prefetch", "stall", "data_wait")


def classify_span(name: str) -> str:
    low = str(name).lower()
    if any(k in low for k in _COLLECTIVE_KEYS):
        return "collective"
    if any(k in low for k in _HOST_STALL_KEYS):
        return "host_stall"
    if low == "step":
        return "step"
    return "other"


def _overlap_s(t0, t1, w0, w1):
    """Seconds of [t0, t1] falling inside the window [w0, w1]."""
    return max(0.0, min(t1, w1) - max(t0, w0))


def overlap_breakdown(spans, compute_s=None):
    """Split each recorded ``step`` span's wall time into compute /
    collective / host_stall / residual using the other host spans that
    overlap it. ``spans`` is ``[(name, t0, t1), ...]`` (the host event
    recorder's shape); ``compute_s`` is the separately measured
    compute-only (fwd+bwd) program time attributed to every step.
    Returns ``{"steps": [per-step rows], "mean": aggregate row}``."""
    steps = sorted(((t0, t1) for name, t0, t1 in spans
                    if classify_span(name) == "step"),
                   key=lambda w: w[0])
    others = [(classify_span(name), t0, t1) for name, t0, t1 in spans
              if classify_span(name) in ("collective", "host_stall")]
    rows = []
    for i, (w0, w1) in enumerate(steps):
        wall = w1 - w0
        coll = sum(_overlap_s(t0, t1, w0, w1)
                   for kind, t0, t1 in others if kind == "collective")
        stall = sum(_overlap_s(t0, t1, w0, w1)
                    for kind, t0, t1 in others if kind == "host_stall")
        comp = min(wall, compute_s) if compute_s is not None else 0.0
        resid = max(0.0, wall - comp - coll - stall)
        rows.append({"step": i, "wall_ms": round(wall * 1e3, 3),
                     "compute_ms": round(comp * 1e3, 3),
                     "collective_ms": round(coll * 1e3, 3),
                     "host_stall_ms": round(stall * 1e3, 3),
                     "non_compute_ms": round(resid * 1e3, 3)})
    mean = {}
    if rows:
        for key in ("wall_ms", "compute_ms", "collective_ms",
                    "host_stall_ms", "non_compute_ms"):
            mean[key] = round(sum(r[key] for r in rows) / len(rows), 3)
        mean["non_compute_frac"] = round(
            (mean["collective_ms"] + mean["host_stall_ms"]
             + mean["non_compute_ms"]) / mean["wall_ms"], 4) \
            if mean["wall_ms"] else 0.0
    return {"steps": rows, "mean": mean}


def print_breakdown_table(breakdown) -> None:
    cols = ("step", "wall_ms", "compute_ms", "collective_ms",
            "host_stall_ms", "non_compute_ms")
    print("".join(f"{c:>16}" for c in cols))
    for r in breakdown["steps"]:
        print("".join(f"{r[c]:>16}" for c in cols))
    m = breakdown["mean"]
    if m:
        print("".join(f"{v:>16}" for v in
                      ("mean", m["wall_ms"], m["compute_ms"],
                       m["collective_ms"], m["host_stall_ms"],
                       m["non_compute_ms"])))
        print(f"non-compute fraction of step wall: "
              f"{m['non_compute_frac']:.1%}  (the number the overlap "
              f"scheduling work drives toward 0)")


def run_overlap(batch=4, seq=128, steps=5, flash=False):
    """The ``--overlap`` mode: instrumented steps on a small config
    (CPU-safe), classic host spans in, breakdown table + JSON out."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu import profiler
    from paddle_tpu.framework.jit import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     param_state)
    from paddle_tpu.optimizer import AdamW

    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=seq,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=flash)
    paddle_tpu.seed(0)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4)
    params = jax.tree.map(lambda x: jnp.array(x, copy=True),
                          param_state(model))
    buffers = buffer_state(model)
    rng = np.random.default_rng(0)
    ids = np.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                     np.int32)

    @jax.jit
    def fwdbwd(p, x):
        def loss(p):
            out, _ = functional_call(model, p, buffers,
                                     jnp.asarray(x), jnp.asarray(x))
            return out

        return jax.value_and_grad(loss)(p)

    t_compute = timeit(fwdbwd, params, ids, n=max(3, steps), warmup=2)
    step = TrainStep(model, opt, loss_fn=None)
    step((ids, ids))   # compile outside the recorded window

    rec = profiler._recorder
    prev_enabled = rec.enabled
    rec.clear()
    rec.enabled = True
    try:
        for _ in range(steps):
            step((ids, ids))
        # tpu-lint: disable=R1(benchmark fence — the last step's wall time must include its device work)
        float(np.asarray(step((ids, ids))))
        with rec.lock:
            spans = list(rec.spans)
    finally:
        rec.enabled = prev_enabled
    breakdown = overlap_breakdown(spans, compute_s=t_compute)
    print_breakdown_table(breakdown)
    record = {
        "metric": "gpt_step_overlap_breakdown",
        "value": breakdown["mean"].get("non_compute_frac", 0.0),
        "unit": "frac_of_step_wall",
        "extra": {"steps": len(breakdown["steps"]),
                  "schedule": "serial",
                  **breakdown["mean"],
                  # the raw fwd+bwd program time, distinct from the
                  # per-step (wall-clamped) compute_ms mean above
                  "fwdbwd_ms": round(t_compute * 1e3, 3),
                  "batch": batch, "seq": seq,
                  "backend": jax.default_backend()},
    }
    print(json.dumps(record))
    return breakdown


# ------------------------------------------- distributed overlap breakdown
def _measure_bucket_allreduce_ms(mesh, axis, buckets, shapes, dtypes,
                                 n=3):
    """Per-bucket collective cost, measured in ISOLATION: one compiled
    shard_map program all-reducing the bucket's grad payload over
    ``axis``. The numbers are attributed into each recorded step window
    as ``allreduce/bucketNN`` spans — a measured estimate of where the
    schedule spends its collective time, not an in-program trace (host
    callbacks inside the step would be an R1 violation and would perturb
    the thing being measured)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec
    from paddle_tpu.framework.jax_compat import shard_map

    def body(xs):
        return tuple(jax.lax.psum(x, axis) for x in xs)

    # ONE compiled callable; each bucket's payload is a different pytree
    # signature, so jit's own cache holds one executable per bucket
    spec = PartitionSpec()
    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                          out_specs=spec))

    out = []
    for b in buckets:
        names = b["params"]
        args = tuple(jnp.zeros(shapes[p], dtypes[p]) for p in names)
        t = timeit(lambda: f(args), n=n, warmup=1)
        out.append({"bucket": b["bucket"], "bytes": b["bytes"],
                    "params": len(names), "allreduce_ms": round(t * 1e3, 3)})
    return out


def _synthesize_bucket_spans(step_windows, bucket_ms, prefix="allreduce"):
    """Lay the isolation-measured bucket costs into each step window as
    consecutive spans so :func:`overlap_breakdown` can classify them."""
    spans = []
    for (w0, w1) in step_windows:
        t = w0
        for b in bucket_ms:
            dur = b["allreduce_ms"] / 1e3
            spans.append((f"{prefix}/bucket{b['bucket']:02d}", t, t + dur))
            t += dur
    return spans


def run_overlap_distributed(batch=8, seq=128, steps=3, stage=1,
                            bucket_mb=8.0, bucket_count=None,
                            hidden=512, layers=2, vocab=4096,
                            json_out=None, serial_stage=0):
    """``--overlap --distributed``: the before/after measurement ROADMAP
    item 1 gates on. Runs the SAME model/batch config through
    ``DistributedTrainStep`` twice and emits one
    ``gpt_step_overlap_breakdown`` record per schedule plus a paired
    artifact (``--json-out``) carrying the reduction factor.

    The pairing is *pre-PR schedule vs new schedule*, not a single-knob
    ablation: ``serial`` is the defaults as they shipped before the
    overlap work (``overlap_grad_reduce=False``, ``sharding_stage=
    serial_stage`` = 0 — fused tail all-reduce, fully replicated weight
    update), and ``bucketed`` is the restructured step
    (``overlap_grad_reduce=True`` at ``--stage``, default 1 — bucketed
    reverse-backward collectives plus the ZeRO-style sharded update, so
    the weight update stops being replicated work). Pass
    ``--serial-stage`` equal to ``--stage`` for the bucketing-only
    ablation; on a single-core host mesh that delta is scheduler noise
    (overlap cannot hide latency when devices timeshare one core), which
    is exactly why the gate pins the schedule-level pairing instead.

    Compute attribution: a single-device fwd+bwd program on the batch —
    the work the schedule cannot shrink. On a multi-chip backend each
    chip holds ``batch/n``, so the local-batch program is timed; on the
    host-platform CPU mesh the virtual devices timeshare the same cores,
    so the FULL-batch program is the right serialized-compute baseline.
    """
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu import profiler
    from paddle_tpu.distributed.mesh import init_mesh, set_mesh
    from paddle_tpu.distributed.shard import DistributedTrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     param_state)
    from paddle_tpu.optimizer import AdamW

    ndev = jax.device_count()
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    num_heads=max(2, hidden // 64),
                    max_position_embeddings=seq,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    rng = np.random.default_rng(0)
    ids = np.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                     np.int32)

    # compute baseline: fwd+bwd only, one device, no collectives
    per_device = jax.default_backend() != "cpu" and ndev > 1
    local = ids[: max(1, batch // ndev)] if per_device else ids
    paddle_tpu.seed(0)
    ref_model = GPTForCausalLM(cfg)
    ref_params = jax.tree.map(lambda x: jnp.array(x, copy=True),
                              param_state(ref_model))
    ref_buffers = buffer_state(ref_model)

    @jax.jit
    def fwdbwd(p, x):
        def loss(p):
            out, _ = functional_call(ref_model, p, ref_buffers,
                                     jnp.asarray(x), jnp.asarray(x))
            return out

        return jax.value_and_grad(loss)(p)

    t_compute = timeit(fwdbwd, ref_params, local, n=max(3, steps), warmup=2)
    del ref_params

    results = {}
    for schedule in ("serial", "bucketed"):
        sched_stage = stage if schedule == "bucketed" else serial_stage
        mesh = init_mesh(sdp=ndev)
        paddle_tpu.seed(0)
        model = GPTForCausalLM(cfg)
        step = DistributedTrainStep(
            model, AdamW(learning_rate=1e-4), loss_fn=None,
            sharding_stage=sched_stage,
            overlap_grad_reduce=(schedule == "bucketed"),
            bucket_size_mb=bucket_mb, bucket_count=bucket_count)
        step((ids, ids))   # compile outside the recorded window

        rec = profiler._recorder
        prev_enabled = rec.enabled
        rec.clear()
        rec.enabled = True
        try:
            for _ in range(steps):
                step((ids, ids))
            # tpu-lint: disable=R1(benchmark fence — the last step's wall time must include its device work)
            float(np.asarray(step((ids, ids))))
            with rec.lock:
                spans = list(rec.spans)
        finally:
            rec.enabled = prev_enabled

        windows = sorted(((t0, t1) for name, t0, t1 in spans
                          if classify_span(name) == "step"),
                         key=lambda w: w[0])
        schedule_buckets = step.collective_schedule() or [
            {"bucket": 0, "bytes": sum(
                int(v.size) * int(jnp.dtype(v.dtype).itemsize)
                for v in step.params.values()),
             "params": list(step.params)}]
        shapes = {k: v.shape for k, v in step.params.items()}
        dtypes = {k: v.dtype for k, v in step.params.items()}
        bucket_ms = _measure_bucket_allreduce_ms(
            mesh, "sdp", schedule_buckets, shapes, dtypes)
        spans += _synthesize_bucket_spans(windows, bucket_ms)
        breakdown = overlap_breakdown(spans, compute_s=t_compute)
        print(f"--- schedule={schedule} stage={sched_stage} "
              f"buckets={len(schedule_buckets)} devices={ndev}")
        print_breakdown_table(breakdown)
        record = {
            "metric": "gpt_step_overlap_breakdown",
            "value": breakdown["mean"].get("non_compute_frac", 0.0),
            "unit": "frac_of_step_wall",
            "extra": {"steps": len(breakdown["steps"]),
                      "schedule": schedule,
                      "sharding_stage": sched_stage,
                      "devices": ndev,
                      **breakdown["mean"],
                      "fwdbwd_ms": round(t_compute * 1e3, 3),
                      "buckets": bucket_ms,
                      "zero_fallback_params":
                          list(step.zero_fallback_params),
                      "batch": batch, "seq": seq, "hidden": hidden,
                      "layers": layers, "vocab": vocab,
                      "backend": jax.default_backend()},
        }
        print(json.dumps(record))
        results[schedule] = record
        del step, model
        set_mesh(None)

    serial = results["serial"]["value"]
    bucketed = results["bucketed"]["value"]
    reduction = round(serial / bucketed, 3) if bucketed else float("inf")
    summary = {"config": {"batch": batch, "seq": seq, "hidden": hidden,
                          "layers": layers, "vocab": vocab, "stage": stage,
                          "serial_stage": serial_stage,
                          "steps": steps, "bucket_mb": bucket_mb,
                          "bucket_count": bucket_count},
               "serial": results["serial"],
               "bucketed": results["bucketed"],
               "non_compute_frac_reduction": reduction}
    print(f"non_compute_frac: serial={serial:.4f} bucketed={bucketed:.4f} "
          f"reduction={reduction}x")
    if json_out:
        with open(json_out, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
        print(f"wrote {json_out}")
    return summary


def main(batch=8, seq=1024, flash=True, loss_chunk=256):
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu import amp
    from paddle_tpu.framework.jit import TrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       gpt_flops_per_token, gpt_loss_fn)  # noqa: F401
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     param_state)
    from paddle_tpu.optimizer import AdamW
    from bench import _chip_peak_flops

    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_heads=16, max_position_embeddings=seq,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=flash, loss_chunk=loss_chunk,
                    dtype="bfloat16")
    paddle_tpu.seed(0)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    params = jax.tree.map(lambda x: jnp.array(x, copy=True), param_state(model))
    buffers = buffer_state(model)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)

    tok = batch * seq
    fpt = gpt_flops_per_token(cfg, seq)
    peak = _chip_peak_flops()

    @jax.jit
    def fwd(p, ids):
        out, _ = functional_call(model, p, buffers, ids, ids)
        return out

    @jax.jit
    def fwdbwd(p, ids):
        def loss(p):
            out, _ = functional_call(model, p, buffers, ids, ids)
            return out

        l, g = jax.value_and_grad(loss)(p)
        return l, g

    t_f = timeit(fwd, params, ids)
    print(f"fwd          {t_f*1e3:8.2f} ms  ({tok/t_f:9.0f} tok/s, "
          f"'fwd-MFU' {tok/t_f*fpt/3*1/peak:.3f} of peak w/ 2N/tok)")
    t_fb = timeit(fwdbwd, params, ids)
    print(f"fwd+bwd      {t_fb*1e3:8.2f} ms  (MFU {tok/t_fb*fpt/peak:.4f})")

    step = TrainStep(model, opt, loss_fn=None)
    t_s = timeit(lambda b: step(b), (np.asarray(ids), np.asarray(ids)))
    print(f"full step    {t_s*1e3:8.2f} ms  (MFU {tok/t_s*fpt/peak:.4f}) "
          f"[optimizer+transfer overhead {100*(t_s-t_fb)/t_s:.1f}%]")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--noflash", action="store_true")
    ap.add_argument("--overlap", action="store_true",
                    help="per-step compute/collective/host-stall "
                         "breakdown (table + JSON) instead of the b8 "
                         "timings")
    ap.add_argument("--distributed", action="store_true",
                    help="run the breakdown through DistributedTrainStep "
                         "on the device mesh, serial vs bucketed schedule "
                         "(the before/after pair ROADMAP item 1 gates on)")
    ap.add_argument("--stage", type=int, default=1,
                    help="sharding_stage for the bucketed schedule "
                         "(default 1: ZeRO weight-update sharding "
                         "engages)")
    ap.add_argument("--serial-stage", type=int, default=0,
                    help="sharding_stage for the serial baseline "
                         "(default 0 — the pre-overlap default schedule: "
                         "fused tail all-reduce + replicated update; set "
                         "equal to --stage for a bucketing-only ablation)")
    ap.add_argument("--buckets", type=int, default=None,
                    help="bucket-count override for the bucketed "
                         "schedule (sweeps; default: size-targeted via "
                         "--bucket-mb)")
    ap.add_argument("--bucket-mb", type=float, default=8.0,
                    help="bucket size target in MB for --distributed "
                         "(default 8.0 — ~4 buckets over the default "
                         "34MB-of-grads config)")
    ap.add_argument("--json-out", default=None,
                    help="write the paired serial/bucketed records + "
                         "reduction factor as one JSON artifact")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default: 1024 for the MFU "
                         "run, 128 for --distributed)")
    args = ap.parse_args()
    if args.overlap and args.distributed:
        # the host-platform mesh needs its virtual devices BEFORE jax
        # initializes; harmless when a real multi-chip backend is up
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", "") and \
                os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " --xla_force_host_platform_"
                                         "device_count=8")
        run_overlap_distributed(steps=args.steps, stage=args.stage,
                                batch=args.batch, seq=args.seq or 128,
                                bucket_mb=args.bucket_mb,
                                bucket_count=args.buckets,
                                json_out=args.json_out,
                                serial_stage=args.serial_stage)
        sys.exit(0)
    if args.overlap:
        # flash stays off here: the breakdown targets schedule structure,
        # not kernel choice, and the small config must stay CPU-safe
        run_overlap(steps=args.steps)
        sys.exit(0)
    main(batch=args.batch, seq=args.seq or 1024, flash=not args.noflash)
