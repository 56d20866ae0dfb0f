"""Benchmark: GPT pretrain step throughput + MFU on one TPU chip.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

The BASELINE.md north star is GPT-3 1.3B at >=35% MFU on v5p-32. This bench
runs the largest GPT config that fits the available chip (single chip under
the driver), measures tokens/sec/chip over timed steps, and reports MFU
against the chip's peak FLOPs. ``vs_baseline`` = measured MFU / 0.35.

Breadth phases ride in ``extra``:
  - ``long_context``: GPT at seq=4096, which takes the Pallas
    flash-attention path (asserted in-run via ``should_use_flash``).
  - ``gpt_1p3b``: the BASELINE.md north-star width on one chip.
  - ``gpt_decode`` / ``gpt_serve``: the offline and continuous-batching
    serving engines.
  - ``resnet50``: imgs/sec for the conv-heavy model zoo path.

One process holds the chip from start to finish and runs the phases in
turn. It refuses any backend but ``tpu``; a phase that fails is reported
in the record and the process exits non-zero.
"""
from __future__ import annotations

import contextlib
import io
import json
import sys
import traceback

import numpy as np


# peak bf16 FLOPs/s per chip by TPU generation (public figures)
PEAK_FLOPS = {
    "v2": 22.5e12, "v3": 123e12 / 2, "v4": 275e12, "v5e": 197e12,
    "v5lite": 197e12, "v5p": 459e12, "v5": 459e12, "v6e": 918e12,
}


def _chip_peak_flops() -> float:
    """Ordered substring match of the device kind against ``PEAK_FLOPS``
    (key order matters: 'v5lite' must match before 'v5'). A device the
    table does not know is an error, never a default."""
    import jax

    kind = jax.devices()[0].device_kind
    compact = kind.lower().replace(" ", "")
    for key, val in PEAK_FLOPS.items():
        if key in compact:
            return val
    raise KeyError(f"device_kind {kind!r} is not in PEAK_FLOPS "
                   f"({', '.join(PEAK_FLOPS)}): add its published figure")


def _chip_hbm_bytes() -> float:
    """What the runtime says this process may allocate on the chip."""
    import jax

    return float(jax.devices()[0].memory_stats()["bytes_limit"])


def _timed_steps(step, batch_data, timed: int, warmup: int) -> float:
    """Run ``warmup`` + ``timed`` steps; returns seconds for the timed ones
    and the last loss. The window closes on a host read of that loss: each
    step consumes the state the one before produced, so the read waits for
    all of them exactly as ``block_until_ready`` would (chip_smoke.py
    checks that it fences), and it yields the value the record prints.
    Every warmup step syncs on its own so that nothing is in flight when
    the window opens."""
    import time

    for _ in range(warmup):
        float(np.asarray(step(batch_data)))
    t0 = time.perf_counter()
    for _ in range(timed):
        loss = step(batch_data)
    final_loss = float(np.asarray(loss))
    return time.perf_counter() - t0, final_loss


def _gpt_train_step(cfg, o2: bool = True, **adamw_kw):
    """Seeded model + AdamW through the fused-loss TrainStep; ``o2`` casts
    the params to bf16 and keeps f32 master weights in the optimizer."""
    import paddle_tpu
    from paddle_tpu import amp
    from paddle_tpu.framework.jit import TrainStep
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.optimizer import AdamW

    paddle_tpu.seed(0)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, **adamw_kw)
    if o2:
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    # loss_chunk fuses head+CE: forward(ids, labels) returns the loss
    return TrainStep(model, opt, loss_fn=None)


def _random_ids(cfg, batch: int, seq: int):
    rng = np.random.default_rng(0)
    return np.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), np.int32)


def bench_long_context() -> dict:
    """GPT at seq>=4096: the config that exercises the Pallas flash kernel
    (should_use_flash asserted live) — the long-context proof."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import should_use_flash
    from paddle_tpu.models.gpt import GPTConfig, gpt_flops_per_token

    batch, seq = 2, 4096
    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_heads=16, max_position_embeddings=seq,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_recompute=False, use_flash_attention=True,
                    loss_chunk=256, dtype="bfloat16")
    # the gate the model's attention dispatch consults — assert the bench
    # really takes the Pallas path for these shapes
    head_dim = cfg.hidden_size // cfg.num_heads
    probe = jnp.zeros((batch * cfg.num_heads, seq, head_dim), jnp.bfloat16)
    if not should_use_flash(probe, probe, None, 0.0):
        raise RuntimeError("seq=4096 config must take the flash path")

    step = _gpt_train_step(cfg)
    ids = _random_ids(cfg, batch, seq)
    dt, _ = _timed_steps(step, (ids, ids), timed=10, warmup=6)
    tokens_per_sec = batch * seq * 10 / dt
    mfu = tokens_per_sec * gpt_flops_per_token(cfg, seq) / _chip_peak_flops()
    return {"seq": seq, "batch": batch, "flash_active": True,
            "tokens_per_sec": round(tokens_per_sec, 1),
            "mfu": round(mfu, 4)}


def bench_gpt_1p3b() -> dict:
    """The BASELINE.md north-star config: GPT-3 1.3B (hidden=2048,
    layers=24, heads=16). The standard O2 recipe (bf16 params + f32 master
    + f32 AdamW moments) needs 14 resident bytes/param = 18.4 GB for
    1.31e9 params —
    more than a v5e's 16 GB HBM, so on small-HBM chips this falls back to a
    documented memory-lean recipe: bf16 params (no separate master) + bf16
    AdamW moment1 + f32 moment2 (bf16 moment2 would freeze its 0.999-EMA —
    sub-ULP updates) = 8 bytes/param resident, + bf16 grads and
    rematerialized activations transient. The FLOPs counted for MFU are identical either
    way; the variant actually run is recorded. Reference target:
    BASELINE.md "GPT-3 1.3B pretrain >=35% MFU" (multi-chip v5p-32 there;
    this is the single-chip record)."""
    from paddle_tpu.models.gpt import GPTConfig, gpt_flops_per_token

    batch, seq = 2, 1024
    cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                    num_heads=16, max_position_embeddings=seq,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_recompute=True, use_flash_attention=True,
                    loss_chunk=256, dtype="bfloat16")
    # params: 12*h^2 per layer (qkvo + 2 mlp mats) + embeddings
    n_params = (12 * cfg.hidden_size ** 2 + 13 * cfg.hidden_size) * cfg.num_layers \
        + (cfg.vocab_size + seq) * cfg.hidden_size + 2 * cfg.hidden_size
    hbm = _chip_hbm_bytes()
    standard_bytes = 14 * n_params   # bf16 p(2) + f32 master(4) + f32 m+v(8)
    lean_bytes = 8 * n_params        # bf16 p(2) + bf16 m(2) + f32 v(4)
    # ~0.75 usable after grads + remat activations + XLA workspace
    standard_fits = standard_bytes < 0.75 * hbm
    hbm_math = {
        "params_billion": round(n_params / 1e9, 3),
        "hbm_gb": round(hbm / 1e9, 1),
        "standard_recipe_gb": round(standard_bytes / 1e9, 1),
        "lean_recipe_gb": round(lean_bytes / 1e9, 1),
    }

    if standard_fits:
        variant = "standard_o2_f32_moments"
        step = _gpt_train_step(cfg)
    else:
        variant = "lean_bf16_params_bf16_moments"
        step = _gpt_train_step(cfg, o2=False, moment_dtype="bfloat16")
    ids = _random_ids(cfg, batch, seq)
    timed = 8
    dt, final_loss = _timed_steps(step, (ids, ids), timed=timed, warmup=5)
    tokens_per_sec = batch * seq * timed / dt
    mfu = tokens_per_sec * gpt_flops_per_token(cfg, seq) / _chip_peak_flops()
    return {"variant": variant, "batch": batch, "seq": seq,
            "hbm_math": hbm_math,
            "tokens_per_sec": round(tokens_per_sec, 1),
            "mfu": round(mfu, 4), "vs_north_star": round(mfu / 0.35, 4),
            "final_loss": round(final_loss, 4)}


def bench_gpt_decode() -> dict:
    """Serving-side decode throughput through the compiled KV-cache
    generation engine (models/generation.py): batched greedy generate,
    tokens/s + time-to-first-token, plus the compile discipline
    (#prefill buckets + 1 programs, zero steady-state recompiles). The
    secondary serving metric next to the pretrain-side primary."""
    import paddle_tpu
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.models.generation import GenerationEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_heads=16, max_position_embeddings=1024,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    dtype="bfloat16")
    batch, prompt_len, new_tokens = 8, 96, 128
    paddle_tpu.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = GenerationEngine(
        model, max_length=min(cfg.max_position_embeddings,
                              prompt_len + new_tokens + 8))
    ids = _random_ids(cfg, batch, prompt_len)
    engine.generate(ids, max_new_tokens=new_tokens)  # warmup: compiles
    compiles_before = compile_cache.cache_stats()["compiles"]
    _, stats = engine.generate(ids, max_new_tokens=new_tokens,
                               return_stats=True)
    cc = stats["compile_stats"]
    return {
        "tokens_per_sec": round(stats["tokens_per_sec"], 1),
        "decode_tokens_per_sec": round(stats["decode_tokens_per_sec"], 1),
        "ttft_ms": round(stats["ttft_s"] * 1e3, 2),
        "batch": batch, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "prefill_compiles": cc["prefill"]["compiles"],
        "decode_compiles": cc["decode"]["compiles"],
        "steady_state_recompiles":
            compile_cache.cache_stats()["compiles"] - compiles_before,
    }


def bench_gpt_serve() -> dict:
    """Continuous-batching serving throughput: ``tools/serve_bench.py
    --check --preset serving --slots 8`` (Poisson open-loop load against
    ``paddle_tpu.serving.InferenceServer``), run IN THIS PROCESS — the
    chip belongs to it, and a child could not open the device. A non-zero
    return (steady-state recompiles, a blown compile budget) fails the
    phase."""
    from tools import serve_bench

    stdout = io.StringIO()
    with contextlib.redirect_stdout(stdout):
        rc = serve_bench.main(["--check", "--preset", "serving",
                               "--slots", "8"])
    rec = json.loads(stdout.getvalue().strip().splitlines()[-1])
    extra = rec["extra"]
    if extra["backend"] != "tpu":
        raise RuntimeError(f"serve_bench ran on {extra['backend']!r}")
    out = {"requests_per_sec": rec["value"]}
    for k in ("goodput", "tokens_per_sec", "ttft_p50_ms", "ttft_p99_ms",
              "inter_token_p50_ms", "inter_token_p99_ms", "slot_occupancy",
              "prefill_compiles", "decode_compiles",
              "steady_state_recompiles"):
        out[k] = extra[k]
    if rc != 0:
        raise RuntimeError(f"serve_bench failed its own checks (rc={rc}, "
                           f"reasons on stderr): {out}")
    return out


def bench_resnet50() -> dict:
    """ResNet-50 train-step imgs/sec (BASELINE.md row 1)."""
    import paddle_tpu
    import paddle_tpu.nn.functional as F
    from paddle_tpu import amp
    from paddle_tpu.framework.jit import TrainStep
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.optimizer import Momentum

    batch, size, timed = 64, 224, 20
    paddle_tpu.seed(0)
    model = resnet50(num_classes=1000)
    opt = Momentum(learning_rate=0.1, momentum=0.9)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    step = TrainStep(model, opt,
                     loss_fn=lambda out, b: F.cross_entropy(out, b[1]))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 3, size, size)).astype(np.float32)
    y = rng.integers(0, 10, batch)
    dt, _ = _timed_steps(step, (x, y), timed=timed, warmup=20)
    return {"imgs_per_sec": round(batch * timed / dt, 1), "batch": batch,
            "image_size": size}


def bench_gpt_primary():
    """The flagship config (recorded across rounds); returns the fields of
    the primary JSON line. Runs in its own frame so its HBM (params +
    master weights + compiled executable) is released before the breadth
    benches run."""
    from paddle_tpu.models.gpt import GPTConfig, gpt_flops_per_token

    # largest single-chip config: GPT ~350M in bf16 params+opt fits HBM.
    # loss_chunk fuses head+CE so [B, L, vocab] logits never materialize;
    # at L=1024 the should_use_flash gate keeps attention on the XLA
    # fused path
    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                    num_heads=16, max_position_embeddings=1024,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_recompute=False, use_flash_attention=True,
                    loss_chunk=256, dtype="bfloat16")
    batch, seq = 8, 1024
    timed_steps, warmup = 20, 6

    step = _gpt_train_step(cfg)
    ids = _random_ids(cfg, batch, seq)
    dt, final_loss = _timed_steps(step, (ids, ids), timed=timed_steps,
                                  warmup=warmup)

    # input-pipeline probe: stream FRESH host buffers through the async
    # H2D prefetch path (io/device_prefetch.py) so the JSON records whether
    # the step is input-bound (stall ~ 0 <=> transfer fully overlapped) and
    # shape-stable (compile_count must not grow while streaming)
    from paddle_tpu.io.device_prefetch import prefetch_to_device

    probe_steps = 8
    pf = prefetch_to_device(
        ((np.array(ids), np.array(ids)) for _ in range(probe_steps)),
        depth=2)
    for b in pf:
        loss = step(b)
    float(np.asarray(loss))
    pf_stats = pf.stats()
    pf.close()
    pipeline = {
        "compile_count": step.cache_stats()["compiles"],
        "step_calls": step.cache_stats()["calls"],
        "input_stall_s": round(pf_stats["consumer_stall_s"], 4),
        "input_stall_per_step_ms": round(
            pf_stats["consumer_stall_s"] / max(pf_stats["batches"], 1) * 1e3,
            3),
        "prefetch_batches": pf_stats["batches"],
    }

    tokens_per_sec = batch * seq * timed_steps / dt
    flops_per_token = gpt_flops_per_token(cfg, seq)
    mfu = tokens_per_sec * flops_per_token / _chip_peak_flops()
    return tokens_per_sec, mfu, cfg, batch, seq, final_loss, pipeline


def _release_device_memory():
    """Drop python references AND the jit executable cache so the next
    bench starts with free HBM (compiled executables pin their buffers;
    the step classes hold reference cycles, hence the collects)."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def main() -> int:
    import jax

    from paddle_tpu.framework import compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        # a CPU run printed under a device metric's name is worse than no
        # run: refuse, loudly
        sys.exit(f"bench.py measures a TPU; jax gave platform "
                 f"{device.platform!r} ({device.device_kind}). "
                 f"Tests run on the CPU, benchmarks do not.")
    _chip_peak_flops()          # an unknown chip fails here, before any work
    compile_cache.enable_persistent_cache()

    tokens_per_sec, mfu, cfg, batch, seq, final_loss, pipeline = \
        bench_gpt_primary()
    _release_device_memory()

    primary = {
        "metric": "gpt_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.35, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "backend": device.platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices()),
            "config": {"hidden": cfg.hidden_size, "layers": cfg.num_layers,
                       "batch": batch, "seq": seq},
            "final_loss": final_loss,
            # shape stability + input-boundness of the flagship step
            # (framework/compile_cache.py + io/device_prefetch.py)
            "compile_count": pipeline["compile_count"],
            "input_pipeline": pipeline,
        },
    }

    # breadth phases, in turn, each from free HBM. A phase that raises is
    # recorded with its error, the rest still run, and the exit code says
    # that the run as a whole failed.
    failed = []

    def breadth(name, fn):
        try:
            result = fn()
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            result = {"error": f"{type(e).__name__}: {e}"[:400]}
        _release_device_memory()
        return result

    long_ctx = breadth("long_context", bench_long_context)
    g13 = breadth("gpt_1p3b", bench_gpt_1p3b)
    decode = breadth("gpt_decode", bench_gpt_decode)
    serve = breadth("gpt_serve", bench_gpt_serve)
    r50 = breadth("resnet50", bench_resnet50)

    from paddle_tpu.observability import default_registry

    primary["extra"].update(
        {"long_context": long_ctx, "gpt_1p3b": g13, "gpt_decode": decode,
         "gpt_serve": serve, "resnet50": r50,
         # the serving-side secondary metrics, hoisted for trend tracking
         "gpt_decode_tokens_per_sec": decode.get("tokens_per_sec"),
         "gpt_serve_requests_per_sec": serve.get("requests_per_sec"),
         "failed_phases": failed,
         # unified-registry scrape: the run's counters/occupancy/compile
         # numbers next to its throughput
         "metrics": default_registry().snapshot()})
    print(json.dumps(primary))
    if failed:
        print(f"bench.py: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
