"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py                        # every phase the host can run
    python chip_smoke.py --phases train,four_chip

One process, one TPU host. Drives the main path through the public entry
points at the full width of the GPT-3-Medium-class decoder the benchmark
and the example use (hidden 1024, 16 heads, vocab 50304, 24 layers):

- **train**: O2 bf16 + AdamW + fused chunked loss, batch 8 x seq 1024,
  10 steps on one seeded batch through ``paddle_tpu.TrainStep``;
- **flash**: the Pallas kernels against the unfused reference at
  [2, 16, 4096, 64] bf16 (output and all three gradients, then one
  dropout call), and the same widths at seq 4096 through ``TrainStep``
  (depth cut to 4 layers for time), whose lowered program must hold the
  Mosaic custom calls;
- **serve**: the per-slot cache write's kernels against the scatter,
  element for element, and the read's kernels against XLA's read of the
  whole leaf, to a stated bound, at the row geometries of the benchmark's
  three serve cells and at its sparse cell's latent pair (``c`` of 512
  beside ``k_r`` of 64, one head, under 32 query heads); then
  ``InferenceServer(slots=8)`` with the default
  prefill buckets, warmed up, then 32 concurrent mixed-length requests,
  greedy and sampled, with zero compiles allowed, the decode program's
  cache write and read on their kernels, and one greedy stream compared
  with ``model.generate()`` (equal up to a first position where the top
  two logits lie closer than a bf16 step: the two programs round
  differently); then the latent geometry, the benchmark's sparse
  latent-attention configuration at its published widths in bfloat16:
  one expert FFN with every expert drawn apart against the reference's
  on equal inputs, a decode step's 32 rows and a prefill's 2048
  (``expert_ffn_check``: picks, routing weights, rows), one stream mixer
  against the reference's on equal streams (``mixer_check``), and the
  model built whole (11 GB: born in float32 it would not fit), two
  prompts prefilled into rows of a 32 x 8192 latent cache, 48 decode
  steps with each row at its own position (which must read the cache
  by the latent body's kernel), the LOGITS held to the plain float32
  reference's full pass, positions within rounding of a routing tie to
  a bound of their own (``latent_logits_check``); then the state
  geometry, the benchmark's hybrid state-space configuration whole (6 GB)
  in bfloat16: two prompts right-padded to a bucket and prefilled into
  rows of a 128 x 8192 cache whose entries are 26 recurrent states beside
  2 key/value pairs, 16 decode steps, the LOGITS and the final scan
  state and window held to the reference's full pass, and two planted
  faults (pads that move the state, a window off by one) that must each
  fail a bound (``state_logits_check``); before it one layer's
  recurrence through a state entry on equal inputs against float64,
  which a bfloat16 state must fail (``state_scan_check``);
- **four_chip**: the train model through ``DistributedTrainStep`` on
  dp=2 x mp=2 and on sdp=4 with ZeRO-2, when the host has four chips.

Any failed check or exception ends the process with a non-zero code and
the phase named. On success the last line of stdout is
``{"ok": true, "device": {...}}``. ``main()`` has no CPU path: it pins
``JAX_PLATFORMS=tpu`` before importing jax and refuses any other
platform. The phase functions take (config, sizes) so that
``tests/test_chip_smoke.py`` can rehearse them tiny on the CPU.

Wall and compile times below are set-up information printed as log
lines; none of them is a benchmark result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import sys
import time

import numpy as np

PHASES = ("train", "flash", "serve", "four_chip")


class CheckFailed(Exception):
    """A phase ran but one of its checks did not hold."""


def check(ok, message: str) -> None:
    if not ok:
        raise CheckFailed(message)


def log(message: str) -> None:
    print(message, flush=True)


def gpt_config(num_layers: int, seq: int, **overrides):
    """The full-width config (no width is cut; depth and length are the
    caller's)."""
    from paddle_tpu.models.gpt import GPTConfig

    kw = dict(vocab_size=50304, hidden_size=1024, num_layers=num_layers,
              num_heads=16, max_position_embeddings=seq,
              hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
              use_recompute=False, use_flash_attention=True,
              loss_chunk=256, dtype="bfloat16")
    kw.update(overrides)
    return GPTConfig(**kw)


def _o2_train_step(cfg, step_cls, **step_kw):
    """Seeded model + AdamW under O2 bf16 — the recipe bench.py times."""
    import paddle_tpu as pt
    from paddle_tpu import amp
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.optimizer import AdamW

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    # loss_fn=None: with loss_chunk the forward returns the loss itself
    return step_cls(model, opt, loss_fn=None, **step_kw)


def _seeded_ids(cfg, batch: int, seq: int) -> np.ndarray:
    rng = np.random.default_rng(0)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def _check_first_loss(loss: float, vocab_size: int) -> None:
    # a randomly initialised LM is near-uniform over the vocabulary
    check(abs(loss - math.log(vocab_size)) <= 1.0,
          f"first loss {loss:.4f} is not within 1.0 of "
          f"ln({vocab_size}) = {math.log(vocab_size):.4f}")


def _run_steps(step, batch, steps: int, phase: str):
    """``steps`` calls of ``step``, each ended by ``block_until_ready``
    and a host read of a finite loss. Returns the losses and, for the
    last call, the seconds until dispatch returned, until the result was
    ready, and the host read took after that."""
    import jax

    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = step(batch)
        t_dispatch = time.perf_counter() - t0
        jax.block_until_ready(loss)
        t_ready = time.perf_counter() - t0
        value = float(np.asarray(loss))      # host read after the fence
        t_read = time.perf_counter() - t0 - t_ready
        check(np.isfinite(value), f"step {i}: loss is {value}")
        losses.append(value)
    log(f"[{phase}] losses: " + " ".join(f"{v:.4f}" for v in losses))
    return losses, (t_dispatch, t_ready, t_read)


# ---------------------------------------------------------------- train
def train_phase(cfg, batch: int, seq: int, steps: int) -> dict:
    import paddle_tpu as pt

    step = _o2_train_step(cfg, pt.TrainStep)
    ids = _seeded_ids(cfg, batch, seq)
    losses, (t_dispatch, t_ready, t_read) = _run_steps(
        step, (ids, ids), steps, "train")
    # does block_until_ready fence? If it does, the step's time sits
    # between dispatch and ready, and the read that follows finds the
    # value already there
    log(f"[train] last step: dispatch returned after "
        f"{t_dispatch * 1e3:.1f} ms, block_until_ready after "
        f"{t_ready * 1e3:.1f} ms, host read then took {t_read * 1e3:.2f} ms")
    check(t_read < max(0.05, 0.2 * t_ready),
          f"host read after block_until_ready took {t_read:.3f}s of a "
          f"{t_ready:.3f}s step: block_until_ready did not wait for the "
          f"result")
    _check_first_loss(losses[0], cfg.vocab_size)
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")
    compiles = step.cache_stats()["compiles"]
    check(compiles == 1, f"TrainStep traced {compiles} programs, want 1")
    return {"first_loss": losses[0], "last_loss": losses[-1]}


# ---------------------------------------------------------------- flash
# Why 2% of the reference's largest element: kernel and reference start
# from the same bf16 inputs. The reference computes in f32 throughout. The
# kernel's dots take the bf16 inputs as they are (products exact, summed in
# f32) and round the probabilities p and the score gradients ds to bf16
# for their dot, as XLA's attention does under seq 2048; statistics and
# accumulators are f32. Both round their results to bf16, where an ulp is
# 2^-8 = 0.4% of the value it rounds; p and ds carry the same relative
# rounding into sums over thousands of keys, where it averages out. On the
# v5e the largest difference measured 0.5% of the largest element with f32
# operands (PR 21); PERF.md section 6 has the four shares on bf16 operands.
# A wrong mask, block index or softmax statistic moves the result by its
# own magnitude, fifty times the tolerance.
KERNEL_TOL = 2e-2


def _check_same_mask(label, q, k, v, w, result) -> None:
    """Two identities of attention's vjp that hold whatever the keep-mask
    is, as long as forward, dq kernel and dkv kernel all use the SAME one:
    <out, w> = <v, dv> (both are sum_ij pd_ij <v_j, w_i>) and <q, dq> =
    <k, dk> (both are scale * sum_ij ds_ij <q_i, k_j>). Kernels that drew
    different masks miss them by tens of per cent; bf16 rounding of the
    results moves them by a fraction of one."""
    q, k, v, w, out, dq, dk, dv = (np.asarray(x, np.float32) for x in
                                   (q, k, v, w, *result))
    for name, left, right in (("<out,w> = <v,dv>", out * w, v * dv),
                              ("<q,dq> = <k,dk>", q * dq, k * dk)):
        lhs, rhs = (float(x.sum(dtype=np.float64)) for x in (left, right))
        # the sums are random walks and may nearly cancel: judge by the
        # walk's length (a wrong mask misses by about half of it)
        size = float(np.sqrt(np.square(left).sum(dtype=np.float64)))
        log(f"[flash] {label} {name}: {lhs:.4e} vs {rhs:.4e} "
            f"(walk length {size:.3e})")
        check(abs(lhs - rhs) <= KERNEL_TOL * size,
              f"{label}: {name} fails ({lhs:.4e} vs {rhs:.4e}): forward and "
              f"backward kernels disagree on the mask")


def _kernel_vs_reference(shape, dtype: str, dropout_p: float) -> None:
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import flash_attention as fa

    rng = np.random.default_rng(1)
    q, k, v, w = (jnp.asarray(rng.standard_normal(shape), dtype)
                  for _ in range(4))

    def out_and_grads(attn):
        """jit of (q, k, v, w, *extra) -> (out, dq, dk, dv) for the
        cotangent ``w``; ``extra`` reaches ``attn`` traced."""
        def run(q, k, v, w, *extra):
            o, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, *extra), q, k, v)
            return (o, *vjp(w))
        return jax.jit(run)

    got = out_and_grads(
        lambda q, k, v: fa.flash_attention_bhld(q, k, v, causal=True))(
            q, k, v, w)
    # the reference materialises [H, L, L] f32 scores several times over
    # in its backward: one batch row at a time bounds that to a few GB
    ref_fn = out_and_grads(
        lambda q, k, v: fa.reference_attention_bhld(q, k, v, causal=True))
    rows = [ref_fn(q[b:b + 1], k[b:b + 1], v[b:b + 1], w[b:b + 1])
            for b in range(shape[0])]
    want = [jnp.concatenate(parts, axis=0) for parts in zip(*rows)]
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(np.isfinite(a).all(), f"kernel {name} has non-finite values")
        err, scale = float(np.abs(a - b).max()), float(np.abs(b).max())
        log(f"[flash] kernel vs reference {name}: max|diff| {err:.3e} "
            f"= {err / scale:.2e} of max|ref| {scale:.3e}")
        check(err <= KERNEL_TOL * scale,
              f"kernel {name} differs from the reference by {err:.3e} "
              f"(> {KERNEL_TOL} x {scale:.3e})")
    _check_same_mask("causal", q, k, v, w, got)

    if dropout_p > 0.0:
        # the flagship runs dropout 0, so only this call compiles the
        # in-kernel PRNG path (forward and both backward kernels); the
        # seed is a traced argument, as in a training step
        drop = out_and_grads(
            lambda q, k, v, seed: fa.flash_attention_bhld(
                q, k, v, causal=True, dropout_p=dropout_p, seed=seed))
        a, b, c = ([np.asarray(x, np.float32)
                    for x in drop(q, k, v, w, jnp.int32(seed))]
                   for seed in (7, 7, 8))
        check(all(np.isfinite(x).all() for x in a),
              "dropout call produced non-finite values")
        check(all((x == y).all() for x, y in zip(a, b)),
              "dropout is not deterministic for a fixed seed")
        check((a[0] != c[0]).any(), "dropout ignores its seed")
        _check_same_mask(f"dropout {dropout_p}", q, k, v, w, a)
        log(f"[flash] dropout {dropout_p}: deterministic per seed, "
            f"seed-sensitive, forward and backward finite")


def flash_phase(cfg, batch: int, seq: int, steps: int, kernel_shape,
                kernel_dtype: str = "bfloat16", dropout_p: float = 0.1,
                min_mosaic_calls: int = 3) -> dict:
    """``min_mosaic_calls``: how many Mosaic custom calls the lowered step
    must hold at least — one each for the forward, dq and dk/dv kernels,
    whose jitted wrappers lower to functions that every layer shares; the
    interpreted kernels of the CPU rehearsal lower to none."""
    import paddle_tpu as pt

    _kernel_vs_reference(kernel_shape, kernel_dtype, dropout_p)

    step = _o2_train_step(cfg, pt.TrainStep)
    ids = _seeded_ids(cfg, batch, seq)
    log(f"[flash] TrainStep at seq {seq} x {cfg.num_layers} layers")
    losses, _ = _run_steps(step, (ids, ids), steps, "flash")
    _check_first_loss(losses[0], cfg.vocab_size)
    # the gate (should_use_flash) falls back to the XLA path silently, so
    # look at the program itself
    mosaic_calls = step.lower((ids, ids)).as_text().count("tpu_custom_call")
    log(f"[flash] Mosaic custom calls in the lowered step: {mosaic_calls}")
    check(mosaic_calls >= min_mosaic_calls,
          f"lowered seq-{seq} step holds {mosaic_calls} Mosaic custom "
          f"calls, want >= {min_mosaic_calls}: attention took the XLA path")
    compiles = step.cache_stats()["compiles"]
    check(compiles == 1, f"TrainStep traced {compiles} programs, want 1")
    return {"mosaic_calls": mosaic_calls}


# ---------------------------------------------------------------- serve
def _bf16_step(x: float) -> float:
    """The distance between ``x`` and the next bf16 number (8 bits of
    precision)."""
    return 2.0 ** (math.floor(math.log2(abs(x))) - 7) if x else 0.0


def greedy_parity(model, prompt, served, solo) -> str:
    """A served greedy stream against ``model.generate()``'s. The two
    come from programs that round differently (the served step reads its
    cache through the kernel's f32 online softmax, block by block;
    ``generate()`` through XLA's einsums over the whole leaf), so what
    holds is: equal, or equal up to a first position where the
    reference's top two logits (a teacher-forced forward over the common
    prefix) lie closer than a bf16 step of the larger; past it the
    streams are two different texts and say nothing. Returns the log
    line, or raises :class:`CheckFailed` with the logits that decided."""
    import paddle_tpu as pt

    if np.array_equal(served, solo):
        return "equals model.generate()"
    n = min(len(served), len(solo))
    pos = next((i for i in range(n) if served[i] != solo[i]), n)
    check(pos < n, f"greedy stream != model.generate(): lengths differ: "
                   f"served {len(served)}, solo {len(solo)}")
    ids = np.concatenate([prompt, solo[:pos]]).astype(np.int32)[None]
    logits = np.asarray(pt.EvalStep(model)(ids), np.float32)[0, -1]
    top = np.argsort(logits)[::-1][:2]
    a, b = int(served[pos]), int(solo[pos])
    gap = float(logits[top[0]] - logits[top[1]])
    step = _bf16_step(float(logits[top[0]]))
    report = (f"first difference at generated position {pos}: served {a} "
              f"(logit {logits[a]:.6f}) vs generate() {b} (logit "
              f"{logits[b]:.6f}); teacher-forced top-2 {int(top[0])}/"
              f"{int(top[1])} gap {gap:.3e}, a bf16 step there {step:.3e}")
    check({a, b} == {int(top[0]), int(top[1])} and gap < step,
          "greedy stream != model.generate(): " + report)
    return "equals model.generate() up to a tie: " + report


#: the serve cells' cache rows (gpt3-medium 16 x 64, gpt3-xl 16 x 128,
#: ouro-2.6b 16 x 128 in leaves of stacked entries), in shorter leaves
CELL_LEAVES = ((8, 1024, 16, 64), (8, 1024, 16, 128), (4, 3, 512, 16, 128))
#: the sparse serve cell's latent pair, in a shorter leaf: (slots, length,
#: query heads, rank, rotated width) of ``c [slots, length, 1, rank]`` and
#: ``k_r [slots, length, 1, rotated]``
LATENT_LEAVES = ((8, 2048, 32, 512, 64),)


def _leaf_case(n: int, shape, dtype):
    """Random ``(k, v)`` leaves of ``shape``, two rows ``[B, 1, Hkv, D]``
    (a step's new key and value, or its query), every slot at a position
    of its own (the first at 0, the last at the leaf's end), and the
    last entry of a stacked leaf."""
    import jax
    import jax.numpy as jnp

    slots, length = shape[0], shape[-3]
    keys = jax.random.split(jax.random.PRNGKey(n), 5)
    row = (slots, 1) + tuple(shape[-2:])
    k, v = (jax.random.normal(key, shape, dtype) for key in keys[:2])
    rows = [jax.random.normal(key, row, dtype) for key in keys[2:4]]
    pos = jax.random.randint(keys[4], (slots,), 0, length)
    pos = pos.at[0].set(0).at[-1].set(length - 1)
    entry = jnp.int32(shape[1] - 1) if len(shape) == 5 else None
    return k, v, rows, pos, entry


def cache_write_check(leaves=CELL_LEAVES, dtype="bfloat16") -> None:
    """``kernels.cache_write.write_rows`` against ``kv_cache._write``, the
    scatter it stands in for, on random leaves with every slot at a
    position of its own: not one element may differ. The gate is
    ``update_kv_cache``'s own (``kv_cache._rows_by_dma``)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import cache_write
    from paddle_tpu.models import kv_cache

    zero = jnp.zeros((), jnp.int32)
    for n, shape in enumerate(leaves):
        k, v, (nk, nv), pos, entry = _leaf_case(n, shape, dtype)
        check(kv_cache._rows_by_dma(k, v, nk, pos),
              f"cache write: the gate refuses a leaf {list(shape)} {dtype}")
        got = jax.jit(lambda *a: cache_write.write_rows(*a))(
            k, v, nk, nv, pos, entry)
        want = jax.jit(lambda *a: tuple(
            kv_cache._write(buf, new, a[4], a[5], zero)
            for buf, new in zip(a[:2], a[2:4])))(k, v, nk, nv, pos, entry)
        for name, g, w, old in zip(("key", "value"), got, want, (k, v)):
            differ = int(jnp.sum(g != w))
            written = int(jnp.sum(g != old))
            log(f"[serve] cache write {list(shape)} {name}: kernel vs "
                f"scatter {differ} of {g.size} elements differ "
                f"({written} written)")
            check(differ == 0 and written > 0,
                  f"cache write {list(shape)} {name}: {differ} elements "
                  f"differ from the scatter's, {written} written")


def cache_read_check(leaves=CELL_LEAVES, latent=LATENT_LEAVES,
                     dtype="bfloat16") -> None:
    """``kernels.cache_read.read_by_position`` against XLA's read of the
    whole leaf under a mask (``kv_cache._read_whole``), on random leaves
    with every slot at a position of its own, and
    ``read_latent_by_position`` against ``kv_cache._latent_read_whole``
    on random latent pairs (``latent``). The truth is XLA's path on f32
    copies; the kernel (f32 scores and softmax, one rounding at the end)
    is held to a bf16 step of the largest output, and XLA's path in
    ``dtype`` (bf16 scores and weights) is reported beside it. The gates
    are ``cached_attention``'s and ``latent_attention``'s own
    (``kv_cache._reads_by_position``, ``_latent_reads_by_position``)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import cache_read
    from paddle_tpu.models import kv_cache

    def held(shape, got, xla, operands, rest):
        plain = jax.jit(xla)(*operands, *rest).astype(jnp.float32)
        truth = jax.jit(xla)(*(x.astype(jnp.float32) for x in operands),
                             *rest)
        bound = _bf16_step(float(jnp.max(jnp.abs(truth))))
        differ = float(jnp.max(jnp.abs(got.astype(jnp.float32) - truth)))
        log(f"[serve] cache read {list(shape)}: kernel vs the f32 read max "
            f"abs difference {differ:.3e} (bound {bound:.3e}); XLA's "
            f"{dtype} read {float(jnp.max(jnp.abs(plain - truth))):.3e}")
        check(differ <= bound,
              f"cache read {list(shape)}: the kernel is {differ:.3e} from "
              f"the f32 read, more than a bf16 step of the largest output "
              f"({bound:.3e})")

    for n, shape in enumerate(leaves):
        k, v, (q, _), pos, entry = _leaf_case(n, shape, dtype)
        check(kv_cache._reads_by_position(q, k, v, pos),
              f"cache read: the gate refuses a leaf {list(shape)} {dtype}")
        got = jax.jit(lambda *a: cache_read.read_by_position(*a))(
            q, k, v, pos, entry)
        held(shape, got, kv_cache._read_whole, (q, k, v), (pos, entry))
    for n, (slots, length, heads, rank, rope) in enumerate(latent):
        keys = jax.random.split(jax.random.PRNGKey(len(leaves) + n), 5)
        c, kr, q_c, q_r = (
            jax.random.normal(key, shape, dtype) for key, shape in zip(keys, (
                (slots, length, 1, rank), (slots, length, 1, rope),
                (slots, 1, heads, rank), (slots, 1, heads, rope))))
        pos = jax.random.randint(keys[4], (slots,), 0, length)
        pos = pos.at[0].set(0).at[-1].set(length - 1)
        check(kv_cache._latent_reads_by_position(q_c, q_r, c, kr, pos),
              f"cache read: the gate refuses a latent pair "
              f"{list(c.shape)} + {list(kr.shape)} {dtype}")
        scale = (rank // 4 + rope) ** -0.5     # heads of rank / 4 + rope
        got = cache_read.read_latent_by_position(q_c, q_r, c, kr, pos, scale)
        held((slots, length, heads, rank, rope), got,
             kv_cache._latent_read_whole, (q_c, q_r, c, kr), (pos, scale))


def serve_phase(cfg, slots: int, prompt_lens, n_requests: int,
                new_tokens=(8, 24), expect_donation: bool = True,
                expect_cache_write: str = "dma",
                expect_cache_read: str = "kernel",
                timeout: float = 600.0) -> dict:
    """``expect_donation``: the engine donates its KV cache to the decode
    program on an accelerator and, by its own branch, not on the CPU.
    ``expect_cache_write`` / ``expect_cache_read``: the same for the
    kernels behind the decode program's per-slot cache write and read,
    ``"scatter"`` and ``"xla"`` on the CPU."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.serving import InferenceServer

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    server = InferenceServer(model, slots=slots)
    engine = server.engine
    buckets = engine.prefill_buckets
    used = sorted({engine.bucket_for_prompt(n) for n in prompt_lens})
    check(len(used) >= min(3, len(buckets)),
          f"prompt lengths {list(prompt_lens)} cover only buckets {used}")

    warm = engine.warmup()
    log(f"[serve] buckets {list(buckets)}; warmup traced "
        f"{warm['prefill_compiles']} prefill + {warm['decode_compiles']} "
        f"decode programs")
    check(warm["prefill_compiles"] == len(buckets)
          and warm["decode_compiles"] == 1,
          f"warmup traced {warm}, want {len(buckets)} prefill + 1 decode")

    rng = np.random.default_rng(2)
    requests = []
    for i in range(n_requests):
        n = int(prompt_lens[i % len(prompt_lens)])
        requests.append((
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            int(rng.integers(new_tokens[0], new_tokens[1] + 1)),
            bool(i % 2)))                  # odd requests sample

    cache_before = jax.tree.leaves(engine.live_cache)[0]
    # zero compiles from here on: a trace anywhere in the process raises
    # inside the serve loop and fails the request that caused it
    with compile_cache.retrace_guard(0, label="serve traffic"), server:
        handles = [
            server.submit(p, max_new_tokens=n, do_sample=sample,
                          temperature=0.8, top_p=0.95, seed=100 + i)
            for i, (p, n, sample) in enumerate(requests)]
        results = [h.result(timeout=timeout) for h in handles]
        snap = server.snapshot()

    for i, ((p, n, _), toks) in enumerate(zip(requests, results)):
        check(len(toks) == n, f"request {i} asked {n} tokens, got {len(toks)}")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {i} produced a token outside [0, {cfg.vocab_size})")
    # the serve loop survives faults by resetting and requeueing; a fault
    # it recovered from is still a fault here
    for key in ("requests_failed", "requests_requeued", "requests_expired",
                "requests_rejected"):
        check(snap[key] == 0, f"serving metrics report {key}={snap[key]}")
    check(snap["requests_completed"] == n_requests,
          f"{snap['requests_completed']} of {n_requests} requests completed")
    decoded = snap["tokens_emitted"] - snap["prefills"]
    mean_batch = decoded / max(snap["decode_steps"], 1)
    log(f"[serve] {n_requests} requests, {snap['tokens_emitted']} tokens, "
        f"{snap['decode_steps']} decode steps, mean live slots per step "
        f"{mean_batch:.2f} of {slots}")
    check(mean_batch >= slots / 2,
          f"slots never filled: mean {mean_batch:.2f} live of {slots}")

    cc = engine.cache_stats()
    traced = cc["prefill"]["compiles"] + cc["decode"]["compiles"]
    check(traced == len(buckets) + 1,
          f"{traced} serving programs traced, want #buckets + 1 = "
          f"{len(buckets) + 1}")
    donated = cache_before.is_deleted()
    log(f"[serve] programs {traced} = {len(buckets)} buckets + 1; "
        f"pre-traffic KV buffer deleted (donated): {donated}")
    check(donated == expect_donation,
          f"KV cache donation is {donated}, want {expect_donation}")
    log(f"[serve] the decode program's per-slot cache write: "
        f"{cc['cache_write']}")
    check(cc["cache_write"] == expect_cache_write,
          f"the decode program writes its cache by {cc['cache_write']}, "
          f"want {expect_cache_write}")
    log(f"[serve] the decode program's cache read: {cc['cache_read']}")
    check(cc["cache_read"] == expect_cache_read,
          f"the decode program reads its cache by {cc['cache_read']}, "
          f"want {expect_cache_read}")

    # greedy parity with the offline engine (own programs, own cache)
    i0 = next(i for i, r in enumerate(requests) if not r[2])
    prompt, n, _ = requests[i0]
    solo = np.asarray(model.generate(prompt[None], max_new_tokens=n))[0]
    verdict = greedy_parity(model, prompt, np.asarray(results[i0]), solo)
    log(f"[serve] greedy request {i0} ({len(prompt)}-token prompt, {n} new "
        f"tokens) {verdict}")
    return {"programs": traced, "donated": donated,
            "cache_write": cc["cache_write"], "cache_read": cc["cache_read"]}


# ------------------------------------------------- the latent geometry
#: Readings are the chip's (one v5e; PERF.md section 6, PR 32, where the
#: planted faults' and the lower precisions' readings are too).
#: A routing weight of the float32 reference and the system's, same
#: input: both float32 at full precision (the chip read 0.0, bit for
#: bit); scores rounded to bfloat16 are 2e-3 off.
ROUTER_WEIGHT_BOUND = 2e-5
#: A row of the expert FFN's output against the reference's, same input,
#: same picks: rms of the difference over the rms of the reference's row.
#: bfloat16 projections with float32 accumulation read 0.0037-0.0040 at
#: most (median 0.0035) at 32 and at 2048 rows; rows through the next
#: expert's weights read 1.14.
EXPERT_ROW_BOUND = 0.01
#: The mixers' outputs (the normed input, H_post, M, the mixed streams)
#: against the reference's, same float32 streams, largest difference
#: over the largest value: float32 on both sides read 2.8e-7; Sinkhorn
#: steps in bfloat16 5.9e-3, streams rounded to bfloat16 2e-3.
MIXER_BOUND = 5e-5
#: Where some expert layer decides a position's routing by less than
#: this (the last picked expert's selecting score over the next one's,
#: as the reference reads it) bfloat16 rounding upstream of the router
#: may pick the other expert, in a correct program too: the chip read
#: such picks up to a margin of 4.1e-3 and none over it (six models, 98
#: positions each). Such a position is held to LATENT_TIE_BOUND, every
#: other one (10 to 18 of the 98) to LATENT_CLEAN_BOUND.
LATENT_TIE_MARGIN = 6e-3
#: The largest |logit - reference| of a position over the standard
#: deviation of the reference's logits there. Where every pick is
#: decided: 0.066 to 0.073 over six models (experts akin by 1/32 and by
#: 1/16, and drawn apart), so that bound does not lean on how the
#: experts start; every row through the next expert's weights read 0.35
#: there with experts akin by 1/32, the routed output zeroed 5.4, and a cache row, a position or a norm computed wrong moves every
#: position alike. Where a pick is not decided: what the other expert
#: adds, by how far apart the configuration's experts start: 0.094 to
#: 0.114 at 1/32 over three models (0.17 at 1/16, 2.6 drawn apart).
LATENT_CLEAN_BOUND = 0.1
LATENT_TIE_BOUND = 0.2


def _bench_config(name: str) -> dict:
    """A benchmark configuration file's content."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _bench_harness():
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import common
    return common


def expert_ffn_check(config: dict, seed: int, row_counts=(32, 2048),
                     weight_bound: float = ROUTER_WEIGHT_BOUND,
                     row_bound: float = EXPERT_ROW_BOUND) -> dict:
    """One expert FFN at the configuration's widths and type, every
    expert drawn APART, against the reference's expert layer on the same
    rows: a decode step's row count and a prefill's. Equal inputs, so no
    pick hangs on rounding upstream: every row's picks must be the
    reference's (rows decided by less than ``weight_bound`` aside), its
    routing weights within ``weight_bound`` and its output within
    ``row_bound`` of the reference's row."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.nn.layer import functional_call, param_state
    from paddle_tpu.nn.layers.expert_ffn import ExpertFFN

    common = _bench_harness()
    reference = common.resolve(config["reference"])
    cfg = config["config"]
    pt.seed(common.fold_seed(seed))
    layer = ExpertFFN(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["n_routed_experts"], cfg["num_experts_per_tok"],
        shared_width=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        init_std=cfg["initializer_range"], dtype=config["run"]["dtype"])
    params = param_state(layer)
    apply = jax.jit(lambda p, x: functional_call(layer, p, {}, x)[0])
    rng = np.random.default_rng(seed)
    out = {}
    for rows in row_counts:
        x = jnp.asarray(rng.standard_normal((rows, cfg["hidden_size"])),
                        layer.experts.gate_proj.dtype)
        got = np.asarray(apply(params, x).astype(jnp.float32))
        picked, w = (np.asarray(a) for a in jax.jit(layer.route)(x))
        routing = []
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference.expert_layer(
                params, cfg, x.astype(jnp.float32), routing))
            _, want_w, _ = reference.route(
                x.astype(jnp.float32), params["router.weight"],
                params["router.e_score_correction_bias"],
                k=cfg["num_experts_per_tok"],
                factor=float(cfg["routed_scaling_factor"]))
        (want_picked, margin), = routing
        decided = margin > weight_bound
        same = (np.sort(picked, -1) == np.sort(want_picked, -1)).all(-1)
        order, want_order = np.argsort(picked, -1), np.argsort(want_picked, -1)
        weights = np.abs(np.take_along_axis(w, order, -1)
                         - np.take_along_axis(np.asarray(want_w),
                                              want_order, -1)).max(-1)
        err = (np.sqrt(((got - want) ** 2).mean(-1))
               / np.sqrt((want ** 2).mean(-1)))
        log(f"[serve] expert FFN, {rows} rows of {cfg['hidden_size']} "
            f"through {cfg['n_routed_experts']} experts of "
            f"{cfg['moe_intermediate_size']}: {int((~same).sum())} rows "
            f"pick other experts than the reference ({int((~decided).sum())}"
            f" decided by under {weight_bound}); routing weights within "
            f"{weights.max(where=same, initial=0):.2e} (bound "
            f"{weight_bound}); rows within "
            f"{err.max(where=same, initial=0):.4f} of the reference's, median "
            f"{np.median(err):.4f} (bound {row_bound})")
        check(not (~same & decided).any(),
              f"expert FFN, {rows} rows: {int((~same & decided).sum())} "
              f"rows pick other experts than the reference on equal inputs")
        check(weights[same].max() <= weight_bound,
              f"expert FFN, {rows} rows: routing weights are "
              f"{weights[same].max():.2e} from the reference's, bound "
              f"{weight_bound}")
        check(err[same].max() <= row_bound,
              f"expert FFN, {rows} rows: a row is {err[same].max():.4f} of "
              f"its size from the reference's, bound {row_bound}")
        out[rows] = {"other_picks": int((~same).sum()),
                     "weights": float(weights[same].max()),
                     "row": float(err[same].max())}
    return out


def mixer_check(config: dict, seed: int, positions: int = 256,
                bound: float = MIXER_BOUND) -> dict:
    """One stream mixer at the configuration's widths against the
    reference's, on the same float32 streams: what it reads (the mixed,
    normed input), how it spreads a branch's output and how it mixes the
    streams meanwhile (``H_post``, ``M`` after its Sinkhorn steps, the
    streams after ``post``), largest difference over the largest value."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.xing import StreamMixer
    from paddle_tpu.nn.layer import param_state

    common = _bench_harness()
    reference = common.resolve(config["reference"])
    raw = config["config"]
    cfg = common.resolve(config["model"]["config_class"])(**raw)
    pt.seed(common.fold_seed(seed))
    mixer = StreamMixer(cfg)
    n, C = cfg.hc_mult, cfg.hidden_size
    rng = np.random.default_rng(seed)
    # streams as a block leaves them: a common part and each its own
    X = jnp.asarray(rng.standard_normal((positions, 1, C))
                    + 0.5 * rng.standard_normal((positions, n, C)),
                    jnp.float32)
    y = jnp.asarray(rng.standard_normal((positions, C)), jnp.float32)

    def system(X, y):
        h, (h_post, M) = mixer.pre(X[None])
        after = mixer.post(X[None], y[None], (h_post, M))
        return (h[0], h_post[:, 0].T,
                jnp.stack([jnp.stack(row, -1) for row in M], -2)[0], after[0])

    got = jax.jit(system)(X, y)
    p = param_state(mixer)
    with jax.default_matmul_precision("highest"):
        h, h_post, M = reference._mix_pre(
            X, p["phi"], p["alpha"], p["bias"], jnp.ones((C,), jnp.float32),
            n=n, iters=cfg.hc_sinkhorn_iters, eps=cfg.rms_norm_eps,
            hc_eps=cfg.hc_eps, lo=cfg.mhc_h_res_clamp_min,
            hi=cfg.mhc_h_res_clamp_max)
        after = reference._mix_post(X, y, h_post, M)
    # the reference hands back rms(h, g): norm the system's h alike
    normed = got[0] * jax.lax.rsqrt(jnp.mean(jnp.square(got[0]), -1,
                                             keepdims=True) + cfg.rms_norm_eps)
    out = {}
    for name, a, b in (("input", normed, h), ("H_post", got[1], h_post),
                       ("M", got[2], M), ("streams", got[3], after)):
        out[name] = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    sums = np.asarray(got[2])
    log(f"[serve] stream mixer, {positions} positions of {n} x {C}: largest "
        f"difference from the reference over the largest value "
        f"{ {k: float(f'{v:.2e}') for k, v in out.items()} } (bound {bound});"
        f" M's rows sum within {np.abs(sums.sum(-1) - 1).max():.1e} and "
        f"columns within {np.abs(sums.sum(-2) - 1).max():.1e} of 1; diagonal"
        f" {np.mean(np.diagonal(sums, axis1=-2, axis2=-1)):.3f}")
    worst = max(out, key=out.get)
    check(out[worst] <= bound,
          f"stream mixer: {worst} is {out[worst]:.2e} of its largest value "
          f"from the reference's, bound {bound}")
    return out


def latent_logits_check(config: dict, seed: int, slots: int, length: int,
                        bucket: int, prompt_lens, steps: int = 16,
                        expect_cache_read: str = "kernel",
                        clean_bound: float = LATENT_CLEAN_BOUND,
                        tie_bound: float = LATENT_TIE_BOUND,
                        tie_margin: float = LATENT_TIE_MARGIN) -> dict:
    """Prefill, then ``steps`` decode steps through the latent cache, at a
    serve cell's geometry, against the plain reference's full pass:
    ``config`` is a benchmark configuration file's content (its model
    classes, its ``config`` and ``run`` blocks, its ``reference``). Two
    seeded texts of ``prompt_lens`` tokens, padded to ``bucket``, go into
    the first and the last row of a ``slots x length`` cache by the
    engine's own admission path (``cache_row_view``); the decode steps
    run the WHOLE batch with each row at its own position (the other
    rows decode filler), teacher-forced on seeded tokens, and must read
    the cache by ``expect_cache_read`` (the kernel on the chip, ``"xla"``
    on the CPU). Compared are the logits at the last prompt position and
    at every decode step,
    both rows: a position's error is the largest |logit - reference|
    over the standard deviation of the reference's logits there (the
    rms over the vocabulary is reported beside it). The reference also
    says by how much every position's routing was decided: a position
    under ``tie_margin`` in some layer is held to ``tie_bound``, the
    others to ``clean_bound``, and a tenth of the positions at least
    must be of those."""
    import jax
    import jax.numpy as jnp
    common = _bench_harness()
    from paddle_tpu.models.kv_cache import (cache_paths, cache_row_buffers,
                                            cache_row_view, init_cache)
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     param_state)

    t0 = time.perf_counter()
    model = common.build_model(config, None, seed)
    model.eval()
    params, buffers = param_state(model), buffer_state(model)
    nbytes = sum(p.size * p.dtype.itemsize for p in params.values())
    log(f"[serve] latent: model built in {time.perf_counter() - t0:.1f} s, "
        f"{sum(p.size for p in params.values()) / 1e9:.3f} B parameters, "
        f"{nbytes / 1e9:.2f} GB, dtypes "
        f"{sorted({str(p.dtype) for p in params.values()})}")
    vocab = config["config"]["vocab_size"]
    rng = np.random.default_rng(seed)
    texts = [rng.integers(0, vocab, n + steps).astype(np.int32)
             for n in prompt_lens]
    rows = (0, slots - 1)

    def prefill(params, buffers, cache, ids, row, last):
        (logits, view), _ = functional_call(
            model, params, buffers, ids, cache=cache_row_view(cache, row),
            position_offset=0, gather_last=last)
        return logits[0, 0].astype(jnp.float32), cache_row_buffers(view)

    def decode(params, buffers, cache, tokens, positions):
        (logits, cache), _ = functional_call(
            model, params, buffers, tokens, cache=cache,
            position_offset=positions)
        return logits[:, 0].astype(jnp.float32), cache

    prefill = jax.jit(prefill, donate_argnums=2)
    decode = jax.jit(decode, donate_argnums=2)
    cache = init_cache(model, slots, length)
    got = {r: [] for r in rows}
    for r, n, text in zip(rows, prompt_lens, texts):
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = text[:n]
        logits, cache = prefill(params, buffers, cache, ids, np.int32(r),
                                np.int32(n - 1))
        got[r].append(np.asarray(logits))
    with cache_paths() as paths:       # open around the decode step's trace
        for i in range(steps):
            tokens = np.zeros((slots, 1), np.int32)
            positions = np.zeros(slots, np.int32)
            for r, n, text in zip(rows, prompt_lens, texts):
                tokens[r, 0], positions[r] = text[n + i], n + i
            logits, cache = decode(params, buffers, cache, tokens, positions)
            logits = np.asarray(logits)
            for r in rows:
                got[r].append(logits[r])
    del cache
    check(paths["read"] == {expect_cache_read},
          f"latent geometry: the decode step reads its cache by "
          f"{sorted(paths['read'])}, want {expect_cache_read}")
    log(f"[serve] latent: prefill of {list(prompt_lens)} tokens (bucket "
        f"{bucket}) into rows {list(rows)} of {slots} x {length}, then "
        f"{steps} decode steps, in {time.perf_counter() - t0:.1f} s")

    reference = common.resolve(config["reference"])
    worst, rms, margin, short, agree = [], [], [], [], 0
    for r, n, text in zip(rows, prompt_lens, texts):
        t1 = time.perf_counter()
        margins = []
        ref = reference.logits(params, config["config"], text[None],
                               margins=margins)[0]
        ref = ref[n - 1:n + steps]             # predicts n ... n + steps
        diff = np.stack(got[r]) - ref
        std = ref.std(axis=-1)
        worst.extend((np.abs(diff).max(axis=-1) / std).tolist())
        rms.extend((np.sqrt(np.mean(diff ** 2, axis=-1)) / std).tolist())
        margin.extend(margins[0][:, n - 1:n + steps].min(axis=0).tolist())
        token = np.stack(got[r]).argmax(-1)
        agree += int((token == ref.argmax(-1)).sum())
        # what a benchmark's serve cell holds a greedy token to
        short.extend(((ref.max(-1) - ref[np.arange(len(ref)), token])
                      / std).tolist())
        log(f"[serve] latent row {r} ({n} + {steps} tokens): reference pass "
            f"{time.perf_counter() - t1:.1f} s")
    worst, rms, margin = (np.asarray(a) for a in (worst, rms, margin))
    clean = margin > tie_margin
    out = {"positions": len(worst), "clean": int(clean.sum()),
           "clean_worst": float(worst[clean].max(initial=0.0)),
           "tie_worst": float(worst[~clean].max(initial=0.0)),
           "rms_median": float(np.median(rms)), "rms_worst": float(rms.max()),
           "argmax_agree": agree, "shortfall_worst": float(max(short)),
           "per_position": {"margin": margin.tolist(), "worst": worst.tolist(),
                            "rms": rms.tolist(), "shortfall": short}}
    log(f"[serve] latent: {len(worst)} positions, {out['clean']} with every "
        f"layer's routing decided by over {tie_margin}: largest |logit - "
        f"reference| over the logits' std {out['clean_worst']:.4f} there "
        f"(bound {clean_bound}), {out['tie_worst']:.4f} at the others (bound "
        f"{tie_bound}); rms median {out['rms_median']:.4f}, worst "
        f"{out['rms_worst']:.4f}; {agree} argmaxes agree, the system's fall short "
        f"of the reference's best logit by {out['shortfall_worst']:.4f} at "
        f"most")
    check(10 * out["clean"] >= len(worst),
          f"latent geometry: only {out['clean']} of {len(worst)} positions "
          f"are decided by over {tie_margin}: the comparison holds too few")
    check(out["clean_worst"] <= clean_bound
          and out["tie_worst"] <= tie_bound,
          f"latent geometry: a logit is {out['clean_worst']:.4f} of the "
          f"logits' std from the reference where every pick is decided "
          f"(bound {clean_bound}) and {out['tie_worst']:.4f} where one is "
          f"not (bound {tie_bound})")
    return out


# Bounds of :func:`state_logits_check`, from readings on the chip (one v5e,
# the configuration whole in bfloat16, rows 0 and 127 of 128 x 8192, 34
# positions; PERF.md section 6, PR 34).
#: The largest |logit - reference| of a position over the standard
#: deviation of the reference's logits there: 0.107 as the program stands
#: (rms 0.024; 0.134 before the branches' matmuls returned float32, 0.177
#: with a bfloat16 residual stream besides), 5.87 with pads that move the
#: state, 5.89 with a window off by one. The distance is bfloat16
#: activations through 56 sublayers of freshly drawn weights, which grow
#: a perturbation eightfold (in float32 too).
STATE_LOGIT_BOUND = 0.25
#: rms(h - reference) over rms(reference) of a row's scan state after the
#: last step, worst of 26 layers: 0.026 as it stands, 8.03 and 1.63 with
#: the faults; the same for the window: 0.018, 1.17 and 1.41.
STATE_BOUND = 0.1
WINDOW_BOUND = 0.1
STATE_FAULTS = ("pads that move the state", "a window off by one")
#: :func:`state_scan_check`: rms(y - reference) over rms(reference) of the
#: recurrence's outputs on EQUAL inputs, and the same of the final state:
#: on the chip a float32 state read 4.0e-6 and 1.7e-5 of float64, a
#: bfloat16 state 1.1e-3 and 5.3e-3.
STATE_SCAN_BOUND = 1e-4


@contextlib.contextmanager
def _planted_state_fault(fault):
    """Plant what a correct-looking recurrent cache could hide."""
    from unittest import mock

    import jax.numpy as jnp
    from paddle_tpu.models import lm_utils

    if fault is None:
        yield
    elif fault == "pads that move the state":
        with mock.patch.object(lm_utils, "block_length",
                               lambda n: contextlib.nullcontext()):
            yield
    elif fault == "a window off by one":
        write = lm_utils.write_state
        with mock.patch.object(
                lm_utils, "write_state", lambda cache, h, window:
                write(cache, h, jnp.roll(window, 1, axis=1))):
            yield
    else:
        raise ValueError(fault)


def state_logits_check(config: dict, seed: int, slots: int, length: int,
                       bucket: int, prompt_lens, steps: int = 16,
                       logit_bound: float = STATE_LOGIT_BOUND,
                       state_bound: float = STATE_BOUND,
                       window_bound: float = WINDOW_BOUND,
                       faults=STATE_FAULTS) -> dict:
    """Prefill of a PADDED bucket, then ``steps`` decode steps through a
    cache that holds recurrent-state entries beside keys and values, at a
    serve cell's geometry, against the plain reference's full pass:
    ``config`` is a benchmark configuration file's content. Two seeded
    texts of ``prompt_lens`` tokens, right-padded to ``bucket``, go into
    the first and the last row of a ``slots x length`` cache by the
    engine's own admission path (``cache_row_view``, the prompt's length
    told through ``gather_last``); the decode steps run the WHOLE batch
    with each row at its own position (the other rows decode filler),
    teacher-forced on seeded tokens. Compared: the logits at the last
    prompt position and at every decode step (a position's error is the
    largest |logit - reference| over the standard deviation of the
    reference's logits there), and, after the last step, each row's scan
    state and window in every recurrent layer against the state and the
    last inputs the reference ends with (rms of the difference over the
    reference's rms, worst layer). Then each of ``faults`` is planted in
    turn and the same comparison must FAIL by at least one of the
    bounds: a bound that pads that move the state or a window off by one
    could pass holds nothing. What this comparison CANNOT see is the
    state's own precision: on the chip a bfloat16 state read the same as
    a float32 one here, logits and states alike (what reaches the state
    from bfloat16 activations differs from the reference by more than the
    state's rounding adds); :func:`state_scan_check` holds that."""
    import jax
    import jax.numpy as jnp
    common = _bench_harness()
    from paddle_tpu.models.kv_cache import (cache_entry_kinds,
                                            cache_row_buffers,
                                            cache_row_view, init_cache)
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     param_state)

    t0 = time.perf_counter()
    model = common.build_model(config, None, seed)
    model.eval()
    params, buffers = param_state(model), buffer_state(model)
    kinds = cache_entry_kinds(model.cache_spec())
    state_layers = [i for i, k in enumerate(kinds) if k == "state"]
    nbytes = sum(p.size * p.dtype.itemsize for p in params.values())
    log(f"[serve] state: model built in {time.perf_counter() - t0:.1f} s, "
        f"{sum(p.size for p in params.values()) / 1e9:.3f} B parameters, "
        f"{nbytes / 1e9:.2f} GB, {len(state_layers)} state entries of "
        f"{len(kinds)}, dtypes "
        f"{sorted({str(p.dtype) for p in params.values()})}")
    vocab = config["config"]["vocab_size"]
    rng = np.random.default_rng(seed)
    texts = [rng.integers(0, vocab, n + steps).astype(np.int32)
             for n in prompt_lens]
    rows = (0, slots - 1)

    reference = common.resolve(config["reference"])
    refs = []
    for n, text in zip(prompt_lens, texts):
        t1 = time.perf_counter()
        states = []
        ref = reference.logits(params, config["config"], text[None],
                               states=states)[0][n - 1:n + steps]
        refs.append((ref, states[0]))
        log(f"[serve] state: reference pass over {n} + {steps} tokens "
            f"{time.perf_counter() - t1:.1f} s")

    def run(fault):
        def prefill(params, buffers, cache, ids, row, last):
            (logits, view), _ = functional_call(
                model, params, buffers, ids, cache=cache_row_view(cache, row),
                position_offset=0, gather_last=last)
            return logits[0, 0].astype(jnp.float32), cache_row_buffers(view)

        def decode(params, buffers, cache, tokens, positions):
            (logits, cache), _ = functional_call(
                model, params, buffers, tokens, cache=cache,
                position_offset=positions)
            return logits[:, 0].astype(jnp.float32), cache

        t1 = time.perf_counter()
        prefill = jax.jit(prefill, donate_argnums=2)
        decode = jax.jit(decode, donate_argnums=2)
        cache = init_cache(model, slots, length)
        got = {r: [] for r in rows}
        with _planted_state_fault(fault):
            for r, n, text in zip(rows, prompt_lens, texts):
                ids = np.zeros((1, bucket), np.int32)
                ids[0, :n] = text[:n]
                logits, cache = prefill(params, buffers, cache, ids,
                                        np.int32(r), np.int32(n - 1))
                got[r].append(np.asarray(logits))
            for i in range(steps):
                tokens = np.zeros((slots, 1), np.int32)
                positions = np.zeros(slots, np.int32)
                for r, n, text in zip(rows, prompt_lens, texts):
                    tokens[r, 0], positions[r] = text[n + i], n + i
                logits, cache = decode(params, buffers, cache, tokens,
                                       positions)
                logits = np.asarray(logits)
                for r in rows:
                    got[r].append(logits[r])
        worst, rms, short, h_err, w_err = [], [], [], [], []
        rel = lambda a, b: float(np.sqrt(np.mean((a - b) ** 2)
                                         / np.mean(b ** 2)))
        for r, (ref, states) in zip(rows, refs):
            diff = np.stack(got[r]) - ref
            std = ref.std(axis=-1)
            worst.extend((np.abs(diff).max(axis=-1) / std).tolist())
            rms.extend((np.sqrt(np.mean(diff ** 2, axis=-1)) / std).tolist())
            token = np.stack(got[r]).argmax(-1)
            short.extend(((ref.max(-1) - ref[np.arange(len(ref)), token])
                          / std).tolist())
            for layer, (h_ref, u_ref) in zip(state_layers, states):
                h, window = (np.asarray(x[r], np.float32)
                             for x in cache[layer])
                h_err.append(rel(h, h_ref))
                w_err.append(rel(window, u_ref[-window.shape[0]:]))
        del cache
        out = {"logit_worst": float(max(worst)),
               "logit_rms_worst": float(max(rms)),
               "shortfall_worst": float(max(short)),
               "state_worst": float(max(h_err)),
               "window_worst": float(max(w_err))}
        log(f"[serve] state, {fault or 'as it stands'}: prefill of "
            f"{list(prompt_lens)} tokens (bucket {bucket}) into rows "
            f"{list(rows)} of {slots} x {length}, then {steps} decode steps, "
            f"in {time.perf_counter() - t1:.1f} s: largest |logit - "
            f"reference| over the logits' std {out['logit_worst']:.4f} "
            f"(bound {logit_bound}), rms {out['logit_rms_worst']:.4f}; scan "
            f"state off by {out['state_worst']:.5f} of its rms (bound "
            f"{state_bound}), window by {out['window_worst']:.5f} (bound "
            f"{window_bound}); greedy tokens fall short of the reference's "
            f"best by {out['shortfall_worst']:.4f} at most")
        return out

    def passes(o):
        return (o["logit_worst"] <= logit_bound
                and o["state_worst"] <= state_bound
                and o["window_worst"] <= window_bound)

    out = run(None)
    check(passes(out),
          f"state geometry: a logit is {out['logit_worst']:.4f} of the "
          f"logits' std from the reference (bound {logit_bound}), the scan "
          f"state {out['state_worst']:.5f} of its rms (bound {state_bound}), "
          f"the window {out['window_worst']:.5f} (bound {window_bound})")
    out["faults"] = {}
    for fault in faults:
        bad = out["faults"][fault] = run(fault)
        check(not passes(bad),
              f"state geometry: {fault} passes every bound, so they hold "
              f"nothing: {bad}")
    return out


def state_scan_check(config: dict, seed: int, slots: int = 16,
                     prompt: int = 40, bucket: int = 64, steps: int = 64,
                     bound: float = STATE_SCAN_BOUND) -> dict:
    """One recurrent layer's state through the cache on EQUAL inputs, at
    the configuration's widths: seeded convolution inputs, step sizes (log
    uniform in Mamba's [1e-3, 1e-1]), B and C go through
    ``lm_utils.scan_with_state`` (a padded prefill of ``prompt`` positions
    in a block of ``bucket``, then ``steps`` decode steps, every row at
    once, the state living in a state entry as ``kv_cache.alloc_cache``
    makes it) and through the same recurrence in numpy float64. With
    equal inputs the outputs differ by the state's arithmetic alone:
    float32 must come within ``bound`` of float64 (outputs and final
    state, rms over rms) and the window must be the inputs' last ones bit
    for bit; then the SAME run over a state leaf cast to bfloat16 must
    fail the bound. This is what tells a bfloat16 state from a float32
    one; :func:`state_logits_check` cannot."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import lm_utils
    from paddle_tpu.models.kv_cache import alloc_cache

    cfg = config["config"]
    d = cfg["mamba_expand"] * cfg["hidden_size"]
    n, K = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    dtype = jnp.dtype(config["run"]["dtype"])
    spec = {"num_layers": 1, "entry_kinds": ("state",), "num_kv_heads": 1,
            "head_dim": 1, "state": (n, K - 1, d), "max_length": 1,
            "dtype": str(dtype)}
    total = prompt + steps
    rng = np.random.default_rng(seed)
    u_pre = jnp.asarray(rng.standard_normal((slots, total, d)), dtype)
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                               (slots, total, d))).astype(np.float32)
    Bm, Cm = (rng.standard_normal((slots, total, n)).astype(np.float32)
              for _ in range(2))
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (K, d)), dtype)
    b = jnp.asarray(rng.uniform(-0.5, 0.5, (d,)), dtype)
    A = -np.broadcast_to(np.arange(1, n + 1, dtype=np.float32)[:, None],
                         (n, d))
    D = np.ones(d, np.float32)

    def at(first, length):
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, first, length, axis=1)
        return lambda u: (cut(delta), cut(Bm), cut(Cm))

    @jax.jit
    def prefill(cache):
        block = jnp.zeros((slots, bucket, d), dtype).at[:, :prompt].set(
            u_pre[:, :prompt])
        pad = lambda x: jnp.pad(x[:, :prompt],
                                ((0, 0), (0, bucket - prompt), (0, 0)),
                                constant_values=0.05)
        with lm_utils.block_length(jnp.int32(prompt)):
            return lm_utils.scan_with_state(
                block, w, b, lambda u: (pad(delta), pad(Bm), pad(Cm)),
                jnp.asarray(A), jnp.asarray(D), cache, 0)

    @jax.jit
    def step(cache, t):
        return lm_utils.scan_with_state(
            jax.lax.dynamic_slice_in_dim(u_pre, t, 1, axis=1), w, b,
            at(t, 1), jnp.asarray(A), jnp.asarray(D), cache,
            jnp.full((slots,), t, jnp.int32))

    def run(state_dtype):
        cache = alloc_cache(spec, slots, 1)[0]
        cache = (cache[0].astype(state_dtype), cache[1])
        y, cache = prefill(cache)
        ys = [np.asarray(y[:, :prompt])]
        for t in range(prompt, total):
            y, cache = step(cache, jnp.int32(t))
            ys.append(np.asarray(y))
        return (np.concatenate(ys, axis=1), np.asarray(cache[0], np.float32),
                np.asarray(cache[1], np.float32))

    # the same recurrence in float64, position by position
    f64 = lambda x: np.asarray(x, np.float64)
    up = np.concatenate([np.zeros((slots, K - 1, d)),
                         f64(u_pre.astype(jnp.float32))], axis=1)
    u = f64(b.astype(jnp.float32)) + sum(
        f64(w.astype(jnp.float32))[k] * up[:, k:k + total] for k in range(K))
    u = u / (1.0 + np.exp(-u))
    h = np.zeros((slots, n, d))
    y_ref = np.empty((slots, total, d))
    for t in range(total):
        dt = f64(delta[:, t])
        h = (np.exp(dt[:, None, :] * A) * h
             + (dt * u[:, t])[:, None, :] * f64(Bm[:, t])[:, :, None])
        y_ref[:, t] = np.sum(h * f64(Cm[:, t])[:, :, None], axis=1) + D * u[:, t]
    rel = lambda a, r: float(np.sqrt(np.mean((a - r) ** 2) / np.mean(r ** 2)))
    out = {}
    for name, state_dtype in (("float32", jnp.float32),
                              ("bfloat16", jnp.bfloat16)):
        y, h_got, window = run(state_dtype)
        out[name] = {"y": rel(y, y_ref), "h": rel(h_got, h),
                     "window_exact": bool(np.array_equal(
                         window, up[:, -(K - 1):].astype(np.float32)))}
    log(f"[serve] state scan, {slots} rows x {prompt} positions in a block "
        f"of {bucket} then {steps} steps, d {d}, n {n}, equal inputs, "
        f"against float64: float32 state {out['float32']}, bfloat16 state "
        f"{out['bfloat16']} (bound {bound})")
    good, bad = out["float32"], out["bfloat16"]
    check(good["y"] <= bound and good["h"] <= bound and good["window_exact"],
          f"state scan: the float32 state is {good} from float64 on equal "
          f"inputs (bound {bound}, and the window bit for bit)")
    check(bad["y"] > bound or bad["h"] > bound,
          f"state scan: a bfloat16 state passes the bound ({bad}), so it "
          f"holds nothing")
    return out


# ------------------------------------------------------------ four chips
def four_chip_phase(cfg, batch: int, seq: int, ref_first_loss: float,
                    n_devices: int = 4, loss_tol: float = 0.05,
                    balance: float = 1.5) -> dict:
    """``loss_tol``: sharding changes the order of bf16 reductions (mp
    splits every contraction in two and sums the halves), which moves
    the f32 loss in its third decimal; a missing or doubled collective
    moves it by whole units."""
    import jax
    from paddle_tpu.distributed.mesh import init_mesh, set_mesh
    from paddle_tpu.distributed.shard import DistributedTrainStep

    devices = jax.devices()[:n_devices]
    ids = _seeded_ids(cfg, batch, seq)
    out = {}
    for label, axes, stage in (("dp2xmp2", {"dp": 2, "mp": 2}, 0),
                               ("sdp4-zero2", {"sdp": n_devices}, 2)):
        mesh = init_mesh(devices=devices, **axes)
        try:
            step = _o2_train_step(cfg, DistributedTrainStep, mesh=mesh,
                                  sharding_stage=stage)
            loss = step((ids, ids))
            jax.block_until_ready(loss)
            loss = float(np.asarray(loss))
            log(f"[four_chip] {label}: first loss {loss:.4f} "
                f"(single chip {ref_first_loss:.4f})")
            check(abs(loss - ref_first_loss) <= loss_tol,
                  f"{label}: first loss {loss:.4f} differs from the "
                  f"single-chip {ref_first_loss:.4f} by more than {loss_tol}")
            compiles = step.cache_stats()["compiles"]
            check(compiles == 1, f"{label}: traced {compiles} programs")

            leaves = jax.tree.leaves(
                {"params": step.params, "opt_state": step.opt_state})
            spread = {len(x.sharding.device_set) for x in leaves}
            check(spread == {n_devices},
                  f"{label}: state lives on device sets of sizes {spread}")
            named = set()
            for x in leaves:
                for entry in getattr(x.sharding, "spec", ()):
                    named.update(entry if isinstance(entry, tuple)
                                 else (entry,))
            want = {a for a in axes if a != "dp"}   # dp shards the batch only
            check(want <= named,
                  f"{label}: no state leaf is sharded over {want - named}")

            # the eager Layer keeps its own copy of the parameters on the
            # default device; everything else should be spread evenly
            layer_bytes = sum(int(p.nbytes) for _, p in
                              step.model.named_parameters())
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            even = [in_use[0] - layer_bytes] + in_use[1:]
            log(f"[four_chip] {label}: bytes in use per device "
                f"{[round(b / 2**30, 2) for b in in_use]} GiB (device 0 "
                f"holds the Layer's own {layer_bytes / 2**30:.2f} GiB)")
            check(min(even) > 0 and max(even) <= balance * min(even),
                  f"{label}: memory is not spread over the devices: "
                  f"{in_use} bytes in use")
            out[label] = loss
        finally:
            set_mesh(None)
        del step, leaves
        _release_device_memory()
    return out


# ------------------------------------------------------------------ main
def device_report(devices) -> dict:
    """The device as JAX reports it."""
    return {"platform": str(devices[0].platform),
            "kind": str(devices[0].device_kind), "count": len(devices)}


def result_line(devices) -> str:
    """The last line of stdout on success. Its reader accepts exactly the
    keys "ok" and "device" {"platform", "kind", "count"} and nothing
    else; per-phase outcomes go out as log lines before it."""
    return json.dumps({"ok": True, "device": device_report(devices)})


def _release_device_memory() -> None:
    """Free the last phase's HBM: the step classes hold reference cycles
    (a jit of a bound method) and compiled programs pin their buffers."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def _run_phase(name: str, fn):
    from paddle_tpu.framework import compile_cache

    before = compile_cache.backend_compile_stats()
    t0 = time.perf_counter()
    log(f"[{name}] start")
    try:
        result = fn()
    except Exception:
        log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s")
        raise
    after = compile_cache.backend_compile_stats()
    log(f"[{name}] passed: wall {time.perf_counter() - t0:.1f} s, of which "
        f"trace+lower+compile "
        f"{after['compile_seconds'] - before['compile_seconds']:.1f} s; "
        f"executables requested {after['requests'] - before['requests']}, "
        f"persistent-cache hits "
        f"{after['persistent_hits'] - before['persistent_hits']}, backend "
        f"compiles {after['backend_compiles'] - before['backend_compiles']}")
    _release_device_memory()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES))
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}")

    # the TPU, or JAX's own error: there is no other backend to fall to
    os.environ["JAX_PLATFORMS"] = "tpu"
    t_start = time.perf_counter()
    import jax

    jax.config.update("jax_platforms", "tpu")
    devices = jax.devices()
    device = device_report(devices)
    if device["platform"] != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU, jax gave {device}")
    import importlib.metadata

    from paddle_tpu.framework import compile_cache

    cache_dir = compile_cache.enable_persistent_cache()

    import jaxlib

    log(f"device: {json.dumps(device)}")
    log(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {importlib.metadata.version('libtpu')}, "
        f"python {sys.version.split()[0]}")
    log(f"compile cache: {cache_dir}")

    done = {}
    train = None
    if "train" in phases:
        train = _run_phase("train", lambda: train_phase(
            gpt_config(24, 1024), batch=8, seq=1024, steps=10))
        done["train"] = "passed"
    if "flash" in phases:
        _run_phase("flash", lambda: flash_phase(
            gpt_config(4, 4096), batch=2, seq=4096, steps=2,
            kernel_shape=(2, 16, 4096, 64)))
        done["flash"] = "passed"
    if "serve" in phases:
        # the serving preset of tools/decode_bench.py: f32 weights, bf16
        # KV cache, every default prefill bucket up to 1024
        def serve():
            cache_write_check()
            cache_read_check()
            serve_phase(
                gpt_config(24, 1024, loss_chunk=0), slots=8,
                prompt_lens=(20, 50, 100, 200, 400, 900), n_requests=32)
            _release_device_memory()
            # the latent entry at its cell's geometry (32 slots x 8192)
            latent = _bench_config("xing4.0-29b-a4b")
            expert_ffn_check(latent, seed=2147483659)
            _release_device_memory()
            mixer_check(latent, seed=2147483659)
            out = latent_logits_check(latent, seed=2147483659, slots=32,
                                      length=8192, bucket=1024,
                                      prompt_lens=(1000, 300), steps=48)
            _release_device_memory()
            # the state entry at its cell's geometry (128 slots x 8192)
            hybrid = _bench_config("ai21-jamba2-3b")
            out["state_scan"] = state_scan_check(hybrid, seed=2147483659)
            out["state"] = state_logits_check(
                hybrid, seed=2147483659, slots=128, length=8192, bucket=1024,
                prompt_lens=(700, 2), steps=16)
            return out

        _run_phase("serve", serve)
        done["serve"] = "passed"
    if "four_chip" in phases:
        if len(devices) < 4:
            log(f"[four_chip] SKIPPED: needs 4 devices, this host has "
                f"{len(devices)}")
            done["four_chip"] = f"skipped: {len(devices)} device(s)"
        elif train is None:
            raise RuntimeError("the four_chip phase compares with the "
                               "train phase's first loss: select both")
        else:
            _run_phase("four_chip", lambda: four_chip_phase(
                gpt_config(24, 1024), batch=8, seq=1024,
                ref_first_loss=train["first_loss"]))
            done["four_chip"] = "passed"

    log(f"phases: {json.dumps(done)}")
    log(f"all selected phases passed in {time.perf_counter() - t_start:.1f} s")
    print(result_line(devices), flush=True)      # nothing after this line
    return 0


if __name__ == "__main__":
    sys.exit(main())
