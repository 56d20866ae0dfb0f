"""Blockwise (flash) attention Pallas kernels for TPU.

Reference parity: ``paddle/fluid/operators/fused/fused_attention_op.cu`` and
``fmha_ref.h`` implement *eager full* attention (materializes the [L, L]
score matrix). These kernels are the TPU-native upgrade: online-softmax
blockwise attention that never materializes scores in HBM, the enabler for
the long-context path (ring attention builds on the same inner loop).

Full forward + backward in Pallas (no O(L^2) recompute fallback):
  - forward emits O and the per-row logsumexp (LSE),
  - backward recomputes P blockwise from (Q, K, LSE) and accumulates
    dQ (one kernel, grid over q blocks) and dK/dV (second kernel, grid
    over k blocks) — the standard FlashAttention-2 decomposition.
Supports causal masking, additive bias (broadcastable [B|1, H|1, Lq, Lk],
e.g. alibi/relative-position/padding masks, differentiable), and in-kernel
attention dropout via the TPU PRNG (same mask regenerated in backward).

Layout: [B, L, H, D] public API (paddle convention), [B, H, L, D] internally.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _operand_dtype(*refs):
    """Dot-operand dtype, decided over ALL of a kernel body's inputs at
    once: when every tensor the body reads is bf16 the MXU takes them as
    they are; any f32 input (an f32 model, bf16 q/k against an f32 value
    cache) makes every operand f32 — per-tensor decisions would hand
    lax.dot_general unequal operand dtypes. Softmax statistics and
    accumulators are f32 either way (``_dot``'s result type)."""
    if all(r.dtype == jnp.bfloat16 for r in refs):
        return jnp.bfloat16
    return jnp.float32


def _lanes_to(x, width):
    """A row statistic (m, l, lse, delta), held lane-replicated as
    [rows, _LANES], at ``width`` columns for use against a block of that
    width: whole vregs reused or sliced, no lane broadcast. A [rows, 1]
    column would fill one lane of each vreg and pay a broadcast at every
    use, which cost the forward kernel a third of its time."""
    if width == _LANES:
        return x
    if width < _LANES:
        return x[:, :width]
    return pltpu.repeat(x, width // _LANES, axis=1)


def _dot(a, b, a_dim, b_dim):
    """``a`` · ``b`` contracting ``a_dim`` of a with ``b_dim`` of b, f32 result.
    A derived f32 matrix (p, ds) is rounded to the other operand's dtype
    — lax.dot_general requires equal operand dtypes. bf16 operands state
    their own single-pass precision: left to inherit the process-wide
    ``jax_default_matmul_precision="float32"`` they would ask Mosaic for
    an fp32 contract precision, which it refuses for bf16 ("Bad lhs
    type"). f32 operands keep the process default."""
    if a.dtype != b.dtype:
        a = a.astype(b.dtype)
    precision = jax.lax.Precision.DEFAULT if b.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, (((a_dim,), (b_dim,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


# 512-blocks, measured on the v5e (PERF.md section 6, PR 26): smaller ones
# pay more grid steps (~0.35 us each) and a longer schedule per score
# element; 1024-blocks need a raised VMEM limit that itself costs 5 %,
# and then gain 3 % at L 2048 (9 % at 4096, none at head size 128)
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30
# row statistics (lse, delta) are stored [B, H, L, _LANES] with the value
# broadcast over the lane dim — Mosaic's minimum tile is (8, 128), so a
# plain [B, H, L] layout can't be block-indexed per q-block (same trick as
# jax.experimental.pallas.ops.tpu.flash_attention MIN_BLOCK_SIZE)
_LANES = 128


def should_use_flash(q, k, attn_mask, dropout_p) -> bool:
    """Pallas path gate: TPU backend and shapes the kernel tiles well.

    Dropout and additive masks run *inside* the kernel now; only truly
    unsupported shapes fall back to the XLA-fused reference path.
    """
    if jax.default_backend() != "tpu":
        return False
    Lq, Lk = q.shape[1], k.shape[1]
    # below ~2k tokens the O(L^2) scores still fit and attention stays on
    # XLA's fused-softmax path; which side of this line is faster is not
    # measured on today's code (ROADMAP S3/D8)
    if Lq < 2048 or Lq % 128 != 0 or Lk % 128 != 0:
        return False
    if attn_mask is not None:
        # bias must broadcast to [B, H, Lq, Lk]
        if attn_mask.ndim != 4:
            return False
        mb, mh, mq, mk = attn_mask.shape
        if mq != Lq or mk != Lk:
            return False
        if mb not in (1, q.shape[0]) or mh not in (1, q.shape[2]):
            return False
    return q.shape[-1] in (64, 128, 256)


def _fit_block(block, length):
    """Largest power-of-two block <= ``block`` that divides ``length``
    (the gate guarantees length % 128 == 0, so 128 always works)."""
    block = min(block, length)
    while length % block:
        block //= 2
    assert block >= 128, (block, length)
    return block


def _block_id(b, h, qi, ki, n_heads, nq, nk):
    """Unique int32 id per (batch, head, q-block, k-block) — fwd and bwd use
    the same formula so dropout masks regenerate identically."""
    return ((b * n_heads + h) * nq + qi) * nk + ki


def _dropout_mask(shape, dropout_p, seed_ref, block_id):
    """Regenerable per-block dropout keep-mask: seed the TPU PRNG with
    (user_seed, block_id) — Mosaic allows at most 2 seed values — and
    threshold uniform bits. Returns float32 {0, 1/(1-p)} scale matrix."""
    pltpu.prng_seed(seed_ref[0], block_id)
    bits = pltpu.prng_random_bits(shape)  # uint32
    threshold = np.uint32(min(int(dropout_p * (2 ** 32)), 2 ** 32 - 1))
    keep = pltpu.bitcast(bits, jnp.uint32) >= threshold
    return keep.astype(jnp.float32) / (1.0 - dropout_p)


def _fwd_kernel(*refs, scale, causal, block_q, block_k, has_bias, dropout_p):
    if dropout_p > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs

    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        od = _operand_dtype(q_ref, k_ref, v_ref)
        q = q_ref[0, 0].astype(od)
        k = k_ref[0, 0].astype(od)
        v = v_ref[0, 0].astype(od)
        s = _dot(q, k, 1, 1) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_s[:]      # [block_q, _LANES], lanes equal
        l_prev = l_s[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - _lanes_to(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        # l accumulates the full softmax denominator (dropout applies to the
        # normalized probabilities, so only the numerator path is masked)
        l_s[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            bid = _block_id(b, h, qi, ki, pl.num_programs(1),
                            pl.num_programs(2), pl.num_programs(3))
            p = p * _dropout_mask((block_q, block_k), dropout_p, seed_ref, bid)
        acc_s[:] = acc_s[:] * _lanes_to(alpha, acc_s.shape[1]) + _dot(p, v, 1, 0)
        m_s[:] = m_new

    if causal:
        # skip blocks entirely above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[0, 0] = (acc_s[:] / _lanes_to(l, acc_s.shape[1])).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[:] + jnp.log(l)


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, has_bias,
                   dropout_p, emit_ds=False):
    """Grid (B, H, nq, nk): accumulate dq for one q block over all k blocks.
    With ``emit_ds`` also writes the ds block (= dbias before reduce)."""
    if dropout_p > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    ds_ref = None
    if has_bias and emit_ds:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
         dq_ref, ds_ref, dq_s) = refs
    elif has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_s) = refs

    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        od = _operand_dtype(q_ref, k_ref, v_ref, do_ref)
        q = q_ref[0, 0].astype(od)
        k = k_ref[0, 0].astype(od)
        v = v_ref[0, 0].astype(od)
        do = do_ref[0, 0].astype(od)
        lse = _lanes_to(lse_ref[0, 0], block_k)
        delta = _lanes_to(delta_ref[0, 0], block_k)
        s = _dot(q, k, 1, 1) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        p = jnp.exp(s - lse)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = _dot(do, v, 1, 1)
        if dropout_p > 0.0:
            bid = _block_id(b, h, qi, ki, pl.num_programs(1),
                            pl.num_programs(2), pl.num_programs(3))
            dp = dp * _dropout_mask((block_q, block_k), dropout_p, seed_ref, bid)
        ds = p * (dp - delta)
        if ds_ref is not None:
            ds_ref[0, 0] = ds.astype(ds_ref.dtype)
        dq_s[:] += _dot(ds, k, 1, 0) * scale

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_body)
    else:
        _body()
    if causal and ds_ref is not None:
        # skipped blocks must still zero their ds output tile
        pl.when(k_start > q_start + block_q - 1)(
            lambda: ds_ref.__setitem__((0, 0), jnp.zeros_like(ds_ref[0, 0])))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, has_bias, dropout_p):
    """Grid (B, H, nk, nq): accumulate dk, dv for one k block over q blocks."""
    if dropout_p > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs

    b, h = pl.program_id(0), pl.program_id(1)
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        od = _operand_dtype(q_ref, k_ref, v_ref, do_ref)
        q = q_ref[0, 0].astype(od)
        k = k_ref[0, 0].astype(od)
        v = v_ref[0, 0].astype(od)
        do = do_ref[0, 0].astype(od)
        # the score block transposed to begin with, [block_k, block_q]:
        # p^T and ds^T come out in the layout their dots want (contracting
        # dim 0 of a [block_q, block_k] operand makes Mosaic transpose it,
        # a quarter of this kernel's time); lse and delta arrive as rows
        lse = lse_ref[0, 0]      # [1, block_q]
        delta = delta_ref[0, 0]
        s = _dot(k, q, 1, 1) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32).T
        p = jnp.exp(s - lse)
        if causal:
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        if dropout_p > 0.0:
            bid = _block_id(b, h, qi, ki, pl.num_programs(1),
                            pl.num_programs(3), pl.num_programs(2))
            drop = _dropout_mask((block_q, block_k), dropout_p, seed_ref, bid).T
            pd = p * drop
        else:
            pd = p
        dv_s[:] += _dot(pd, do, 1, 0)
        dp = _dot(v, do, 1, 1)
        if dropout_p > 0.0:
            dp = dp * drop
        ds = p * (dp - delta)
        dk_s[:] += _dot(ds, q, 1, 0) * scale

    if causal:
        # q block participates unless entirely above this k block's diagonal
        pl.when(q_start + block_q - 1 >= k_start)(_body)
    else:
        _body()

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _bias_index_map(bias):
    Bb, Hb = bias.shape[0], bias.shape[1]

    def idx(b, h, qi, ki):
        return (b if Bb > 1 else 0, h if Hb > 1 else 0, qi, ki)

    return idx


@functools.partial(
    jax.jit, static_argnames=("causal", "dropout_p", "block_q", "block_k"))
def _flash_fwd_impl(q, k, v, bias, seed, causal, dropout_p,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Forward returning (o, lse) on [B, H, L, D]."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    block_q = _fit_block(block_q, Lq)
    block_k = _fit_block(block_k, Lk)
    scale = 1.0 / math.sqrt(D)
    grid = (B, H, Lq // block_q, Lk // block_k)
    has_bias = bias is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, has_bias=has_bias, dropout_p=dropout_p)

    in_specs = []
    args = []
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray([seed], jnp.int32))
    in_specs += [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
    ]
    args += [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, block_q, block_k), _bias_index_map(bias)))
        args.append(bias)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Lq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*args)
    return o, lse


@functools.partial(
    jax.jit, static_argnames=("causal", "dropout_p", "block_q", "block_k",
                              "bias_grad"))
def _flash_bwd_impl(q, k, v, bias, seed, o, lse, do, causal, dropout_p,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    bias_grad=True):
    """Backward: returns (dq, dk, dv, dbias_or_None) on [B, H, L, D].

    ``bias_grad=False`` skips the [B, H, Lq, Lk] ds materialization (the
    only O(L^2) HBM cost in this file) — used for non-trained masks."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    block_q = _fit_block(block_q, Lq)
    block_k = _fit_block(block_k, Lk)
    scale = 1.0 / math.sqrt(D)
    has_bias = bias is not None
    want_dbias = has_bias and bias_grad

    # delta_i = rowsum(dO_i * O_i) (cheap XLA reduction); lse arrives slim
    # [B, H, Lq] (the residual saved by the fwd). The dq kernel block-indexes
    # both in the [B, H, Lq, _LANES] row-stat layout, re-broadcast here; the
    # dkv kernel, whose score blocks are transposed, as [B, H, 1, Lq] rows
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta_row, lse_row = delta[:, :, None, :], lse[:, :, None, :]
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES))

    seed_args, seed_specs = [], []
    if dropout_p > 0.0:
        seed_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        seed_args = [jnp.asarray([seed], jnp.int32)]

    qkv_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0)),
    ]
    bias_specs = ([pl.BlockSpec((1, 1, block_q, block_k), _bias_index_map(bias))]
                  if has_bias else [])
    row_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),  # do
        pl.BlockSpec((1, 1, block_q, _LANES),
                     lambda b, h, qi, ki: (b, h, qi, 0)),                      # lse
        pl.BlockSpec((1, 1, block_q, _LANES),
                     lambda b, h, qi, ki: (b, h, qi, 0)),                      # delta
    ]
    bias_args = [bias] if has_bias else []

    # ---- dq (+ ds when bias) over grid (B, H, nq, nk) -------------------
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, has_bias=has_bias, dropout_p=dropout_p,
        emit_ds=want_dbias)
    dq_out_specs = [pl.BlockSpec((1, 1, block_q, D),
                                 lambda b, h, qi, ki: (b, h, qi, 0))]
    dq_out_shape = [jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype)]
    if want_dbias:
        dq_out_specs.append(pl.BlockSpec((1, 1, block_q, block_k),
                                         lambda b, h, qi, ki: (b, h, qi, ki)))
        dq_out_shape.append(jax.ShapeDtypeStruct((B, H, Lq, Lk), jnp.float32))
    dq_res = pl.pallas_call(
        dq_kernel,
        grid=(B, H, Lq // block_q, Lk // block_k),
        in_specs=seed_specs + qkv_specs + bias_specs + row_specs,
        out_specs=dq_out_specs,
        out_shape=dq_out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*seed_args, q, k, v, *bias_args, do, lse, delta)
    if want_dbias:
        dq, ds = dq_res
        dbias = ds
        # reduce over broadcast dims back to the bias shape
        if bias.shape[0] == 1:
            dbias = jnp.sum(dbias, axis=0, keepdims=True)
        if bias.shape[1] == 1:
            dbias = jnp.sum(dbias, axis=1, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    else:
        (dq,) = dq_res if isinstance(dq_res, (tuple, list)) else (dq_res,)
        # mask/bias is not trained: zero cotangent, no O(L^2) ds pass
        dbias = jnp.zeros_like(bias) if has_bias else None

    # ---- dk/dv over grid (B, H, nk, nq) ---------------------------------
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, has_bias=has_bias, dropout_p=dropout_p)
    kv_in_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
    ]
    kv_bias_specs = []
    if has_bias:
        bidx = _bias_index_map(bias)
        kv_bias_specs = [pl.BlockSpec(
            (1, 1, block_q, block_k),
            lambda b, h, ki, qi: bidx(b, h, qi, ki))]
    kv_row_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, ki, qi: (b, h, 0, qi)),
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, ki, qi: (b, h, 0, qi)),
    ]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, H, Lk // block_k, Lq // block_q),
        in_specs=seed_specs + kv_in_specs + kv_bias_specs + kv_row_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Lk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*seed_args, q, k, v, *bias_args, do, lse_row, delta_row)
    return dq, dk, dv, dbias


# --------------------------------------------------------- differentiable API
# seed is a PRIMAL (traced) arg so per-step dropout seeds don't retrace;
# its cotangent is float0 (integer arg).
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_diff(q, k, v, bias, seed, causal, dropout_p, block_sizes, bias_grad):
    o, _ = _flash_fwd_impl(q, k, v, bias, seed, causal, dropout_p,
                           block_q=block_sizes[0], block_k=block_sizes[1])
    return o


def _flash_diff_fwd(q, k, v, bias, seed, causal, dropout_p, block_sizes,
                    bias_grad):
    o, lse = _flash_fwd_impl(q, k, v, bias, seed, causal, dropout_p,
                             block_q=block_sizes[0], block_k=block_sizes[1])
    # residual keeps lane 0 only: the [B, H, L, _LANES] kernel layout is
    # 128x redundant and would dominate saved-activation HBM (128 MB/layer
    # at B=16, L=1024, H=16)
    return o, (q, k, v, bias, seed, o, lse[..., 0])


def _flash_diff_bwd(causal, dropout_p, block_sizes, bias_grad, res, g):
    q, k, v, bias, seed, o, lse = res
    dq, dk, dv, dbias = _flash_bwd_impl(
        q, k, v, bias, seed, o, lse, g, causal, dropout_p,
        block_q=block_sizes[0], block_k=block_sizes[1], bias_grad=bias_grad)
    dseed = np.zeros((), jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention_bhld(q, k, v, causal=False, bias=None, dropout_p=0.0,
                         seed=0, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         bias_grad=True):
    """Flash attention on [B, H, L, D] tensors. Differentiable (Pallas
    forward AND backward), with optional additive bias and dropout.
    ``seed`` may be a traced int32 scalar (fresh per step, no retrace).
    Pass ``bias_grad=False`` for non-trained masks to skip the O(L^2)
    dbias pass in the backward."""
    return _flash_diff(q, k, v, bias, jnp.asarray(seed, jnp.int32), causal,
                       float(dropout_p), (block_q, block_k), bool(bias_grad))


def flash_attention_blhd(q, k, v, causal=False, bias=None, dropout_p=0.0, seed=0,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         bias_grad=True):
    """Public entry on paddle-layout [B, L, H, D] tensors."""
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    out = _flash_diff(qt, kt, vt, bias, jnp.asarray(seed, jnp.int32), causal,
                      float(dropout_p), (block_q, block_k), bool(bias_grad))
    return jnp.swapaxes(out, 1, 2)


def reference_attention_bhld(q, k, v, causal=False, bias=None):
    """Unfused reference for kernel tests.

    Causal mask is top-left aligned (q_pos >= k_pos), matching
    ``_fwd_kernel`` exactly — including when Lq != Lk."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        Lq, Lk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Lq, Lk), dtype=bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
