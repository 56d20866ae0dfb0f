"""Shared decoder plumbing (GPT/Llama/ERNIE families): the block stack,
causal and cached-decode attention (the cache itself is :mod:`.kv_cache`'s)
and the memory-fused chunked LM loss: head projection + softmax-CE computed
over sequence chunks inside ``jax.checkpoint`` regions, so the
[B, L, vocab] logits tensor — the single largest HBM allocation in LM
pretrain — never materializes. Reference contrast:
``paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu``
fuses softmax+CE but still materializes full logits.
"""
from __future__ import annotations

import contextlib
import math
import threading

import jax
import jax.numpy as jnp

from ..distributed.mesh import get_mesh, sharding
from ..distributed.parallel.recompute import recompute_wrap
from ..kernels import flash_attention as fa
from ..nn import functional as F
from ..nn.layer import Layer
from .kv_cache import (cached_attention, latent_attention, read_state,
                       update_kv_cache, write_state)

__all__ = ["chunked_lm_loss", "DecoderBlockList", "constrain_seq",
           "causal_attention", "repeat_kv", "attend_with_cache",
           "latent_block_attention", "attend_with_latent_cache",
           "selective_scan", "scan_with_state", "block_length",
           "cached_lm_forward"]


def constrain_seq(x, cfg):
    """Between-block activation sharding for decoder stacks: [dp, sp,
    mp-free] when ``cfg.sequence_parallel`` and the mesh has an "sp" axis,
    else [dp, None, None]."""
    mesh = get_mesh()
    if mesh is None or x.ndim != 3:
        return x
    seq_axis = "sp" if (cfg.sequence_parallel and "sp" in mesh.shape) else None
    batch_axes = tuple(a for a in ("dp", "sdp") if a in mesh.shape) or None
    return jax.lax.with_sharding_constraint(
        x, sharding(batch_axes, seq_axis, None, mesh=mesh))


def causal_attention(q, k, v, dropout_p=0.0, training=True, use_flash=True,
                     scale=None):
    """Causal self-attention on [B, L, H, D]; Pallas flash path when the
    gate allows, XLA-fused softmax otherwise. ``scale`` multiplies the
    scores in place of ``1 / sqrt(D)`` (XLA's path alone: a caller that
    gives one turns ``use_flash`` off); ``v`` may be narrower than ``q``
    and ``k`` there."""
    p_drop = dropout_p if training else 0.0
    # tpu-lint: disable=R2(flash gate reads only static shape/dtype/platform of q,k — per-shape program selection inside the bucketed compile budget, re-audited PR 12)
    if use_flash and fa.should_use_flash(q, k, None, p_drop):
        if p_drop > 0.0:
            from ..nn.layer import take_rng_key

            seed = jax.random.randint(take_rng_key("dropout"), (), 0,
                                      2 ** 31 - 1)
        else:
            seed = 0
        return fa.flash_attention_blhd(q, k, v, causal=True,
                                       dropout_p=p_drop, seed=seed)
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((Lq, Lk), dtype=bool), k=Lk - Lq)
    s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        p = F.dropout(p, p=dropout_p, training=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# -------------------------------------------------------- cached decode
def repeat_kv(x, groups: int):
    """[B, L, Hkv, D] -> [B, L, Hkv*groups, D] for GQA (each kv head
    serves ``groups`` query heads)."""
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=2)


def attend_with_cache(q, k_new, v_new, cache, position_offset,
                      use_flash=True, entry=None):
    """The cached-decode attention dispatch shared by GPT and Llama.

    Always writes ``k_new``/``v_new`` into the cache. The PREFILL shape
    (multi-token at static offset 0) attends block-locally via
    :func:`causal_attention` — flash-eligible, no O(S) mask work; every
    other shape (single-token decode, chunked continuation) runs
    :func:`cached_attention` against the full cache with the position
    mask. ``entry`` selects one of the entries a looped model stacks in
    each row of its leaves. Returns ``(out, (k_cache, v_cache))``.
    """
    cache = update_kv_cache(cache, k_new, v_new, position_offset, entry)
    is_prefill = (q.shape[1] > 1 and isinstance(position_offset, int)
                  and position_offset == 0)
    if is_prefill:
        # prefill attends over the un-quantized k_new/v_new block — the
        # quantized values land in the cache for LATER reads only, so
        # prefill logits stay bit-identical across kv_dtype settings
        groups = q.shape[2] // k_new.shape[2]
        out = causal_attention(q, repeat_kv(k_new, groups),
                               repeat_kv(v_new, groups), dropout_p=0.0,
                               training=False, use_flash=use_flash)
    else:
        out = cached_attention(q, cache[0], cache[1], position_offset,
                               entry)
    return out, cache


def latent_block_attention(q_nope, q_rope, c, k_rope, w_uk, w_uv, scale):
    """Multi-head latent attention over a block's own positions, keys and
    values DECOMPRESSED: ``k = [c W_uk | k_rope]`` per head (the rotated
    part shared by all heads), ``v = c W_uv``. ``q_nope`` [B, L, H, N],
    ``q_rope`` [B, L, H, R], ``c`` [B, L, rank], ``k_rope`` [B, L, 1, R],
    ``w_uk`` [rank, H, N], ``w_uv`` [rank, H, V]. Returns [B, L, H, V].
    The key is ``N + R`` wide and the value ``V``: none of the flash
    kernel's shapes, so XLA's path."""
    H = q_nope.shape[2]
    k_nope = jnp.einsum("blc,chn->blhn", c, w_uk)
    v = jnp.einsum("blc,chv->blhv", c, w_uv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H,)
                                  + k_rope.shape[3:])], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return causal_attention(q, k, v, training=False, use_flash=False,
                            scale=scale)


def attend_with_latent_cache(q_nope, q_rope, c_new, k_rope_new, w_uk, w_uv,
                             cache, position_offset, scale):
    """:func:`attend_with_cache` for a latent cache entry ``(c, k_r)``
    (:mod:`.kv_cache`): always writes ``c_new`` [B, L, rank] and
    ``k_rope_new`` [B, L, 1, R]; the PREFILL shape decompresses the
    block's keys and values and attends block-locally
    (:func:`latent_block_attention`); every other shape (single-token
    decode, chunked continuation) attends IN THE LATENT SPACE against the
    full cache, ``w_uk`` absorbed into the query and ``w_uv`` applied to
    the result, so a step never decompresses ``S`` positions:

        q_c = q_nope W_uk^h;  score = (q_c . c + q_r . k_r) * scale
        o_c = softmax(score) c;  out = o_c W_uv^h

    which equals the decompressed form in exact arithmetic. Returns
    ``(out [B, L, H, V], (c_cache, k_r_cache))``."""
    with jax.named_scope("mla"):
        cache = update_kv_cache(cache, c_new[:, :, None, :], k_rope_new,
                                position_offset)
        is_prefill = (q_nope.shape[1] > 1 and isinstance(position_offset, int)
                      and position_offset == 0)
        if is_prefill:
            return latent_block_attention(q_nope, q_rope, c_new, k_rope_new,
                                          w_uk, w_uv, scale), cache
        with jax.named_scope("absorb"):
            q_c = jnp.einsum("blhn,chn->blhc", q_nope, w_uk)
        o_c = latent_attention(q_c, q_rope, cache[0], cache[1],
                               position_offset, scale)
        return jnp.einsum("blhc,chv->blhv", o_c, w_uv), cache


# ----------------------------------------------------- recurrent state
# Trace-time state, thread-local as kv_cache.cache_paths is: how many of
# the block's positions are real, where the block is right-padded.
_BLOCK = threading.local()


@contextlib.contextmanager
def block_length(n):
    """The block traced under this context holds ``n`` real positions (a
    traced scalar) and padding after them. Attention needs no telling (a
    pad's key sits behind the position mask); a recurrence would run on
    through the pads, so :func:`scan_with_state` reads it."""
    outer = getattr(_BLOCK, "n", None)
    _BLOCK.n = n
    try:
        yield
    finally:
        _BLOCK.n = outer


# Positions a trip of the prefill scan's loop advances: the recurrence is
# sequential in time, and a trip of one position is mostly the loop's own
# cost on the chip.
SCAN_UNROLL = 8


def selective_scan(u, delta, A, Bm, Cm, D, h0):
    """The selective state-space recurrence, float32 throughout, per
    channel ``c`` and state ``s``:

        h_t[s, c] = exp(delta_t[c] A[s, c]) h_{t-1}[s, c] + delta_t[c] u_t[c] B_t[s]
        y_t[c]    = sum_s h_t[s, c] C_t[s] + D[c] u_t[c]

    ``u``, ``delta`` [B, L, d]; ``A`` [n, d]; ``Bm``, ``Cm`` [B, L, n];
    ``D`` [d]; ``h0`` [B, n, d]. Returns ``(y [B, L, d], h_L)``. The inner
    width stays on the lanes. One position is one fused update (the
    decode step); a block is a ``lax.scan`` over time with the state as
    carry, so ``[L, n, d]`` never exists. ``delta_t = 0`` leaves the
    state as it was, exactly (``exp(0) = 1``, ``0 * u * B = 0``)."""
    def step(h, x):
        u_t, d_t, b_t, c_t = x                       # [B, d] x2, [B, n] x2
        h = (jnp.exp(d_t[:, None, :] * A) * h
             + (d_t * u_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    xs = (u, delta, Bm, Cm)
    if u.shape[1] == 1:
        h, y = step(h0, tuple(x[:, 0] for x in xs))
        y = y[:, None]
    else:
        h, y = jax.lax.scan(step, h0,
                            tuple(jnp.swapaxes(x, 0, 1) for x in xs),
                            unroll=min(SCAN_UNROLL, u.shape[1]))
        y = jnp.swapaxes(y, 0, 1)
    return y + D * u, h


def scan_with_state(u_pre, conv_weight, conv_bias, ssm_params, A, D,
                    cache=None, position_offset=0):
    """A recurrent mixer's one call into the cache, as
    :func:`attend_with_cache` is attention's: the causal depthwise
    convolution over time, the mixer's own ``ssm_params(u) -> (delta,
    B, C)`` (float32), and :func:`selective_scan`, started from what the
    cache's state entry holds and leaving there what the next token
    needs. ``u_pre`` [B, L, d] is the convolution's input, ``conv_weight``
    [K, d] (``conv_weight[k]`` multiplies the input ``K - 1 - k``
    positions back), ``conv_bias`` [d], ``A`` [n, d] (negative), ``D``
    [d]. Returns ``(y [B, L, d] float32, cache)``.

    Three shapes of the one recurrence. A sequence that starts at
    position 0 (static ``position_offset == 0``: prefill, or no cache at
    all) starts from a zero state and a zero window WHATEVER the row
    held: no mask hides a freed slot's state. Any other offset continues
    from the cached pair, a block at a time (chunked continuation) or one
    token a row, every row at once (the decode step: ``[B]`` positions or
    a scalar; the recurrence does not read them). Under
    :func:`block_length` only the first ``n`` positions are real: delta
    is forced to 0 on the pads, so the state written is the state after
    exactly ``n`` tokens, and the window written is the last ``K - 1``
    REAL inputs (zeros, or the cached window's tail, to their left)."""
    B, L, d = u_pre.shape
    K = conv_weight.shape[0]
    f32 = jnp.float32
    fresh = cache is None or (isinstance(position_offset, int)
                              and position_offset == 0)
    if fresh:
        h0 = jnp.zeros((B, A.shape[0], d), f32)
        window = jnp.zeros((B, K - 1, d), u_pre.dtype)
    else:
        h0, window = read_state(cache)
    n = getattr(_BLOCK, "n", None)
    with jax.named_scope("conv"):
        ext = jnp.concatenate([window.astype(u_pre.dtype), u_pre], axis=1)
        w = conv_weight.astype(f32)
        u = conv_bias.astype(f32) + sum(
            w[k] * ext[:, k:k + L].astype(f32) for k in range(K))
        u = jax.nn.silu(u)
    with jax.named_scope("ssm_params"):
        delta, Bm, Cm = ssm_params(u)
        if n is not None:
            real = jnp.arange(L, dtype=jnp.int32) < n
            delta = jnp.where(real[None, :, None], delta, 0.0)
    with jax.named_scope("scan" if L > 1 else "state_update"):
        y, h = selective_scan(u, delta, A, Bm, Cm, D, h0)
        if cache is not None:
            # ext[j] is the input at block position j - (K - 1): the last
            # K - 1 real ones end at position n - 1
            tail = (ext[:, L:] if n is None else
                    jax.lax.dynamic_slice_in_dim(ext, n, K - 1, axis=1))
            cache = write_state(cache, h, tail)
    return y, cache


def cached_lm_forward(backbone, logits_fn, input_ids, cache,
                      position_offset, gather_last):
    """The serving-side CausalLM forward shared by GPT and Llama: run the
    backbone (cache-threaded when given), optionally slice the hidden
    states to the single ``gather_last`` position BEFORE the head
    projection (so serving never materializes [B, L, vocab]), and return
    ``logits`` or ``(logits, new_cache)``. ``gather_last`` is the index
    of the block's last REAL token wherever a caller right-pads a prompt
    to its bucket, so it also tells the backbone how long the block
    really is (:func:`block_length`): a recurrent mixer must not run on
    through the pads."""
    with (contextlib.nullcontext() if gather_last is None
          else block_length(gather_last + 1)):
        h = backbone(input_ids, cache=cache, position_offset=position_offset)
    if cache is not None:
        h, cache = h
    if gather_last is not None:
        h = jax.lax.dynamic_slice_in_dim(h, gather_last, 1, axis=1)
    logits = logits_fn(h)
    return logits if cache is None else (logits, cache)


class DecoderBlockList(Layer):
    """Shared N-block decoder stack with per-block recompute dispatch
    (GPT/Llama): ``cfg`` provides ``num_layers``/``use_recompute``/
    ``recompute_policy``; ``block_cls(cfg)`` builds one block, called
    once a layer in layer order (any callable: a model whose layers
    differ hands in one that counts). With
    ``caches`` (a per-layer tuple of ``(k, v)`` pairs) each block runs its
    cached-decode path and the updated caches ride back alongside the
    activations; ``cache_entry`` goes to blocks whose pair stacks several
    entries (a looped model's step)."""

    def __init__(self, cfg, block_cls):
        super().__init__()
        self.cfg = cfg
        for i in range(cfg.num_layers):
            self.add_sublayer(str(i), block_cls(cfg))

    def forward(self, x, caches=None, position_offset=0, cache_entry=None):
        # "block": what a block does itself (its norms, the residual adds)
        # is booked there; attention, mlp and the rest open their own
        if caches is None:
            for blk in self._sub_layers.values():
                fn = (recompute_wrap(blk, policy=self.cfg.recompute_policy)
                      if self.cfg.use_recompute else blk)
                with jax.named_scope("block"):
                    x = fn(x)
            return x
        new_caches = []
        kw = {} if cache_entry is None else {"cache_entry": cache_entry}
        for blk, cache in zip(self._sub_layers.values(), caches):
            with jax.named_scope("block"):
                x, cache = blk(x, cache=cache,
                               position_offset=position_offset, **kw)
            new_caches.append(cache)
        return x, tuple(new_caches)


@jax.named_scope("loss_head")
def chunked_lm_loss(h, labels, logits_fn, ce, chunk: int = 256):
    """Shifted next-token loss over ``h`` [B, L, H] without full logits.

    ``logits_fn(h_chunk) -> logits`` is the head projection (possibly
    vocab-sharded); ``ce(logits, labels) -> per-token loss`` (e.g.
    ParallelCrossEntropy). Labels are shifted internally; padding chunks
    use label -100 (ignored).
    """
    hs = h[:, :-1]
    ys = jnp.asarray(labels)[:, 1:]
    B, Lm1, H = hs.shape
    nchunk = -(-Lm1 // chunk)
    pad = nchunk * chunk - Lm1
    hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
    ys = jnp.pad(ys, ((0, 0), (0, pad)), constant_values=-100)
    hs = jnp.swapaxes(hs.reshape(B, nchunk, chunk, H), 0, 1)
    ys = jnp.swapaxes(ys.reshape(B, nchunk, chunk), 0, 1)

    @jax.checkpoint
    def chunk_losses(h_c, y_c):
        per_tok = ce(logits_fn(h_c), y_c)
        valid = (y_c != -100).astype(jnp.float32)
        return jnp.sum(per_tok * valid), jnp.sum(valid)

    def body(carry, xs):
        s, c = chunk_losses(*xs)
        return (carry[0] + s, carry[1] + c), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0)), (hs, ys))
    return total / jnp.maximum(count, 1.0)
