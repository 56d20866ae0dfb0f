"""Shared language-model loss plumbing (GPT/Llama/ERNIE families).

The memory-fused chunked LM loss: head projection + softmax-CE computed
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
from ..kernels import cache_write
from ..kernels import flash_attention as fa
from ..nn import functional as F
from ..nn.layer import Layer

__all__ = ["chunked_lm_loss", "DecoderBlockList", "constrain_seq", "CacheRow",
           "causal_attention", "repeat_kv", "update_kv_cache",
           "cache_write_paths", "cached_attention", "attend_with_cache",
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


def causal_attention(q, k, v, dropout_p=0.0, training=True, use_flash=True):
    """Causal self-attention on [B, L, H, D]; Pallas flash path when the
    gate allows, XLA-fused softmax otherwise."""
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
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((Lq, Lk), dtype=bool), k=Lk - Lq)
    s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        p = F.dropout(p, p=dropout_p, training=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ------------------------------------------------------------- KV cache
def repeat_kv(x, groups: int):
    """[B, L, Hkv, D] -> [B, L, Hkv*groups, D] for GQA (each kv head
    serves ``groups`` query heads)."""
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=2)


@jax.tree_util.register_pytree_node_class
class CacheRow:
    """Row ``row`` (a traced index) of a live cache leaf ``buf`` ``[B, S,
    Hkv, D]``, standing where a batch-1 cache leaf would: a prefill
    given these writes its keys and values straight into the live batch
    (:func:`update_kv_cache`), so that an admission builds no row of its
    own beside it (1.6 GB at 1.5 MiB a token and 1024 positions). Write
    only: the prefill shape attends over its own block."""

    def __init__(self, buf, row):
        self.buf, self.row = buf, row

    def tree_flatten(self):
        return (self.buf, self.row), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _write_window(buf, new, pos, entry=None):
    """Write ``new`` into ``buf`` along the length axis at ``pos`` —
    scalar offset (one dynamic_update_slice, into that row alone where
    ``buf`` is a :class:`CacheRow`) or per-row [B] vector (the vmapped
    windowed write). With ``entry`` (a traced index) each row of ``buf``
    stacks several cache entries, ``[B, E, S, ...]``, and the write lands
    in that one."""
    if isinstance(buf, CacheRow):
        return CacheRow(_write(buf.buf, new, pos, entry, buf.row), buf.row)
    return _write(buf, new, pos, entry, jnp.zeros((), jnp.int32))


def _write(buf, new, pos, entry, row):
    zero = jnp.zeros((), jnp.int32)
    stack = () if entry is None else (jnp.asarray(entry, jnp.int32),)
    new = new.astype(buf.dtype)
    if entry is not None:
        new = new[:, None]
    if pos.ndim == 1:
        _note_write("scatter")

        def write(c, n, p):
            return jax.lax.dynamic_update_slice(
                c, n, stack + (p,) + (zero,) * (c.ndim - 1 - len(stack)))

        return jax.vmap(write)(buf, new, pos)
    start = (row,) + stack + (pos,) + (zero,) * (buf.ndim - 2 - len(stack))
    return jax.lax.dynamic_update_slice(buf, new, start)


# Trace-time state, thread-local as the adapter context of lora.layers
# is: the serving engine opens it around the trace of its decode program.
_WRITES = threading.local()


@contextlib.contextmanager
def cache_write_paths():
    """The set of ways the program traced under this context issues its
    per-slot cache writes: ``"dma"`` (:mod:`..kernels.cache_write`) or
    ``"scatter"`` (the vmapped ``dynamic_update_slice``)."""
    outer = getattr(_WRITES, "paths", None)
    paths = _WRITES.paths = set()
    try:
        yield paths
    finally:
        _WRITES.paths = outer


def _note_write(path: str) -> None:
    paths = getattr(_WRITES, "paths", None)
    if paths is not None:
        paths.add(path)


def _rows_by_dma(k_cache, v_cache, new, pos) -> bool:
    """One operation, two ways to issue it, told apart by what the trace
    shows: the kernel takes a TPU's per-slot (``[B]``-position) write of
    one token into plain leaves on one device whose rows are whole tiles
    (:func:`cache_write.rows_fit`); the scatter takes everything else."""
    mesh = get_mesh()
    return (pos.ndim == 1 and jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)
            and cache_write.rows_fit(k_cache, new)
            and cache_write.rows_fit(v_cache, new))


def update_kv_cache(cache, k_new, v_new, position_offset, entry=None):
    """Write ``k_new``/``v_new`` [B, L, Hkv, D] into the preallocated
    ``(k, v)`` cache pair at ``position_offset`` along the length axis
    (of entry ``entry`` where the pair's leaves stack several entries,
    ``[B, E, S, Hkv, D]``: a looped model's recurrent steps).

    ``position_offset`` may be a traced scalar (the single-token decode
    step passes the running position as a device int32, so ONE compiled
    program serves every position) or a traced ``[B]`` vector — the
    continuous-batching decode step, where every slot of the live batch
    sits at its own position (one per-row windowed write, still one
    program).

    Quantized caches (``kv_dtype="int8"``: each entry a ``(values,
    scales)`` pair, see :mod:`paddle_tpu.quantization`) quantize on
    write — new keys/values are reduced to int8 + per-head scale here,
    so the full-precision window never lands in the cache buffers."""
    from ..quantization import is_quantized_kv, kv_quantize

    k_cache, v_cache = cache
    pos = jnp.asarray(position_offset, jnp.int32)
    # tpu-lint: disable=R2(is_quantized_kv reads pytree STRUCTURE — tuple pair vs bare array — fixed at trace time, one program per cache layout)
    if is_quantized_kv(k_cache):
        kq, ks = kv_quantize(k_new)
        vq, vs = kv_quantize(v_new)
        return ((_write_window(k_cache[0], kq, pos, entry),
                 _write_window(k_cache[1], ks, pos, entry)),
                (_write_window(v_cache[0], vq, pos, entry),
                 _write_window(v_cache[1], vs, pos, entry)))
    # tpu-lint: disable=R2(the gate reads the backend and the leaves' static type, shape and dtype — one program per cache layout)
    if _rows_by_dma(k_cache, v_cache, k_new, pos):
        _note_write("dma")
        return cache_write.write_rows(k_cache, v_cache, k_new, v_new, pos,
                                      entry)
    return (_write_window(k_cache, k_new, pos, entry),
            _write_window(v_cache, v_new, pos, entry))


def cached_attention(q, k_cache, v_cache, position_offset, entry=None):
    """Dot-product attention of ``q`` [B, L, H, D] against the FULL cache
    [B, S, Hkv, D] (entry ``entry`` of ``[B, E, S, Hkv, D]`` leaves where
    given) with a position mask: query at absolute position
    ``position_offset + i`` sees keys at positions ``<= position_offset + i``
    only, so stale/unwritten cache slots beyond the current position never
    leak in. ``position_offset`` may be a scalar or a per-row ``[B]``
    vector (continuous-batching decode: each slot masks at its own
    position). GQA is a grouped einsum — the kv heads are never repeated
    into [B, S, H, D]. int8-quantized caches (``(values, scales)``
    entries) dequantize here, on read — the [B, S, Hkv, D] buffers stay
    int8 in HBM and only this program's working set pays the upcast."""
    from ..quantization import is_quantized_kv, kv_dequantize

    if entry is not None:
        k_cache, v_cache = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, entry, 1,
                                                   keepdims=False),
            (k_cache, v_cache))
    # tpu-lint: disable=R2(is_quantized_kv reads pytree STRUCTURE — tuple pair vs bare array — fixed at trace time, one program per cache layout)
    if is_quantized_kv(k_cache):
        k_cache = kv_dequantize(*k_cache, dtype=q.dtype)
        v_cache = kv_dequantize(*v_cache, dtype=q.dtype)
    B, L, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    groups = H // Hkv
    qg = q.reshape(B, L, Hkv, groups, D)
    s = jnp.einsum("blhgd,bshd->bhgls", qg, k_cache.astype(q.dtype))
    s = s * (1.0 / math.sqrt(D))
    # qpos [B|1, L]: scalar offsets broadcast over the batch, vector
    # offsets give every row its own mask frontier
    off = jnp.asarray(position_offset, jnp.int32).reshape(-1, 1)
    qpos = off + jnp.arange(L, dtype=jnp.int32)[None, :]
    allowed = (jnp.arange(S, dtype=jnp.int32)[None, None, :]
               <= qpos[:, :, None])                      # [B|1, L, S]
    s = jnp.where(allowed[:, None, None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgls,bshd->blhgd", p, v_cache.astype(q.dtype))
    return out.reshape(B, L, H, D)


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


def cached_lm_forward(backbone, logits_fn, input_ids, cache,
                      position_offset, gather_last):
    """The serving-side CausalLM forward shared by GPT and Llama: run the
    backbone (cache-threaded when given), optionally slice the hidden
    states to the single ``gather_last`` position BEFORE the head
    projection (so serving never materializes [B, L, vocab]), and return
    ``logits`` or ``(logits, new_cache)``."""
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
    ``recompute_policy``; ``block_cls(cfg)`` builds one block. With
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
        if caches is None:
            for blk in self._sub_layers.values():
                fn = (recompute_wrap(blk, policy=self.cfg.recompute_policy)
                      if self.cfg.use_recompute else blk)
                x = fn(x)
            return x
        new_caches = []
        kw = {} if cache_entry is None else {"cache_entry": cache_entry}
        for blk, cache in zip(self._sub_layers.values(), caches):
            x, cache = blk(x, cache=cache, position_offset=position_offset,
                           **kw)
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
