"""The continuous-batching decode step's cache read, by position.

Every decode step attends one query a slot over that slot's keys and
values, each slot at its own position. XLA reads the whole ``[B, S, Hkv,
D]`` leaf under a mask, at its roofline for what it reads: every position
of every slot, live or free (two thirds of a GPT-3 step at 48 slots of
2048). This kernel brings in only the blocks of positions ``0 ... pos[b]``
of slot b and runs an online softmax over them in f32.

One body for all slots (no grid: a grid step costs about as much as a
small block's copy): a loop over the slots and, inside it, over the slot's
live blocks, the copies ``_BUFFERS - 1`` blocks ahead of the arithmetic
and running on across slots. The arithmetic is the VPU's (one query row:
the MXU would spend its time loading keys as weights), and follows where
the TPU keeps a leaf, as :mod:`.cache_write` does:

- ``D`` a multiple of the 128 lanes: row-major, a position is ``[Hkv, D]``
  tiles. Scores reduce over the lanes; the running max, sum and
  accumulator are a few registers (:func:`_rows_kernel`).
- ``D`` under 128 (heads of 64): ``S`` is on the lanes, ``[B, Hkv, D, S]``
  in memory (the transposes around the call relabel that and move
  nothing). Scores reduce over the sublanes, and max, sum and accumulator
  stay apart lane by lane until the slot's last block, so no block pays a
  reduction over lanes (:func:`_columns_kernel`).

A slot's last block is masked at ``position <= pos[b]`` (keys and values
both: what lies past a slot's frontier is stale and may be anything).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["reads_fit", "read_by_position"]

#: positions a block, one tile of lanes where S is on the lanes: what a
#: slot reads past its frontier is half of one on average, and a block's
#: copy (256 KB a leaf at 16 heads of 64, 512 KB at 16 of 128) is long
#: enough to hide the next one's issue
_BLOCK = _LANES = 128
#: blocks of each leaf in VMEM: one under the arithmetic, the rest in flight
_BUFFERS = 4
#: positions the row-major arithmetic takes at a time
_CHUNK = 16
#: the widest query row ``H * D``: the heads-of-64 body is unrolled over
#: the heads, and the blocks in VMEM (2 to 8 MB at this width) leave the
#: limit below room
_MAX_ROW = 4096
_VMEM_LIMIT = 64 * 1024 * 1024
#: stands for minus infinity where a difference of two must stay a number
_NEG = -1e30


def reads_fit(buf, q) -> bool:
    """Can :func:`read_by_position` read the leaf ``buf`` for ``q`` ``[B,
    L, H, D]``? One query a slot, whole groups of query heads, and a plain
    floating leaf whose blocks are whole tiles: 8 sublanes of 32 bits (16
    rows of bf16) by 128 lanes, counted over ``[Hkv, D]`` where ``D`` fills
    the lanes and over ``[D, S]`` where it does not."""
    if not isinstance(buf, jax.Array) or q.shape[1] != 1:
        return False
    if buf.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    packed = 32 // buf.dtype.itemsize
    s, hkv, d = buf.shape[-3:]
    h = q.shape[2]
    if q.shape[-1] != d or h % hkv or h * d > _MAX_ROW or s % _BLOCK:
        return False
    return (hkv if d % _LANES == 0 else d) % packed == 0


def _pipeline(pos_ref, leaves, buffers, sems, window, begin, step, end):
    """The loop both kernels share: for every slot b, ``begin(b)``, then
    ``step(b, i, slot)`` for each live block i with that block of every
    leaf in ``buffers[...][slot]``, then ``end(b)``. ``window(b, at)``
    indexes the block of slot b that starts at position ``at`` in a leaf.
    Copies run ``_BUFFERS - 1`` blocks ahead of ``step``, across slots."""
    slots = pos_ref.shape[0]
    depth = buffers[0].shape[0]

    def blocks(b):
        return pos_ref[jnp.minimum(b, slots - 1)] // _BLOCK + 1

    def copies(b, i, slot):
        at = pl.ds(pl.multiple_of(i * _BLOCK, _BLOCK), _BLOCK)
        return [pltpu.make_async_copy(leaf.at[window(b, at)], buf.at[slot],
                                      sems.at[n, slot])
                for n, (leaf, buf) in enumerate(zip(leaves, buffers))]

    def start(b, i, item):
        @pl.when(b < slots)
        def _():
            for copy in copies(b, i, item % depth):
                copy.start()

    def after(b, i):
        last = i + 1 >= blocks(b)
        return jnp.where(last, b + 1, b), jnp.where(last, 0, i + 1)

    ahead = (jnp.int32(0), jnp.int32(0))
    for item in range(depth - 1):
        start(*ahead, item)
        ahead = after(*ahead)

    def slot_body(b, carry):
        begin(b)

        def block_body(i, carry):
            ahead_b, ahead_i, item = carry
            slot = item % depth
            for copy in copies(b, i, slot):
                copy.wait()
            # into the buffer the block before this one has left
            start(ahead_b, ahead_i, item + depth - 1)
            step(b, i, slot)
            return (*after(ahead_b, ahead_i), item + 1)

        carry = jax.lax.fori_loop(0, blocks(b), block_body, carry)
        end(b)
        return carry

    jax.lax.fori_loop(0, slots, slot_body, (*ahead, jnp.int32(0)))


def _reset(m_ref, l_ref, acc_ref):
    """A slot's softmax state before its first block."""
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _rows_kernel(pos_ref, entry_ref, q_ref, k_hbm, v_hbm, out_ref, k_buf,
                 v_buf, sems, m_ref, l_ref, acc_ref, *, scale):
    """Leaves ``[B, E, S, Hkv, D]``, ``q`` and ``out`` ``[B, G, Hkv, D]``
    (G the query heads a KV head serves)."""
    groups = q_ref.shape[1]
    entry = entry_ref[0]

    def chunk(b, slot, base, c, q, state, masked):
        m, l, acc = state
        rows = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
        k = k_buf[slot, rows].astype(jnp.float32)          # [C, Hkv, D]
        v = v_buf[slot, rows].astype(jnp.float32)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)   # [C, Hkv, 1]
        if masked:
            at = base + c * _CHUNK + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            live = at <= pos_ref[b]
            s = jnp.where(live, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=0))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[None])
        if masked:
            p = jnp.where(live, p, 0.0)
            v = jnp.where(live, v, 0.0)
        l = alpha * l + jnp.sum(p, axis=0)
        acc = alpha * acc + jnp.sum(p * v, axis=0)
        return m_new, l, acc

    def step(b, i, slot):
        base = i * _BLOCK
        last = base + _BLOCK > pos_ref[b]

        def run(masked):
            # a slot's last block stops at the chunk its frontier is in
            chunks = (jnp.minimum(pos_ref[b] - base, _BLOCK - 1) // _CHUNK + 1
                      if masked else _BLOCK // _CHUNK)
            for g in range(groups):
                q = q_ref[b, g].astype(jnp.float32) * scale
                state = (m_ref[g], l_ref[g], acc_ref[g])
                state = jax.lax.fori_loop(
                    0, chunks,
                    lambda c, state: chunk(b, slot, base, c, q, state, masked),
                    state, unroll=not masked)
                m_ref[g], l_ref[g], acc_ref[g] = state

        jax.lax.cond(last, lambda: run(True), lambda: run(False))

    def end(b):
        out_ref[b] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    _pipeline(pos_ref, (k_hbm, v_hbm), (k_buf, v_buf), sems,
              window=lambda b, at: (b, entry, at),
              begin=lambda b: _reset(m_ref, l_ref, acc_ref), step=step,
              end=end)


def _columns_kernel(pos_ref, entry_ref, q_ref, k_hbm, v_hbm, out_ref, k_buf,
                    v_buf, sems, q_wide, m_ref, l_ref, acc_ref, *, scale):
    """Leaves ``[B, E, Hkv, D, S]``, ``q`` and ``out`` ``[B, G, D, Hkv]``:
    a head's query and output are columns, ``D`` on the sublanes as the
    leaf has it."""
    _, hkv, d, _ = k_buf.shape
    groups = q_ref.shape[1]
    entry = entry_ref[0]

    def begin(b):
        for g in range(groups):
            q = q_ref[b, g].astype(jnp.float32) * scale    # [D, Hkv]
            for h in range(hkv):
                q_wide[g, h] = jnp.broadcast_to(q[:, h:h + 1], (d, _LANES))
        _reset(m_ref, l_ref, acc_ref)

    def head(slot, g, h, live):
        """Head h of the block in ``slot``: a block is one tile of lanes.
        ``live`` ``[1, 128]`` masks a slot's last block, None elsewhere."""
        k = k_buf[slot, h].astype(jnp.float32)             # [D, 128]
        v = v_buf[slot, h].astype(jnp.float32)
        s = jnp.sum(k * q_wide[g, h], axis=0, keepdims=True)    # [1, 128]
        if live is not None:
            s = jnp.where(live, s, _NEG)
        m = m_ref[g, h]
        m_new = jnp.maximum(m, s)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if live is not None:
            p = jnp.where(live, p, 0.0)
            v = jnp.where(live, v, 0.0)
        m_ref[g, h] = m_new
        l_ref[g, h] = alpha * l_ref[g, h] + p
        acc_ref[g, h] = alpha * acc_ref[g, h] + p * v

    def step(b, i, slot):
        at = i * _BLOCK + jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        last = (i + 1) * _BLOCK > pos_ref[b]

        def run(live):
            for g in range(groups):
                for h in range(hkv):
                    head(slot, g, h, live)

        jax.lax.cond(last, lambda: run(at <= pos_ref[b]), lambda: run(None))

    def end(b):
        # lane by lane until here: bring the lanes to one max, then sum
        for g in range(groups):
            m = m_ref[g]                                   # [Hkv, 1, 128]
            weight = jnp.exp(m - jnp.max(m, axis=-1, keepdims=True))
            total = jnp.sum(l_ref[g] * weight, axis=-1, keepdims=True)
            out = jnp.sum(acc_ref[g] * weight, axis=-1,
                          keepdims=True) / total           # [Hkv, D, 1]
            for h in range(hkv):
                out_ref[b, g, :, h:h + 1] = out[h].astype(out_ref.dtype)

    _pipeline(pos_ref, (k_hbm, v_hbm), (k_buf, v_buf), sems,
              window=lambda b, at: (b, entry, slice(None), slice(None), at),
              begin=begin, step=step, end=end)


@jax.jit       # a model's layers share one trace and one lowering of it
def read_by_position(q, k_buf, v_buf, pos, entry=None):
    """Attention of ``q`` ``[B, 1, H, D]``, slot b at position ``pos[b]``,
    over positions ``0 ... pos[b]`` of row b of the leaves ``[B, S, Hkv,
    D]`` (of their entry ``entry``, a traced scalar, where they are ``[B,
    E, S, Hkv, D]``): ``[B, 1, H, D]`` in ``q``'s dtype. Scores, softmax
    and the weighted sum are f32. Only the blocks that hold those
    positions are read; the leaves are not copied."""
    if entry is None:           # one entry a row: [B, 1, S, Hkv, D]
        k_buf, v_buf = (x[:, None] for x in (k_buf, v_buf))
        entry = 0
    slots, entries, s, hkv, d = k_buf.shape
    groups = q.shape[2] // hkv
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, s - 1)
    entry = jnp.clip(jnp.asarray(entry, jnp.int32), 0, entries - 1)
    rows = d % _LANES == 0
    # [B, G, Hkv, D]: query head h * G + g is row (g, h)
    q = jnp.swapaxes(q.reshape(slots, hkv, groups, d), 1, 2)
    state = functools.partial(pltpu.VMEM, dtype=jnp.float32)
    if rows:
        kernel = _rows_kernel
        buffer = pltpu.VMEM((_BUFFERS, _BLOCK, hkv, d), k_buf.dtype)
        scratch = [state((groups, hkv, 1)), state((groups, hkv, 1)),
                   state((groups, hkv, d))]
    else:
        kernel = _columns_kernel
        # [B, E, Hkv, D, S]: the leaves as the chip holds them
        k_buf, v_buf = (jnp.moveaxis(x, 2, -1) for x in (k_buf, v_buf))
        q = jnp.swapaxes(q, 2, 3)
        buffer = pltpu.VMEM((_BUFFERS, hkv, d, _BLOCK), k_buf.dtype)
        scratch = [state((groups, hkv, d, _LANES)),
                   state((groups, hkv, 1, _LANES)),
                   state((groups, hkv, 1, _LANES)),
                   state((groups, hkv, d, _LANES))]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    leaf = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(kernel, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(),
            in_specs=[whole, leaf, leaf], out_specs=whole,
            scratch_shapes=[buffer, buffer,
                            pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                            *scratch]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="cache_read_by_position",
    )(pos, entry.reshape(1), q, k_buf, v_buf)
    if not rows:
        out = jnp.swapaxes(out, 2, 3)
    return jnp.swapaxes(out, 1, 2).reshape(slots, 1, hkv * groups, d)
