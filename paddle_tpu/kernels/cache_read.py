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
and running on across slots (:func:`_pipeline`, which all three bodies
share). The arithmetic follows where the TPU keeps a leaf, as
:mod:`.cache_write` does, and how many query rows share a key:

- A ``(k, v)`` pair (:func:`read_by_position`): a key head serves one
  query row a group, so the arithmetic is the VPU's (the MXU would spend
  its time loading keys as weights for one row).

  - ``D`` a multiple of the 128 lanes: row-major, a position is ``[Hkv,
    D]`` tiles. Scores reduce over the lanes; the running max, sum and
    accumulator are a few registers (:func:`_rows_kernel`).
  - ``D`` under 128 (heads of 64): ``S`` is on the lanes, ``[B, Hkv, D,
    S]`` in memory (the transposes around the call relabel that and move
    nothing). Scores reduce over the sublanes, and max, sum and
    accumulator stay apart lane by lane until the slot's last block, so
    no block pays a reduction over lanes (:func:`_columns_kernel`).

- A LATENT pair ``(c, k_r)`` (:func:`read_latent_by_position`): ONE key a
  position under every query head, ``c`` serving as the values too. A
  block's keys loaded as weights serve all ``H`` rows at once, so scores
  and weighted sum are matmuls on the MXU (bf16 operands, f32
  accumulation) and the VPU keeps the softmax; both use the one block of
  ``c`` in VMEM, which is read once. ``c`` is row-major (its width fills
  the lanes) and ``k_r`` lives with ``S`` on the lanes, which is
  ``k_r``'s block transposed as the scores' matmul wants it
  (:func:`_shared_key_kernel`). A position is 1152 B where a ``(k, v)``
  one is 4 to 8 KB, so its blocks are longer (``_LATENT_BLOCK``).

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

from .flash_attention import _dot      # bf16 operands, f32 result, on the MXU

__all__ = ["reads_fit", "read_by_position", "latent_reads_fit",
           "read_latent_by_position"]

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
#: positions a block of a latent pair: 1152 B a position, so a block of
#: 128 is a copy of 144 KB, too short to hide what a block costs besides
#: (its copies' issue and wait, three matmuls' start, the softmax's
#: reductions). What a slot reads past its frontier is half a block on
#: average. Chosen on the chip (``PERF.md`` section 6, PR 37): one call
#: for 32 slots at a serve cell's positions took 0.204, 0.126, 0.098 and
#: 0.107 ms at 128, 256, 512 and 1024; full rows read at 274, 500, 721
#: and 725 GB/s
_LATENT_BLOCK = 512
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


def latent_reads_fit(c_buf, kr_buf, q_c, q_r) -> bool:
    """Can :func:`read_latent_by_position` read the latent pair ``(c_buf
    [B, S, 1, rank], kr_buf [B, S, 1, rope])`` for ``q_c`` ``[B, L, H,
    rank]`` and ``q_r`` ``[B, L, H, rope]``? One query a slot; plain
    floating leaves of one dtype and one head; ``rank`` whole tiles of
    lanes (``c`` is row-major) and ``rope`` under a tile of them (``k_r``
    lives with ``S`` on the lanes) but whole sublanes of it, as the
    query heads are; whole blocks of positions; and queries, output and
    the blocks in flight within the VMEM the call may use."""
    if not all(isinstance(x, jax.Array) and x.ndim == 4
               for x in (c_buf, kr_buf, q_c, q_r)):
        return False
    if c_buf.dtype != kr_buf.dtype or c_buf.dtype not in (jnp.bfloat16,
                                                          jnp.float32):
        return False
    slots, s, _, rank = c_buf.shape
    heads, rope = q_r.shape[2:]
    if (c_buf.shape[2] != 1 or kr_buf.shape != (slots, s, 1, rope)
            or q_c.shape != (slots, 1, heads, rank) or q_r.shape[1] != 1):
        return False
    packed = 32 // c_buf.dtype.itemsize
    if (s % _LATENT_BLOCK or rank % _LANES or rope >= _LANES or rope % packed
            or heads % packed):
        return False
    in_vmem = (2 * slots * heads * (rank + _LANES)
               + _BUFFERS * _LATENT_BLOCK * (rank + rope)) * c_buf.dtype.itemsize
    return in_vmem <= _VMEM_LIMIT // 2


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


class _Leaf:
    """A leaf as :func:`_pipeline` indexes one, ``leaf.at[window(b, at)]``,
    for a body whose leaves keep ``S`` on different axes and whose blocks
    are ``block`` positions, a multiple of the pipeline's ``_BLOCK``: the
    pipeline counts the body's blocks as if they were its own (it is
    handed ``pos // block * _BLOCK`` for a slot's frontier), and
    ``index(b, at)`` is the leaf's own index of slot b's block ``at``, a
    ``pl.ds`` of ``block`` positions."""

    def __init__(self, ref, block, index):
        self.ref, self.block, self.index = ref, block, index

    at = property(lambda self: self)

    def __getitem__(self, window):
        b, at = window
        start = pl.multiple_of(at.start * (self.block // _BLOCK), self.block)
        return self.ref.at[self.index(b, pl.ds(start, self.block))]


def _shared_key_kernel(pos_ref, frontier_ref, scale_ref, q_ref, qx_ref, k_hbm,
                       kx_hbm, out_ref, k_buf, kx_buf, sems, m_ref, l_ref,
                       acc_ref):
    """ONE key a position under all ``H`` query rows. Leaves ``k`` ``[B, S,
    D]`` (row-major; its block is the values' too) and ``kx`` ``[B, Dx,
    S]`` (more of the key, ``S`` on the lanes); ``q`` and ``out`` ``[B, H,
    D]``, ``qx`` ``[B, H, Dx]``. A later caller with values of their own
    hands :func:`fold` their block where this one hands the keys'."""
    block = k_buf.shape[1]
    scale = scale_ref[0]

    def fold(s, v, live):
        """One block into the slot's softmax state: scores ``s`` ``[H,
        block]`` f32, values ``v`` ``[block, D]``; ``live`` ``[1, block]``
        masks a slot's last block, None elsewhere."""
        if live is not None:
            s = jnp.where(live, s, _NEG)
        m = m_ref[...]                                      # [H, 1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if live is not None:
            p = jnp.where(live, p, 0.0)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _dot(p, v, 1, 0)  # p as v is

    def step(b, i, slot):
        base = i * block
        k = k_buf[slot].astype(q_ref.dtype)                 # [block, D]
        kx = kx_buf[slot].astype(qx_ref.dtype)              # [Dx, block]
        s = (_dot(q_ref[b], k, 1, 1) + _dot(qx_ref[b], kx, 1, 0)) * scale

        def last():
            def at(shape, axis):
                return base + jax.lax.broadcasted_iota(jnp.int32, shape, axis)

            rows = at((block, 1), 0) <= pos_ref[b]
            fold(s, jnp.where(rows, k, jnp.zeros_like(k)),
                 at((1, block), 1) <= pos_ref[b])

        jax.lax.cond(base + block > pos_ref[b], last,
                     lambda: fold(s, k, None))

    def end(b):
        out_ref[b] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    _pipeline(frontier_ref,
              (_Leaf(k_hbm, block, lambda b, at: (b, at)),
               _Leaf(kx_hbm, block, lambda b, at: (b, slice(None), at))),
              (k_buf, kx_buf), sems, window=lambda b, at: (b, at),
              begin=lambda b: _reset(m_ref, l_ref, acc_ref), step=step,
              end=end)


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


@jax.jit       # a model's layers share one trace and one lowering of it
def read_latent_by_position(q_c, q_r, c_buf, kr_buf, pos, scale):
    """Attention in the latent space of ``q_c`` ``[B, 1, H, rank]`` and
    its rotated part ``q_r`` ``[B, 1, H, rope]``, slot b at position
    ``pos[b]``, over positions ``0 ... pos[b]`` of row b of a latent
    pair's leaves ``c_buf`` ``[B, S, 1, rank]`` (every head's keys and
    every head's values) and ``kr_buf`` ``[B, S, 1, rope]`` (the one
    rotated key they share):

        score = (q_c . c + q_r . k_r) * scale;  out = softmax(score) c

    ``[B, 1, H, rank]`` in ``q_c``'s dtype. The matmuls take their
    operands in the queries' dtype and accumulate in f32; mask, softmax
    and the accumulator are f32. Only the blocks that hold those
    positions are read, ``c``'s once; the leaves are not copied."""
    slots, s, _, rank = c_buf.shape
    heads, rope = q_r.shape[2:]
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, s - 1)
    # the leaves as the chip holds them: c [B, S, rank] row-major, k_r
    # [B, rope, S] with S on the lanes (relabelled: nothing moves)
    c_buf = c_buf.reshape(slots, s, rank)
    kr_buf = jnp.swapaxes(kr_buf.reshape(slots, s, rope), 1, 2)
    state = functools.partial(pltpu.VMEM, dtype=jnp.float32)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    leaf = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        _shared_key_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), whole, whole,
                      leaf, leaf],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, _LATENT_BLOCK, rank), c_buf.dtype),
                pltpu.VMEM((_BUFFERS, rope, _LATENT_BLOCK), kr_buf.dtype),
                pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                state((heads, 1)), state((heads, 1)), state((heads, rank))]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, rank), q_c.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="cache_read_latent_by_position",
    )(pos, pos // _LATENT_BLOCK * _BLOCK,
      jnp.asarray(scale, jnp.float32).reshape(1),
      q_c.reshape(slots, heads, rank), q_r.reshape(slots, heads, rope),
      c_buf, kr_buf)
    return out.reshape(slots, 1, heads, rank)
