"""The continuous-batching decode step's cache write, tile by tile.

Every decode step lands one new key and one new value per live slot in
that slot's cache row, each slot at its own position. XLA runs the
vmapped ``dynamic_update_slice`` of ``lm_utils._write`` as a ``while`` of
one small update a slot and leaf (16 of a 33.7 ms step on GPT-3 Medium at
48 slots: 2.4 MB moved). What a kernel can do instead depends on where
the TPU keeps a leaf ``[B, S, Hkv, D]``:

- ``D`` a multiple of the 128 lanes: row-major, a position's ``[Hkv, D]``
  is whole tiles in one piece. One async copy a slot and leaf, HBM to
  HBM, all in flight at once (:func:`_copy_rows_kernel`).
- ``D`` under 128 (heads of 64): the compiler gives such a shape the
  layout with ``S`` on the lanes, so a position is one lane of ``Hkv * D /
  16`` tiles. The tiles around it, ``[Hkv, D, 128 positions]``, pass
  through VMEM, take the new lane and go back, a slot a grid step, the
  copies pipelined (:func:`_merge_columns_kernel`). The transposes around
  the call relabel that layout and move nothing.

Either way the leaves are aliased input to output, so a donated cache is
updated where it lies and every other byte of it stays as it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rows_fit", "write_rows"]

_LANES = 128
#: the merge kernel holds eight blocks of [Hkv * D, 128] in VMEM (two
#: leaves, in and out, double-buffered): 2 MB at 16 heads of 64
_MERGE_MAX_ROW = 4096


def rows_fit(buf, new) -> bool:
    """Can :func:`write_rows` land ``new`` ``[B, L, Hkv, D]`` in the leaf
    ``buf``? One token a slot and a plain floating array whose row is
    whole tiles: 8 sublanes of 32 bits (16 rows of bf16) by 128 lanes,
    counted over ``[Hkv, D]`` for the copies and over ``[D, S]`` for the
    merge."""
    if not isinstance(buf, jax.Array) or new.shape[1] != 1:
        return False
    if buf.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    packed = 32 // buf.dtype.itemsize
    s, hkv, d = buf.shape[-3:]
    if d % _LANES == 0:
        return hkv % packed == 0
    return (d % packed == 0 and s % _LANES == 0
            and hkv * d <= _MERGE_MAX_ROW)


def _copy_rows_kernel(pos_ref, entry_ref, k_new, v_new, k_in, v_in, k_out,
                      v_out, sems):
    del k_in, v_in              # aliased to the outputs: the same buffers
    slots = k_new.shape[0]

    def row_copies(b):
        where = (b, entry_ref[0], pl.ds(pos_ref[b], 1))
        return [pltpu.make_async_copy(new.at[b], out.at[where], sems.at[i])
                for i, (new, out) in enumerate(((k_new, k_out),
                                                (v_new, v_out)))]

    @pl.loop(0, slots)
    def _(b):
        for copy in row_copies(b):
            copy.start()

    # one semaphore a leaf counts that leaf's copies: equal sizes, so
    # waiting for `slots` of them is waiting for all
    @pl.loop(0, slots)
    def _(b):
        for copy in row_copies(b):
            copy.wait()


def _merge_columns_kernel(pos_ref, entry_ref, k_new, v_new, k_in, v_in,
                          k_out, v_out):
    del entry_ref               # read by the index map
    b = pl.program_id(0)
    hkv, d, lanes = k_out.shape
    # slot b's column of new [Hkv * D, slots], on every lane: the product
    # with a one-hot has one term, so it is exact
    hot = (jax.lax.broadcasted_iota(jnp.int32, (k_new.shape[1], lanes), 0)
           == b)
    here = (jax.lax.broadcasted_iota(jnp.int32, (hkv, d, lanes), 2)
            == pos_ref[b] % lanes)
    # bf16 states its single pass (the process default asks Mosaic for an
    # fp32 contraction, which it refuses for bf16); f32 takes all six
    precision = (jax.lax.Precision.DEFAULT if k_new.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    for new, src, dst in ((k_new, k_in, k_out), (v_new, v_in, v_out)):
        column = jnp.dot(new[...], hot.astype(new.dtype), precision=precision,
                         preferred_element_type=jnp.float32)
        column = column.astype(dst.dtype).reshape(hkv, d, lanes)
        dst[...] = jnp.where(here, column, src[...])


def write_rows(k_buf, v_buf, k_new, v_new, pos, entry=None):
    """``(k_buf, v_buf)`` with row b of ``k_new`` / ``v_new`` ``[B, 1,
    Hkv, D]`` at ``(b, pos[b])`` of leaves ``[B, S, Hkv, D]``, or at ``(b,
    entry, pos[b])`` of leaves ``[B, E, S, Hkv, D]`` (``entry`` a traced
    scalar). Every other element is the input's, in the input's buffer
    where the caller donated it. A position (or ``entry``) past the leaf's
    end is clamped to its last, as ``dynamic_update_slice`` clamps it: a
    copy on its own would land outside the leaf."""
    shape = k_buf.shape
    if entry is None:           # one entry a row: [B, 1, S, Hkv, D]
        k_buf, v_buf = (x[:, None] for x in (k_buf, v_buf))
        entry = 0
    slots, entries, s, hkv, d = k_buf.shape
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, s - 1)
    entry = jnp.clip(jnp.asarray(entry, jnp.int32), 0, entries - 1)
    k_new, v_new = k_new.astype(k_buf.dtype), v_new.astype(v_buf.dtype)
    if d % _LANES == 0:
        kernel = _copy_rows_kernel
        leaf = columns = pl.BlockSpec(memory_space=pl.ANY)
        grid, scratch = (), [pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = _merge_columns_kernel
        # [B, E, Hkv, D, S]: the leaves as the chip holds them
        k_buf, v_buf = (jnp.moveaxis(x, 2, -1) for x in (k_buf, v_buf))
        leaf = pl.BlockSpec(
            (None, None, hkv, d, _LANES),
            lambda b, pos_ref, entry_ref: (b, entry_ref[0], 0, 0,
                                           pos_ref[b] // _LANES))
        padded = -(-slots // _LANES) * _LANES
        columns = pl.BlockSpec((hkv * d, padded), lambda b, *_: (0, 0))
        k_new, v_new = (
            jnp.pad(x.reshape(slots, hkv * d).T,
                    ((0, 0), (0, padded - slots))) for x in (k_new, v_new))
        grid, scratch = (slots,), []
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[columns, columns, leaf, leaf], out_specs=[leaf, leaf],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (k_buf, v_buf)],
        # operands count from the scalar-prefetch pair: 4, 5 are the leaves
        input_output_aliases={4: 0, 5: 1},
        name="cache_write_rows",
    )(pos, entry.reshape(1), k_new, v_new, k_buf, v_buf)
    if kernel is _merge_columns_kernel:
        out = [jnp.moveaxis(x, -1, 2) for x in out]
    return tuple(x.reshape(shape) for x in out)
