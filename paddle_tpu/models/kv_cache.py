"""The KV cache of the decode engines: what it is, and every operation on it.

A PREALLOCATED pytree: a tuple of ``(k, v)`` pairs whose leaves are ``[B,
*stack, S, Hkv, D]``, rows leading, shapes fixed while decoding (only
positions advance); under ``kv_dtype="int8"`` each of ``k`` and ``v`` is a
``(int8 values, float32 scales [..., 1])`` pair. A LATENT entry (multi-head
latent attention: ``spec["latent"] = (rank, rope_dim)``) is the same pair
with other widths and one head: ``(c [B, S, 1, rank], k_r [B, S, 1,
rope_dim])``, the normed compressed vector that every head's keys AND
values are decompressed from and the one rotated key all heads share;
values are not stored. Two leaves and not one of ``rank + rope_dim``: 576
is 4.5 tiles of 128 lanes, and the pair keeps the pytree every function
here, ``BlockPool`` and ``serving.disagg`` index. Tests, ``serving.disagg``'s
wire format and ``DecoderBlockList`` index the tuple, so it stays a plain
pytree under free functions. The models reach them through
``lm_utils.attend_with_cache`` alone, the engines and the prefix pool
directly; this module imports nothing of theirs.

A STATE entry (a recurrent mixer: ``spec["state"] = (d_state, window,
d_inner)``, and ``spec["entry_kinds"]`` saying entry by entry which layers
hold one) is a pair too, with rows leading and NO length axis: ``(h [B,
d_state, d_inner] float32, window [B, window, d_inner])``, the scan state
and the convolution's last inputs, all a slot carries whatever its
position. The inner width lies on the lanes: ``[B, d_inner, d_state]``
would pad each 16 to a tile's 128. It sits in the same tuple beside the
``(k, v)`` pairs of the model's attention layers, so ``leaf[slot]`` is a
slot's cache for every leaf and the row copies serve both. No mask hides a
state: whoever starts a request in a row overwrites both leaves whole
(:func:`write_state` through a :class:`CacheRow`), and the block copies,
which need a length axis, are not for it (:func:`refuse_state_entries`).

The continuous-batching decode step (one token a slot, each slot at its
own position) has a kernel for each half, and this module is their one
importer: the write as direct copies (:mod:`..kernels.cache_write`, gate
:func:`_rows_by_dma`) and the read by position, over the blocks that a
slot's positions fill and no others (:mod:`..kernels.cache_read`, gate
:func:`_reads_by_position` for a ``(k, v)`` pair and
:func:`_latent_reads_by_position` for a latent one, whose write has no
kernel yet). A gate reads what the trace shows (backend, mesh, shapes,
dtypes); every other shape keeps XLA's path.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..distributed.mesh import get_mesh, sharding
from ..framework.dtype import convert_dtype
from ..kernels import cache_read, cache_write
from ..quantization import is_quantized_kv, kv_dequantize, kv_quantize

__all__ = ["cache_entries", "state_entries", "cache_layout",
           "cache_entry_kinds", "cache_entry_kind", "cache_entry_widths",
           "refuse_state_entries", "latent_attention", "cache_sharding_spec",
           "state_sharding_spec", "normalize_kv_dtype", "alloc_cache",
           "init_cache", "cache_nbytes", "cache_split_nbytes",
           "cache_token_nbytes", "cache_state_nbytes", "constrain_cache",
           "cache_geometry", "CacheRow", "cache_row_view",
           "cache_row_buffers", "update_kv_cache", "read_state",
           "write_state", "cache_paths", "cached_attention",
           "scatter_cache_rows", "gather_cache_blocks",
           "scatter_cache_blocks"]


# ------------------------------------------------- layout and allocation
def cache_entry_kinds(spec: dict) -> tuple:
    """What each pair of the cache's tuple holds, in order: ``"kv"``,
    ``"latent"`` or ``"state"``. ``spec["entry_kinds"]`` is a model's own
    pattern (a hybrid: one kind a layer); without it every pair is the
    spec's one positional kind."""
    kinds = spec.get("entry_kinds")
    if kinds is not None:
        return tuple(kinds)
    return ("latent" if spec.get("latent") else "kv",) * cache_layout(spec)[0]


def cache_entries(spec: dict) -> int:
    """How many entries of a model's ``cache_spec()`` are indexed by
    position: one per layer application that writes keys and values (or
    a latent pair). That is ``spec["cache_entries"]``; a spec without the
    key has one per layer, one with a pattern those the pattern names."""
    if spec.get("entry_kinds") is not None:
        return sum(k != "state" for k in spec["entry_kinds"])
    return int(spec.get("cache_entries", spec["num_layers"]))


def state_entries(spec: dict) -> int:
    """How many entries hold a recurrent state and no positions."""
    return sum(k == "state" for k in spec.get("entry_kinds") or ())


def cache_layout(spec: dict):
    """``(pairs, stack)`` of a model's ``cache_spec()``: the cache is a
    tuple of ``pairs`` pairs, and a ``(k, v)`` pair's leaves are ``[B,
    *stack, S, Hkv, D]``. ``spec["entry_stack"]`` of the
    :func:`cache_entries` share a leaf pair on an axis after the batch's
    (a looped model's recurrent steps; 1 and no axis when absent), so
    that a program can index them by a traced step. Rows lead whatever
    the stack: a slot's cache is ``leaf[slot]`` for every model. A spec
    with a pattern (:func:`cache_entry_kinds`) has one pair an entry of
    it and no stack."""
    stack = int(spec.get("entry_stack", 1))
    if spec.get("entry_kinds") is not None:
        if stack > 1:
            raise ValueError("entry_stack and entry_kinds do not combine")
        return len(spec["entry_kinds"]), ()
    entries = cache_entries(spec)
    if entries % stack:
        raise ValueError(f"cache_entries {entries} is no multiple of "
                         f"entry_stack {stack}")
    return entries // stack, ((stack,) if stack > 1 else ())


def cache_entry_kind(spec: dict, index: Optional[int] = None) -> str:
    """What entry ``index`` of the cache holds
    (:func:`cache_entry_kinds`) or, for the whole spec, ``"kv"`` (keys
    and values per head), ``"latent"`` (``spec["latent"]``) or, where
    state entries sit beside those, ``"kv+state"``."""
    kinds = cache_entry_kinds(spec)
    if index is not None:
        return kinds[index]
    by_position = {k for k in kinds if k != "state"}
    return "+".join(sorted(by_position) + ["state"] * ("state" in kinds))


def cache_entry_widths(spec: dict):
    """Last-axis widths of a positional entry's two leaves: ``head_dim``
    for keys and for values, or a latent entry's ``(rank, rope_dim)``."""
    if spec.get("latent"):
        rank, rope_dim = spec["latent"]
        return int(rank), int(rope_dim)
    return (int(spec["head_dim"]),) * 2


def refuse_state_entries(spec: dict, who: str, why: str) -> None:
    """Raise for a spec with state entries: ``who`` cannot serve it, for
    ``why`` (what it would have to learn first)."""
    states = state_entries(spec)
    if states:
        raise ValueError(
            f"{who} does not support a cache with recurrent-state entries "
            f"({states} of this model's {len(cache_entry_kinds(spec))}): "
            f"{why}")


def _mesh_axes(batch: int, width: int, mesh):
    """``(mesh, batch axes, "mp" or None)`` for a cache leaf of ``batch``
    rows and ``width`` things to split over mp; None where no axis of
    the mesh divides either (or there is no mesh)."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return None
    batch_axes = tuple(a for a in ("dp", "sdp") if a in mesh.shape)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    if bsz <= 1 or batch % bsz != 0:
        batch_axes = None
    mp = mesh.shape.get("mp", 1)
    split = "mp" if (mp > 1 and width % mp == 0) else None
    if batch_axes is None and split is None:
        return None
    return mesh, batch_axes or None, split


def cache_sharding_spec(batch: int, n_kv_heads: int, mesh=None, stack=0):
    """GSPMD sharding for one cache leaf [B, S, Hkv, D] (``stack``
    replicated axes of stacked entries after the batch's): batch over
    dp/sdp, kv heads over mp — matching the Column-parallel K/V
    projections, so tp decode reads/writes only local heads (no gathers).
    Axes that don't divide evenly stay replicated: a latent entry's one
    head always is, as the compressed vector is what every head reads."""
    axes = _mesh_axes(batch, n_kv_heads, mesh)
    if axes is None:
        return None
    mesh, batch_axes, head_axis = axes
    return sharding(batch_axes, *(None,) * stack, None, head_axis, None,
                    mesh=mesh)


def state_sharding_spec(batch: int, width: int, mesh=None):
    """GSPMD sharding for one state leaf ``[B, n, d_inner]``: batch over
    dp/sdp, the inner width over mp where it divides (the recurrence
    never mixes channels), else replicated."""
    axes = _mesh_axes(batch, width, mesh)
    if axes is None:
        return None
    mesh, batch_axes, channels = axes
    return sharding(batch_axes, None, channels, mesh=mesh)


def _leaf_sharding(leaf):
    """A cache leaf's sharding from its shape: a state leaf has three
    axes, every positional one (a quantized leaf's scales too) four and
    its stack."""
    if leaf.ndim == 3:
        return state_sharding_spec(leaf.shape[0], leaf.shape[-1])
    return cache_sharding_spec(leaf.shape[0], leaf.shape[-2],
                               stack=leaf.ndim - 4)


def normalize_kv_dtype(kv_dtype):
    """Canonicalize a ``kv_dtype`` knob: ``None``/``"none"`` -> None
    (full-precision cache, the PR 9-bit-identical default), ``"int8"`` ->
    ``"int8"``. Anything else is an error at construction time, not a
    silent full-precision fallback."""
    if kv_dtype is None or kv_dtype in ("none", "fp", "full"):
        return None
    if str(kv_dtype) == "int8":
        return "int8"
    raise ValueError(f"unsupported kv_dtype {kv_dtype!r}; expected None "
                     f"or 'int8'")


def alloc_cache(spec: dict, rows: int, length: int, dtype=None,
                kv_dtype=None, placed: bool = False):
    """The allocator: zeros for ``rows`` rows of ``length`` positions of
    a model's ``cache_spec()``, a tuple of pairs, one an entry
    (:func:`cache_entry_kinds`). A positional entry is ``(k, v)`` with
    leaves ``[rows, *stack, length, Hkv, D]`` of ``dtype`` (the spec's
    when None); a state entry ``(h [rows, d_state, d_inner] float32,
    window [rows, window, d_inner] dtype)``, whatever ``length``.
    ``kv_dtype="int8"`` makes each of ``k`` and ``v`` a ``(int8 values,
    float32 scales [..., Hkv, 1])`` pair (see
    :mod:`paddle_tpu.quantization`), roughly halving the footprint at
    head_dim 64+; the scale keeps the value leaf's rank, so every
    function here maps over both leaves alike. A latent entry
    (:func:`cache_entry_widths`) is refused with it: its one compressed
    vector stands for every head's keys and values, and a per-head scale
    has no head to belong to. So is a spec with state entries: the state
    is float32 by the recurrence's own need, and a cache quantized in
    two of its twenty-eight entries is not what the knob promises.
    ``placed``: each leaf is put in its GSPMD layout as it is made, where
    a mesh is installed (:func:`cache_sharding_spec`,
    :func:`state_sharding_spec`)."""
    dtype = convert_dtype(dtype or spec["dtype"])
    kinds = cache_entry_kinds(spec)
    quantized = normalize_kv_dtype(kv_dtype) == "int8"
    if quantized:
        if "latent" in kinds:
            raise ValueError(
                "kv_dtype='int8' is not supported with a latent cache entry "
                "(multi-head latent attention): the entry is already the "
                "compressed form; use kv_dtype=None")
        refuse_state_entries(
            spec, "kv_dtype='int8'",
            "the state is float32 and is not quantized; use kv_dtype=None")
    _, stack = cache_layout(spec)
    lead = (rows,) + stack + (length, spec["num_kv_heads"])

    def zeros(shape, dtype):
        z = jnp.zeros(shape, dtype)
        shd = _leaf_sharding(z) if placed else None
        return z if shd is None else jax.device_put(z, shd)

    def entry(width):
        if quantized:
            return (zeros(lead + (width,), jnp.int8),
                    zeros(lead + (1,), jnp.float32))
        return zeros(lead + (width,), dtype)

    def state():
        d_state, window, d_inner = spec["state"]
        return (zeros((rows, d_state, d_inner), jnp.float32),
                zeros((rows, window, d_inner), dtype))

    first, second = cache_entry_widths(spec)
    return tuple(state() if kind == "state" else (entry(first), entry(second))
                 for kind in kinds)


def init_cache(model, batch: int, max_length: Optional[int] = None,
               dtype=None, kv_dtype=None):
    """Preallocate the KV cache pytree for ``model``
    (:func:`alloc_cache` on its ``cache_spec()``), placed in its GSPMD
    layout when a mesh is installed: a scale leaf shares its value
    leaf's sharding spec (batch over dp/sdp, kv heads over mp)."""
    spec = model.cache_spec()
    return alloc_cache(spec, batch, int(max_length or spec["max_length"]),
                       dtype, kv_dtype, placed=True)


def cache_nbytes(cache) -> int:
    """Total bytes of a cache pytree, of arrays or of shapes (quantized
    scale leaves included): what the HBM-per-slot accounting asserts on."""
    return int(sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(cache)))


def cache_split_nbytes(spec: dict, cache):
    """``(positional, state)``: the bytes of ``cache``'s entries that are
    indexed by position, and of those that hold a state."""
    by_kind = [0, 0]
    for kind, pair in zip(cache_entry_kinds(spec), cache):
        by_kind[kind == "state"] += cache_nbytes(pair)
    return tuple(by_kind)


def _unit_nbytes(spec, dtype, kv_dtype):
    return cache_split_nbytes(spec, jax.eval_shape(
        lambda: alloc_cache(spec, 1, 1, dtype, kv_dtype)))


def cache_token_nbytes(spec: dict, dtype=None, kv_dtype=None) -> int:
    """Bytes a position of a row holds, from :func:`alloc_cache`'s shapes."""
    return _unit_nbytes(spec, dtype, kv_dtype)[0]


def cache_state_nbytes(spec: dict, dtype=None) -> int:
    """Bytes a row holds whatever its length: its state entries'. A row
    of ``length`` positions holds ``length * cache_token_nbytes +
    cache_state_nbytes``."""
    return _unit_nbytes(spec, dtype, None)[1]


def constrain_cache(cache):
    """with_sharding_constraint on every cache leaf (inside jit), so the
    compiled steps keep the cache resident in its sharded layout: each
    leaf by its own shape (:func:`_leaf_sharding`)."""
    if get_mesh() is None:
        return cache

    def constrain(x):
        shd = _leaf_sharding(x)
        return x if shd is None else jax.lax.with_sharding_constraint(x, shd)

    return jax.tree.map(constrain, cache)


def cache_geometry(spec: dict, max_length, prefill_buckets: Sequence[int],
                   who: str = "model's"):
    """``(max_length, prefill_buckets)`` of an engine over a model's
    ``cache_spec()``: the cache length (the spec's when None) and the
    buckets that fit it, sorted (the length itself where none does).
    A length past the position table is refused, by ``who``'s name: the
    tables slice with CLAMPED dynamic_slice, so positions past one would
    silently reuse its last row."""
    max_length = int(max_length or spec["max_length"])
    if max_length > spec["max_length"]:
        raise ValueError(
            f"max_length {max_length} exceeds the {who} position table "
            f"({spec['max_length']} positions)")
    buckets = tuple(sorted(int(b) for b in prefill_buckets
                           if int(b) <= max_length))
    return max_length, buckets or (max_length,)


# ----------------------------------------------------------------- write
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

    dtype = property(lambda self: self.buf.dtype)   # tells an int8 entry

    def tree_flatten(self):
        return (self.buf, self.row), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def cache_row_view(cache, row):
    """``cache`` with every leaf standing for its row ``row`` (a traced
    index) alone (:class:`CacheRow`): a batch-1 forward writes through."""
    return jax.tree.map(lambda x: CacheRow(x, row), cache)


def cache_row_buffers(view):
    """The live cache back out of a :func:`cache_row_view`."""
    return jax.tree.map(lambda r: r.buf, view,
                        is_leaf=lambda r: isinstance(r, CacheRow))


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
        _note("write", "scatter")

        def write(c, n, p):
            return jax.lax.dynamic_update_slice(
                c, n, stack + (p,) + (zero,) * (c.ndim - 1 - len(stack)))

        return jax.vmap(write)(buf, new, pos)
    start = (row,) + stack + (pos,) + (zero,) * (buf.ndim - 2 - len(stack))
    return jax.lax.dynamic_update_slice(buf, new, start)


# Trace-time state, thread-local as the adapter context of lora.layers
# is: the serving engine opens it around the trace of its decode program.
_PATHS = threading.local()


@contextlib.contextmanager
def cache_paths():
    """``{"write": set, "read": set}``: the ways the program traced under
    this context issues its per-slot cache writes, ``"dma"``
    (:mod:`..kernels.cache_write`) or ``"scatter"`` (the vmapped
    ``dynamic_update_slice``), and its cache reads, ``"kernel"``
    (:mod:`..kernels.cache_read`: a ``(k, v)`` pair's read by position or
    a latent pair's) or ``"xla"`` (the masked einsums over the whole
    leaf, :func:`_read_whole`'s or :func:`latent_attention`'s)."""
    outer = getattr(_PATHS, "noted", None)
    noted = _PATHS.noted = {"write": set(), "read": set()}
    try:
        yield noted
    finally:
        _PATHS.noted = outer


def _note(kind: str, path: str) -> None:
    noted = getattr(_PATHS, "noted", None)
    if noted is not None:
        noted[kind].add(path)


def _per_slot_on_one_tpu(pos) -> bool:
    """What every kernel's gate asks first: ``[B]`` positions (the
    continuous-batching decode step), a TPU, and no mesh over more than
    one device."""
    mesh = get_mesh()
    return (pos.ndim == 1 and jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1))


def _rows_by_dma(k_cache, v_cache, new, pos) -> bool:
    """One operation, two ways to issue it, told apart by what the trace
    shows: the kernel takes a TPU's per-slot (``[B]``-position) write of
    one token into plain leaves on one device whose rows are whole tiles
    (:func:`cache_write.rows_fit`); the scatter takes everything else."""
    return (_per_slot_on_one_tpu(pos) and cache_write.rows_fit(k_cache, new)
            and cache_write.rows_fit(v_cache, new))


@jax.named_scope("cache_write")
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

    A latent entry's pair goes through here too: ``k_new`` the compressed
    vectors ``[B, L, 1, rank]``, ``v_new`` the shared rotated keys ``[B,
    L, 1, rope_dim]``.

    Quantized caches (``kv_dtype="int8"``: each entry a ``(values,
    scales)`` pair, see :mod:`paddle_tpu.quantization`) quantize on
    write — new keys/values are reduced to int8 + per-head scale here,
    so the full-precision window never lands in the cache buffers."""
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
        _note("write", "dma")
        return cache_write.write_rows(k_cache, v_cache, k_new, v_new, pos,
                                      entry)
    return (_write_window(k_cache, k_new, pos, entry),
            _write_window(v_cache, v_new, pos, entry))


def read_state(cache):
    """``(h [B, d_state, d_inner] float32, window [B, window, d_inner])``
    of a state entry's pair; of its one row where the pair's leaves
    stand for a row (:class:`CacheRow`). A continuation reads it; a
    sequence that starts at position 0 does not, whatever the row held."""
    def whole(x):
        if isinstance(x, CacheRow):
            return jax.lax.dynamic_slice_in_dim(x.buf, x.row, 1, axis=0)
        return x

    return whole(cache[0]), whole(cache[1])


def write_state(cache, h, window):
    """The state entry's pair with ``h`` and ``window`` in it, WHOLE:
    every row of plain leaves (a decode step advances every slot at
    once, free ones too: nobody reads theirs before :func:`write_state`
    through a row view overwrites it) or the one row that
    :class:`CacheRow` leaves stand for (an admission: the row holds
    exactly the prompt's state afterwards, nothing of its last
    request's)."""
    def put(buf, new):
        if isinstance(buf, CacheRow):
            zero = jnp.zeros((), jnp.int32)
            return CacheRow(jax.lax.dynamic_update_slice(
                buf.buf, new.astype(buf.dtype), (buf.row, zero, zero)),
                buf.row)
        return new.astype(buf.dtype)

    return put(cache[0], h), put(cache[1], window)


# ------------------------------------------------------------------ read
def _reads_by_position(q, k_cache, v_cache, pos) -> bool:
    """One operation, two ways to issue it, told apart by what the trace
    shows, as :func:`_rows_by_dma` tells the write's: the kernel takes a
    TPU's per-slot (``[B]``-position) read for one query a slot from
    plain leaves on one device whose blocks are whole tiles
    (:func:`cache_read.reads_fit`); :func:`_read_whole` takes everything
    else."""
    return (_per_slot_on_one_tpu(pos) and cache_read.reads_fit(k_cache, q)
            and cache_read.reads_fit(v_cache, q))


@jax.named_scope("cache_read")
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
    int8 in HBM and only this program's working set pays the upcast.

    The continuous-batching decode step's shape (one query a slot, a
    ``[B]`` vector of positions, plain leaves on one TPU) reads only the
    blocks that positions ``0 ... position_offset[b]`` of slot b fill
    (:func:`_reads_by_position`), with f32 scores and softmax."""
    # tpu-lint: disable=R2(the gate reads the backend and the leaves' static type, shape and dtype — one program per cache layout)
    if _reads_by_position(q, k_cache, v_cache,
                          jnp.asarray(position_offset, jnp.int32)):
        _note("read", "kernel")
        return cache_read.read_by_position(q, k_cache, v_cache,
                                           position_offset, entry)
    _note("read", "xla")
    return _read_whole(q, k_cache, v_cache, position_offset, entry)


def _visible(position_offset, L: int, S: int):
    """The position mask ``[B|1, L, S]``: the query at ``position_offset
    + i`` sees positions up to its own. Scalar offsets broadcast over
    the batch, vector offsets give every row its own frontier."""
    off = jnp.asarray(position_offset, jnp.int32).reshape(-1, 1)
    qpos = off + jnp.arange(L, dtype=jnp.int32)[None, :]
    return (jnp.arange(S, dtype=jnp.int32)[None, None, :]
            <= qpos[:, :, None])


def _read_whole(q, k_cache, v_cache, position_offset, entry=None):
    """:func:`cached_attention` as XLA issues it: two einsums over every
    position of the leaf, under the mask."""
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
    allowed = _visible(position_offset, L, S)
    s = jnp.where(allowed[:, None, None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgls,bshd->blhgd", p, v_cache.astype(q.dtype))
    return out.reshape(B, L, H, D)


def _latent_reads_by_position(q_c, q_r, c_cache, kr_cache, pos) -> bool:
    """:func:`_reads_by_position` for a latent pair: the kernel takes a
    TPU's per-slot (``[B]``-position) read for one query a slot from
    plain leaves on one device that :func:`cache_read.latent_reads_fit`;
    the einsums over the whole leaf take everything else."""
    return (_per_slot_on_one_tpu(pos)
            and cache_read.latent_reads_fit(c_cache, kr_cache, q_c, q_r))


@jax.named_scope("cache_read")
def latent_attention(q_c, q_r, c_cache, kr_cache, position_offset, scale):
    """Attention IN THE LATENT SPACE against the full cache of a latent
    entry, for single-token decode and chunked continuation: ``q_c`` [B,
    L, H, rank] is the query with the keys' up-projection absorbed into
    it, ``q_r`` [B, L, H, rope_dim] its rotated part; ``c_cache`` [B, S,
    1, rank] serves as every head's keys and as every head's values,
    ``kr_cache`` [B, S, 1, rope_dim] is the one rotated key they share.

        score = (q_c . c + q_r . k_r) * scale;  out = softmax(score) c

    under :func:`cached_attention`'s position mask (scalar or per-row
    ``[B]`` offsets), float32 scores and softmax. Returns [B, L, H, rank];
    the caller applies the values' up-projection. No position is ever
    decompressed.

    The continuous-batching decode step's shape (one query a slot, a
    ``[B]`` vector of positions, plain leaves on one TPU) reads only the
    blocks that positions ``0 ... position_offset[b]`` of slot b fill,
    ``c``'s once for scores and weighted sum
    (:func:`_latent_reads_by_position`); every other shape takes XLA's
    einsums over every position of the leaf."""
    # tpu-lint: disable=R2(the gate reads the backend and the leaves' static type, shape and dtype — one program per cache layout)
    if _latent_reads_by_position(q_c, q_r, c_cache, kr_cache,
                                 jnp.asarray(position_offset, jnp.int32)):
        _note("read", "kernel")
        return cache_read.read_latent_by_position(
            q_c, q_r, c_cache, kr_cache, position_offset, scale)
    _note("read", "xla")
    return _latent_read_whole(q_c, q_r, c_cache, kr_cache, position_offset,
                              scale)


def _latent_read_whole(q_c, q_r, c_cache, kr_cache, position_offset, scale):
    """:func:`latent_attention` as XLA issues it: three einsums, two of
    them over every position of ``c``, under the mask."""
    c, kr = c_cache[:, :, 0], kr_cache[:, :, 0]            # [B, S, width]
    L, S = q_c.shape[1], c.shape[1]
    s = (jnp.einsum("blhc,bsc->bhls", q_c, c.astype(q_c.dtype),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("blhr,bsr->bhls", q_r, kr.astype(q_r.dtype),
                      preferred_element_type=jnp.float32)) * scale
    s = jnp.where(_visible(position_offset, L, S)[:, None], s,
                  jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1).astype(q_c.dtype)
    return jnp.einsum("bhls,bsc->blhc", p, c.astype(q_c.dtype))


# --------------------------------------------------- row and block copies
def scatter_cache_rows(cache, row_cache, index):
    """Write ``row_cache`` (``[r, S, Hkv, D]`` leaves) into ``cache``
    (``[B, ...]`` leaves) at batch row ``index`` (may be traced).

    This is the slot-scatter primitive of continuous batching: a freshly
    prefilled single-slot cache lands in the live B-slot decode batch
    without the batch's shape ever changing — same program for every slot
    index."""
    zero = jnp.zeros((), jnp.int32)
    idx = jnp.asarray(index, jnp.int32)

    def up(live, row):
        return jax.lax.dynamic_update_slice(
            live, row.astype(live.dtype),
            (idx,) + (zero,) * (live.ndim - 1))

    return jax.tree.map(up, cache, row_cache)


def gather_cache_blocks(pool, block_indices, length: int):
    """Assemble a cache row from a paged block pool: gather ``pool``
    leaves ``[N, bs, Hkv, D]`` at (possibly traced) ``block_indices``
    ``[n]`` and lay the blocks out contiguously as ``[1, length, Hkv,
    D]`` (zero-padded past ``n*bs``).

    The prefix-cache read primitive: matched prompt blocks land in a
    slot's cache rows in-program, so a cache hit never re-prefills the
    shared prefix. Indices past the matched chain point at the pool's
    reserved dump block (row 0) — those positions hold garbage, which is
    safe under the same invariant as slot reuse: the position mask never
    lets a query see beyond its request's frontier, and every position
    is rewritten before it first becomes visible."""
    idx = jnp.asarray(block_indices, jnp.int32)

    def assemble(leaf):
        n, bs = idx.shape[0], leaf.shape[-3]
        # [n, *stack, bs, Hkv, D] -> [*stack, n, bs, Hkv, D]
        blocks = jnp.moveaxis(jnp.take(leaf, idx, axis=0), 0, -4)
        flat = blocks.reshape(1, *leaf.shape[1:-3], n * bs, *leaf.shape[-2:])
        if n * bs < length:
            pad = [(0, 0)] * flat.ndim
            pad[-3] = (0, length - n * bs)
            flat = jnp.pad(flat, pad)
        return jax.lax.slice_in_dim(flat, 0, length, axis=flat.ndim - 3)

    return jax.tree.map(assemble, pool)


def scatter_cache_blocks(pool, row_cache, block_indices):
    """Write a cache row back into a paged block pool: split ``row_cache``
    leaves ``[1, S, Hkv, D]`` into ``n`` blocks of the pool's block size
    and scatter them at (possibly traced) ``block_indices`` ``[n]``.

    The prefix-cache store primitive (inverse of
    :func:`gather_cache_blocks`). Blocks the host chose not to cache
    point their index at the reserved dump row 0 — duplicate writes to
    the dump are harmless because its content is never read as valid."""
    idx = jnp.asarray(block_indices, jnp.int32)

    def store(leaf, row):
        n, bs = idx.shape[0], leaf.shape[-3]
        blocks = jax.lax.slice_in_dim(row[0], 0, n * bs, axis=row.ndim - 4)
        blocks = jnp.moveaxis(
            blocks.reshape(*leaf.shape[1:-3], n, bs, *leaf.shape[-2:]), -4, 0)
        return leaf.at[idx].set(blocks.astype(leaf.dtype))

    return jax.tree.map(store, pool, row_cache)
