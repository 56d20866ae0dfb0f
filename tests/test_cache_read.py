"""The decode step's cache read by position (``kernels/cache_read.py``)
against XLA's read of the whole leaf under a mask
(``kv_cache.cached_attention``'s einsums): the kernels in Pallas interpret
mode, to the tolerance of an f32 softmax; what lies past a slot's frontier
kept out; the gate that chooses between the two; and an engine decoding
the same tokens either way. On the CPU the programs themselves always take
XLA's path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.kernels import cache_read
from paddle_tpu.models import kv_cache
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.quantization import kv_quantize
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.scheduler import Request

B, S = 6, 256
BLOCK = 128
#: every slot at a position of its own: a free slot's 0, a block's last
#: position, the next block's first and second, the leaf's last, and one
#: in the middle of a block
POSITIONS = np.array([0, BLOCK - 1, BLOCK, BLOCK + 1, S - 1, 77], np.int32)
# heads of 64 live with S on the lanes (the columns kernel), heads of 128
# row-major (the rows kernel); gpt_tiny's heads of 32 in f32
LEAVES = {
    "heads64-bf16": ((B, S, 4, 64), jnp.bfloat16, "_columns_kernel"),
    "heads128-bf16": ((B, S, 16, 128), jnp.bfloat16, "_rows_kernel"),
    "heads64-f32": ((B, S, 2, 64), jnp.float32, "_columns_kernel"),
    "heads128-f32": ((B, S, 8, 128), jnp.float32, "_rows_kernel"),
    "heads32-f32": ((B, S, 4, 32), jnp.float32, "_columns_kernel"),
    "stacked64-bf16": ((B, 3, S, 2, 64), jnp.bfloat16, "_columns_kernel"),
    "stacked128-bf16": ((B, 3, S, 16, 128), jnp.bfloat16, "_rows_kernel"),
}
#: against an f32 reference: the output's own rounding (2**-8 of values up
#: to 4 in bf16), and the order of an f32 sum
TOLERANCE = {jnp.bfloat16: 2e-2, jnp.float32: 2e-5}


def _operands(shape, dtype, groups=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[2], (shape[0], 1, shape[-2] * groups,
                                  shape[-1]), dtype)
    return (q, jax.random.normal(ks[0], shape, dtype),
            jax.random.normal(ks[1], shape, dtype))


def _xla(q, k, v, pos, entry=None):
    """``cached_attention``'s own einsum path, on f32 copies."""
    return kv_cache._read_whole(
        *(x.astype(jnp.float32) for x in (q, k, v)), pos, entry)


def _past_the_frontier(shape, pos):
    """True at every ``[b, (e,) s]`` with ``s > pos[b]``, shaped to
    broadcast against the leaf."""
    past = np.arange(shape[-3])[None, :] > np.asarray(pos)[:, None]
    past = past.reshape((shape[0],) + (1,) * (len(shape) - 4)
                        + (shape[-3], 1, 1))
    return jnp.asarray(np.broadcast_to(past, shape))


def _close(got, want, dtype):
    assert got.dtype == dtype and got.shape == want.shape
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=TOLERANCE[dtype],
                               rtol=0)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("leaf", list(LEAVES))
def test_kernel_agrees_with_xla(interpret_pallas, leaf, groups):
    shape, dtype, kernel = LEAVES[leaf]
    q, k, v = _operands(shape, dtype, groups)
    pos = jnp.asarray(POSITIONS)
    entry = jnp.int32(shape[1] - 2) if len(shape) == 5 else None
    # a fresh function a case: each traces its own kernel
    got = jax.jit(lambda *a: cache_read.read_by_position(*a))(
        q, k, v, pos, entry)
    _close(got, _xla(q, k, v, pos, entry), dtype)
    assert interpret_pallas == [kernel]
    # every slot's answer is its own: position 0 attends to one key alone
    first = v[:, entry] if entry is not None else v
    np.testing.assert_allclose(
        np.asarray(got[0, 0], np.float32).reshape(-1, groups, shape[-1]),
        np.repeat(np.asarray(first[0, 0], np.float32)[:, None], groups, 1),
        atol=TOLERANCE[dtype], rtol=0)


@pytest.mark.parametrize("stale", [np.nan, np.inf, -3e38, 3e38])
@pytest.mark.parametrize("leaf", ["heads64-bf16", "heads128-bf16",
                                  "heads32-f32", "stacked128-bf16"])
def test_what_lies_past_a_frontier_never_reaches_the_output(
        interpret_pallas, leaf, stale):
    """A freed slot's rows stay as its last request left them, and a
    prefix-pool row brings whatever the dump block held: NaN, infinity or
    huge values past ``pos[b]``, in keys and in values, change nothing."""
    shape, dtype, _ = LEAVES[leaf]
    q, k, v = _operands(shape, dtype, seed=2)
    pos = jnp.asarray(POSITIONS)
    entry = jnp.int32(1) if len(shape) == 5 else None
    past = _past_the_frontier(shape, POSITIONS)
    dirty_k, dirty_v = (jnp.where(past, jnp.asarray(stale, dtype), x)
                        for x in (k, v))
    clean_k, clean_v = (jnp.where(past, jnp.zeros((), dtype), x)
                        for x in (k, v))
    got = cache_read.read_by_position(q, dirty_k, dirty_v, pos, entry)
    _close(got, _xla(q, clean_k, clean_v, pos, entry), dtype)
    # and to the bit what the same call gives on clean rows
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(cache_read.read_by_position(q, clean_k, clean_v, pos,
                                               entry), np.float32))


@pytest.mark.parametrize("leaf", ["stacked64-bf16", "stacked128-bf16"])
def test_kernel_under_scan_with_a_traced_entry(interpret_pallas, leaf):
    """A looped model's recurrent steps: every step reads its own entry
    of the stacked leaves, each slot one position on, and its output is
    the next step's query."""
    shape, dtype, _ = LEAVES[leaf]
    q, k, v = _operands(shape, dtype, seed=3)
    pos = jnp.asarray(np.minimum(POSITIONS, S - shape[1]))

    def run(read):
        def step(q, t):
            out = read(q, k, v, pos + t, t)
            return out.astype(q.dtype), out

        return jax.lax.scan(step, q, jnp.arange(shape[1], dtype=jnp.int32))[1]

    got = jax.jit(lambda: run(cache_read.read_by_position))()
    want = jax.jit(lambda: run(
        lambda q, *a: _xla(q, *a).astype(dtype)))()
    # three steps feed each other: three roundings of the query
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3 * TOLERANCE[dtype], rtol=0)
    assert got.shape == (shape[1],) + q.shape


def test_positions_past_the_leaf_read_all_of_it(interpret_pallas):
    """A position past the leaf's end is clamped to its last, where the
    masked einsum reads every position too."""
    shape, dtype, _ = LEAVES["heads128-bf16"]
    q, k, v = _operands(shape, dtype, seed=5)
    pos = jnp.asarray([3, S, S + 7, 2, 0, S - 1], jnp.int32)
    _close(cache_read.read_by_position(q, k, v, pos),
           _xla(q, k, v, pos), dtype)


# --------------------------------------------------------------- the gate
def _read(case):
    """``cached_attention`` on a Medium-shaped pair, varied by ``case``;
    returns (result, the read paths it noted, XLA's path alone)."""
    shape, dtype, _ = LEAVES["heads64-bf16"]
    q, k, v = _operands(shape, dtype, seed=7)
    pos = jnp.asarray(POSITIONS)
    if case == "scalar-position":
        pos = jnp.int32(9)
    elif case == "two-tokens":
        q = jnp.concatenate([q, q + 1], axis=1)
        pos = jnp.minimum(pos, S - 2)
    elif case == "ragged-rows":       # 12 heads of 128 in bf16: no whole tiles
        q, k, v = (jnp.concatenate([x, x], -1) for x in (q, k, v))
        q, k, v = (jnp.concatenate([x, x, x], -2) for x in (q, k, v))
    elif case == "short-leaf":        # 192 positions: a block and a half
        k, v = k[:, :192], v[:, :192]
        pos = pos % 192
    elif case == "int8-pair":
        k, v = kv_quantize(k), kv_quantize(v)
    elif case == "f32-query":         # the kernel takes any query dtype
        q = q.astype(jnp.float32)
    with kv_cache.cache_paths() as paths:
        got = kv_cache.cached_attention(q, k, v, pos)
    assert paths["write"] == set()
    return got, paths["read"], kv_cache._read_whole(q, k, v, pos)


@pytest.mark.parametrize("case,path", [
    ("plain", "kernel"), ("f32-query", "kernel"), ("scalar-position", "xla"),
    ("two-tokens", "xla"), ("ragged-rows", "xla"), ("short-leaf", "xla"),
    ("int8-pair", "xla")])
def test_gate_on_a_tpu(as_on_tpu, case, path):
    got, paths, want = _read(case)
    assert paths == {path}
    assert bool(as_on_tpu) == (path == "kernel")      # a kernel was traced
    assert got.dtype == want.dtype and got.shape == want.shape
    if path == "xla":                 # letter for letter
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    else:                             # XLA's scores are bf16 for a bf16 query
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=0)


def test_gate_on_the_cpu_keeps_xla(interpret_pallas):
    got, paths, want = _read("plain")
    assert paths == {"xla"} and interpret_pallas == []
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_gate_keeps_xla_under_a_mesh(as_on_tpu):
    from paddle_tpu.distributed.mesh import init_mesh, set_mesh

    init_mesh(devices=jax.devices()[:2], dp=2)
    try:
        _, paths, _ = _read("plain")
    finally:
        set_mesh(None)
    assert paths == {"xla"} and as_on_tpu == []


def test_gate_takes_a_mesh_of_one(as_on_tpu):
    from paddle_tpu.distributed.mesh import init_mesh, set_mesh

    init_mesh(devices=jax.devices()[:1], dp=1)
    try:
        _, paths, _ = _read("plain")
    finally:
        set_mesh(None)
    assert paths == {"kernel"}


# ------------------------------------------------------------- the engine
def _decode(model, cfg, steps=5):
    eng = ContinuousBatchingEngine(model, slots=3, max_length=128,
                                   prefill_buckets=(32,))
    assert eng.cache_stats()["cache_read"] is None       # nothing traced
    rng = np.random.default_rng(4)
    toks = []
    for slot, n in enumerate((5, 17, 30)):
        prompt = rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
        first, _, _ = eng.admit(
            Request(prompt=prompt, max_new_tokens=steps + 1, greedy=True,
                    seed=0), slot)
        toks.append([first])
    for _ in range(steps):
        for ev in eng.step():
            toks[ev.slot].append(ev.token)
    return toks, eng


def test_engine_with_grouped_heads_decodes_the_same_tokens_on_either_path(
        show_the_gate_a_tpu, interpret_pallas):
    """Llama tiny: 4 query heads on 2 KV heads of 32, rotary positions."""
    pt.seed(3)
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()
    plain, eng = _decode(model, cfg)
    assert eng.cache_stats()["cache_read"] == "xla"
    assert interpret_pallas == []
    show_the_gate_a_tpu()
    direct, eng = _decode(model, cfg)
    assert eng.cache_stats()["cache_read"] == "kernel"
    assert "_columns_kernel" in interpret_pallas
    assert direct == plain
    assert all(len(t) == 6 for t in direct)


def test_a_scalar_position_engine_keeps_xla(as_on_tpu):
    """``generate()`` decodes a closed batch at one scalar position: XLA's
    path, kernels or no."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(3)
    model = GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                    attention_dropout_prob=0.0,
                                    use_flash_attention=False))
    model.eval()
    with kv_cache.cache_paths() as paths:
        model.generate(np.arange(1, 9, dtype=np.int32)[None],
                       max_new_tokens=3, max_length=128)
    assert paths["read"] == {"xla"} and as_on_tpu == []
