"""The decode step's cache read by position (``kernels/cache_read.py``)
against XLA's read of the whole leaf under a mask
(``kv_cache.cached_attention``'s einsums, and ``latent_attention``'s for a
latent pair): the kernels in Pallas interpret mode, to the tolerance of an
f32 softmax; what lies past a slot's frontier kept out; the gate that
chooses between the two; and an engine decoding the same tokens either
way. On the CPU the programs themselves always take XLA's path."""
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



# ------------------------------------------------------- the latent pair
LATENT_BLOCK = cache_read._LATENT_BLOCK
LS = 2 * LATENT_BLOCK
#: the file's positions at the latent body's own block length
LATENT_POSITIONS = np.array([0, LATENT_BLOCK - 1, LATENT_BLOCK,
                             LATENT_BLOCK + 1, LS - 1, 77], np.int32)
#: (query heads, rank, rotated width, dtype): ``c`` [B, LS, 1, rank] is
#: row-major, ``k_r`` [B, LS, 1, rope] lives with S on the lanes
LATENT = {
    "rank128-bf16": (16, 128, 16, jnp.bfloat16),
    "rank256-rope64-bf16": (32, 256, 64, jnp.bfloat16),
    "rank128-f32": (8, 128, 8, jnp.float32),
    "rank256-rope64-f32": (16, 256, 64, jnp.float32),
}
SCALE = 0.11


def _latent_operands(case, seed=0, length=LS):
    heads, rank, rope, dtype = LATENT[case]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, 1, heads, rank), dtype),
            jax.random.normal(ks[1], (B, 1, heads, rope), dtype),
            jax.random.normal(ks[2], (B, length, 1, rank), dtype),
            jax.random.normal(ks[3], (B, length, 1, rope), dtype))


def _latent_xla(q_c, q_r, c, kr, pos):
    """``latent_attention``'s own einsum path, on f32 copies."""
    return kv_cache._latent_read_whole(
        *(x.astype(jnp.float32) for x in (q_c, q_r, c, kr)), pos, SCALE)


@pytest.mark.parametrize("case", list(LATENT))
def test_latent_kernel_agrees_with_xla(interpret_pallas, case):
    dtype = LATENT[case][-1]
    q_c, q_r, c, kr = _latent_operands(case)
    pos = jnp.asarray(LATENT_POSITIONS)
    assert cache_read.latent_reads_fit(c, kr, q_c, q_r)
    got = cache_read.read_latent_by_position(q_c, q_r, c, kr, pos, SCALE)
    _close(got, _latent_xla(q_c, q_r, c, kr, pos), dtype)
    assert interpret_pallas == ["_shared_key_kernel"]
    # position 0 attends to one position alone: every head reads its c
    np.testing.assert_allclose(
        np.asarray(got[0, 0], np.float32),
        np.broadcast_to(np.asarray(c[0, 0], np.float32), got.shape[2:]),
        atol=TOLERANCE[dtype], rtol=0)


def test_the_latent_kernels_scale_is_applied_as_given(interpret_pallas):
    """A traced scale (YaRN's, computed in a program) is the same call."""
    q_c, q_r, c, kr = _latent_operands("rank128-f32", seed=4)
    pos = jnp.asarray(LATENT_POSITIONS)
    for scale in (0.03, 0.3):
        got = jax.jit(cache_read.read_latent_by_position)(
            q_c, q_r, c, kr, pos, jnp.float32(scale))
        want = kv_cache._latent_read_whole(q_c, q_r, c, kr, pos, scale)
        _close(got, want, jnp.float32)
    assert interpret_pallas == ["_shared_key_kernel"]     # one trace


@pytest.mark.parametrize("stale", [np.nan, np.inf, -3e38, 3e38])
@pytest.mark.parametrize("dirty", ["c", "k_r", "both"])
@pytest.mark.parametrize("case", ["rank128-bf16", "rank128-f32"])
def test_what_lies_past_a_latent_frontier_never_reaches_the_output(
        interpret_pallas, case, dirty, stale):
    """``c`` is keys AND values: a stale row must stay out of the scores
    and out of the weighted sum, where a weight of zero would not keep a
    NaN out; ``k_r`` is keys alone."""
    dtype = LATENT[case][-1]
    q_c, q_r, c, kr = _latent_operands(case, seed=2)
    pos = jnp.asarray(LATENT_POSITIONS)

    def fill(x, value):
        past = _past_the_frontier(x.shape, LATENT_POSITIONS)
        return jnp.where(past, jnp.asarray(value, dtype), x)

    clean = fill(c, 0), fill(kr, 0)
    leaves = (fill(c, stale) if dirty != "k_r" else clean[0],
              fill(kr, stale) if dirty != "c" else clean[1])
    got = cache_read.read_latent_by_position(q_c, q_r, *leaves, pos, SCALE)
    _close(got, _latent_xla(q_c, q_r, *clean, pos), dtype)
    # and to the bit what the same call gives on clean rows
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(cache_read.read_latent_by_position(
            q_c, q_r, *clean, pos, SCALE), np.float32))


def test_latent_positions_past_the_leaf_read_all_of_it(interpret_pallas):
    q_c, q_r, c, kr = _latent_operands("rank128-bf16", seed=5)
    pos = jnp.asarray([3, LS, LS + 7, 2, 0, LS - 1], jnp.int32)
    _close(cache_read.read_latent_by_position(q_c, q_r, c, kr, pos, SCALE),
           _latent_xla(q_c, q_r, c, kr, pos), jnp.bfloat16)


def _latent_read(case):
    """``latent_attention`` on a latent pair, varied by ``case``; returns
    (result, the read paths it noted, the einsums' result alone)."""
    q_c, q_r, c, kr = _latent_operands("rank128-bf16", seed=7)
    pos = jnp.asarray(LATENT_POSITIONS)
    if case == "scalar-position":
        pos = jnp.int32(9)
    elif case == "two-tokens":
        q_c, q_r = (jnp.concatenate([x, x + 1], axis=1) for x in (q_c, q_r))
        pos = jnp.minimum(pos, LS - 2)
    elif case == "short-leaf":        # a block and a half
        c, kr = (x[:, :LATENT_BLOCK * 3 // 2] for x in (c, kr))
        pos = pos % c.shape[1]
    elif case == "ragged-rank":       # 192: a tile and a half of lanes
        q_c, c = (jnp.concatenate([x, x[..., :64]], -1) for x in (q_c, c))
    elif case == "wide-rope":         # 128 rotated: row-major, not S on lanes
        q_r, kr = (jnp.tile(x, (1, 1, 1, 8)) for x in (q_r, kr))
    elif case == "ragged-heads":      # 12 query heads: no whole bf16 tile
        q_c, q_r = (x[:, :, :12] for x in (q_c, q_r))
    elif case == "two-dtypes":
        kr = kr.astype(jnp.float32)
    elif case == "f32-query":         # the kernel takes any query dtype
        q_c, q_r = (x.astype(jnp.float32) for x in (q_c, q_r))
    with kv_cache.cache_paths() as paths:
        got = kv_cache.latent_attention(q_c, q_r, c, kr, pos, SCALE)
    assert paths["write"] == set()
    return got, paths["read"], kv_cache._latent_read_whole(q_c, q_r, c, kr,
                                                           pos, SCALE)


@pytest.mark.parametrize("case,path", [
    ("plain", "kernel"), ("f32-query", "kernel"), ("scalar-position", "xla"),
    ("two-tokens", "xla"), ("short-leaf", "xla"), ("ragged-rank", "xla"),
    ("wide-rope", "xla"), ("ragged-heads", "xla"), ("two-dtypes", "xla")])
def test_latent_gate_on_a_tpu(as_on_tpu, case, path):
    got, paths, want = _latent_read(case)
    assert paths == {path}
    assert bool(as_on_tpu) == (path == "kernel")      # a kernel was traced
    assert got.dtype == want.dtype and got.shape == want.shape
    if path == "xla":                 # letter for letter
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    else:                             # the einsums round the weights to bf16 too
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=0)


def test_latent_gate_on_the_cpu_keeps_xla(interpret_pallas):
    got, paths, want = _latent_read("plain")
    assert paths == {"xla"} and interpret_pallas == []
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("devices,path", [(2, "xla"), (1, "kernel")])
def test_latent_gate_under_a_mesh(as_on_tpu, devices, path):
    from paddle_tpu.distributed.mesh import init_mesh, set_mesh

    init_mesh(devices=jax.devices()[:devices], dp=devices)
    try:
        _, paths, _ = _latent_read("plain")
    finally:
        set_mesh(None)
    assert paths == {path} and bool(as_on_tpu) == (path == "kernel")


# ------------------------------------------------------------- the engine
def _decode(model, cfg, steps=5, max_length=128):
    eng = ContinuousBatchingEngine(model, slots=3, max_length=max_length,
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


def test_engine_with_a_latent_entry_decodes_the_same_tokens_on_either_path(
        show_the_gate_a_tpu, interpret_pallas):
    """Xing tiny at widths the latent body takes (a rank of 128, 8 query
    heads in f32, a cache of one block): one key a position under every
    head, YaRN's scale past 32 positions."""
    from paddle_tpu.models.xing import XingForCausalLM, xing_tiny

    pt.seed(3)
    cfg = xing_tiny(num_heads=8, kv_lora_rank=128,
                    max_position_embeddings=LATENT_BLOCK)
    model = XingForCausalLM(cfg)
    model.eval()
    plain, eng = _decode(model, cfg, max_length=LATENT_BLOCK)
    assert eng.cache_stats()["cache_read"] == "xla"
    assert eng.cache_stats()["cache_entry"] == "latent"
    assert interpret_pallas == []
    show_the_gate_a_tpu()
    direct, eng = _decode(model, cfg, max_length=LATENT_BLOCK)
    assert eng.cache_stats()["cache_read"] == "kernel"
    assert eng.cache_stats()["cache_write"] == "scatter"    # no kernel yet
    assert set(interpret_pallas) == {"_shared_key_kernel"}
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
