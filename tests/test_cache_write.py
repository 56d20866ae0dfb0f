"""The decode step's per-slot cache write (``kernels/cache_write.py``)
against the scatter it stands in for (``kv_cache._write``): the kernels in
Pallas interpret mode, bit for bit over the whole leaf; the gate that
chooses between the two; and engines decoding the same tokens either way
(with the read's kernel, ``tests/test_cache_read.py``, beside the
write's). On the CPU the programs themselves always take the scatter."""
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.kernels import cache_write
from paddle_tpu.models import kv_cache, lm_utils
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny
from paddle_tpu.quantization import kv_quantize
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.scheduler import Request

B = 5
# the three cells' row geometries (Medium's heads of 64 take the merge,
# XL's and the looped decoder's heads of 128 the copies), a stacked leaf
# with heads of 64, and a tiny model's f32 leaf
LEAVES = {
    "medium": ((B, 256, 16, 64), jnp.bfloat16),
    "xl": ((B, 24, 16, 128), jnp.bfloat16),
    "looped": ((B, 3, 24, 16, 128), jnp.bfloat16),
    "looped64": ((B, 3, 128, 16, 64), jnp.bfloat16),
    "xl-f32": ((B, 24, 8, 128), jnp.float32),
    "tiny-f32": ((B, 128, 4, 16), jnp.float32),
}


def _positions(which, s):
    return {"first": np.zeros(B), "last": np.full(B, s - 1),
            "mixed": np.array([0, s - 1, s // 2, 129 % s, 1])}[which].astype(
                np.int32)


def _operands(shape, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    row = (shape[0], 1) + shape[-2:]
    return (jax.random.normal(ks[0], shape, dtype),
            jax.random.normal(ks[1], shape, dtype),
            jax.random.normal(ks[2], row, dtype),
            jax.random.normal(ks[3], row, dtype))


def _scatter(k, v, nk, nv, pos, entry):
    zero = jnp.zeros((), jnp.int32)
    return (kv_cache._write(k, nk, pos, entry, zero),
            kv_cache._write(v, nv, pos, entry, zero))


def _same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("which", ["first", "last", "mixed"])
@pytest.mark.parametrize("leaf", list(LEAVES))
def test_kernel_equals_scatter_bit_for_bit(interpret_pallas, leaf, which):
    shape, dtype = LEAVES[leaf]
    k, v, nk, nv = _operands(shape, dtype)
    pos = jnp.asarray(_positions(which, shape[-3]))
    entry = jnp.int32(shape[1] - 2) if len(shape) == 5 else None
    # a fresh function a case: each traces its own kernel
    got = jax.jit(lambda *a: cache_write.write_rows(*a))(k, v, nk, nv, pos,
                                                         entry)
    want = _scatter(k, v, nk, nv, pos, entry)
    _same(got, want)
    # k and v are not each other's, and exactly B rows of each changed
    assert not np.array_equal(np.asarray(got[0], np.float32),
                              np.asarray(got[1], np.float32))
    for new, old in zip(got, (k, v)):
        changed = np.asarray(new != old).any(axis=(-1, -2))
        assert changed.sum() == B
    merge = shape[-1] % 128 != 0
    assert interpret_pallas == [
        "_merge_columns_kernel" if merge else "_copy_rows_kernel"]


@pytest.mark.parametrize("leaf", ["looped", "looped64"])
def test_kernel_under_scan_with_a_traced_entry(interpret_pallas, leaf):
    """A looped model's recurrent steps: every step writes its own entry
    of the stacked leaves, each slot one position on."""
    shape, dtype = LEAVES[leaf]
    k, v, nk, nv = _operands(shape, dtype, seed=3)
    pos = jnp.asarray(_positions("mixed", shape[-3] - shape[1]))

    def run(write):
        def step(carry, t):
            k, v = carry
            scale = (1 + t).astype(dtype)
            return write(k, v, nk * scale, nv - scale, pos + t, t), None

        (k2, v2), _ = jax.lax.scan(step, (k, v),
                                   jnp.arange(shape[1], dtype=jnp.int32))
        return k2, v2

    _same(jax.jit(lambda: run(cache_write.write_rows))(),
          jax.jit(lambda: run(_scatter))())


def test_positions_past_the_leaf_are_clamped_as_the_scatter_clamps(
        interpret_pallas):
    shape, dtype = LEAVES["xl"]
    k, v, nk, nv = _operands(shape, dtype, seed=5)
    pos = jnp.asarray([3, shape[1], shape[1] + 7, 2, 0], jnp.int32)
    _same(cache_write.write_rows(k, v, nk, nv, pos),
          _scatter(k, v, nk, nv, pos, None))


# --------------------------------------------------------------- the gate
def _update(case):
    """``update_kv_cache`` on a Medium-shaped pair, varied by ``case``;
    returns (result, the paths it noted, what the scatter alone gives)."""
    shape, dtype = LEAVES["medium"]
    k, v, nk, nv = _operands(shape, dtype, seed=7)
    pos = jnp.asarray(_positions("mixed", shape[1]))
    if case == "scalar-position":
        pos = jnp.int32(9)
    elif case == "two-tokens":
        nk, nv = (jnp.concatenate([x, x + 1], axis=1) for x in (nk, nv))
    elif case == "ragged-rows":       # 12 heads of bf16: not whole tiles
        k, v, nk, nv = (x[:, :, :12] for x in (k, v, nk, nv))
        k, v, nk, nv = (jnp.concatenate([x, x], -1) for x in (k, v, nk, nv))
    elif case == "short-leaf":        # 64 positions: not a lane tile
        k, v = k[:, :64], v[:, :64]
        pos = pos % 64
    elif case == "int8-pair":
        k, v = kv_quantize(k), kv_quantize(v)
    elif case == "cache-row":
        k, v = (kv_cache.CacheRow(x, jnp.int32(2)) for x in (k, v))
        nk, nv, pos = nk[:1], nv[:1], jnp.int32(9)
    with kv_cache.cache_paths() as paths:
        got = kv_cache.update_kv_cache((k, v), nk, nv, pos)
    with mock.patch.object(kv_cache, "_rows_by_dma", lambda *a: False):
        want = kv_cache.update_kv_cache((k, v), nk, nv, pos)
    assert paths["read"] == set()
    return got, paths["write"], want


@pytest.mark.parametrize("case,path", [
    ("plain", {"dma"}), ("scalar-position", set()), ("two-tokens", {"scatter"}),
    ("ragged-rows", {"scatter"}), ("short-leaf", {"scatter"}),
    ("int8-pair", {"scatter"}), ("cache-row", set())])
def test_gate_on_a_tpu(as_on_tpu, case, path):
    got, paths, want = _update(case)
    assert paths == path
    assert bool(as_on_tpu) == (path == {"dma"})      # a kernel was traced
    _same(jax.tree.leaves(got), jax.tree.leaves(want))


def test_gate_on_the_cpu_keeps_the_scatter(interpret_pallas):
    got, paths, want = _update("plain")
    assert paths == {"scatter"} and interpret_pallas == []
    _same(got, want)


def test_gate_keeps_the_scatter_under_a_mesh(as_on_tpu):
    from paddle_tpu.distributed.mesh import init_mesh, set_mesh

    init_mesh(devices=jax.devices()[:2], dp=2)
    try:
        _, paths, _ = _update("plain")
    finally:
        set_mesh(None)
    assert paths == {"scatter"} and as_on_tpu == []


# ------------------------------------------- compiled for the chip, not run
@pytest.mark.parametrize("cell,shape", [
    ("gpt3-medium.serve-chat", (48, 2048, 16, 64)),
    ("gpt3-xl.serve-batch", (24, 2048, 16, 128)),
    ("ouro-2.6b.serve-longgen", (5, 4, 1024, 16, 128))])
def test_a_layers_write_and_read_compile_for_the_chip_in_place(
        one_chip, show_the_gate_a_tpu, cell, shape):
    """The cell's cache pair through ``attend_with_cache`` as the decode
    program runs it (donated; the stacked leaves under a ``scan`` over
    their entries): the write's kernel and the read's are in the program,
    the scatter's ``while`` is not, and no copy of a leaf is (the leaves
    alias their outputs and the program needs no temporary the size of
    one, nor of one entry of a stacked leaf)."""
    show_the_gate_a_tpu()
    slots, (heads, dim) = shape[0], shape[-2:]
    stacked = len(shape) == 5

    def layer(k, v, q, nk, nv, pos, entry):
        out, (k, v) = lm_utils.attend_with_cache(
            q, nk, nv, (k, v), pos, use_flash=False, entry=entry)
        return k, v, out

    def program(k, v, q, nk, nv, pos):
        if not stacked:
            return layer(k, v, q, nk, nv, pos, None)

        def step(carry, t):
            return layer(*carry, nk, nv, pos, t), None

        return jax.lax.scan(step, (k, v, q),
                            jnp.arange(shape[1], dtype=jnp.int32))[0]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    row = arg((slots, 1, heads, dim))
    with kv_cache.cache_paths() as paths:
        compiled = jax.jit(program, donate_argnums=(0, 1)).lower(
            arg(shape), arg(shape), row, row, row,
            arg((slots,), jnp.int32)).compile()
    assert paths == {"write": {"dma"}, "read": {"kernel"}}
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "cache_write_rows" in text and "cache_read_by_position" in text
    assert text.count(" while(") == int(stacked)        # the scan alone
    memory = compiled.memory_analysis()
    leaf_bytes = 2 * int(np.prod(shape))
    assert memory.alias_size_in_bytes == 2 * leaf_bytes
    assert memory.temp_size_in_bytes < leaf_bytes // 64


def test_a_latent_layers_write_and_read_compile_for_the_chip_in_place(
        one_chip, show_the_gate_a_tpu):
    """``xing4.0-29b-a4b.serve-reason``'s cache pair, ``(c [32, 8192, 1,
    512], k_r [32, 8192, 1, 64])``, through ``attend_with_latent_cache``
    as the decode program runs it: one head is no whole tile, so the
    write keeps the scatter (a ``while`` a leaf); the read is the latent
    body's kernel, handed each leaf as the chip keeps it (``c`` row-major,
    ``k_r`` with S on the lanes: bitcasts, no copy or transpose of a
    leaf); the leaves alias their outputs, and a leaf with one head is
    held without padding: the program's arguments are the leaves' own
    bytes."""
    show_the_gate_a_tpu()
    slots, length, heads, rank, rope, nope = 32, 8192, 32, 512, 64, 128

    def layer(c, kr, q_nope, q_rope, c_new, kr_new, w_uk, w_uv, pos):
        out, (c, kr) = lm_utils.attend_with_latent_cache(
            q_nope, q_rope, c_new, kr_new, w_uk, w_uv, (c, kr), pos, 0.1)
        return c, kr, out

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaves = [(slots, length, 1, rank), (slots, length, 1, rope)]
    with kv_cache.cache_paths() as paths:
        compiled = jax.jit(layer, donate_argnums=(0, 1)).lower(
            arg(leaves[0]), arg(leaves[1]), arg((slots, 1, heads, nope)),
            arg((slots, 1, heads, rope)), arg((slots, 1, rank)),
            arg((slots, 1, 1, rope)), arg((rank, heads, nope)),
            arg((rank, heads, nope)), arg((slots,), jnp.int32)).compile()
    assert paths == {"write": {"scatter"}, "read": {"kernel"}}
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "cache_read_latent_by_position" in text
    assert text.count(" while(") == 2
    # a leaf reaches the kernel relabelled, never moved: the only
    # instructions that yield something of a leaf's size are the entry's
    # parameters, the scatters' loops and bitcasts
    moved = [line.split(" = ")[0].strip() for line in text.splitlines()
             if re.search(r" = bf16\[32,(8192,(1,)?(512|64)|64,8192)\]", line)
             and not re.search(r" (parameter|bitcast|while|get-tuple-element|"
                               r"dynamic-update-slice)\(", line)]
    assert moved == []
    memory = compiled.memory_analysis()
    leaf_bytes = sum(2 * int(np.prod(shape)) for shape in leaves)
    assert leaf_bytes == 32 * 8192 * 1152
    assert memory.alias_size_in_bytes == leaf_bytes
    # the leaves, two 4 MB up-projections and the step's rows
    assert memory.argument_size_in_bytes < leaf_bytes * 1.05
    # the queries and the output, a megabyte each: no scores over 8192
    # positions, not a leaf
    assert memory.temp_size_in_bytes < leaf_bytes // 256


@pytest.mark.parametrize("rows", [32, 2048])
def test_the_expert_ffn_compiles_for_the_chip_as_grouped_matmuls(
        one_chip, rows):
    """64 experts of 3584 x 1024 in bfloat16, top 4, for a decode step's
    32 tokens and a prefill's 2048: the three ``ragged_dot`` are Mosaic
    kernels of the compiler's own (a bf16 operand left at the package's
    float32 matmul precision is refused there: "Bad lhs type"), and the
    program holds no temporary the size of the experts."""
    from paddle_tpu.nn.layer import functional_call, param_state
    from paddle_tpu.nn.layers.expert_ffn import ExpertFFN

    holder = {}

    def build():
        holder["layer"] = ExpertFFN(3584, 1024, 64, 4, shared_width=1024,
                                    routed_scaling_factor=2.0,
                                    dtype="bfloat16")
        return param_state(holder["layer"])

    shapes = jax.eval_shape(build)
    pt.seed(0)      # the abstract build left tracers in the generator
    layer = holder["layer"]

    def arg(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda p, x: functional_call(layer, p, {}, x)[0]).lower(
        jax.tree.map(arg, shapes),
        jax.ShapeDtypeStruct((rows, 3584), jnp.bfloat16,
                             sharding=one_chip)).compile()
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot-none"') >= 3
    expert_bytes = 3 * 64 * 3584 * 1024 * 2
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > expert_bytes
    # the gathered rows (58 MB of 8192 picks at 2048 tokens) and what
    # follows them, never a copy of the experts
    assert memory.temp_size_in_bytes < expert_bytes // 4


# ------------------------------------------------------------- the engine
def _tiny_gpt():
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False)          # f32 [B, 128, 4, 32]
    return GPTForCausalLM(cfg), cfg, {"_merge_columns_kernel",
                                      "_columns_kernel"}


def _tiny_looped():
    # f32 [B, 2, 128, 8, 128]: the copies want 8 heads of 128
    cfg = ouro_tiny(hidden_size=1024, num_heads=8, num_layers=1,
                    intermediate_size=128, total_ut_steps=2)
    return OuroForCausalLM(cfg), cfg, {"_copy_rows_kernel", "_rows_kernel"}


def _decode(model, cfg, steps=5):
    eng = ContinuousBatchingEngine(model, slots=3, max_length=128,
                                   prefill_buckets=(32,))
    assert eng.cache_stats()["cache_write"] is None      # nothing traced
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


@pytest.mark.parametrize("build", [_tiny_gpt, _tiny_looped])
def test_engine_decodes_the_same_tokens_on_either_path(
        build, show_the_gate_a_tpu, interpret_pallas):
    pt.seed(3)
    model, cfg, kernels = build()
    model.eval()
    plain, eng = _decode(model, cfg)
    assert eng.cache_stats()["cache_write"] == "scatter"
    assert eng.cache_stats()["cache_read"] == "xla"
    assert interpret_pallas == []
    show_the_gate_a_tpu()
    direct, eng = _decode(model, cfg)
    assert eng.cache_stats()["cache_write"] == "dma"
    assert eng.cache_stats()["cache_read"] == "kernel"
    assert set(interpret_pallas) == kernels
    assert direct == plain
    assert all(len(t) == 6 for t in direct)


def test_statusz_carries_the_path():
    from paddle_tpu.serving import InferenceServer

    pt.seed(3)
    model, cfg, _ = _tiny_gpt()
    model.eval()
    with InferenceServer(model, slots=2, max_length=64,
                         prefill_buckets=(32,)) as srv:
        srv.submit(np.arange(1, 9, dtype=np.int32),
                   max_new_tokens=3).result(timeout=240)
        stats = srv.statusz()["snapshot"]["compile_stats"]
    assert stats["cache_write"] == "scatter" and stats["cache_read"] == "xla"
