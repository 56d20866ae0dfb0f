"""The hybrid state-space / attention decoder (``models/jamba.py``, the state
entry of ``models/kv_cache.py``, ``lm_utils.scan_with_state``) against its
plain reference (``benchmarks/harness/reference_hybrid_ssm.py``), on the
CPU in float32 at a tiny size with both mixer kinds: four layers,
attention at index 1, inner width 64, 8 states, step rank 8; norm gains,
``A_log`` and ``D`` off their initial values, so that one left out or
misplaced shows.

Tolerances. Both sides are float32 at full matmul precision and differ by
summation order alone, a few 1e-7 of a logit: logits are held to ``TOL`` =
1e-4 of their standard deviation, a hundred times that noise and far under
what bfloat16 arithmetic gives (``test_bfloat16_arithmetic_fails_the_
tolerance`` holds that to be so). States are held to ``TOL`` of the
reference state's own spread. What must be EXACT is compared exactly: what
a bucket's pads hold changes not one bit of the state, the window or the
next-token logits (the pads' delta is 0: ``exp(0) = 1``, ``0 * u * B =
0``), and a served stream is token for token ``generate()``'s.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp
from paddle_tpu.framework.jit import param_state
from paddle_tpu.models import kv_cache
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.jamba import (JambaConfig, JambaForCausalLM,
                                     jamba_tiny)
from paddle_tpu.models.kv_cache import init_cache
from paddle_tpu.models.speculative import SpeculativeEngine
from paddle_tpu.serving import InferenceServer
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.prefix_cache import BlockPool
from paddle_tpu.serving.scheduler import Request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from harness import reference_hybrid_ssm as reference  # noqa: E402

TOL = 1e-4          # of the spread of what is compared; see the docstring
BUCKETS = (8, 16, 32)
GEO = dict(max_length=64, prefill_buckets=BUCKETS)
MAMBA_LAYERS = (0, 2, 3)        # of the tiny preset; 1 is attention


def _ref_cfg(cfg: JambaConfig) -> dict:
    """The configuration block as a benchmark file would hold it."""
    return dataclasses.asdict(cfg)


def _perturb(model, seed=5):
    rng = np.random.default_rng(seed)
    state = {}
    for name, p in model.named_parameters():
        if name.endswith("layernorm.weight"):
            state[name] = 1.0 + 0.3 * rng.standard_normal(p.shape)
        elif name.endswith((".A_log", ".D")):
            state[name] = np.asarray(p) + 0.3 * rng.standard_normal(p.shape)
    model.set_state_dict(dict(model.state_dict(), **{
        k: np.asarray(v, np.float32) for k, v in state.items()}))
    return len(state)


@pytest.fixture(scope="module")
def lm():
    pt.seed(11)
    # 0.2 and not the published 0.02: at hidden 32 a tied head over a
    # residual stream that is mostly the token's own embedding copies its
    # input; the branches must outweigh it for a state to show in a token
    cfg = jamba_tiny(initializer_range=0.2)
    model = JambaForCausalLM(cfg)
    model.eval()
    # a Mamba block has 2 + 3 gains, A_log and D; the attention block 2
    # gains; and the final norm
    assert _perturb(model) == 3 * 7 + 2 + 1
    return model, cfg


@pytest.fixture(scope="module")
def run(lm):
    """The model's entry shapes, compiled once each shape."""
    model, _ = lm

    def padded(c, x, last):
        return model(x, cache=c, position_offset=0, gather_last=last)

    return {"full": jax.jit(lambda ids: model(ids)),
            "prefill": jax.jit(
                lambda c, x: model(x, cache=c, position_offset=0)),
            "padded": jax.jit(padded),
            "at": jax.jit(
                lambda c, x, off: model(x, cache=c, position_offset=off))}


def _ids(cfg, shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape).astype(np.int32)


def _ref(model, cfg, ids, states=None):
    return reference.logits(param_state(model), _ref_cfg(cfg), ids, states)


def _close(ours, ref, what=""):
    err = np.abs(np.asarray(ours) - ref).max() / ref.std()
    assert err < TOL, f"{what}: {err:.2e} of the spread"


def _states_close(cache, ref_states, row, n, what=""):
    """Row ``row`` of the cache's state entries against the reference's
    ``(h_L, u')`` per Mamba layer after ``n`` positions."""
    for layer, (h_ref, u_ref) in zip(MAMBA_LAYERS, ref_states):
        h, window = (np.asarray(x[row]) for x in cache[layer])
        _close(h, h_ref, f"{what} layer {layer} h")
        want = np.zeros_like(window)
        k = min(n, window.shape[0])
        want[window.shape[0] - k:] = u_ref[n - k:n]
        _close(window, want, f"{what} layer {layer} window")


# ------------------------------------------------------------- the model
def test_layer_pattern_and_parameter_count():
    cfg = JambaConfig()
    kinds = ["kv" if cfg.is_attention_layer(i) else "state"
             for i in range(cfg.num_layers)]
    assert [i for i, k in enumerate(kinds) if k == "kv"] == [7, 21]
    # the published sizes by shape alone (nothing is allocated)
    shapes = jax.eval_shape(
        lambda: param_state(JambaForCausalLM(JambaConfig())))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == \
        3_029_337_472
    mixer = {k: v for k, v in shapes.items() if ".layers.0.mamba." in k}
    assert sum(int(np.prod(s.shape)) for s in mixer.values()) == 41_241_792
    assert shapes["model.layers.0.mamba.A_log"].shape == (16, 5120)
    assert not any(".layers.7.mamba." in k or ".layers.0.self_attn." in k
                   for k in shapes)
    assert "lm_head.weight" not in shapes           # tied


@pytest.mark.parametrize("what,bad", [
    ("num_experts", dict(num_experts=2)),
    ("mamba_conv_bias", dict(mamba_conv_bias=False)),
    ("rope_theta", dict(rope_theta=10000.0))])
def test_config_refuses_what_is_not_built(what, bad):
    with pytest.raises(ValueError, match=what):
        jamba_tiny(**bad)


@pytest.mark.parametrize("shape", [(2, 24), (1, 1), (3, 7)])
def test_full_forward_agrees_with_the_reference(lm, run, shape):
    model, cfg = lm
    ids = _ids(cfg, shape, seed=shape[1])
    _close(run["full"](ids), _ref(model, cfg, ids), f"logits {shape}")


def test_bfloat16_arithmetic_fails_the_tolerance(lm):
    """The tolerance is tight enough to tell float32 from bfloat16."""
    model, cfg = lm
    ids = _ids(cfg, (2, 24))
    ref = _ref(model, cfg, ids)
    low = JambaForCausalLM(cfg)
    low.set_state_dict(model.state_dict())
    low = amp.decorate(low, level="O2", dtype="bfloat16")
    low.eval()
    err = np.abs(np.asarray(low(ids), np.float32) - ref).max() / ref.std()
    assert err > 30 * TOL, err


def test_a_cast_of_the_model_keeps_the_recurrences_constants(lm):
    _, cfg = lm
    low = amp.decorate(JambaForCausalLM(cfg), level="O2", dtype="bfloat16")
    kept = {n: str(p.dtype) for n, p in low.named_parameters()
            if n.endswith((".A_log", ".D"))}
    assert len(kept) == 6 and set(kept.values()) == {"float32"}
    assert {str(p.dtype) for n, p in low.named_parameters()
            if n not in kept} == {"bfloat16"}


# ------------------------------------------------------------- the cache
def test_prefill_then_per_slot_decode_equals_the_full_pass(lm, run):
    """Two rows prefilled to different lengths, then decoded one token a
    step with a ``[B]`` vector of positions: every logit and, at the end,
    every state is the reference's."""
    model, cfg = lm
    lens, total = (5, 9), 16
    ids = _ids(cfg, (2, total), seed=1)
    states = []
    ref = _ref(model, cfg, ids, states)
    cache = init_cache(model, 2, 64)
    for row, n in enumerate(lens):      # a row at a time, as admissions do
        view = kv_cache.cache_row_view(cache, jnp.int32(row))
        lg, view = run["prefill"](view, ids[row:row + 1, :n])
        cache = kv_cache.cache_row_buffers(view)
        _close(lg[0], ref[row, :n], f"prefill row {row}")
    pos = np.asarray(lens, np.int32)
    for _ in range(total - max(lens)):
        tok = ids[np.arange(2), pos][:, None]
        lg, cache = run["at"](cache, tok, jnp.asarray(pos))
        for row in range(2):
            _close(lg[row, 0], ref[row, pos[row]], f"decode row {row}")
        pos += 1
    for row in range(2):
        short = []
        _ref(model, cfg, ids[row:row + 1, :pos[row]], short)
        _states_close(cache, short[0], row, int(pos[row]), f"row {row}")


@pytest.mark.parametrize("bucket,n", [
    (8, 1), (8, 2), (8, 3), (8, 7), (8, 8), (16, 4), (16, 15), (32, 1),
    (32, 17), (32, 31)])
def test_a_padded_bucket_leaves_exactly_the_prompts_state(lm, run, bucket, n):
    """A prompt of ``n`` tokens right-padded to ``bucket`` and told its
    length through ``gather_last``: the state, the window and the
    next-token logits are the unpadded prompt's and the reference's, and
    WHAT the pads hold changes not one bit of them (pads of token 0, as
    the engines pad, against pads of other tokens)."""
    model, cfg = lm
    prompt = _ids(cfg, (1, n), seed=bucket + n)
    padded = np.zeros((1, bucket), np.int32)
    padded[:, :n] = prompt
    noisy = _ids(cfg, (1, bucket), seed=99)
    noisy[:, :n] = prompt
    lg_p, cache_p = run["padded"](init_cache(model, 1, 64), padded,
                                  jnp.int32(n - 1))
    lg_n, cache_n = run["padded"](init_cache(model, 1, 64), noisy,
                                  jnp.int32(n - 1))
    assert np.array_equal(np.asarray(lg_p), np.asarray(lg_n))
    for layer in MAMBA_LAYERS:
        for a, b in zip(cache_p[layer], cache_n[layer]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), layer
    lg_u, cache_u = run["prefill"](init_cache(model, 1, 64), prompt)
    _close(lg_p[0, 0], np.asarray(lg_u[0, -1]), "padded against unpadded")
    for layer in MAMBA_LAYERS:
        for a, b in zip(cache_p[layer], cache_u[layer]):
            if np.asarray(b).any():
                _close(a, np.asarray(b), f"layer {layer} against unpadded")
            else:       # a window of zeros stays zeros
                assert not np.asarray(a).any()
    states = []
    ref = _ref(model, cfg, prompt, states)
    _close(lg_p[0, 0], ref[0, -1], "next-token logits")
    _states_close(cache_p, states[0], 0, n, f"{n} of {bucket}")


def test_pads_that_are_not_masked_would_move_the_state(lm, run):
    """The same padded block without its length: the recurrence runs on
    through the pads, and the comparison above would catch it."""
    model, cfg = lm
    prompt = _ids(cfg, (1, 5), seed=3)
    padded = np.zeros((1, 8), np.int32)
    padded[:, :5] = prompt
    _, told = run["padded"](init_cache(model, 1, 64), padded, jnp.int32(4))
    _, untold = run["prefill"](init_cache(model, 1, 64), padded)
    h_told, h_untold = (np.asarray(c[0][0]) for c in (told, untold))
    assert np.abs(h_told - h_untold).max() > 100 * TOL * h_told.std()


@pytest.mark.parametrize("first,second", [(8, 8), (3, 13), (15, 1), (2, 2)])
def test_chunked_continuation_equals_one_block(lm, run, first, second):
    """The first chunk prefilled, the second continued from the cached
    state at a traced offset: logits and final state equal one block's."""
    model, cfg = lm
    ids = _ids(cfg, (2, first + second), seed=first)
    states = []
    ref = _ref(model, cfg, ids, states)
    lg1, cache = run["prefill"](init_cache(model, 2, 64), ids[:, :first])
    lg2, cache = run["at"](cache, ids[:, first:], jnp.int32(first))
    _close(np.concatenate([lg1, lg2], axis=1), ref, "chunked logits")
    for row in range(2):
        _states_close(cache, states[row], row, first + second, f"row {row}")


def test_a_padded_continuation_keeps_the_cached_windows_tail(lm):
    """A continuation of ONE real token in a padded chunk: the window
    written is the cached window's last two inputs and the new one."""
    model, cfg = lm
    ids = _ids(cfg, (1, 6), seed=9)
    states = []
    _ref(model, cfg, ids, states)
    _, cache = model(ids[:, :5], cache=init_cache(model, 1, 64),
                     position_offset=0)
    chunk = np.zeros((1, 4), np.int32)
    chunk[:, :1] = ids[:, 5:]
    _, cache = model(chunk, cache=cache, position_offset=jnp.int32(5),
                     gather_last=jnp.int32(0))
    _states_close(cache, states[0], 0, 6, "continued")


def test_cache_shapes_and_bytes_against_a_hand_count(lm):
    model, cfg = lm
    spec = model.cache_spec()
    assert spec["entry_kinds"] == ("state", "kv", "state", "state")
    assert spec["state"] == (8, 3, 64)
    assert kv_cache.cache_entry_kind(spec) == "kv+state"
    assert [kv_cache.cache_entry_kind(spec, i) for i in range(4)] == \
        list(spec["entry_kinds"])
    assert (kv_cache.cache_entries(spec), kv_cache.state_entries(spec)) == \
        (1, 3)
    assert kv_cache.cache_layout(spec) == (4, ())
    cache = init_cache(model, 5, 48)
    shapes = [tuple((x.shape, str(x.dtype)) for x in pair) for pair in cache]
    state = (((5, 8, 64), "float32"), ((5, 3, 64), "float32"))
    kv = (((5, 48, 1, 8), "float32"),) * 2
    assert shapes == [state, kv, state, state]
    # a position: one key and one value of one head of 8, float32
    assert kv_cache.cache_token_nbytes(spec) == 2 * 8 * 4 == 64
    # a slot, whatever its length: three layers of 8 x 64 float32 states
    # and 3 x 64 window inputs
    assert kv_cache.cache_state_nbytes(spec) == 3 * (8 * 64 * 4 + 3 * 64 * 4)
    assert kv_cache.cache_split_nbytes(spec, cache) == (
        5 * 48 * 64, 5 * kv_cache.cache_state_nbytes(spec))
    # a bfloat16 cache keeps the state float32 and narrows the window
    low = kv_cache.alloc_cache(spec, 2, 16, dtype="bfloat16")
    assert [str(x.dtype) for x in low[0]] == ["float32", "bfloat16"]
    # the published widths: 1024 B a position, 9 318 400 B a slot
    big = dict(spec, entry_kinds=("state",) * 7 + ("kv",) + ("state",) * 13
               + ("kv",) + ("state",) * 6, num_layers=28, head_dim=128,
               state=(16, 3, 5120), dtype="bfloat16")
    assert kv_cache.cache_token_nbytes(big) == 2 * 2 * 128 * 2 == 1024
    assert kv_cache.cache_state_nbytes(big) == \
        26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    # a spec without a pattern answers as it did
    plain = GPTForCausalLM(gpt_tiny()).cache_spec()
    assert kv_cache.cache_entry_kind(plain) == "kv"
    assert kv_cache.state_entries(plain) == 0
    assert kv_cache.cache_state_nbytes(plain) == 0


def test_row_copies_carry_the_state(lm):
    """``scatter_cache_rows`` lands a one-row cache, state leaves and all,
    in a row of the live batch; ``cache_row_view`` round-trips."""
    model, cfg = lm
    _, one = model(_ids(cfg, (1, 6)), cache=init_cache(model, 1, 32),
                   position_offset=0)
    live = kv_cache.scatter_cache_rows(init_cache(model, 3, 32), one, 2)
    for pair_live, pair_one in zip(live, one):
        for a, b in zip(pair_live, pair_one):
            assert np.array_equal(np.asarray(a[2]), np.asarray(b[0]))
            assert not np.asarray(a[:2]).any()
    view = kv_cache.cache_row_view(live, jnp.int32(2))
    h, window = kv_cache.read_state(view[0])
    assert np.array_equal(np.asarray(h), np.asarray(one[0][0]))
    assert np.array_equal(np.asarray(window), np.asarray(one[0][1]))
    back = kv_cache.cache_row_buffers(view)
    assert all(a is b for a, b in zip(jax.tree.leaves(back),
                                      jax.tree.leaves(live)))


def test_the_state_is_placed_and_constrained_leaf_by_leaf(lm):
    """On a dp2 x mp2 mesh a state leaf splits its rows over dp and its
    inner width over mp; the one key/value head stays whole."""
    from paddle_tpu.distributed import mesh as mesh_mod

    model, _ = lm
    prev = mesh_mod.get_mesh()
    mesh_mod.init_mesh({"dp": 2, "mp": 2})
    try:
        cache = init_cache(model, 4, 32)
        P = jax.sharding.PartitionSpec
        assert cache[0][0].sharding.spec == P("dp", None, "mp")
        assert cache[0][1].sharding.spec == P("dp", None, "mp")
        assert cache[1][0].sharding.spec == P("dp", None, None, None)
        out = jax.jit(kv_cache.constrain_cache)(cache)
        assert out[0][0].sharding.spec == P("dp", None, "mp")
        assert out[1][1].sharding.spec in (P("dp"), P("dp", None, None, None))
        # an inner width that mp does not divide stays whole
        assert kv_cache.state_sharding_spec(4, 63).spec == \
            P("dp", None, None)
    finally:
        mesh_mod.set_mesh(prev)


# ----------------------------------------------------------- the engines
@pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 9, 20])
def test_engine_greedy_stream_equals_generate(lm, n):
    model, cfg = lm
    prompt = _ids(cfg, (n,), seed=40 + n)
    eng = ContinuousBatchingEngine(model, slots=2, **GEO)
    req = Request(prompt=prompt, max_new_tokens=10, greedy=True, seed=0)
    first, _, _ = eng.admit(req, 1)
    toks = [first] + [eng.step()[0].token for _ in range(9)]
    solo = model.generate(prompt[None], max_new_tokens=10, **GEO)[0]
    assert toks == solo.tolist()
    # and the tokens are the reference's own greedy continuation
    ids = np.concatenate([prompt, solo])[None]
    ref = _ref(model, cfg, ids)[0, n - 1:-1]
    assert (ref.max(-1) - ref[np.arange(10), solo]).max() < TOL * ref.std()


def test_a_reused_slot_serves_what_a_fresh_engine_serves(lm):
    """A long request, released; filler steps advance the freed slot's
    state; the next request in that slot gets the tokens of a fresh
    engine, and ``reset()`` zeroes every state leaf."""
    model, cfg = lm
    eng = ContinuousBatchingEngine(model, slots=2, **GEO)
    other = Request(prompt=_ids(cfg, (4,), seed=1), max_new_tokens=60,
                    greedy=True, seed=0)
    eng.admit(other, 1)                 # keeps the batch decoding
    long = Request(prompt=_ids(cfg, (20,), seed=2), max_new_tokens=30,
                   greedy=True, seed=0)
    eng.admit(long, 0)
    for _ in range(25):
        eng.step()
    eng.release(0)
    before = np.asarray(eng.live_cache[0][0][0])
    for _ in range(6):                  # slot 0 decodes filler
        eng.step()
    moved = np.asarray(eng.live_cache[0][0][0])
    assert np.abs(moved - before).max() > 0     # nothing masks a state
    prompt = _ids(cfg, (3,), seed=3)
    req = Request(prompt=prompt, max_new_tokens=8, greedy=True, seed=0)
    first, _, _ = eng.admit(req, 0)
    toks = [first] + [next(e.token for e in eng.step() if e.slot == 0)
                      for _ in range(7)]
    fresh = ContinuousBatchingEngine(model, slots=2, **GEO)
    first_f, _, _ = fresh.admit(
        Request(prompt=prompt, max_new_tokens=8, greedy=True, seed=0), 0)
    assert toks == [first_f] + [fresh.step()[0].token for _ in range(7)]
    eng.reset()
    for layer in MAMBA_LAYERS:
        assert not any(np.asarray(x).any() for x in eng.live_cache[layer])


def test_a_mixed_batch_equals_the_solo_runs(lm):
    """Two requests of different lengths admitted at different steps."""
    model, cfg = lm
    a, b = _ids(cfg, (13,), seed=5), _ids(cfg, (2,), seed=6)
    eng = ContinuousBatchingEngine(model, slots=3, **GEO)
    got = {0: [], 2: []}
    tok, _, _ = eng.admit(Request(prompt=a, max_new_tokens=12, greedy=True,
                                  seed=0), 2)
    got[2].append(tok)
    for _ in range(4):
        for e in eng.step():
            got[e.slot].append(e.token)
    tok, _, _ = eng.admit(Request(prompt=b, max_new_tokens=12, greedy=True,
                                  seed=0), 0)
    got[0].append(tok)
    for _ in range(7):
        for e in eng.step():
            got[e.slot].append(e.token)
    solo_a = model.generate(a[None], max_new_tokens=12, **GEO)[0].tolist()
    solo_b = model.generate(b[None], max_new_tokens=8, **GEO)[0].tolist()
    assert got[2] == solo_a and got[0] == solo_b


def test_cache_stats_and_compile_budget(lm):
    model, cfg = lm
    eng = ContinuousBatchingEngine(model, slots=2, **GEO)
    eng.warmup()
    stats = eng.cache_stats()
    assert stats["prefill"]["compiles"] == len(BUCKETS)
    assert stats["decode"]["compiles"] == 1
    assert (stats["cache_entry"], stats["cache_entries"],
            stats["state_entries"]) == ("kv+state", 1, 3)
    assert stats["cache_bytes_per_token"] == 64
    assert stats["state_bytes_per_slot"] == eng.state_bytes_per_slot == \
        kv_cache.cache_state_nbytes(model.cache_spec())
    assert eng.cache_bytes_per_slot() == 64 * 64 + stats["state_bytes_per_slot"]
    assert (stats["cache_write"], stats["cache_read"]) == ("scatter", "xla")


def test_snapshot_books_the_admissions_of_a_state_model(lm):
    model, cfg = lm
    rng = np.random.default_rng(0)
    lens = [1, 5, 8, 9, 16, 17, 30]
    with InferenceServer(model, slots=2, **GEO) as srv:
        hs = [srv.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                         max_new_tokens=3) for n in lens]
        for h in hs:
            h.result(timeout=240)
        snap = srv.snapshot()
        status = srv.statusz()["snapshot"]
        buckets = [min(b for b in BUCKETS if b >= n) for n in lens]
        assert snap["state"] == {
            "admissions": len(lens), "prompt_tokens": int(np.sum(lens)),
            "bucket_tokens": int(np.sum(buckets)),
            "state_bytes_per_slot":
                kv_cache.cache_state_nbytes(model.cache_spec())}
        for s in (snap, status):
            assert s["compile_stats"]["cache_entry"] == "kv+state"
            assert s["compile_stats"]["state_entries"] == 3
        srv.metrics.reset()
        assert srv.snapshot()["state"]["admissions"] == 0


def test_a_model_without_state_has_no_state_block():
    pt.seed(3)
    model = GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                    attention_dropout_prob=0.0,
                                    use_flash_attention=False))
    model.eval()
    with InferenceServer(model, slots=2, max_length=32,
                         prefill_buckets=(8,)) as srv:
        srv.submit(np.arange(1, 5, dtype=np.int32),
                   max_new_tokens=2).result(timeout=240)
        snap = srv.snapshot()
    assert "state" not in snap
    assert "state_entries" not in snap["compile_stats"]
    assert "state_bytes_per_slot" not in snap["compile_stats"]
    assert snap["compile_stats"]["cache_entry"] == "kv"


@pytest.mark.parametrize("who", ["BlockPool", "engine_pool", "speculative",
                                 "speculative_draft", "int8",
                                 "engine_int8"])
def test_what_cannot_carry_a_state_yet_says_so(lm, who):
    model, _ = lm
    draft = GPTForCausalLM(gpt_tiny(vocab_size=256))
    build = {
        "BlockPool": lambda: BlockPool(model, max_length=64),
        "engine_pool": lambda: ContinuousBatchingEngine(
            model, prefix_cache=True, **GEO),
        "speculative": lambda: SpeculativeEngine(model, draft, **GEO),
        "speculative_draft": lambda: SpeculativeEngine(draft, model, **GEO),
        "int8": lambda: init_cache(model, 1, 16, kv_dtype="int8"),
        "engine_int8": lambda: ContinuousBatchingEngine(
            model, kv_dtype="int8", **GEO)}[who]
    with pytest.raises(ValueError, match="recurrent-state entries"):
        build()
