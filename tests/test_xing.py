"""The latent-attention, sparse-expert, multi-stream decoder
(``models/xing.py``, ``nn/layers/expert_ffn.py``, the latent entry of
``models/kv_cache.py``) against its plain reference
(``benchmarks/harness/reference_latent_moe.py``), on the CPU in float32
at a tiny size with every mechanism on: 4 streams, 8 experts top 2 and a
shared one, one dense and two expert blocks, latent 16 + rotated 8, YaRN
past 32 positions; gains, the mixers' gates and biases and the router's
selection bias off their initial values, so that one left out or
misplaced shows.

Tolerances. Both sides are float32 at full matmul precision and differ
by summation order alone, a few 1e-7 of a logit: logits are held to
``TOL`` = 1e-4 of their standard deviation, a hundred times that noise
and far under what bfloat16 arithmetic gives (8 bits of mantissa through
three blocks: about a hundredth of the spread;
``test_bfloat16_arithmetic_fails_the_tolerance`` holds that to be so). A
router pick decided the other way swaps an expert; the model's routed
experts start akin (a sixteenth of each its own, ``ExpertFFN(own_share=)``),
so one swap moves a position's logits by a few thousandths of their
spread: the tolerance cannot hide one. The layer's own tests draw every
expert apart.
"""
import copy
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework import compile_cache
from paddle_tpu.framework.jit import param_state
from paddle_tpu.models import kv_cache, lm_utils
from paddle_tpu.models.kv_cache import cache_nbytes, init_cache
from paddle_tpu.models.xing import (XingConfig, XingForCausalLM, sinkhorn,
                                    xing_tiny, yarn_inv_freq)
from paddle_tpu.nn.layers.expert_ffn import ExpertFFN, expert_load
from paddle_tpu.serving import InferenceServer
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.scheduler import Request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from harness import reference_latent_moe as reference  # noqa: E402

TOL = 1e-4          # of the logits' standard deviation; see the docstring
GEO = dict(max_length=64, prefill_buckets=(16, 32))


def _ref_cfg(cfg: XingConfig) -> dict:
    """The configuration block as a benchmark file would hold it."""
    return dataclasses.asdict(cfg)


def _perturb(model, seed=5):
    rng = np.random.default_rng(seed)
    state = {}
    for name, p in model.named_parameters():
        if name.endswith(("layernorm.weight", "model.norm.weight")):
            state[name] = 1.0 + 0.3 * rng.standard_normal(p.shape)
        elif name.endswith("_hc.alpha"):
            state[name] = np.asarray([0.8, 1.2, 1.5])
        elif name.endswith("_hc.bias"):
            state[name] = np.asarray(p) + 0.3 * rng.standard_normal(p.shape)
        elif name.endswith("e_score_correction_bias"):
            state[name] = 0.2 * rng.standard_normal(p.shape)
    model.set_state_dict(dict(model.state_dict(), **{
        k: np.asarray(v, np.float32) for k, v in state.items()}))
    return len(state)


@pytest.fixture(scope="module")
def lm():
    pt.seed(11)
    cfg = xing_tiny()
    model = XingForCausalLM(cfg)
    model.eval()
    # a block has 2 mixers x (alpha, bias), 4 gains; an expert block a
    # selection bias; and the final norm
    assert _perturb(model) == 3 * 8 + 2 + 1
    return model, cfg


@pytest.fixture(scope="module")
def run(lm):
    """The model's three entry shapes, compiled once each shape: eager,
    a forward pass is thousands of dispatches."""
    model, _ = lm
    return {"full": jax.jit(lambda ids: model(ids)),
            "prefill": jax.jit(
                lambda c, x: model(x, cache=c, position_offset=0)),
            "at": jax.jit(
                lambda c, x, off: model(x, cache=c, position_offset=off))}


def _ids(cfg, shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape).astype(np.int32)


def _ref_logits(model, cfg, ids):
    return reference.logits(param_state(model), _ref_cfg(cfg), ids)


def _close(ours, ref, what=""):
    err = np.abs(np.asarray(ours) - ref).max() / ref.std()
    assert err < TOL, f"{what}: {err:.2e} of the logits' std"
    return err


def _scopes(lowered) -> set:
    import re

    return {seg for path in re.findall(r'loc\("([^"]+)"',
                                       lowered.as_text(debug_info=True))
            for seg in path.split("/")}


# ------------------------------------------------------------ the model
def test_logits_agree_with_the_reference(lm, run):
    model, cfg = lm
    ids = _ids(cfg, (2, 40))
    _close(run["full"](ids), _ref_logits(model, cfg, ids), "full forward")


def test_bfloat16_arithmetic_fails_the_tolerance(lm):
    """The same weights through bfloat16 projections miss ``TOL`` by two
    orders: the tolerance tells the two precisions apart."""
    from paddle_tpu import amp

    model, cfg = lm
    ids = _ids(cfg, (2, 40))
    ref = _ref_logits(model, cfg, ids)
    low = amp.decorate(copy.deepcopy(model), level="O2", dtype="bfloat16")
    low.eval()
    out = jax.jit(lambda i: low(i))(ids)
    err = np.abs(np.asarray(out, np.float32) - ref).max() / ref.std()
    assert err > 30 * TOL, err


def test_cached_prefill_and_decode_agree_with_the_reference(lm, run):
    """Prefill of 24 positions, then one token at a time through the
    latent cache (the absorbed path), each row at its own position."""
    model, cfg = lm
    ids = _ids(cfg, (2, 40), seed=1)
    ref = _ref_logits(model, cfg, ids)
    cache = init_cache(model, 2, 64)
    logits, cache = run["prefill"](cache, ids[:, :24])
    _close(logits, ref[:, :24], "prefill")
    for t in range(24, 40):
        logits, cache = run["at"](cache, ids[:, t:t + 1],
                                  jnp.full((2,), t, jnp.int32))
        _close(logits[:, 0], ref[:, t], f"decode at {t}")


def test_rows_at_different_positions_decode_alike(lm, run):
    """Two rows of one batch at DIFFERENT positions: each equals the
    reference's pass over its own tokens."""
    model, cfg = lm
    a, b = _ids(cfg, (1, 30), seed=2), _ids(cfg, (1, 13), seed=3)
    cache = init_cache(model, 2, 64)
    into_row = jax.jit(lambda c, x, row: kv_cache.cache_row_buffers(model(
        x, cache=kv_cache.cache_row_view(c, row), position_offset=0)[1]))
    for row, ids in ((0, a), (1, b)):
        cache = into_row(cache, ids[:, :-1], jnp.int32(row))
    last = np.stack([a[0, -1:], b[0, -1:]])
    logits, _ = run["at"](cache, last, jnp.asarray([29, 12], jnp.int32))
    _close(logits[0, 0], _ref_logits(model, cfg, a)[0, -1], "row 0 at 29")
    _close(logits[1, 0], _ref_logits(model, cfg, b)[0, -1], "row 1 at 12")


def test_chunked_continuation_agrees_with_the_reference(lm, run):
    """A 9-token chunk at a traced offset after a 16-token prefill."""
    model, cfg = lm
    ids = _ids(cfg, (1, 25), seed=4)
    ref = _ref_logits(model, cfg, ids)
    cache = init_cache(model, 1, 64)
    _, cache = run["prefill"](cache, ids[:, :16])
    logits, _ = run["at"](cache, ids[:, 16:], jnp.int32(16))
    _close(logits, ref[:, 16:], "chunk at 16")


def test_absorbed_attention_equals_decompressed():
    """``attend_with_latent_cache``'s latent-space path against the
    decompressed block attention on the same data: equal up to float32
    rounding of sums in another order (1e-5 of values of order 1)."""
    rng = np.random.default_rng(0)
    B, L, H, N, R, V, rank = 2, 12, 4, 16, 8, 16, 24
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q_nope, q_rope = f(B, L, H, N), f(B, L, H, R)
    c, k_rope = f(B, L, rank), f(B, L, 1, R)
    w_uk, w_uv = f(rank, H, N) / 4, f(rank, H, V) / 4
    scale = 0.3
    want = lm_utils.latent_block_attention(q_nope, q_rope, c, k_rope, w_uk,
                                           w_uv, scale)
    spec = {"num_layers": 1, "num_kv_heads": 1, "head_dim": rank,
            "latent": (rank, R), "max_length": 16, "dtype": "float32"}
    (cache,) = kv_cache.alloc_cache(spec, B, 16)
    # all but the last position by the prefill path, the last one absorbed
    _, cache = lm_utils.attend_with_latent_cache(
        q_nope[:, :-1], q_rope[:, :-1], c[:, :-1], k_rope[:, :-1], w_uk,
        w_uv, cache, 0, scale)
    got, _ = lm_utils.attend_with_latent_cache(
        q_nope[:, -1:], q_rope[:, -1:], c[:, -1:], k_rope[:, -1:], w_uk,
        w_uv, cache, jnp.full((B,), L - 1, jnp.int32), scale)
    np.testing.assert_allclose(got[:, 0], want[:, -1], atol=1e-5)


def test_yarn_frequencies_and_scale(lm):
    _, cfg = lm
    ours = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                         cfg.rope_scaling)
    np.testing.assert_allclose(
        ours, reference.inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                 cfg.rope_scaling), rtol=1e-6)
    plain = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, None)
    # the fastest dimension keeps its frequency, the slowest is divided
    assert ours[0] == plain[0] and np.isclose(ours[-1], plain[-1] / 8)
    assert np.isclose(cfg.attention_scale,
                      24 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    published = XingConfig(rope_scaling={
        "factor": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096})
    assert np.isclose(published.attention_scale, 192 ** -0.5 * 1.4159 ** 2,
                      rtol=1e-4)


def _sinkhorn_of(raw, iters):
    m = sinkhorn([[jnp.exp(jnp.asarray(raw[j, i], jnp.float32))
                   for i in range(4)] for j in range(4)], iters, 1e-6)
    return np.asarray(m)                                # [j, i, samples]


def test_sinkhorn_rows_and_columns_sum_to_one():
    """20 steps from ``exp`` of a diagonal of 2 plus noise of spread 0.3
    (2000 matrices): every column, normalised last, sums to 1 within a
    few float32 ulps and every row within 1e-5; the result is neither the
    identity nor uniform."""
    rng = np.random.default_rng(0)
    raw = rng.standard_normal((4, 4, 2000)) * 0.3 + 2.0 * np.eye(4)[..., None]
    m = _sinkhorn_of(raw, 20)
    np.testing.assert_allclose(m.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-5)
    assert m.max() < 0.9 and np.abs(m - 0.25).max() > 0.4


def test_sinkhorn_converges_slowly_on_high_contrast():
    """At a spread of 1 (the mixers' exponents at ``alpha`` 1) entries of
    one matrix differ a thousandfold and 20 steps, the published count,
    leave the worst row of 2000 matrices 2 % off, four times closer than
    5 steps do: the columns are exact, the rows approximate, in the
    program and in the reference alike."""
    rng = np.random.default_rng(0)
    raw = rng.standard_normal((4, 4, 2000)) + 2.0 * np.eye(4)[..., None]
    off = {n: np.abs(_sinkhorn_of(raw, n).sum(axis=1) - 1).max()
           for n in (5, 20)}
    assert off[20] < 0.03 and off[20] < off[5] / 4
    np.testing.assert_allclose(_sinkhorn_of(raw, 20).sum(axis=0), 1.0,
                               atol=1e-5)


def test_parameters_are_born_in_the_config_dtype():
    """No parameter is ever float32 when the config says bfloat16 (the
    published size born in float32 would not fit its chip), and the
    default type is as it was afterwards."""
    model = XingForCausalLM(xing_tiny(dtype="bfloat16"))
    assert {str(p.dtype) for _, p in model.named_parameters()} == {"bfloat16"}
    assert pt.get_default_dtype() == jnp.float32
    ids = _ids(model.cfg, (1, 8))
    assert jax.jit(lambda i: model(i))(ids).dtype == jnp.bfloat16


def test_published_sizes_count_to_the_cut():
    """7 of 40 layers (1 dense + 6 expert), every expert and the whole
    vocabulary: 5.54 billion parameters, counted from shapes alone."""
    cfg = XingConfig(num_layers=7, first_k_dense_replace=1)
    shapes = jax.eval_shape(lambda: param_state(XingForCausalLM(cfg)))
    pt.seed(0)      # the abstract build left tracers in the generator
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert round(n / 1e9, 2) == 5.54
    expert_layer = sum(int(np.prod(s.shape)) for k, s in shapes.items()
                       if k.startswith("model.layers.1."))
    assert round(expert_layer / 1e6) == 745


# ------------------------------------------------------- the expert FFN
def _expert_ffn(**kw):
    pt.seed(4)
    args = dict(shared_width=32, routed_scaling_factor=2.0, init_std=0.3)
    args.update(kw)
    return ExpertFFN(64, 32, 8, 2, **args)


_LAYER_CFG = {"n_routed_experts": 8, "num_experts_per_tok": 2,
              "routed_scaling_factor": 2.0}


def _layer_ref(layer, x, **cfg):
    p = {k: v for k, v in param_state(layer).items()}
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.expert_layer(
            p, dict(_LAYER_CFG, **cfg), jnp.asarray(x)))


def test_expert_layer_agrees_with_the_reference():
    layer = _expert_ffn()
    x = np.random.default_rng(1).standard_normal((3, 7, 64)).astype(np.float32)
    want = _layer_ref(layer, x.reshape(-1, 64)).reshape(x.shape)
    # outputs of order 1; float32 sums in another order
    np.testing.assert_allclose(layer(jnp.asarray(x)), want, atol=2e-5)


def test_routing_drops_nothing_under_skew():
    """A selection bias that sends EVERY token to experts 5 and 2: both
    take all 40 tokens (a capacity of 40 * 2 / 8 = 10 would drop 30 of
    each), and the result is still the reference's."""
    layer = _expert_ffn()
    bias = np.zeros(8, np.float32)
    bias[[5, 2]] = 10.0
    layer.router.e_score_correction_bias = bias
    x = np.random.default_rng(2).standard_normal((40, 64)).astype(np.float32)
    picked, w = layer.route(jnp.asarray(x))
    assert (np.sort(np.asarray(picked), axis=-1) == [2, 5]).all()
    # the bias selects and does not weigh: weights sum to the factor
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.0, rtol=1e-5)
    np.testing.assert_allclose(layer(jnp.asarray(x)), _layer_ref(layer, x),
                               atol=2e-5)


def test_the_shares_add_up():
    """Four holders of two experts each: their parts, the shared expert
    counted once, sum to the whole layer's output and to the uncut
    reference's."""
    whole = _expert_ffn()
    state = whole.state_dict()
    x = np.random.default_rng(3).standard_normal((25, 64)).astype(np.float32)
    shared = np.asarray(whole.shared_expert(jnp.asarray(x)))
    total = np.zeros_like(x)
    for first in (0, 2, 4, 6):
        part = _expert_ffn(experts_held=(first, 2))
        assert part.experts.gate_proj.shape == (2, 64, 32)
        part.set_state_dict({
            k: (np.asarray(v)[first:first + 2] if k.startswith("experts.")
                else v) for k, v in state.items()})
        got = np.asarray(part(jnp.asarray(x)))
        np.testing.assert_allclose(
            got, _layer_ref(part, x, experts_held=(first, 2)), atol=2e-5)
        total += got - shared
    total += shared
    np.testing.assert_allclose(total, whole(jnp.asarray(x)), atol=5e-5)
    np.testing.assert_allclose(total, _layer_ref(whole, x), atol=5e-5)


@pytest.mark.parametrize("own", [1.0, 0.25, 0.0])
def test_experts_start_as_far_apart_as_own_share_says(own):
    """Each routed expert is ``sqrt(1 - own ** 2)`` of one draw the layer's
    experts share plus ``own`` of its own: the std is the one asked for
    whatever ``own``, two experts correlate by ``1 - own ** 2``, and the
    shared expert is drawn apart from both."""
    layer = ExpertFFN(256, 128, 4, 2, shared_width=128, init_std=0.3,
                      out_init_std=0.1, own_share=own)
    for name, std in (("gate_proj", 0.3), ("up_proj", 0.3),
                      ("down_proj", 0.1)):
        w = np.asarray(getattr(layer.experts, name), np.float64)
        assert abs(w.std() / std - 1) < 0.02, (name, w.std())
        corr = np.corrcoef(w[0].ravel(), w[3].ravel())[0, 1]
        assert abs(corr - (1 - own ** 2)) < 0.02, (name, corr)
        shared = np.asarray(getattr(layer.shared_expert, name), np.float64)
        assert abs(shared.std() / std - 1) < 0.02
        assert abs(np.corrcoef(w[0].ravel(), shared.ravel())[0, 1]) < 0.02


def test_expert_load_counts_live_tokens_only():
    layer = _expert_ffn()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((6, 64)),
                    jnp.float32)
    live = np.asarray([1, 0, 1, 1, 0, 1], bool)
    with expert_load(jnp.asarray(live)) as tally:
        layer(x)
    ((per_expert, touched),) = tally
    picked = np.asarray(layer.route(x)[0])
    want = np.bincount(picked[live].ravel(), minlength=8)
    assert (np.asarray(per_expert) == want).all()
    assert int(touched) == (want > 0).sum()


def test_bad_expert_ranges_are_refused():
    with pytest.raises(ValueError, match="experts_held"):
        _expert_ffn(experts_held=(6, 4))
    with pytest.raises(ValueError, match="top_k"):
        ExpertFFN(64, 32, 4, 5)
    with pytest.raises(ValueError, match="only sigmoid"):
        xing_tiny(scoring_func="softmax")


# -------------------------------------------------------------- the cache
def test_cache_spec_and_bytes_a_token(lm):
    model, cfg = lm
    spec = model.cache_spec()
    assert spec["latent"] == (16, 8) and spec["cache_entries"] == 3
    assert kv_cache.cache_entry_kind(spec) == "latent"
    assert kv_cache.cache_entry_widths(spec) == (16, 8)
    cache = init_cache(model, 3, 32)
    assert len(cache) == 3
    assert cache[0][0].shape == (3, 32, 1, 16)       # c
    assert cache[0][1].shape == (3, 32, 1, 8)        # the shared rotated key
    per_token = 3 * (16 + 8) * 4
    assert cache_nbytes(cache) == 3 * 32 * per_token
    assert kv_cache.cache_token_nbytes(spec) == per_token
    stats = ContinuousBatchingEngine(model, slots=2, **GEO).cache_stats()
    assert stats["cache_entry"] == "latent"
    assert stats["cache_bytes_per_token"] == per_token
    # the published widths in bfloat16: 1152 bytes a token a layer
    big = dict(spec, latent=(512, 64), head_dim=512, cache_entries=7,
               num_layers=7, dtype="bfloat16")
    assert kv_cache.cache_token_nbytes(big) == 7 * 1152 == 8064


def test_a_kv_model_reports_its_entry():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    other = LlamaForCausalLM(llama_tiny(hidden_size=64, num_layers=1))
    eng = ContinuousBatchingEngine(other, slots=2, **GEO)
    assert eng.cache_stats()["cache_entry"] == "kv"
    assert eng.expert_load() is None


def test_int8_with_a_latent_entry_is_refused(lm):
    model, _ = lm
    with pytest.raises(ValueError, match="latent cache entry"):
        ContinuousBatchingEngine(model, slots=2, kv_dtype="int8", **GEO)
    with pytest.raises(ValueError, match="latent cache entry"):
        init_cache(model, 1, 16, kv_dtype="int8")


def test_pool_refuses_another_entry(lm):
    from paddle_tpu.serving.prefix_cache import BlockPool

    model, cfg = lm
    other = XingForCausalLM(xing_tiny(kv_lora_rank=16, qk_rope_head_dim=4,
                                      num_heads=4))
    pool = BlockPool(other, block_tokens=8, max_length=64)
    with pytest.raises(ValueError, match="entry_widths"):
        ContinuousBatchingEngine(model, slots=2, prefix_cache=pool, **GEO)


def test_block_pool_admission_equals_admission_without_one(lm):
    """Two prompts sharing a 16-token prefix and one that does not,
    through an engine with a prefix pool (block copies of the latent
    entry, the suffix by chunked continuation) and one without."""
    model, cfg = lm
    shared = _ids(cfg, (16,), seed=9)
    prompts = [np.concatenate([shared, _ids(cfg, (n,), seed=s)])
               for n, s in ((4, 1), (7, 2))] + [_ids(cfg, (11,), seed=3)]

    def run(**kw):
        eng = ContinuousBatchingEngine(model, slots=2, **GEO, **kw)
        out, hits = [], []
        for p in prompts:
            first, _, hit = eng.admit(
                Request(prompt=p, max_new_tokens=6, greedy=True, seed=0), 1)
            out.append([first] + [eng.step()[0].token for _ in range(5)])
            eng.release(1)
            hits.append(hit)
        return out, hits, eng

    plain, _, _ = run()
    pooled, hits, eng = run(prefix_cache={"block_tokens": 8,
                                          "max_bytes": 1 << 20})
    assert pooled == plain and hits == [0, 16, 0]
    assert eng.pool.block_bytes == 3 * 8 * (16 + 8) * 4
    assert eng.pool.tensors[0][1].shape[1:] == (8, 1, 8)


# ------------------------------------------------------------ the engine
def test_engine_stream_equals_generate(lm):
    model, cfg = lm
    prompts = [_ids(cfg, (n,), seed=n) for n in (5, 17, 30)]
    want = [np.asarray(model.generate(p[None], max_new_tokens=10))[0]
            for p in prompts]
    with InferenceServer(model, slots=2, **GEO) as srv:
        handles = [srv.submit(p, max_new_tokens=10) for p in prompts]
        got = [h.result(timeout=300) for h in handles]
    for w, g in zip(want, got):
        assert np.array_equal(w[-10:], g)


def test_serving_compiles_buckets_plus_one(lm):
    _, cfg = lm
    pt.seed(2)
    model = XingForCausalLM(cfg)    # fresh: its counters start at zero
    model.eval()
    eng = ContinuousBatchingEngine(model, slots=2, **GEO)
    warm = eng.warmup()
    assert (warm["prefill_compiles"], warm["decode_compiles"]) == (2, 1)
    with compile_cache.retrace_guard(max_compiles=0, label="xing serving"):
        for slot, n in ((0, 7), (1, 25), (0, 16)):
            eng.admit(Request(prompt=_ids(cfg, (n,), seed=n),
                              max_new_tokens=4, greedy=True, seed=0), slot)
            eng.step(), eng.step()
            if slot:
                eng.release(0), eng.release(1)
    assert eng.cache_stats()["cache_write"] == "scatter"
    assert eng.cache_stats()["cache_read"] == "xla"


def test_expert_load_equals_a_recount(lm):
    """Two requests admitted at different times, then both live, then one
    alone: ``expert_load()`` against the reference's picks for the tokens
    each decode step fed, the free slot's filler not counted."""
    model, cfg = lm
    eng = ContinuousBatchingEngine(model, slots=3, **GEO)
    prompts = {0: _ids(cfg, (9,), seed=1), 1: _ids(cfg, (20,), seed=2)}
    fed = []        # per step: [(request, position of the token fed)]
    tokens = {0: [], 1: []}
    slot_of = {0: 2, 1: 0}

    def admit(r):
        first, _, _ = eng.admit(Request(prompt=prompts[r], max_new_tokens=12,
                                        greedy=True, seed=0), slot_of[r])
        tokens[r].append(first)

    def step(live):
        fed.append([(r, len(prompts[r]) + len(tokens[r]) - 1) for r in live])
        for ev in eng.step():
            r = next(r for r in live if slot_of[r] == ev.slot)
            tokens[r].append(ev.token)

    admit(0)
    step([0]), step([0])
    admit(1)
    step([0, 1]), step([0, 1]), step([0, 1])
    eng.release(slot_of[0])
    step([1]), step([1])
    load = eng.expert_load()
    picks = {r: reference.picks(
        param_state(model), _ref_cfg(cfg),
        np.concatenate([prompts[r], tokens[r]])[None]) for r in (0, 1)}
    layers = cfg.num_layers - cfg.first_k_dense_replace
    per_expert = np.zeros((layers, cfg.n_routed_experts), int)
    touched = np.zeros(layers, int)
    for live in fed:
        for l in range(layers):
            here = np.concatenate([picks[r][l][0, pos] for r, pos in live])
            per_expert[l] += np.bincount(here, minlength=cfg.n_routed_experts)
            touched[l] += len(set(here.tolist()))
    assert load["steps"] == 7
    assert load["tokens_per_expert"] == per_expert.tolist()
    assert load["experts_touched_steps"] == touched.tolist()
    assert per_expert.sum() == layers * 2 * sum(len(s) for s in fed)
    eng.reset()
    assert eng.expert_load()["steps"] == 0


def test_snapshot_and_statusz_carry_the_expert_load(lm):
    model, cfg = lm
    with InferenceServer(model, slots=2, **GEO) as srv:
        assert len(srv.submit(_ids(cfg, (10,), seed=1),
                              max_new_tokens=5).result(timeout=120)) == 5
        snap = srv.snapshot()
        moe = snap["moe"]
        assert moe["steps"] == snap["decode"]["steps"] == 4
        assert np.asarray(moe["tokens_per_expert"]).shape == (2, 8)
        # one live slot, top 2, four steps, in each expert layer
        assert np.asarray(moe["tokens_per_expert"]).sum(axis=1).tolist() \
            == [8, 8]
        assert moe["experts_touched_steps"] == [8, 8]
        status = srv.statusz()["snapshot"]
        assert status["moe"]["steps"] == 4
        assert status["compile_stats"]["cache_entry"] == "latent"
        lowered = srv.engine._decode_compiled.lower(
            srv.engine._params, srv.engine._buffers, srv.engine.live_cache,
            *srv.engine._decode_inputs())
    assert {"decode", "attention", "mla", "absorb", "cache_write",
            "cache_read", "moe", "router", "dispatch", "experts",
            "shared_expert", "combine", "streams", "hc_pre", "sinkhorn",
            "hc_post", "mlp", "lm_head"} <= _scopes(lowered)
