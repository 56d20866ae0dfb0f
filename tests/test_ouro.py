"""The looped decoder (``models/ouro.py``) against its plain reference
(``benchmarks/harness/reference_looped.py``), on the CPU in float32 at a
tiny size with T = 3 steps over L = 2 layers, so that the cache index
``t * L + l`` and its transpose differ.

Tolerances. Everything here is float32 on both sides and differs from
the reference by summation order alone: a few 1e-7 of a logit. Logits
are held to ``TOL`` = 1e-4 of their standard deviation (about a
hundred times the float32 noise seen, a hundred times under what
bfloat16 arithmetic gives: 8 bits of mantissa over T * L layer
applications come to about a hundredth of the spread, and
``test_bfloat16_arithmetic_fails_the_tolerance`` holds that to be so).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework import compile_cache
from paddle_tpu.framework.jit import param_state
from paddle_tpu.models.kv_cache import cache_nbytes, init_cache
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM, ouro_tiny
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.scheduler import Request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from harness import reference_looped  # noqa: E402

TOL = 1e-4          # of the logits' standard deviation; see the docstring
GEO = dict(max_length=64, prefill_buckets=(16, 32))


def _ref_cfg(cfg: OuroConfig) -> dict:
    """The configuration block as a benchmark file would hold it."""
    keys = ("vocab_size", "hidden_size", "num_layers", "num_heads",
            "num_kv_heads", "intermediate_size", "rope_theta",
            "rms_norm_eps", "total_ut_steps", "early_exit_threshold",
            "tie_word_embeddings")
    return {k: getattr(cfg, k) for k in keys}


@pytest.fixture(scope="module")
def lm():
    pt.seed(11)
    cfg = ouro_tiny(num_kv_heads=2)     # grouped queries, T = 3, L = 2
    model = OuroForCausalLM(cfg)
    model.eval()
    # the gate and the gains off their initial values, so that a norm or
    # a bias left out or misplaced shows
    rng = np.random.default_rng(5)
    state = {name: 1.0 + 0.3 * rng.standard_normal(p.shape).astype(np.float32)
             for name, p in model.named_parameters()
             if name.endswith(("layernorm.weight", "layernorm_2.weight",
                               "model.norm.weight"))}
    assert len(state) == 4 * cfg.num_layers + 1
    state["model.early_exit_gate.bias"] = np.asarray([0.4], np.float32)
    model.set_state_dict(dict(model.state_dict(), **state))
    return model, cfg


def _ids(cfg, shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape).astype(np.int32)


def _ref_logits(model, cfg, ids):
    return np.asarray(reference_looped.logits(param_state(model),
                                              _ref_cfg(cfg), ids))


def _scopes(lowered) -> set:
    """The ``jax.named_scope`` names in a lowered program's op metadata."""
    import re

    return {seg for path in re.findall(r'loc\("([^"]+)"',
                                       lowered.as_text(debug_info=True))
            for seg in path.split("/")}


def _close(ours, ref, what=""):
    err = np.abs(np.asarray(ours) - ref).max() / ref.std()
    assert err < TOL, f"{what}: {err:.2e} of the logits' std"
    return err


def test_cache_index_orders_differ(lm):
    _, cfg = lm
    T, L = cfg.total_ut_steps, cfg.num_layers
    assert [t * L + l for t in range(T) for l in range(L)] != \
        [l * T + t for t in range(T) for l in range(L)]


def test_logits_agree_with_the_reference(lm):
    model, cfg = lm
    ids = _ids(cfg, (2, 40))
    _close(model(ids), _ref_logits(model, cfg, ids), "full forward")


def test_bfloat16_arithmetic_fails_the_tolerance(lm):
    """The same weights through bfloat16 matmuls miss ``TOL`` by two
    orders: the tolerance tells the two precisions apart."""
    import copy

    from paddle_tpu import amp

    model, cfg = lm
    ids = _ids(cfg, (2, 40))
    ref = _ref_logits(model, cfg, ids)
    low = amp.decorate(copy.deepcopy(model), level="O2", dtype="bfloat16")
    low.eval()
    err = np.abs(np.asarray(low(ids), np.float32) - ref).max() / ref.std()
    assert err > 30 * TOL, err


def test_exit_distribution_agrees_and_sums_to_one(lm):
    model, cfg = lm
    ids = _ids(cfg, (2, 24), seed=3)
    pdf = np.asarray(model.exit_pdf(ids))
    ref = np.asarray(reference_looped.exit_pdf(param_state(model),
                                               _ref_cfg(cfg), ids))
    assert pdf.shape == (2, 24, cfg.total_ut_steps)
    # probabilities of order 0.1 to 0.6: 1e-6 is ten float32 ulps of them
    np.testing.assert_allclose(pdf, ref, atol=1e-6)
    np.testing.assert_allclose(pdf.sum(-1), 1.0, atol=1e-6)
    assert pdf.min() > 0.01     # every step keeps some mass: all are tested


def test_threshold_below_one_is_refused():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ouro_tiny(early_exit_threshold=0.9)


def test_cached_prefill_and_decode_agree_with_the_reference(lm):
    """Prefill, then one token at a time through the cache at a scalar
    and at per-row positions: every position's logits against the
    reference's one full pass."""
    model, cfg = lm
    ids = _ids(cfg, (2, 12), seed=1)
    ref = _ref_logits(model, cfg, ids)
    cache = init_cache(model, 2, 16)
    logits, cache = model(jnp.asarray(ids[:, :5]), cache=cache,
                          position_offset=0)
    _close(logits, ref[:, :5], "prefill")
    for t in range(5, 12):
        pos = jnp.int32(t) if t % 2 else jnp.full((2,), t, jnp.int32)
        logits, cache = model(jnp.asarray(ids[:, t:t + 1]), cache=cache,
                              position_offset=pos)
        _close(logits[:, 0], ref[:, t], f"decode at {t}")


class _Tap:
    """Records the logits a serving program hands its sampler, in
    order, through an ordered host callback: what the engines computed,
    not what they picked."""

    def __init__(self, monkeypatch, module, name):
        self.seen = []
        inner = getattr(module, name)

        def tapped(logits, *a, **kw):
            jax.experimental.io_callback(
                lambda x: self.seen.append(np.asarray(x)), None, logits,
                ordered=True)
            return inner(logits, *a, **kw)

        monkeypatch.setattr(module, name, tapped)


def test_generate_logits_agree_with_the_reference(lm, monkeypatch):
    from paddle_tpu.models import generation

    model, cfg = lm
    tap = _Tap(monkeypatch, generation, "sample_logits")
    ids = _ids(cfg, (2, 9), seed=2)
    out = generation.GenerationEngine(model, **GEO).generate(
        ids, max_new_tokens=6)
    jax.effects_barrier()
    full = np.concatenate([ids, out], axis=1)
    ref = _ref_logits(model, cfg, full)
    assert len(tap.seen) == 6
    for i, got in enumerate(tap.seen):      # step i predicts token 9 + i
        _close(got, ref[:, 8 + i], f"generate step {i}")
        assert (got.argmax(-1) == out[:, i]).all()


def test_engine_logits_at_different_positions_agree_with_the_reference(
        lm, monkeypatch):
    """Three requests of different lengths admitted at different times
    into a 3-slot batch (one of them into a slot another has left):
    each decode step's logits, slot by slot, against the reference's
    full pass over that request's own tokens."""
    from paddle_tpu.serving import engine as engine_mod

    model, cfg = lm
    tap = _Tap(monkeypatch, engine_mod, "sample_logits_rows")
    eng = ContinuousBatchingEngine(model, slots=3, **GEO)
    prompts = [_ids(cfg, (n,), seed=s) for n, s in ((9, 1), (20, 2), (5, 3))]
    reqs = [Request(prompt=p, max_new_tokens=9, greedy=True, seed=0)
            for p in prompts]
    tokens = {0: [], 1: [], 2: []}          # by request
    logits = {0: [], 1: [], 2: []}
    slot_of, req_in = {}, {}

    def admit(r, slot):
        n = len(tap.seen)
        first, _, _ = eng.admit(reqs[r], slot)
        jax.effects_barrier()
        assert len(tap.seen) == n + 1
        tokens[r].append(first)
        logits[r].append(tap.seen[-1][0])
        slot_of[r], req_in[slot] = slot, r

    def step():
        events = eng.step()
        jax.effects_barrier()
        for ev in events:
            r = req_in[ev.slot]
            tokens[r].append(ev.token)
            logits[r].append(tap.seen[-1][ev.slot])

    admit(0, 2)
    step(), step()
    admit(1, 0)                             # slots now at 11 and 20
    step(), step(), step()
    eng.release(2)                          # request 0 leaves early
    del req_in[2]
    admit(2, 2)                             # its slot is reused: stale rows
    for _ in range(4):
        step()
    assert [eng._positions[slot_of[r]] for r in (1, 2)] == [27, 9]
    for r, p in enumerate(prompts):
        full = np.concatenate([p, tokens[r]])[None]
        ref = _ref_logits(model, cfg, full)[0]
        for i, got in enumerate(logits[r]):
            _close(got, ref[len(p) - 1 + i], f"request {r} token {i}")
            assert got.argmax() == tokens[r][i]
    assert len(tokens[0]) == 6 and len(tokens[1]) == 8 and len(tokens[2]) == 5


def test_loss_and_gradients_through_train_step(lm):
    """One SGD step at learning rate 1 moves every parameter by its
    gradient: loss and gradients of the program against ``jax.grad`` of
    the reference's loss."""
    from paddle_tpu.optimizer import SGD

    _, cfg = lm
    pt.seed(4)
    model = OuroForCausalLM(cfg)
    ids = _ids(cfg, (2, 16), seed=7)
    before = {k: np.asarray(v) for k, v in param_state(model).items()}
    ref_loss, ref_grads = jax.value_and_grad(reference_looped.loss)(
        {k: jnp.asarray(v) for k, v in before.items()}, _ref_cfg(cfg), ids,
        ids)
    step = pt.TrainStep(model, SGD(learning_rate=1.0), loss_fn=None,
                        inputs_fn=lambda b: b)
    loss = float(step((ids, ids)))
    # a loss of 6.2: 1e-5 is six float32 ulps
    assert abs(loss - float(ref_loss)) < 1e-5
    worst = 0.0
    for name, g_ref in ref_grads.items():
        g = before[name] - np.asarray(step.params[name])
        g_ref = np.asarray(g_ref)
        # relative to the gradient's largest element; the subtraction
        # above costs an ulp of the parameter (1e-7 of O(1) gains), so
        # 2e-4 of gradients of 1e-3 and more; bfloat16 would give 1e-2
        if not np.abs(g_ref).max():     # the gate: the loss does not read it
            assert name.startswith("model.early_exit_gate") and not g.any()
            continue
        worst = max(worst, np.abs(g - g_ref).max() / np.abs(g_ref).max())
    assert worst < 2e-4, worst


def test_parameter_count_does_not_depend_on_the_steps():
    def count(**kw):
        return sum(int(np.prod(p.shape)) for p in
                   OuroForCausalLM(ouro_tiny(**kw)).parameters())

    h, f, v, L = 64, 160, 512, 2
    per_layer = 4 * h * h + 3 * h * f + 4 * h
    assert count(total_ut_steps=1) == count(total_ut_steps=5) == \
        L * per_layer + 2 * v * h + h + h + 1


def test_published_sizes_count_to_2_668_billion():
    cfg = OuroConfig()
    h, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    assert (cfg.num_layers, cfg.total_ut_steps, h // cfg.num_heads) == \
        (48, 4, 128)
    n = cfg.num_layers * (4 * h * h + 3 * h * f + 4 * h) + 2 * v * h + 2 * h + 1
    assert round(n / 1e6) == 2668


def test_cache_bytes_a_token(lm):
    model, cfg = lm
    spec = model.cache_spec()
    T, L = cfg.total_ut_steps, cfg.num_layers
    assert spec["cache_entries"] == T * L and spec["num_layers"] == L
    cache = init_cache(model, 3, 32)
    assert len(cache) == L and cache[0][0].shape == (3, T, 32, 2, 16)
    per_token = 2 * T * L * cfg.num_kv_heads * 16 * 4
    assert cache_nbytes(cache) == 3 * 32 * per_token
    eng = ContinuousBatchingEngine(model, slots=2, **GEO)
    stats = eng.cache_stats()
    assert stats["cache_entries"] == T * L
    assert stats["cache_bytes_per_token"] == per_token


def test_block_pool_admission_equals_admission_without_one(lm):
    """The same three prompts, two sharing a 16-token prefix, through an
    engine with a prefix pool and one without: the same tokens, and the
    pool served the shared blocks."""
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
            toks = [first] + [eng.step()[0].token for _ in range(5)]
            eng.release(1)
            out.append(toks)
            hits.append(hit)
        return out, hits, eng

    plain, _, _ = run()
    pooled, hits, eng = run(prefix_cache={"block_tokens": 8,
                                          "max_bytes": 1 << 20})
    assert pooled == plain
    assert hits == [0, 16, 0]
    T, L = cfg.total_ut_steps, cfg.num_layers
    assert eng.pool.block_bytes == 2 * T * L * 8 * cfg.num_kv_heads * 16 * 4
    assert eng.pool.tensors[0][0].shape[1:] == (T, 8, cfg.num_kv_heads, 16)


def test_pool_refuses_another_cache_layout(lm):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving.prefix_cache import BlockPool

    model, cfg = lm
    other = LlamaForCausalLM(llama_tiny(
        hidden_size=64, num_heads=4, num_kv_heads=2, num_layers=2))
    pool = BlockPool(other, block_tokens=8, max_length=64)
    with pytest.raises(ValueError, match="cache_layout"):
        ContinuousBatchingEngine(model, slots=2, prefix_cache=pool, **GEO)


def test_serving_compiles_buckets_plus_one(lm):
    """Warm-up compiles one prefill a bucket and one decode program;
    after it, requests of any length and slot compile nothing."""
    _, cfg = lm
    pt.seed(2)
    model = OuroForCausalLM(cfg)    # fresh: its counters start at zero
    model.eval()
    eng = ContinuousBatchingEngine(model, slots=2, **GEO)
    warm = eng.warmup()
    assert (warm["prefill_compiles"], warm["decode_compiles"]) == (2, 1)
    with compile_cache.retrace_guard(max_compiles=0, label="ouro serving"):
        for slot, n in ((0, 7), (1, 25), (0, 16)):
            eng.admit(Request(prompt=_ids(cfg, (n,), seed=n),
                              max_new_tokens=4, greedy=True, seed=0), slot)
            eng.step(), eng.step()
            if slot:
                eng.release(0), eng.release(1)
    cc = eng.cache_stats()
    assert cc["prefill"]["compiles"] + cc["decode"]["compiles"] == \
        len(GEO["prefill_buckets"]) + 1


def test_decode_counters_and_scopes_through_the_server(lm):
    """``snapshot()["decode"]`` sums the live slots and the positions
    they read over the decode steps, and resets with the rest."""
    from paddle_tpu.serving import InferenceServer

    model, cfg = lm
    with InferenceServer(model, slots=2, **GEO) as srv:
        h = srv.submit(_ids(cfg, (10,), seed=1), max_new_tokens=5)
        assert len(h.result(timeout=120)) == 5
        d = srv.snapshot()["decode"]
        # prompt 10: the first token comes from the prefill, then four
        # steps whose queries sit at positions 10..13 and read 11..14 keys
        # (every step but the first handed over while the one before ran)
        assert d == {"steps": 4, "live_slot_steps": 4,
                     "live_position_steps": 11 + 12 + 13 + 14,
                     "launched_ahead_steps": 3}
        assert srv.statusz()["snapshot"]["compile_stats"][
            "cache_entries"] == cfg.total_ut_steps * cfg.num_layers
        srv.metrics.reset()
        assert srv.snapshot()["decode"]["steps"] == 0
        lowered = srv.engine._decode_compiled.lower(
            srv.engine._params, srv.engine._buffers, srv.engine.live_cache,
            *srv.engine._decode_inputs())
    found = _scopes(lowered)
    assert {"decode", "ut_step", "attention", "mlp", "lm_head"} <= found
    # served logits are the last step's: the gate is not in the program
    assert "exit_gate" not in found
    assert {"ut_step", "exit_gate"} <= _scopes(
        jax.jit(lambda i: model.exit_pdf(i)).lower(np.zeros((1, 8), np.int32)))
