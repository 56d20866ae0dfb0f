"""Compiled KV-cache generation engine (models/generation.py).

The two load-bearing guarantees:

1. **Equivalence** — token-by-token cached decode produces the same
   logits as the full-sequence forward (GPT positional embeddings and
   Llama RoPE/GQA both thread ``(cache, position_offset)`` correctly);
2. **Compile discipline** — a 64-token batched ``generate()`` compiles
   exactly ``#prefill_buckets + 1`` XLA programs under ``retrace_guard``
   (the O(1)-compile serving claim the README makes).

Plus the sampling knobs (greedy/temperature/top-k/top-p, EOS done-mask)
and the hapi surface. Tier-1 budget discipline: the models are
module-scoped and most tests share ONE engine geometry (GEO below), so
the compiled prefill/decode programs are paid for once per family.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.framework import compile_cache

# the shared engine geometry: tests that use it reuse each other's
# compiled programs (engines are cached per (max_length, buckets))
GEO = dict(max_length=64, prefill_buckets=(16, 32))


@pytest.fixture(scope="module")
def gpt_model():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(7)
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model, cfg


@pytest.fixture(scope="module")
def llama_model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(7)
    cfg = llama_tiny(use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model, cfg


def _assert_cached_matches_full(model, cfg, prefill_len=3, total_len=9):
    """Prefill ``prefill_len`` tokens, decode the rest one-by-one, and
    compare every position's logits against the full-sequence forward."""
    from paddle_tpu.models.kv_cache import init_cache

    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, total_len)).astype(np.int32)
    full = np.asarray(model(jnp.asarray(ids)))
    cache = init_cache(model, 2, 16)
    logits, cache = model(jnp.asarray(ids[:, :prefill_len]), cache=cache,
                          position_offset=0)
    np.testing.assert_allclose(np.asarray(logits), full[:, :prefill_len],
                               rtol=2e-4, atol=2e-4)
    for t in range(prefill_len, total_len):
        logits, cache = model(jnp.asarray(ids[:, t:t + 1]), cache=cache,
                              position_offset=jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits)[:, 0], full[:, t],
                                   rtol=2e-4, atol=2e-4)


def test_gpt_cached_decode_matches_full_forward(gpt_model):
    _assert_cached_matches_full(*gpt_model)


def test_llama_gqa_cached_decode_matches_full_forward(llama_model):
    model, cfg = llama_model
    assert cfg.num_kv_heads < cfg.num_heads  # the GQA path, not MHA
    _assert_cached_matches_full(model, cfg)


def test_gpt_model_position_offset_threaded(gpt_model):
    """Satellite: position_offset reaches GPTEmbeddings from the model
    entry point — offset k must select position table rows k..k+L."""
    model, cfg = gpt_model
    ids = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    h0 = np.asarray(model.gpt(jnp.asarray(ids)))
    h0b = np.asarray(model.gpt(jnp.asarray(ids), position_offset=0))
    np.testing.assert_allclose(h0, h0b, rtol=1e-6)
    h3 = np.asarray(model.gpt(jnp.asarray(ids), position_offset=3))
    assert not np.allclose(h0, h3)  # different positions, different codes


def test_generate_compiles_buckets_plus_one():
    """The acceptance criterion: 64 tokens, batch 4, under retrace_guard —
    one prefill compile per bucket USED plus exactly one decode compile,
    never one per token. Fresh model: the counters must start at zero."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    buckets = (16, 32)
    rng = np.random.default_rng(0)
    with compile_cache.retrace_guard(max_compiles=len(buckets) + 1,
                                    label="generate"):
        ids = rng.integers(0, cfg.vocab_size, (4, 12)).astype(np.int32)
        out, stats = model.generate(ids, max_new_tokens=64, max_length=128,
                                    prefill_buckets=buckets,
                                    return_stats=True)
        assert out.shape == (4, 64)
        cc = stats["compile_stats"]
        assert cc["prefill"]["compiles"] == 1  # one bucket used so far
        assert cc["decode"]["compiles"] == 1   # O(1), not O(N)
        assert cc["decode"]["calls"] == 64 - 1
        # a second prompt landing in the OTHER bucket adds exactly one
        # prefill program; decode stays fully cached
        ids2 = rng.integers(0, cfg.vocab_size, (4, 20)).astype(np.int32)
        _, stats2 = model.generate(ids2, max_new_tokens=8, max_length=128,
                                   prefill_buckets=buckets,
                                   return_stats=True)
        cc2 = stats2["compile_stats"]
        assert cc2["prefill"]["compiles"] == len(buckets)
        assert cc2["decode"]["compiles"] == 1
    total = cc2["prefill"]["compiles"] + cc2["decode"]["compiles"]
    assert total == len(buckets) + 1


def test_generate_greedy_matches_argmax_rollout(gpt_model):
    model, cfg = gpt_model
    ids = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 10)).astype(np.int32)
    out = model.generate(ids, max_new_tokens=3, **GEO)
    rolled = ids.copy()
    for _ in range(3):
        logits = np.asarray(model(jnp.asarray(rolled)))
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        rolled = np.concatenate([rolled, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, rolled[:, 10:])


def test_generate_eos_early_stop_done_mask(gpt_model):
    model, cfg = gpt_model
    ids = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, 10)).astype(np.int32)
    probe = model.generate(ids, max_new_tokens=1, **GEO)
    eos = int(probe[0, 0])  # the token greedy emits first for row 0
    out = model.generate(ids, max_new_tokens=32, eos_token_id=eos, **GEO)
    # row 0 finished on its first token: the loop must stop well short of
    # 32 once EVERY row is done, and finished rows keep emitting eos
    assert out.shape[1] < 32 or (out == eos).all(axis=1).any()
    row0 = out[0]
    assert row0[0] == eos
    assert (row0 == eos).all()  # done-mask holds the row on eos


def test_generate_do_sample_seeded_and_in_vocab(gpt_model):
    model, cfg = gpt_model
    ids = np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    kw = dict(max_new_tokens=4, do_sample=True, temperature=0.7, top_k=8,
              top_p=0.9, seed=11, **GEO)
    a = model.generate(ids, **kw)
    b = model.generate(ids, **kw)
    np.testing.assert_array_equal(a, b)  # same seed, same stream
    assert (a >= 0).all() and (a < cfg.vocab_size).all()


def test_sample_logits_knobs():
    from paddle_tpu.models.generation import sample_logits

    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 10.0]] * 32, jnp.float32)
    # greedy ignores the key
    g = sample_logits(logits, None, greedy=True)
    assert (np.asarray(g) == 4).all()
    key = jax.random.PRNGKey(0)
    # top_k=2 restricts support to the two largest logits
    s = np.asarray(sample_logits(logits, key, temperature=5.0, top_k=2))
    assert set(s.tolist()) <= {3, 4}
    # tiny top_p keeps only the dominant token
    s = np.asarray(sample_logits(logits, key, temperature=1.0, top_p=0.05))
    assert (s == 4).all()
    # near-zero temperature concentrates on the argmax even unmasked
    s = np.asarray(sample_logits(logits, key, temperature=1e-4))
    assert (s == 4).all()


def test_sampled_randomness_fresh_per_step_and_per_row(gpt_model):
    """PRNG regression (PR-4 satellite): under a fixed seed, sampled
    decode must NOT reuse one key — (a) a row's tokens vary across steps
    (position folded into the key), (b) IDENTICAL prompts in one batch
    sample different continuations (row index folded in too), (c) the
    stream stays deterministic for a given seed."""
    model, cfg = gpt_model
    row = np.random.default_rng(8).integers(
        0, cfg.vocab_size, (8,)).astype(np.int32)
    ids = np.stack([row, row])  # two IDENTICAL prompts
    kw = dict(max_new_tokens=10, do_sample=True, temperature=8.0, seed=13,
              **GEO)
    a = model.generate(ids, **kw)
    b = model.generate(ids, **kw)
    np.testing.assert_array_equal(a, b)          # (c) seeded determinism
    assert len(set(a[0].tolist())) > 3           # (a) steps differ
    assert not np.array_equal(a[0], a[1])        # (b) rows differ


def test_done_check_interval_output_equivalence(gpt_model):
    """Satellite: reading the all-done flag every k-th step (fewer host
    syncs) + host-side overshoot trim must produce EXACTLY the per-step
    checked output, for eos stops landing on and off the interval."""
    model, cfg = gpt_model
    ids = np.random.default_rng(9).integers(
        0, cfg.vocab_size, (2, 10)).astype(np.int32)
    probe = model.generate(ids, max_new_tokens=16, **GEO)
    for stop_step in (2, 5, 7):  # off- and on-interval stops
        eos = int(probe[0, stop_step])
        ref = model.generate(ids, max_new_tokens=16, eos_token_id=eos,
                             done_check_interval=1, **GEO)
        for k in (3, 4, 16):
            out = model.generate(ids, max_new_tokens=16, eos_token_id=eos,
                                 done_check_interval=k, **GEO)
            np.testing.assert_array_equal(out, ref)


def test_prompt_len_exactly_at_largest_bucket(gpt_model):
    """Edge: a prompt filling the largest prefill bucket exactly — no
    padding, last_index at the bucket edge — still matches greedy
    rollout."""
    model, cfg = gpt_model
    L = max(GEO["prefill_buckets"])  # == 32
    ids = np.random.default_rng(10).integers(
        0, cfg.vocab_size, (1, L)).astype(np.int32)
    out, stats = model.generate(ids, max_new_tokens=3, return_stats=True,
                                **GEO)
    assert stats["prefill_bucket"] == L
    logits = np.asarray(model(jnp.asarray(ids)))
    assert out[0, 0] == logits[0, -1].argmax()


def test_eos_from_prefill_means_zero_decode_iterations(gpt_model):
    """Edge: when the PREFILL step itself emits eos for every row, the
    loop must run 0 decode iterations — output is exactly one column."""
    model, cfg = gpt_model
    ids = np.random.default_rng(11).integers(
        0, cfg.vocab_size, (2, 6)).astype(np.int32)
    probe = model.generate(ids, max_new_tokens=1, **GEO)
    eos = int(probe[0, 0])
    if int(probe[1, 0]) != eos:  # make BOTH rows hit eos at prefill
        ids = np.stack([ids[0], ids[0]])
    out = model.generate(ids, max_new_tokens=32, eos_token_id=eos, **GEO)
    assert out.shape == (2, 1)
    assert (out == eos).all()


def test_rows_finish_at_different_steps(gpt_model):
    """Edge: B>1 where rows hit eos at different steps — the finished
    row holds at eos while the other keeps decoding unperturbed, and the
    batch only drains when the LAST row finishes (here: at the token
    budget)."""
    model, cfg = gpt_model
    rng = np.random.default_rng(12)
    kw = dict(max_new_tokens=12, do_sample=True, temperature=4.0, seed=21,
              **GEO)
    # seeded sampled streams are diverse: find a token row 1 emits
    # mid-stream that row 0 never emits — row 1 finishes there, row 0
    # runs to the budget (seeded: draw 1 suffices today; the bound caps
    # tier-1 cost if the model init ever shifts)
    for _ in range(6):
        ids = rng.integers(0, cfg.vocab_size, (2, 10)).astype(np.int32)
        probe = model.generate(ids, **kw)
        row0 = set(probe[0].tolist())
        hit = [(int(t), j) for j, t in enumerate(probe[1].tolist())
               if 2 <= j <= 8 and int(t) not in row0]
        if hit:
            break
    assert hit, "could not construct a staggered-finish pair"
    eos, j = hit[0]
    out = model.generate(ids, eos_token_id=eos, done_check_interval=1,
                         **kw)
    assert out.shape[1] == 12              # row 0 never finishes early
    np.testing.assert_array_equal(out[0], probe[0])  # unperturbed
    assert out[1, j] == eos
    assert (out[1, j:] == eos).all()       # done-mask holds the row


def test_generate_rejects_overlong_request(gpt_model):
    model, _ = gpt_model
    ids = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="max_length"):
        model.generate(ids, max_new_tokens=100, max_length=32)


def test_hapi_model_generate(gpt_model):
    from paddle_tpu.hapi import Model

    net, cfg = gpt_model
    m = Model(net)
    ids = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    out = m.generate(ids, max_new_tokens=2, **GEO)
    assert out.shape == (2, 2)
    assert out.dtype == np.int32
    # non-LM networks fail loudly, not confusingly
    import paddle_tpu.nn as nn

    with pytest.raises(TypeError, match="generate"):
        Model(nn.Linear(4, 4)).generate(ids)


def test_llama_generate_smoke(llama_model):
    model, cfg = llama_model
    ids = np.random.default_rng(6).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    out, stats = model.generate(ids, max_new_tokens=4, return_stats=True,
                                **GEO)
    assert out.shape == (2, 4)
    assert stats["compile_stats"]["decode"]["compiles"] == 1
    assert stats["ttft_s"] > 0 and stats["tokens_per_sec"] > 0


def test_vector_position_offset_matches_scalar_decode(llama_model):
    """Continuous-batching substrate: a [B] position_offset VECTOR with
    per-row (staggered) positions reproduces the full-forward logits —
    RoPE tables, the causal mask frontier, and the GQA cache write all
    index per row. Eager (no jit), so tier-1 pays no extra compiles."""
    from paddle_tpu.models.kv_cache import init_cache

    model, cfg = llama_model
    ids = np.random.default_rng(13).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    full = np.asarray(model(jnp.asarray(ids)))
    cache = init_cache(model, 2, 16)
    _, cache = model(jnp.asarray(ids[:, :5]), cache=cache,
                     position_offset=0)
    # row 0 advances to position 6 while row 1 replays position 5: the
    # slots sit at DIFFERENT frontiers, like a live serving batch
    tok = jnp.asarray(np.stack([ids[0, 5:6], ids[1, 5:6]]))
    _, cache = model(tok, cache=cache,
                     position_offset=jnp.asarray([5, 5], jnp.int32))
    tok2 = jnp.asarray(np.stack([ids[0, 6:7], ids[1, 5:6]]))
    logits, cache = model(tok2, cache=cache,
                          position_offset=jnp.asarray([6, 5], jnp.int32))
    out = np.asarray(logits)[:, 0]
    np.testing.assert_allclose(out[0], full[0, 6], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(out[1], full[1, 5], rtol=2e-4, atol=2e-4)


def test_cache_sharding_spec_on_mesh():
    """On a dp×mp mesh the cache shards batch over dp and kv heads over
    mp; indivisible kv heads stay replicated rather than erroring."""
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.models.kv_cache import cache_sharding_spec

    init_mesh(dp=2, mp=2)
    spec = cache_sharding_spec(batch=4, n_kv_heads=4)
    assert spec is not None
    parts = tuple(spec.spec)
    assert "mp" in str(parts) and "dp" in str(parts)
    # 3 kv heads don't divide mp=2: head axis replicated, batch still dp
    spec_odd = cache_sharding_spec(batch=4, n_kv_heads=3)
    assert "mp" not in str(tuple(spec_odd.spec))


@pytest.mark.slow
def test_decode_bench_cli_runs():
    """tools/decode_bench.py end-to-end on CPU: emits tokens/s + TTFT
    JSON and exits 0 (no steady-state recompiles)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "decode_bench.py"),
         "--new-tokens", "16"],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(
        [l for l in proc.stdout.splitlines() if l.startswith('{"')][-1])
    assert rec["metric"] == "gpt_decode_tokens_per_sec"
    assert rec["value"] > 0
    assert rec["extra"]["ttft_ms"] > 0
    assert rec["extra"]["decode_compiles"] == 1
    assert rec["extra"]["steady_state_recompiles"] == 0
