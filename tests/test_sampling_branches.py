"""The serving sampler branches once a step on what its live rows ask for.

``generation.sample_logits_rows`` takes one ``lax.switch`` over
``generation.sample_branch``: an argmax alone where every live row is
greedy, the categorical draw without the nucleus filter where a live row
samples and none set ``top_p < 1``, the whole graph otherwise. What is
held here:

- every branch gives every live row the token of the old single graph
  (kept below as the plain reference), bit for bit;
- a free slot's stale settings do not choose the branch;
- the decode program has its ``sort`` inside one branch of one ``cond``
  and nowhere else;
- through ``InferenceServer``: streams equal their solo ``generate()``,
  ``snapshot()["sample"]`` counts each branch's steps, and every server
  takes a ``top_p`` request.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.generation import (per_row_keys, sample_branch,
                                          sample_logits, sample_logits_rows)
from paddle_tpu.serving import InferenceServer
from paddle_tpu.serving.engine import ContinuousBatchingEngine
from paddle_tpu.serving.metrics import SAMPLE_BRANCHES, ServingMetrics

B, V = 6, 257
GEO = dict(max_length=64, prefill_buckets=(16,))


def reference_rows(logits, row_keys, temperature, top_k, top_p, greedy_mask):
    """The single graph the serving programs held before the switch: the
    whole temperature / top-k / nucleus / categorical pipeline for every
    row, argmax picked by ``where`` afterwards."""

    def row(l, k, t, p):
        return sample_logits(l[None], k, t, top_k, p, greedy=False,
                             use_top_p=True)[0]

    sampled = jax.vmap(row)(logits, row_keys, temperature, top_p)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(greedy_mask, greedy_tok, sampled)


def _inputs(seed):
    rng = np.random.default_rng(seed)
    # a few logits of a row lie close together, so that a filter that
    # cut elsewhere or a key that moved would pick another token
    logits = jnp.asarray(rng.normal(0.0, 2.0, (B, V)), jnp.bfloat16)
    keys = jax.vmap(jax.random.PRNGKey)(
        jnp.asarray(rng.integers(0, 2**31, B), jnp.uint32))
    temp = jnp.asarray(rng.uniform(1.5, 3.0, B), jnp.float32)
    return logits, keys, temp


#         name: (greedy rows, top_p by row, static top_k, branch taken)
BATCHES = {
    "all_greedy": ([1] * 6, [1.0] * 6, 0, 0),
    "greedy_and_temperature_only": ([1, 0, 1, 0, 0, 1], [1.0] * 6, 0, 1),
    "greedy_and_top_p": ([1, 0, 1, 0, 1, 1],
                         [1.0, 0.9, 0.9, 1.0, 1.0, 1.0], 0, 2),
    "all_nucleus": ([0] * 6, [0.9, 0.5, 0.95, 0.3, 0.7, 0.99], 0, 2),
    "static_top_k_greedy": ([1] * 6, [1.0] * 6, 8, 0),
    "static_top_k_sampled": ([0, 1, 0, 0, 1, 0], [1.0] * 6, 8, 1),
    "static_top_k_nucleus": ([0, 1, 0, 0, 1, 0],
                             [0.8, 1.0, 1.0, 0.6, 0.9, 1.0], 8, 2),
    # a greedy row's top_p does not count: it draws nothing
    "top_p_on_greedy_rows_only": ([1, 0, 1, 0, 0, 1],
                                  [0.5, 1.0, 0.5, 1.0, 1.0, 0.5], 0, 1),
}


@pytest.mark.parametrize("name", list(BATCHES))
@pytest.mark.parametrize("seed", [0, 1])
def test_every_branch_gives_the_single_graphs_tokens(name, seed):
    greedy, top_p, top_k, branch = BATCHES[name]
    logits, keys, temp = _inputs(seed)
    greedy = jnp.asarray(greedy, bool)
    top_p = jnp.asarray(top_p, jnp.float32)
    assert int(sample_branch(jnp.ones(B, bool), greedy, top_p)) == branch
    new = jax.jit(lambda *a: sample_logits_rows(
        a[0], a[1], a[2], top_k, a[3], greedy_mask=a[4]))
    old = jax.jit(lambda *a: reference_rows(a[0], a[1], a[2], top_k, *a[3:]))
    got = np.asarray(new(logits, keys, temp, top_p, greedy))
    want = np.asarray(old(logits, keys, temp, top_p, greedy))
    np.testing.assert_array_equal(got, want)
    if branch:      # the sampled rows did sample: some leave the argmax
        assert (got != np.asarray(jnp.argmax(logits, -1)))[~greedy].any()


@pytest.mark.parametrize("use_top_p", [False, True])
def test_a_static_use_top_p_is_the_offline_engines_graph(use_top_p):
    """The offline engines know their one ``top_p`` when they trace and
    say so: no switch, the graph they always had."""
    logits, keys, temp = _inputs(3)
    top_p = jnp.full((B,), 0.8 if use_top_p else 1.0, jnp.float32)
    fn = jax.jit(lambda *a: sample_logits_rows(*a[:3], 0, a[3],
                                               use_top_p=use_top_p))
    assert "cond" not in str(jax.make_jaxpr(fn)(logits, keys, temp, top_p))
    want = reference_rows(logits, keys, temp, 0, top_p, jnp.zeros(B, bool))
    np.testing.assert_array_equal(
        np.asarray(fn(logits, keys, temp, top_p)), np.asarray(want))


def test_a_free_slots_stale_settings_do_not_choose_the_branch():
    live = np.array([True, True, False, True, False, False])
    greedy = np.array([True, True, False, True, True, False])
    top_p = np.array([1.0, 1.0, 0.5, 1.0, 1.0, 0.5], np.float32)
    for xp in (np, jnp):
        assert int(sample_branch(xp.asarray(live), xp.asarray(greedy),
                                 xp.asarray(top_p))) == 0
        assert int(sample_branch(xp.ones(B, bool), xp.asarray(greedy),
                                 xp.asarray(top_p))) == 2
        assert int(sample_branch(xp.zeros(B, bool), xp.asarray(greedy),
                                 xp.asarray(top_p))) == 0
    # and the program that is given ``live`` takes the argmax: its live
    # rows read as the reference's, whatever the free rows would draw
    logits, keys, temp = _inputs(4)
    got = np.asarray(jax.jit(sample_logits_rows, static_argnums=3)(
        logits, keys, temp, 0, jnp.asarray(top_p),
        greedy_mask=jnp.asarray(greedy), live=jnp.asarray(live)))
    np.testing.assert_array_equal(got, np.asarray(jnp.argmax(logits, -1)))
    want = np.asarray(reference_rows(logits, keys, temp, 0,
                                     jnp.asarray(top_p), jnp.asarray(greedy)))
    np.testing.assert_array_equal(got[live], want[live])


# ------------------------------------------------------ the programs' text
def _sorts(jaxpr, under_cond=None, found=None):
    """Every ``sort`` of a jaxpr, each with the chain of ``(cond id,
    branch)`` it lies under (empty at top level)."""
    found = [] if found is None else found
    under_cond = under_cond or ()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            found.append(under_cond)
        for name, val in eqn.params.items():
            subs = val if isinstance(val, (tuple, list)) else (val,)
            for i, sub in enumerate(subs):
                inner = getattr(sub, "jaxpr", sub)
                if not hasattr(inner, "eqns"):
                    continue
                where = under_cond
                if eqn.primitive.name == "cond" and name == "branches":
                    where = under_cond + ((id(eqn), i),)
                _sorts(inner, where, found)
    return found


@pytest.fixture(scope="module")
def lm():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(7)
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model, cfg


def test_the_branchs_sort_fills_its_tiles_on_the_chip(one_chip):
    """Compiled for a described v5e at the chat cell's shapes, behind a
    head as the decode step has one: the sort in the switch's branch works
    on tiles of eight rows (``T(8,128)``) as the open graph's does. Under
    ``vmap`` the filter's ``[B, 1, V]`` was tiled one row a tile there
    (``T(1,128)``) and the chip's sort took 20.3 ms against 2.6."""
    B, V, H = 48, 50304, 1024

    def step(hidden, wte, keys, positions, temp, top_p, greedy, done):
        logits = jnp.einsum("bsh,vh->bsv", hidden, wte)[:, -1, :]
        step_keys = jax.vmap(
            lambda k, p: per_row_keys(k, 1, position=p)[0])(keys, positions)
        return sample_logits_rows(logits, step_keys, temp, 0, top_p,
                                  greedy_mask=greedy, live=~done)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(step).lower(
        arg((B, 1, H), jnp.bfloat16), arg((V, H), jnp.bfloat16),
        arg((B, 2), jnp.uint32), arg((B,), jnp.int32),
        arg((B,), jnp.float32), arg((B,), jnp.float32),
        arg((B,), jnp.bool_), arg((B,), jnp.bool_)).compile().as_text()
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert len(sorts) == 1 and " conditional(" in text
    result = sorts[0].split(" sort(")[0]
    assert "T(8,128)" in result and "T(1,128)" not in result, result


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill_pool"])
def test_the_sort_is_inside_one_branch_of_one_switch(lm, program):
    model, _ = lm
    eng = ContinuousBatchingEngine(
        model, slots=3, prefix_cache=True if program == "prefill_pool"
        else None, **GEO)
    if program == "decode":
        args = (eng._params, eng._buffers, eng.live_cache,
                *eng._decode_inputs())
        fn = eng._decode_fn
    else:
        scalars = (np.asarray([0, 0], np.uint32), np.int32(-1),
                   np.float32(1.0), np.float32(1.0), np.bool_(True))
        ids = np.zeros((1, 16), np.int32)
        if program == "prefill":
            args = (eng._params, eng._buffers, eng.live_cache, ids,
                    np.int32(0), np.int32(3)) + scalars
            fn = eng._prefill_fn
        else:
            idx = np.zeros(eng.max_length // eng.pool.block_tokens, np.int32)
            args = (eng._params, eng._buffers, eng.live_cache,
                    eng.pool.tensors, ids, np.int32(0), np.int32(3),
                    np.int32(0), idx, idx) + scalars
            fn = eng._prefill_pool_fn
    with eng._eval_mode():
        sorts = _sorts(jax.make_jaxpr(fn)(*args).jaxpr)
    assert sorts, "the nucleus filter left the program"
    # one cond, its last branch, and nothing of it outside: a ``cond``
    # under ``vmap`` would have become a ``select`` with the sort at top
    # level
    assert all(len(chain) == 1 for chain in sorts), sorts
    assert len({chain[0][0] for chain in sorts}) == 1
    assert {chain[0][1] for chain in sorts} == {2}


# ------------------------------------------------------ through the server
def test_streams_equal_solo_generate_and_the_branches_are_counted(lm):
    model, cfg = lm
    rng = np.random.default_rng(5)
    p_greedy, p_nucleus, p_temp = (
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        for n in (9, 12, 7))
    solo_greedy = model.generate(p_greedy[None], max_new_tokens=6, **GEO)[0]
    kw_nucleus = dict(max_new_tokens=9, do_sample=True, temperature=0.9,
                      top_p=0.6, seed=11)
    solo_nucleus = model.generate(p_nucleus[None], **kw_nucleus, **GEO)[0]
    kw_temp = dict(max_new_tokens=5, do_sample=True, temperature=1.3, seed=3)
    solo_temp = model.generate(p_temp[None], **kw_temp, **GEO)[0]
    with InferenceServer(model, slots=3, **GEO) as srv:
        assert srv.snapshot()["sample"] == dict.fromkeys(SAMPLE_BRANCHES, 0)
        # alone: 5 steps, all of them an argmax
        got = srv.submit(p_greedy, max_new_tokens=6).result(timeout=300)
        np.testing.assert_array_equal(got, solo_greedy)
        assert srv.snapshot()["sample"] == {
            "argmax_steps": 5, "categorical_steps": 0, "nucleus_steps": 0}
        # temperature alone: 4 steps of the draw without the filter; the
        # slot it leaves keeps greedy False behind it
        got = srv.submit(p_temp, **kw_temp).result(timeout=300)
        np.testing.assert_array_equal(got, solo_temp)
        assert srv.snapshot()["sample"] == {
            "argmax_steps": 5, "categorical_steps": 4, "nucleus_steps": 0}
        # a greedy stream beside a seeded nucleus stream
        a = srv.submit(p_greedy, max_new_tokens=6)
        b = srv.submit(p_nucleus, **kw_nucleus)
        np.testing.assert_array_equal(a.result(timeout=300), solo_greedy)
        np.testing.assert_array_equal(b.result(timeout=300), solo_nucleus)
        snap = srv.snapshot()
        assert snap["sample"]["nucleus_steps"] == 8
        assert snap["sample"]["categorical_steps"] == 4
        assert sum(snap["sample"].values()) == snap["decode_steps"]
        assert srv.statusz()["snapshot"]["sample"] == snap["sample"]
        # the nucleus request is gone and its slot is free: greedy
        # traffic is back on the argmax, stale top_p 0.6 or not
        before = snap["sample"]
        got = srv.submit(p_greedy, max_new_tokens=6).result(timeout=300)
        np.testing.assert_array_equal(got, solo_greedy)
        after = srv.snapshot()["sample"]
        assert after["argmax_steps"] == before["argmax_steps"] + 5
        assert after["nucleus_steps"] == before["nucleus_steps"]
        assert srv.snapshot()["compile_stats"]["decode"]["compiles"] == 1
        srv.metrics.reset()
        assert srv.snapshot()["sample"] == dict.fromkeys(SAMPLE_BRANCHES, 0)


def test_a_default_server_takes_a_top_p_request(lm):
    model, _ = lm
    srv = InferenceServer(model, slots=1, **GEO)
    try:
        out = srv.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=3,
                         do_sample=True, top_p=0.5, seed=2).result(
                             timeout=300)
        assert len(out) == 3
    finally:
        srv.shutdown(drain=False, timeout=30)


def test_sample_counters_are_booked_by_branch_number():
    m = ServingMetrics(slots=2)
    for branch in (0, 0, 2, 1, 0):
        m.sample_step(branch)
    assert m.snapshot()["sample"] == {
        "argmax_steps": 3, "categorical_steps": 1, "nucleus_steps": 1}
    m.reset()
    assert sum(m.snapshot()["sample"].values()) == 0
