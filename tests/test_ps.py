"""Parameter-server tests: native tables, optimizer rules vs numpy
references, save/load, SSD pass lifecycle, and jit-fused SparseEmbedding.

Pattern follows the reference's PS tests (table unit tests +
``PsLocalClient`` in-proc stack, SURVEY.md §4 mechanism 3).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.ps import (MemorySparseTable, PSContext, StagedPull,
                                       SSDSparseTable, SparseAccessorConfig,
                                       SparseEmbedding)


def make_table(optimizer="sgd", dim=4, lr=0.1, **kw):
    return MemorySparseTable(SparseAccessorConfig(
        embed_dim=dim, optimizer=optimizer, learning_rate=lr,
        initial_range=0.01, seed=7, **kw))


def test_pull_deterministic_init():
    t = make_table()
    a = t.pull([3, 5, 3])
    assert a.shape == (3, 4)
    np.testing.assert_array_equal(a[0], a[2])
    assert np.abs(a).max() <= 0.01
    # same seed -> same init in a fresh table
    b = make_table().pull([3])
    np.testing.assert_array_equal(a[0], b[0])
    assert len(t) == 2


def test_sgd_rule():
    t = make_table("sgd", lr=0.5)
    w0 = t.pull([11])
    g = np.full((1, 4), 2.0, np.float32)
    t.push([11], g)
    np.testing.assert_allclose(t.pull([11]), w0 - 0.5 * g, rtol=1e-6)


def test_adagrad_rule():
    t = make_table("adagrad", lr=0.1)
    w0 = t.pull([1]).astype(np.float64)
    g1 = np.array([[1.0, -2.0, 0.5, 3.0]], np.float32)
    g2 = np.array([[0.5, 1.0, -1.0, 2.0]], np.float32)
    t.push([1], g1)
    t.push([1], g2)
    g2sum = g1.astype(np.float64) ** 2
    w = w0 - 0.1 * g1 / (np.sqrt(g2sum) + 1e-8)
    g2sum += g2.astype(np.float64) ** 2
    w = w - 0.1 * g2 / (np.sqrt(g2sum) + 1e-8)
    np.testing.assert_allclose(t.pull([1]), w, rtol=1e-5)


def test_adam_rule():
    t = make_table("adam", lr=0.01)
    w = t.pull([42]).astype(np.float64)
    m = np.zeros(4)
    v = np.zeros(4)
    b1, b2, eps = 0.9, 0.999, 1e-8
    rng = np.random.default_rng(0)
    for step in range(1, 4):
        g = rng.normal(size=(1, 4)).astype(np.float32)
        t.push([42], g)
        g64 = g.astype(np.float64)[0]
        m = b1 * m + (1 - b1) * g64
        v = b2 * v + (1 - b2) * g64 ** 2
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        w = w - 0.01 * mhat / (np.sqrt(vhat) + eps)
    np.testing.assert_allclose(t.pull([42]), w, rtol=1e-4)


def test_duplicate_keys_in_batch_apply_serially():
    t = make_table("sgd", lr=1.0)
    w0 = t.pull([9])
    g = np.ones((3, 4), np.float32)
    t.push([9, 9, 9], g)
    np.testing.assert_allclose(t.pull([9]), w0 - 3.0, rtol=1e-6)


def test_save_load_roundtrip(tmp_path):
    t = make_table("adagrad")
    t.push(np.arange(100), np.random.default_rng(1).normal(
        size=(100, 4)).astype(np.float32))
    want = t.pull(np.arange(100))
    path = str(tmp_path / "t.bin")
    t.save(path)
    t2 = make_table("adagrad")
    t2.load(path)
    np.testing.assert_array_equal(t2.pull(np.arange(100)), want)
    assert len(t2) == 100


def test_shrink_evicts_cold_keys():
    t = make_table()
    t.pull([1, 2, 3])       # usage 1 each
    t.pull([1])             # key 1 usage 2
    dropped = t.shrink(2.0)
    assert dropped == 2
    assert set(t.keys().tolist()) == {1}


def test_ssd_pass_lifecycle(tmp_path):
    spill = str(tmp_path / "spill")
    t = SSDSparseTable(spill, SparseAccessorConfig(
        embed_dim=4, optimizer="sgd", learning_rate=1.0, seed=3))
    t.begin_pass()
    w0 = t.pull([5])
    t.push([5], np.ones((1, 4), np.float32))
    trained = t.pull([5])
    t.pull([6, 7])  # cold keys
    t.end_pass()    # snapshot + evict (key 5 usage 2, cold usage 1 < thresh? all >=1)
    # evict everything below 3 uses
    t.shrink(3.0)
    assert len(t) == 0
    t.begin_pass()  # reload from snapshot
    np.testing.assert_allclose(t.pull([5]), trained, rtol=1e-6)
    assert not np.allclose(t.pull([5]), w0)


def test_sparse_embedding_jit_train_step():
    """End-to-end: SparseEmbedding inside a jitted loss/grad step; grads
    flow into the table via the custom_vjp push and the loss decreases."""
    emb = SparseEmbedding(8, optimizer="adagrad", learning_rate=0.5, seed=0)
    target = jnp.asarray(np.random.default_rng(2).normal(size=(4, 8)),
                         jnp.float32)

    ids = jnp.asarray([100, 2000, 100, 31337], jnp.int32)

    # The table is not a jax parameter: the grads reach it through the
    # lookup's custom_vjp push, which runs whenever the model's (anchor)
    # params are differentiated — the normal functional train-step path.
    from paddle_tpu.nn.layer import buffer_state, functional_call, param_state

    params = param_state(emb)
    buffers = buffer_state(emb)

    @jax.jit
    def train_step(params):
        def loss_fn(p):
            e, _ = functional_call(emb, p, buffers, ids)
            return jnp.mean((e - target) ** 2)
        return jax.value_and_grad(loss_fn)(params)

    losses = []
    for _ in range(20):
        val, g = train_step(params)
        losses.append(float(val))
    assert losses[-1] < losses[0] * 0.2, losses
    assert len(emb.table) == 3
    # the anchor param itself gets zero grad
    (anchor_g,) = jax.tree_util.tree_leaves(g)
    assert float(jnp.abs(anchor_g).max()) == 0.0


def test_sparse_embedding_only_anchor_param():
    emb = SparseEmbedding(4, optimizer="sgd", seed=1)
    from paddle_tpu.nn.layer import param_state

    leaves = jax.tree_util.tree_leaves(param_state(emb))
    assert len(leaves) == 1 and leaves[0].shape == ()


def test_sparse_embedding_push_dce_guard():
    """A user-composed step that forgets the embedding's params must fail
    loudly — the silent alternative is AD pruning the push-vjp and the
    embedding never training (VERDICT r3 item 7)."""
    emb = SparseEmbedding(4, optimizer="sgd", seed=3)
    ids = jnp.asarray([1, 2], jnp.int32)

    @jax.jit
    def user_step(w):
        # emb's grad_anchor is a closed-over concrete array here, not a
        # differentiated input — the push could never fire
        e = emb(ids)
        return jnp.sum(w * jnp.sum(e))

    with pytest.raises(RuntimeError, match="grad_anchor"):
        jax.grad(user_step)(jnp.ones(4, jnp.float32))

    # same composition is legitimate for inference after .eval()
    emb.eval()
    out = jax.jit(lambda: jnp.sum(emb(ids)))()
    assert np.isfinite(float(out))
    emb.train()

    # and the supported path (params threaded functionally) still pushes:
    # the table rows must actually change after a grad step
    from paddle_tpu.nn.layer import buffer_state, functional_call, param_state

    params, buffers = param_state(emb), buffer_state(emb)
    before = emb.table.pull(np.asarray([1, 2])).copy()

    def loss_fn(p):
        e, _ = functional_call(emb, p, buffers, ids)
        return jnp.sum(e ** 2)

    jax.grad(loss_fn)(params)
    after = emb.table.pull(np.asarray([1, 2]))
    assert not np.allclose(before, after), "push was dead-code-eliminated"


def test_ps_context_persistables(tmp_path):
    ctx = PSContext()
    t1 = ctx.create_table("emb_a", embed_dim=4, optimizer="sgd", seed=1)
    ctx.create_table("emb_b", embed_dim=4, optimizer="sgd", seed=2)
    with pytest.raises(ValueError):
        ctx.create_table("emb_a", embed_dim=4)
    t1.push([1, 2], np.ones((2, 4), np.float32))
    want = t1.pull([1, 2])
    ctx.init_server()
    ctx.save_persistables(str(tmp_path / "ps"))
    ctx2 = PSContext()
    ctx2.create_table("emb_a", embed_dim=4, optimizer="sgd", seed=1)
    ctx2.load_persistables(str(tmp_path / "ps"))
    np.testing.assert_array_equal(ctx2.get_table("emb_a").pull([1, 2]), want)


def test_staged_pull_train_dedup():
    """StagedPull: pull-before/push-after staging (the PSGPUWorker
    PullSparse/PushSparseGrad structure) — works on backends without
    host-callback support; duplicate ids arrive merged."""
    from paddle_tpu.distributed.ps import StagedPull

    t = make_table("sgd", lr=1.0)
    staged = StagedPull(t)
    ids = np.asarray([7, 9, 7, 7])
    rows, inv, uniq = staged.pull(ids)
    assert rows.shape == (2, 4) and uniq.tolist() == [7, 9]
    np.testing.assert_array_equal(np.asarray(inv), [0, 1, 0, 0])
    w7 = np.asarray(rows[0])

    @jax.jit
    def step(rows, inv):
        def loss_fn(rows):
            return jnp.sum(StagedPull.lookup(rows, inv))
        return jax.value_and_grad(loss_fn)(rows)

    _, g = step(rows, inv)
    # id 7 appears 3x -> merged grad 3.0 per element
    np.testing.assert_allclose(np.asarray(g), [[3.0] * 4, [1.0] * 4])
    staged.push(uniq, g)
    np.testing.assert_allclose(t.pull([7])[0], w7 - 3.0, rtol=1e-6)


def test_load_merge_keeps_live_rows(tmp_path):
    """merge=True load inserts only missing keys — live rows win."""
    t = make_table("sgd")
    t.push([1, 2], np.ones((2, 4), np.float32))
    path = str(tmp_path / "t.bin")
    t.save(path)
    # train key 1 further, drop key 2
    t.push([1], np.ones((1, 4), np.float32))
    live = t.pull([1])
    t2 = make_table("sgd")
    t2.push([1], np.ones((1, 4), np.float32) * 5)  # divergent live row
    mine = t2.pull([1])
    t2.load(path, merge=True)
    np.testing.assert_array_equal(t2.pull([1]), mine)  # not rolled back
    assert 2 in set(t2.keys().tolist())               # missing key inserted
    # plain load overwrites
    t.load(path)
    assert not np.allclose(t.pull([1]), live)


def test_begin_pass_no_rollback(tmp_path):
    """begin_pass after extra training must not restore snapshot values."""
    spill = str(tmp_path / "spill")
    t = SSDSparseTable(spill, SparseAccessorConfig(
        embed_dim=4, optimizer="sgd", learning_rate=1.0, seed=3))
    t.pull([5])
    t.end_pass()
    t.push([5], np.ones((1, 4), np.float32))  # post-snapshot training
    trained = t.pull([5])
    t.begin_pass()  # unpaired begin_pass
    np.testing.assert_array_equal(t.pull([5]), trained)


def test_int64_ids_beyond_int32_contract():
    """Pin the int64-ids contract (VERDICT round-1 weak #8): feature signs
    above 2^31 must flow losslessly through the HOST path — the slot feed,
    the C++ table, and StagedPull's dedup/remap — because jax's global x64
    disable would truncate them on device. The device only ever sees the
    int32 `inv` remap indices, never the raw ids."""
    big_a, big_b = 2 ** 40 + 3, 2 ** 40 + (2 ** 32) + 3  # equal mod 2^32
    t = make_table("sgd")
    ra = t.pull(np.asarray([big_a]))
    rb = t.pull(np.asarray([big_b]))
    assert not np.allclose(ra, rb), \
        "keys differing only above bit 32 must hit distinct rows"
    # StagedPull end to end: int64 dedup on host, int32 remap on device
    staged = StagedPull(t)
    ids = np.asarray([[big_a, big_b], [big_b, big_a]], np.int64)
    rows, inv, uniq = staged.pull(ids)
    assert uniq.dtype == np.int64 and set(uniq) == {big_a, big_b}
    assert np.asarray(inv).dtype in (np.int32, np.int64)
    emb = np.asarray(StagedPull.lookup(rows, inv))
    np.testing.assert_array_equal(emb[0, 0], emb[1, 1])
    np.testing.assert_array_equal(emb[0, 1], emb[1, 0])
    assert not np.array_equal(emb[0, 0], emb[0, 1])
    # grads push back to the right int64 keys
    g = np.zeros((2, 4), np.float32)
    g[list(uniq).index(big_a)] = 1.0
    before_b = t.pull(np.asarray([big_b]))
    staged.push(uniq, g)
    lr = t.accessor.learning_rate
    np.testing.assert_allclose(t.pull(np.asarray([big_a]))[0],
                               np.asarray(ra)[0] - lr * 1.0, rtol=1e-5)
    np.testing.assert_array_equal(t.pull(np.asarray([big_b])), before_b)


def test_int64_signs_through_slot_feed(tmp_path):
    big = 2 ** 40 + 7
    f = tmp_path / "part"
    f.write_text(f"1\t101:{big},{big + 2 ** 32}\n")
    from paddle_tpu.io.slot_dataset import InMemoryDataset

    ds = InMemoryDataset(slots=[101], batch_size=1, max_per_slot=2,
                         drop_last=False)
    ds.load_into_memory([str(f)])
    signs, counts, labels = next(iter(ds))
    assert signs[101].dtype == np.int64
    np.testing.assert_array_equal(signs[101][0], [big, big + 2 ** 32])


def test_pipelined_pass_builder_overlap_and_parity():
    """PipelinedPassBuilder (PSGPUWrapper pre_build_thread analogue): the
    prefetched pass equals a direct StagedPull, pushes land on the right
    keys, and the build genuinely overlaps foreground work."""
    import threading
    import time

    from paddle_tpu.distributed.ps import PipelinedPassBuilder

    t = make_table("sgd")
    rng = np.random.default_rng(0)
    passes = [rng.integers(0, 500, (16, 3)) for _ in range(3)]

    builder = PipelinedPassBuilder(t)
    builder.prefetch(0, passes[0])
    ref = MemorySparseTable(SparseAccessorConfig(
        embed_dim=4, optimizer="sgd", learning_rate=0.1,
        initial_range=0.01, seed=7))
    ref_staged = StagedPull(ref)
    ref_results = {0: ref_staged.pull(passes[0])}
    for p in range(3):
        if p + 1 < 3:
            builder.prefetch(p + 1, passes[p + 1])
            # builds are as-of build time (pre-update values, same
            # staleness as the reference's pre_build_thread); join before
            # pushing so the parity comparison is deterministic, and pull
            # the mirror table at the matching point
            builder._threads[p + 1].join()
            ref_results[p + 1] = ref_staged.pull(passes[p + 1])
        rows, inv, uniq = builder.get(p)
        r_rows, r_inv, r_uniq = ref_results[p]
        np.testing.assert_array_equal(uniq, r_uniq)
        np.testing.assert_allclose(rows, r_rows, rtol=1e-6)
        g = np.ones((uniq.size, 4), np.float32)
        builder.push(p, g)
        ref.push(r_uniq, g)
        builder.end_pass(p)
    np.testing.assert_allclose(t.pull(np.arange(500)),
                               ref.pull(np.arange(500)), rtol=1e-6)

    # overlap: a slow pull must not block the foreground between prefetch
    # and get
    class SlowTable(MemorySparseTable):
        def pull(self, keys):
            time.sleep(0.3)
            return super().pull(keys)

    slow = SlowTable(SparseAccessorConfig(embed_dim=4, optimizer="sgd"))
    b2 = PipelinedPassBuilder(slow)
    t0 = time.perf_counter()
    b2.prefetch(0, np.arange(8))
    foreground = time.perf_counter() - t0
    assert foreground < 0.1, f"prefetch blocked {foreground:.2f}s"
    rows, _, _ = b2.get(0)
    assert rows.shape == (8, 4)


def test_pass_builder_errors():
    from paddle_tpu.distributed.ps import PipelinedPassBuilder

    b = PipelinedPassBuilder(make_table())
    with pytest.raises(KeyError, match="never prefetched"):
        b.get(9)
    with pytest.raises(KeyError, match="no pulled key set"):
        b.push(9, np.zeros((1, 4), np.float32))


def test_ssd_beyond_ram_working_set(tmp_path):
    """Weak #5 (round 1): cycle a working set LARGER than what stays in RAM
    through pass-based spill — every key's trained value must survive
    eviction via the snapshot, across several passes."""
    spill = str(tmp_path / "spill")
    t = SSDSparseTable(spill, SparseAccessorConfig(
        embed_dim=8, optimizer="sgd", learning_rate=1.0, seed=5),
        cache_threshold=1e9)  # evict EVERYTHING at end_pass (tiny "RAM")
    n, chunk = 5000, 1000
    expected = {}
    for p in range(5):  # each pass touches a different 1k-key chunk
        t.begin_pass()
        keys = np.arange(p * chunk, (p + 1) * chunk, dtype=np.int64)
        t.pull(keys)
        t.push(keys, np.full((chunk, 8), float(p + 1), np.float32))
        vals = t.pull(keys)
        t.end_pass()
        assert len(t) == 0, "cache_threshold must evict all of RAM"
        expected.update({int(k): vals[i] for i, k in enumerate(keys)})
    # all 5k keys reload correctly from the spill file
    t.begin_pass()
    all_keys = np.arange(n, dtype=np.int64)
    got = t.pull(all_keys)
    for i, k in enumerate(all_keys):
        np.testing.assert_allclose(got[i], expected[int(k)], rtol=1e-6,
                                   err_msg=f"key {k}")
    assert len(t) == n


def test_pass_builder_ssd_no_data_loss(tmp_path):
    """With an SSD table that evicts everything at end_pass, the builder
    must warm-reload evicted keys (begin_pass inside the build) so trained
    values survive across passes."""
    from paddle_tpu.distributed.ps import PipelinedPassBuilder

    t = SSDSparseTable(str(tmp_path / "spill"), SparseAccessorConfig(
        embed_dim=4, optimizer="sgd", learning_rate=1.0, seed=3),
        cache_threshold=1e9)
    b = PipelinedPassBuilder(t)
    ids = np.arange(10, dtype=np.int64)
    b.prefetch(0, ids)
    rows0, inv, uniq = b.get(0)
    # PIPELINED order: the next pass's build starts (and may finish)
    # before the current pass ends
    b.prefetch(1, ids)
    b._threads[1].join()
    b.push(0, np.ones((uniq.size, 4), np.float32))
    trained = t.pull(ids)
    b.end_pass(0)  # spill + evict ALL — including pass 1's pulled keys
    assert len(t) == 0
    rows1, _, uniq1 = b.get(1)
    # pass 1 pushes AFTER the eviction: must warm-reload, not re-init
    b.push(1, np.ones((uniq1.size, 4), np.float32))
    np.testing.assert_allclose(t.pull(ids), trained - 1.0, rtol=1e-6)


# ---------------------------------------------- FL coordinator (round 3)
def test_fl_coordinator_round_loop():
    """Reference ps/coordinator.py flow: clients push ClientInfoAttr, the
    coordinator's selector publishes per-client FLStrategy, clients pull
    their decision; final round FINISHes everyone."""
    import threading

    from paddle_tpu.distributed.ps import (ClientInfoAttr, Coordinator,
                                           FLClient, FLStrategy)
    from paddle_tpu.distributed.ps.coordinator import ClientSelector

    coord = Coordinator(selector=ClientSelector(max_rounds=2))
    try:
        clients = [FLClient(f"c{i}", coord.endpoint) for i in range(3)]
        results = {}

        def client_loop(c):
            for r in range(2):
                c.push_client_info(r, ClientInfoAttr(
                    loss=1.0 / (r + 1), num_samples=64))
                results[(c.client_id, r)] = c.pull_fl_strategy(r, timeout=30)

        ts = [threading.Thread(target=client_loop, args=(c,)) for c in clients]
        for t in ts:
            t.start()
        rounds = coord.run(num_clients=3, timeout=30)
        for t in ts:
            t.join(timeout=30)
        assert rounds == 2
        assert all(results[(f"c{i}", 0)].action == FLStrategy.JOIN
                   for i in range(3))
        assert all(results[(f"c{i}", 1)].action == FLStrategy.FINISH
                   for i in range(3))
    finally:
        coord.stop()


def test_fl_coordinator_custom_selector():
    """Loss-aware selection: only the worst-loss half JOINs."""
    from paddle_tpu.distributed.ps import ClientInfoAttr, Coordinator, FLClient
    from paddle_tpu.distributed.ps.coordinator import (ClientSelector,
                                                       FLStrategy)

    def pick_worst(round_idx, states):
        ranked = sorted(states, key=lambda c: -(states[c].loss or 0))
        join = set(ranked[:len(ranked) // 2])
        return {c: FLStrategy(FLStrategy.JOIN if c in join
                              else FLStrategy.WAIT) for c in states}

    coord = Coordinator(selector=ClientSelector(select_fn=pick_worst))
    try:
        cs = [FLClient(f"c{i}", coord.endpoint) for i in range(4)]
        for i, c in enumerate(cs):
            c.push_client_info(0, ClientInfoAttr(loss=float(i)))
        coord.run_round(0, num_clients=4, timeout=30)
        acts = {c.client_id: c.pull_fl_strategy(0, timeout=30).action
                for c in cs}
        assert acts["c3"] == "JOIN" and acts["c2"] == "JOIN"
        assert acts["c0"] == "WAIT" and acts["c1"] == "WAIT"
    finally:
        coord.stop()
