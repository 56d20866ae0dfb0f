"""Graph engine tests: native CSR store sampling/walks, GraphDataGenerator
batch stream, and the geometric message-passing/sampling API.

Pattern follows the reference's HeterPS graph tests (test_graph.cu /
test_sample_rate.cu: build a small CSR graph, sample, assert neighbor
sets — SURVEY.md §4).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.distributed.ps.graph import (DistGraphClient,
                                             GraphDataGenerator, GraphServer,
                                             GraphTable, launch_graph_servers)
from paddle_tpu import geometric as G


def toy_graph(symmetric=False):
    g = GraphTable()
    # 0-1, 0-2, 1-2, 2-3 directed
    g.add_edges([0, 0, 1, 2], [1, 2, 2, 3])
    g.build(symmetric=symmetric)
    return g


def test_graph_build_counts():
    g = toy_graph()
    assert g.num_nodes == 4
    assert g.num_edges == 4
    assert g.degree(0) == 2 and g.degree(3) == 0
    gs = toy_graph(symmetric=True)
    assert gs.num_edges == 8
    assert gs.degree(3) == 1


def test_sample_neighbors_exact_sets():
    g = toy_graph()
    nb, cnt = g.sample_neighbors([0, 3, 777], sample_size=4)
    assert nb.shape == (3, 4)
    assert set(nb[0][nb[0] >= 0].tolist()) == {1, 2} and cnt[0] == 2
    assert cnt[1] == 0 and cnt[2] == 0
    assert (nb[1] == -1).all()


def test_sample_neighbors_without_replacement_subset():
    g = GraphTable()
    g.add_edges(np.zeros(50, np.int64), np.arange(1, 51))
    g.build()
    nb, cnt = g.sample_neighbors([0], sample_size=10, seed=3)
    vals = nb[0]
    assert cnt[0] == 10
    assert len(set(vals.tolist())) == 10  # no duplicates
    assert all(1 <= v <= 50 for v in vals)
    # different seed -> different sample (overwhelmingly likely)
    nb2, _ = g.sample_neighbors([0], sample_size=10, seed=4)
    assert not np.array_equal(nb, nb2)


def test_random_walk_follows_edges():
    g = toy_graph()
    edges = {(0, 1), (0, 2), (1, 2), (2, 3)}
    walks = g.random_walk([0, 1], walk_len=5, seed=11)
    for start, walk in zip([0, 1], walks):
        prev = start
        for v in walk:
            if v < 0:
                break
            assert (prev, int(v)) in edges
            prev = int(v)
    # node 3 is a sink: walk from 3 is all padding
    assert (g.random_walk([3], 4) == -1).all()


def test_graph_data_generator_static_shapes():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 200, 2000)
    dst = rng.integers(0, 200, 2000)
    g = GraphTable()
    g.add_edges(src, dst)
    g.build(symmetric=True)
    gen = GraphDataGenerator(g, batch_size=64, walk_len=6, window=2,
                             num_neg=3, seed=1)
    batches = list(gen)
    assert len(batches) >= 10
    for c, x, neg in batches:
        assert c.shape == (64,) and x.shape == (64,) and neg.shape == (64, 3)
        assert (c >= 0).all() and (x >= 0).all()
    # epochs reshuffle
    b2 = list(gen)
    assert not np.array_equal(batches[0][0], b2[0][0])


# ------------------------------------------------- node features (local)
def test_node_features_roundtrip():
    g = toy_graph()
    g.set_features([0, 2], [[1.0, 2.0], [3.0, 4.0]])
    assert g.feature_dim == 2
    out = g.get_features([2, 0, 99])
    np.testing.assert_allclose(out, [[3, 4], [1, 2], [0, 0]])  # missing -> 0
    with pytest.raises(ValueError):
        g.set_features([1], [[1.0, 2.0, 3.0]])  # dim mismatch


def test_walk_step_composes_to_random_walk():
    """random_walk == repeated walk_step (the distributed-walk invariant)."""
    g = toy_graph(symmetric=True)
    starts = np.asarray([0, 1, 2, 3], np.int64)
    walks = g.random_walk(starts, walk_len=5, seed=9)
    cur = starts.copy()
    rows = np.arange(starts.size)
    for step in range(5):
        cur = g.walk_step(cur, rows, step, seed=9)
        np.testing.assert_array_equal(cur, walks[:, step])


# ------------------------------------- sharded multi-host graph engine
def random_coo(n_nodes=120, n_edges=1500, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_nodes, n_edges).astype(np.int64),
            rng.integers(0, n_nodes, n_edges).astype(np.int64))


@pytest.fixture(scope="module")
def graph_cluster():
    """Two graph-shard server subprocesses + a connected client, BUILT
    with the canonical random_coo graph so every dependent test is
    self-sufficient (the reference's TestDistBase subprocess-cluster
    pattern, SURVEY §4)."""
    procs, endpoints = launch_graph_servers(2)
    client = DistGraphClient(endpoints)
    src, dst = random_coo()
    client.add_edges(src, dst)
    client.build(symmetric=True)
    yield client
    client.stop_servers()
    client.close()
    for p in procs:
        p.wait(timeout=10)


def test_dist_graph_parity_with_single_host(graph_cluster):
    """The sharded store is observationally identical to the single-host
    store: same node set, per-node degrees, bit-identical neighbor samples
    and hop-by-hop random walks (each node's adjacency lives wholly on its
    owner shard, and sampling/hopping is deterministic per node)."""
    src, dst = random_coo()
    local = GraphTable()
    local.add_edges(src, dst)
    local.build(symmetric=True)

    assert graph_cluster.num_nodes == local.num_nodes
    assert graph_cluster.num_edges == local.num_edges
    np.testing.assert_array_equal(graph_cluster.node_ids(),
                                  np.sort(local.node_ids()))
    for k in [0, 5, 77, 119]:
        assert graph_cluster.degree(k) == local.degree(k)

    nodes = np.asarray([0, 3, 50, 111, 999], np.int64)  # 999 unknown
    nb_d, ct_d = graph_cluster.sample_neighbors(nodes, 8, seed=5)
    nb_l, ct_l = local.sample_neighbors(nodes, 8, seed=5)
    np.testing.assert_array_equal(nb_d, nb_l)
    np.testing.assert_array_equal(ct_d, ct_l)

    starts = np.arange(40, dtype=np.int64)
    np.testing.assert_array_equal(graph_cluster.random_walk(starts, 6, seed=3),
                                  local.random_walk(starts, 6, seed=3))


def test_dist_graph_features(graph_cluster):
    """Features route to each node's owner shard and come back verbatim;
    missing nodes zero-fill — GpuPsCommGraphFea payload semantics."""
    rng = np.random.default_rng(7)
    keys = np.arange(0, 120, dtype=np.int64)
    feats = rng.normal(size=(120, 16)).astype(np.float32)
    graph_cluster.set_features(keys, feats)
    assert graph_cluster.feature_dim == 16
    got = graph_cluster.get_features(keys[::-1])
    np.testing.assert_array_equal(got, feats[::-1])
    # a miss zero-fills, hits around it unaffected
    got = graph_cluster.get_features([5, 100000, 6])
    np.testing.assert_array_equal(got[0], feats[5])
    np.testing.assert_array_equal(got[1], np.zeros(16, np.float32))
    np.testing.assert_array_equal(got[2], feats[6])


def test_sample_with_features_local_and_dist(graph_cluster):
    """graph_neighbor_sample_v3 analogue: samples arrive with feature
    payloads; padding rows carry zero features. Dist == local."""
    src, dst = random_coo()
    local = GraphTable()
    local.add_edges(src, dst)
    local.build(symmetric=True)
    rng = np.random.default_rng(7)
    keys = np.arange(0, 120, dtype=np.int64)
    feats = rng.normal(size=(120, 16)).astype(np.float32)
    local.set_features(keys, feats)  # cluster already has these (same rng)

    nodes = np.asarray([0, 7, 999], np.int64)
    nb_l, ct_l, f_l = local.sample_with_features(nodes, 4, seed=2)
    nb_d, ct_d, f_d = graph_cluster.sample_with_features(nodes, 4, seed=2)
    np.testing.assert_array_equal(nb_l, nb_d)
    np.testing.assert_array_equal(f_l, f_d)
    assert f_l.shape == (3, 4, 16)
    np.testing.assert_array_equal(f_l[2], np.zeros((4, 16)))  # unknown node
    for i in range(2):
        for j in range(4):
            if nb_l[i, j] >= 0:
                np.testing.assert_array_equal(f_l[i, j], feats[nb_l[i, j]])


def test_dist_graph_feeds_deepwalk_generator(graph_cluster):
    """GraphDataGenerator runs unchanged over the sharded client (the
    PGLBox walk-based feed over the distributed engine)."""
    if not getattr(graph_cluster, "_built", False):
        # self-sufficient under -k subset runs: earlier tests normally
        # populate the module-scoped cluster, but must not be required
        src, dst = random_coo()
        graph_cluster.add_edges(src, dst)
        graph_cluster.build()
    gen = GraphDataGenerator(graph_cluster, batch_size=32, walk_len=4,
                             window=2, num_neg=3, seed=1)
    batches = list(gen)
    assert len(batches) >= 5
    ids = set(graph_cluster.node_ids().tolist())
    for c, x, neg in batches[:3]:
        assert c.shape == (32,) and x.shape == (32,) and neg.shape == (32, 3)
        assert set(c.tolist()) <= ids and set(x.tolist()) <= ids


def test_inproc_graph_server_roundtrip():
    """GraphServer can host in-process (single-host multi-shard tests)."""
    srv = GraphServer()
    client = DistGraphClient([("127.0.0.1", srv.port)])
    client.add_edges([0, 0, 1], [1, 2, 2])
    client.build()
    assert client.num_nodes == 3 and client.num_edges == 3
    nb, ct = client.sample_neighbors([0], 4)
    assert set(nb[0][nb[0] >= 0].tolist()) == {1, 2} and ct[0] == 2
    client.close()
    srv.stop()


# ------------------------------------------------------------- geometric
def test_send_u_recv_sum_mean():
    x = jnp.asarray([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])
    src = jnp.asarray([0, 1, 2, 0])
    dst = jnp.asarray([1, 2, 1, 0])
    out = G.send_u_recv(x, src, dst, "sum")
    np.testing.assert_allclose(out, [[1, 2], [6, 8], [3, 4]])
    out = G.send_u_recv(x, src, dst, "mean")
    np.testing.assert_allclose(out, [[1, 2], [3, 4], [3, 4]])


def test_send_u_recv_max_empty_segment_zero():
    x = jnp.asarray([[1.0], [2.0]])
    out = G.send_u_recv(x, jnp.asarray([0]), jnp.asarray([0]), "max",
                        out_size=3)
    np.testing.assert_allclose(out, [[1.0], [0.0], [0.0]])


def test_send_ue_recv_and_send_uv():
    x = jnp.asarray([[1.0], [2.0]])
    e = jnp.asarray([[10.0], [20.0]])
    out = G.send_ue_recv(x, e, jnp.asarray([0, 1]), jnp.asarray([0, 0]),
                         "mul", "sum")
    np.testing.assert_allclose(out, [[50.0], [0.0]])
    uv = G.send_uv(x, x, jnp.asarray([0, 1]), jnp.asarray([1, 0]), "add")
    np.testing.assert_allclose(uv, [[3.0], [3.0]])


def test_send_u_recv_differentiable():
    import jax

    x = jnp.ones((3, 2))
    src = jnp.asarray([0, 1, 2])
    dst = jnp.asarray([0, 0, 1])

    def f(x):
        return G.send_u_recv(x, src, dst, "sum").sum()

    g = jax.grad(f)(x)
    np.testing.assert_allclose(g, np.ones((3, 2)))


def test_sample_neighbors_csc():
    # CSC: node 0 has neighbors [1,2], node 1 has [2], node 2 none
    row = np.asarray([1, 2, 2], np.int64)
    colptr = np.asarray([0, 2, 3, 3], np.int64)
    out, cnt = G.sample_neighbors(row, colptr, [0, 1, 2], sample_size=5)
    assert cnt.tolist() == [2, 1, 0]
    assert set(out[:2].tolist()) == {1, 2} and out[2] == 2


def test_reindex_graph():
    src, dst, nodes = G.reindex_graph(
        x=[10, 20], neighbors=[30, 20, 10, 40], count=[2, 2])
    assert nodes.tolist() == [10, 20, 30, 40]
    assert src.tolist() == [2, 1, 0, 3]
    assert dst.tolist() == [0, 0, 1, 1]


def test_khop_sampler():
    # chain 0->1->2->3 in CSC form: neighbors(i) = {i+1}
    row = np.asarray([1, 2, 3], np.int64)
    colptr = np.asarray([0, 1, 2, 3, 3], np.int64)
    src, dst, table = G.khop_sampler(row, colptr, [0], [1, 1])
    assert table.tolist() == [0, 1, 2]
    # hop edges: 1->0 (local 1->0), 2->1 (local 2->1)
    assert src.tolist() == [1, 2]
    assert dst.tolist() == [0, 1]


def test_segment_pool():
    x = jnp.asarray([[1.0], [2.0], [3.0]])
    out = G.segment_pool(x, jnp.asarray([0, 0, 1]), "mean")
    np.testing.assert_allclose(out, [[1.5], [3.0]])


# ----------------------------------------------- weighted graphs (r3)
def test_weighted_sampling_bias():
    """Edge weights bias replace-sampling and walks toward heavy edges
    (the reference CSR's weight payloads)."""
    g = GraphTable()
    g.add_edges([0, 0], [1, 2], weights=[9.0, 1.0])
    g.build()
    nb, cnt = g.sample_neighbors([0], sample_size=400, replace=True, seed=5)
    frac1 = (np.asarray(nb[0]) == 1).mean()
    assert 0.8 < frac1 < 0.98, frac1  # ~0.9 expected
    # weighted hops: most walks step to node 1
    walks = g.random_walk(np.zeros(500, np.int64), walk_len=1, seed=3)
    frac1 = (np.asarray(walks[:, 0]) == 1).mean()
    assert 0.8 < frac1 < 0.98, frac1
    # weighted without replacement (A-Res) heavily prefers heavy edges
    g2 = GraphTable()
    g2.add_edges(np.zeros(20, np.int64), np.arange(1, 21),
                 weights=[100.0] * 2 + [0.01] * 18)
    g2.build()
    nb2, _ = g2.sample_neighbors([0], sample_size=2, seed=7)
    assert set(np.asarray(nb2[0]).tolist()) == {1, 2}


def test_weighted_dist_graph_parity(graph_cluster):
    """Sharded weighted store matches single-host: deterministic weighted
    hops are bit-identical; weighted sampling draws the same rows."""
    rng = np.random.default_rng(3)
    src = rng.integers(0, 60, 600).astype(np.int64)
    dst = rng.integers(0, 60, 600).astype(np.int64)
    w = rng.uniform(0.1, 5.0, 600).astype(np.float32)
    local = GraphTable()
    local.add_edges(src, dst, weights=w)
    local.build(symmetric=True)
    graph_cluster.clear_edges()  # module fixture carries earlier graphs
    graph_cluster.add_edges(src, dst, weights=w)
    graph_cluster.build(symmetric=True)
    starts = np.arange(40, dtype=np.int64)
    np.testing.assert_array_equal(
        graph_cluster.random_walk(starts, 5, seed=11),
        local.random_walk(starts, 5, seed=11))
    nb_d, ct_d = graph_cluster.sample_neighbors(starts, 6, replace=True,
                                                seed=2)
    nb_l, ct_l = local.sample_neighbors(starts, 6, replace=True, seed=2)
    np.testing.assert_array_equal(nb_d, nb_l)
    np.testing.assert_array_equal(ct_d, ct_l)


def test_khop_sampler_from_store_local_vs_sharded(graph_cluster):
    """Multi-hop GNN minibatch over the graph STORE: the sampled subgraph
    (edges + node table + features) is identical on the single-host table
    and the 2-server sharded client — the GpuPs khop path restated."""
    from paddle_tpu import geometric as G

    src, dst = random_coo(n_nodes=80, n_edges=800, seed=9)
    local = GraphTable()
    local.add_edges(src, dst)
    local.build(symmetric=True)
    rngf = np.random.default_rng(1)
    # dim 16: the module-scoped cluster's feature table fixed its dim in
    # an earlier test (first set_features wins)
    feats = rngf.normal(size=(80, 16)).astype(np.float32)
    local.set_features(np.arange(80), feats)

    graph_cluster.clear_edges()
    graph_cluster.add_edges(src, dst)
    graph_cluster.build(symmetric=True)
    graph_cluster.set_features(np.arange(80), feats)

    seeds = np.asarray([0, 3, 11], np.int64)
    es_l, ed_l, idx_l, f_l = G.khop_sampler_from_store(
        local, seeds, [4, 3], seed=5, with_features=True)
    es_d, ed_d, idx_d, f_d = G.khop_sampler_from_store(
        graph_cluster, seeds, [4, 3], seed=5, with_features=True)
    np.testing.assert_array_equal(es_l, es_d)
    np.testing.assert_array_equal(ed_l, ed_d)
    np.testing.assert_array_equal(idx_l, idx_d)
    np.testing.assert_array_equal(f_l, f_d)
    # structure sanity: every edge endpoint indexes the node table, seeds
    # occupy the first rows
    assert idx_l[:3].tolist() == seeds.tolist()
    assert es_l.max(initial=-1) < idx_l.size
    assert f_l.shape == (idx_l.size, 16)

    # and the minibatch feeds message passing end-to-end
    import jax.numpy as jnp

    h = G.send_u_recv(jnp.asarray(f_l), jnp.asarray(es_l), jnp.asarray(ed_l),
                      "mean", out_size=idx_l.size)
    assert np.asarray(h).shape == (idx_l.size, 16)


def test_graph_bench_tool_smoke():
    """tools/graph_bench.py (the scale-proof harness) stays runnable: tiny
    graph, all sections produce positive numbers."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graph_bench.py"),
         "--edges", "20000", "--iters", "3"],
        capture_output=True, text=True, timeout=420, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-800:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    for section in ("single_host", "two_shard"):
        for metric, v in data[section].items():
            assert v > 0, (section, metric, data)
    assert data["feed_train_overlap"]["overlapped_s"] > 0


def test_multi_hop_walk_uses_fewer_rpc_rounds(graph_cluster):
    """The server-side multi-hop walk (VERDICT r4 item 4) must pay one
    scatter-gather round per shard-CROSSING, not one per hop: for 2
    uniform shards a walker crosses with p~=0.5 per hop, so a
    walk_len=20 walk should need ~11 rounds, and must stay well under
    the old per-hop protocol's 20. (Wall-clock parity on this 1-core
    host is bounded by total work; the round count is the mechanism.)"""
    src, dst = random_coo(seed=3)
    graph_cluster.clear_edges()  # module fixture: drop prior tests' edges
    graph_cluster.add_edges(src, dst)
    graph_cluster.build(symmetric=True)
    starts = graph_cluster.node_ids()[:64]

    rounds = []
    orig = graph_cluster._request_multi

    def counting(reqs):
        rounds.append(len(reqs))
        return orig(reqs)

    graph_cluster._request_multi = counting
    try:
        walks = graph_cluster.random_walk(starts, walk_len=20, seed=5)
    finally:
        graph_cluster._request_multi = orig
    assert walks.shape == (64, 20)
    # every round advances every active walker >= 1 hop; crossings gate
    # the count. 16 leaves slack over the ~11 expectation without ever
    # tolerating per-hop behavior (20).
    assert 1 <= len(rounds) <= 16, rounds

    # and the result still matches the single-host walk bit-for-bit
    local = GraphTable()
    local.add_edges(src, dst)
    local.build(symmetric=True)
    np.testing.assert_array_equal(local.random_walk(starts, 20, seed=5),
                                  walks)
