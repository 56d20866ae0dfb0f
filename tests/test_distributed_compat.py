"""paddle.distributed API long tail (r4): groups, P2P over RPC,
reduce/scatter in shard_map, group_sharded_parallel, stream module,
entry configs (reference python/paddle/distributed/__init__.py)."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
import paddle_tpu.distributed as dist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_groups_env_mode():
    g = dist.new_group([0, 1, 2])
    assert dist.get_group(g.id) is g
    assert g.nranks == 3 and g.get_group_rank(1) == 1
    dist.destroy_process_group(g)
    assert dist.get_group(g.id) is None
    env = dist.ParallelEnv()
    assert env.rank == 0 and env.world_size >= 1
    assert dist.ParallelMode.SHARDING_PARALLEL == 3
    assert float(np.asarray(dist.wait(jnp.ones(())))) == 1.0


def test_reduce_scatter_in_shard_map():
    from paddle_tpu.framework.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.mesh import init_mesh, set_mesh

    m = init_mesh(dp=8)

    g0 = dist.new_group(list(range(8)), axis="dp")

    def body(x):
        r = dist.reduce(x, dst=2, group=g0)      # Group objects map to axes
        s = dist.scatter(jnp.arange(16.0), src=0, group="dp")
        s2 = dist.scatter(None, [jnp.full((2,), float(i))
                                 for i in range(8)], src=0, group="dp")
        return r, s, s2

    f = shard_map(body, mesh=m, in_specs=(P("dp"),),
                  out_specs=(P("dp"), P("dp"), P("dp")))
    r, s, s2 = f(jnp.ones((8,)))
    r = np.asarray(r)
    assert r[2] == 8.0 and r[0] == 0.0  # kept only on dst
    np.testing.assert_allclose(np.asarray(s), np.arange(16.0))
    # tensor_list form: rank i gets chunk i
    np.testing.assert_allclose(np.asarray(s2),
                               np.repeat(np.arange(8.0), 2))
    # a Group without an axis mapping fails loudly in collectives
    import pytest as _pytest

    bad = dist.new_group([0, 1])
    with _pytest.raises(ValueError, match="mesh-axis"):
        f2 = shard_map(lambda x: dist.reduce(x, group=bad), mesh=m,
                       in_specs=(P("dp"),), out_specs=P("dp"))
        f2(jnp.ones((8,)))
    # alltoall_single delegates; uneven splits refused loudly
    with pytest.raises(NotImplementedError):
        dist.alltoall_single(jnp.ones((8,)), in_split_sizes=[1, 7])
    set_mesh(None)


def test_all_gather_object_single_process():
    out = []
    dist.all_gather_object(out, {"a": 1})
    assert out == [{"a": 1}]


def test_group_sharded_parallel_tags_and_trains():
    from paddle_tpu.distributed.mesh import init_mesh, mesh_scope, set_mesh
    from paddle_tpu.distributed.shard import DistributedTrainStep
    from paddle_tpu.optimizer import AdamW
    import paddle_tpu.nn as nn

    with pytest.raises(ValueError):
        dist.group_sharded_parallel(None, AdamW(learning_rate=1e-3), "bogus")
    pt.seed(0)
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 8))
    opt = AdamW(learning_rate=1e-3)
    model, opt, _ = dist.group_sharded_parallel(model, opt, "p_g_os")
    assert opt._group_sharded_stage == 3
    m = init_mesh(sdp=8)
    with mesh_scope(m):
        step = DistributedTrainStep(
            model, opt, loss_fn=lambda out, b: jnp.mean((out - b[1]) ** 2),
            mesh=m, batch_axes=("sdp",))
        x = np.random.default_rng(0).normal(size=(8, 8)).astype(np.float32)
        l0 = float(np.asarray(step((x, np.tanh(x)))))
        l1 = float(np.asarray(step((x, np.tanh(x)))))
        assert np.isfinite(l0) and l1 < l0
    set_mesh(None)


def test_save_group_sharded_model(tmp_path):
    import paddle_tpu.nn as nn

    pt.seed(1)
    model = nn.Linear(4, 2)
    dist.save_group_sharded_model(model, str(tmp_path / "out"))
    state = pt.load(str(tmp_path / "out" / "model.pdparams"))
    assert "weight" in state


def test_stream_module_and_entries():
    from paddle_tpu.distributed import stream

    # stream variants accept the knobs and delegate
    from paddle_tpu.framework.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.mesh import init_mesh, set_mesh

    m = init_mesh(dp=8)
    f = shard_map(lambda x: stream.all_reduce(x, sync_op=False,
                                              use_calc_stream=True),
                  mesh=m, in_specs=(P("dp"),), out_specs=P("dp"))
    np.testing.assert_allclose(np.asarray(f(jnp.ones((8,)))), 8.0)
    set_mesh(None)

    assert dist.CountFilterEntry(5).accessor_kwargs() == \
        {"min_show_to_keep": 5.0}
    assert dist.ShowClickEntry("s", "c").accessor_kwargs() == \
        {"show_name": "s", "click_name": "c"}
    assert dist.ProbabilityEntry(0.5).accessor_kwargs() == \
        {"admit_probability": 0.5}
    with pytest.raises(NotImplementedError, match="ColumnParallelLinear"):
        dist.split(jnp.ones((2, 4)), (4, 8), "linear")


P2P_WORKER = textwrap.dedent("""
    import sys
    import numpy as np
    from paddle_tpu.distributed import rpc
    import paddle_tpu.distributed as dist

    rank = int(sys.argv[1])
    rpc.init_rpc(name=f"rank{rank}", rank=rank, world_size=2,
                 master_endpoint=sys.argv[2])
    if rank == 0:
        dist.send(np.arange(6, dtype=np.float32), dst=1, tag=7)
        got = dist.recv(src=1, tag=9)
        assert got.tolist() == [5.0], got
        objs = []
        dist.all_gather_object(objs, {"rank": 0})
        assert sorted(o["rank"] for o in objs) == [0, 1], objs
        print("P2P_OK", flush=True)
    else:
        got = dist.recv(src=0, tag=7)
        assert got.tolist() == list(range(6)), got
        reqs = dist.batch_isend_irecv([
            dist.P2POp(dist.isend, np.asarray([5.0]), 0, tag=9)])
        for r in reqs:
            r.wait()
        objs = []
        dist.all_gather_object(objs, {"rank": 1})
    rpc.shutdown()
""")


def test_p2p_over_rpc_two_processes():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    ep = f"127.0.0.1:{probe.getsockname()[1]}"
    probe.close()
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    w1 = subprocess.Popen([sys.executable, "-c", P2P_WORKER, "1", ep],
                          env=env, cwd=REPO)
    try:
        w0 = subprocess.run([sys.executable, "-c", P2P_WORKER, "0", ep],
                            env=env, cwd=REPO, capture_output=True,
                            text=True, timeout=240)
        assert w0.returncode == 0, w0.stderr
        assert "P2P_OK" in w0.stdout
        assert w1.wait(timeout=60) == 0
    finally:
        if w1.poll() is None:
            w1.kill()
            w1.communicate()


def test_p2p_rpc_calls_are_deadline_bounded(monkeypatch):
    """tpu_lint R11 regression: send/all_gather_object must thread an
    explicit timeout into rpc_sync instead of riding the transport's
    120s default — a dead peer fails the caller at ITS deadline."""
    import paddle_tpu.distributed.api_compat as ac
    from paddle_tpu.distributed import rpc

    seen = []

    def fake_rpc_sync(to, fn, args=None, kwargs=None, timeout=None, **kw):
        seen.append(timeout)
        return 0

    monkeypatch.setattr(ac, "_peer_name", lambda r: "w1")
    monkeypatch.setattr(ac, "_my_rank", lambda: 0)
    monkeypatch.setattr(rpc, "rpc_sync", fake_rpc_sync)
    dist.send(np.ones(3, np.float32), dst=1, tag=1, timeout=3.5)
    assert seen == [3.5]
    dist.send(np.ones(3, np.float32), dst=1, tag=1)   # default stays finite
    assert seen[-1] is not None and seen[-1] > 0
