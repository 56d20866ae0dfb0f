"""Heterogeneous CPU<->TPU stage pipeline tests (VERDICT r2 missing #7):
in-process section-queue overlap, loss parity with the unpipelined loop,
and the multi-process RPC-backed heter-worker split
(HeterPipelineTrainer / HeterClient-HeterServer, trainer.h:345)."""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import HeterPipelineTrainer
from paddle_tpu.framework.jit import TrainStep
from paddle_tpu.optimizer import SGD

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_heter_pipeline_overlaps_stages():
    """CPU stage for batch N+1 overlaps compute for batch N: wall time of
    the pipelined loop is well under the sequential sum."""
    def cpu_stage(b):
        time.sleep(0.05)
        return b * 2

    def step(staged):
        time.sleep(0.05)
        return staged + 1

    batches = list(range(8))
    t0 = time.perf_counter()
    seq = [step(cpu_stage(b)) for b in batches]
    t_seq = time.perf_counter() - t0

    trainer = HeterPipelineTrainer(cpu_stage, step, prefetch_depth=3)
    t0 = time.perf_counter()
    out = trainer.run(batches)
    t_pipe = time.perf_counter() - t0
    trainer.stop()
    assert out == seq  # order + values preserved
    assert t_pipe < t_seq * 0.8, (t_pipe, t_seq)


def test_heter_pipeline_training_parity():
    """Sparse-pull CPU stage + compiled dense TPU step: losses are
    bit-identical to the unpipelined loop (ordering preserved)."""
    from paddle_tpu.distributed.ps import MemorySparseTable

    pt.seed(0)
    table = MemorySparseTable(embed_dim=8, optimizer="sgd",
                              learning_rate=0.5, seed=3)
    rng = np.random.default_rng(0)
    one = (rng.integers(0, 100, 16).astype(np.int64),
           rng.integers(0, 4, 16))
    batches = [one] * 6  # fixed batch: loss must fall monotonically

    def cpu_stage(batch):
        ids, labels = batch
        return table.pull(ids), labels  # host-side sparse stage

    pt.seed(1)
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))
    step = TrainStep(model, SGD(learning_rate=0.1),
                     loss_fn=lambda out, b: F.cross_entropy(out, b[1]))
    trainer = HeterPipelineTrainer(cpu_stage, step, prefetch_depth=2)
    pipe_losses = [float(np.asarray(l)) for l in trainer.run(batches)]
    trainer.stop()

    pt.seed(1)
    model2 = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))
    step2 = TrainStep(model2, SGD(learning_rate=0.1),
                      loss_fn=lambda out, b: F.cross_entropy(out, b[1]))
    ref_losses = [float(np.asarray(step2(cpu_stage(b)))) for b in batches]
    np.testing.assert_allclose(pipe_losses, ref_losses, rtol=1e-6)
    assert pipe_losses[-1] < pipe_losses[0]


def test_heter_pipeline_cpu_stage_error_propagates():
    def cpu_stage(b):
        if b == 2:
            raise ValueError("bad batch")
        return b

    trainer = HeterPipelineTrainer(cpu_stage, lambda s: s, prefetch_depth=2)
    with pytest.raises(ValueError, match="bad batch"):
        trainer.run(range(4))
    trainer.stop()


HETER_WORKER = textwrap.dedent("""
    import sys
    from paddle_tpu.distributed import rpc

    rank = int(sys.argv[1])
    rpc.init_rpc(name=f"worker{rank}", rank=rank, world_size=3,
                 master_endpoint=sys.argv[2])
    # heter workers just serve RPCs until shutdown's barrier releases
    rpc.shutdown()
""")

TRAINER = textwrap.dedent("""
    import sys
    import numpy as np
    from paddle_tpu.distributed import HeterPipelineTrainer, rpc
    from tests.heter_stage import cpu_stage

    rpc.init_rpc(name="worker0", rank=0, world_size=3,
                 master_endpoint=sys.argv[1])
    trainer = HeterPipelineTrainer(cpu_stage, lambda s: float(s.sum()),
                                   prefetch_depth=2,
                                   heter_workers=["worker1", "worker2"])
    out = trainer.run([np.full((4,), i, np.float32) for i in range(6)])
    assert out == [i * 4.0 * 3 for i in range(6)], out
    print("HETER_RPC_OK", flush=True)
    trainer.stop()
    rpc.shutdown()
""")


def test_heter_pipeline_rpc_workers(tmp_path):
    """The multi-host split: CPU stages execute on remote heter workers by
    name over RPC; the trainer only sees dense staged tensors."""
    stage_mod = os.path.join(REPO, "tests", "heter_stage.py")
    with open(stage_mod, "w") as f:
        f.write("import numpy as np\n\n\n"
                "def cpu_stage(batch):\n"
                "    return np.asarray(batch) * 3.0\n")
    try:
        import socket as socket_mod

        probe = socket_mod.socket()
        probe.bind(("127.0.0.1", 0))
        master = f"127.0.0.1:{probe.getsockname()[1]}"
        probe.close()
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        w_script = tmp_path / "w.py"
        w_script.write_text(HETER_WORKER)
        t_script = tmp_path / "t.py"
        t_script.write_text(TRAINER)
        workers = [subprocess.Popen(
            [sys.executable, str(w_script), str(r), master], env=env,
            cwd=REPO) for r in (1, 2)]
        trainer = subprocess.run(
            [sys.executable, str(t_script), master], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=360)
        assert trainer.returncode == 0, trainer.stderr
        assert "HETER_RPC_OK" in trainer.stdout
        for w in workers:
            assert w.wait(timeout=60) == 0
    finally:
        os.unlink(stage_mod)


def test_rpc_executor_bounds_stage_calls(monkeypatch):
    """tpu_lint R11 regression: the heter RPC executor passes its
    rpc_timeout into every stage call (a dead heter worker must fail
    the micro-batch at the trainer's deadline, not hang 120s)."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.heter import _RpcExecutor

    seen = []

    def fake_rpc_async(to, fn, args=None, kwargs=None, timeout=None, **kw):
        seen.append((to, timeout))
        return "fut"

    monkeypatch.setattr(rpc, "rpc_async", fake_rpc_async)
    ex = _RpcExecutor(lambda b: b, ["w1", "w2"], rpc_timeout=7.0)
    assert ex.submit([1]) == "fut"
    assert ex.submit([2]) == "fut"
    assert seen == [("w1", 7.0), ("w2", 7.0)]
