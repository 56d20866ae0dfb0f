"""Hybrid parallelism as configuration: dp x mp (+ ZeRO-2) on a device mesh.

This demo builds an 8-device VIRTUAL CPU mesh — exactly how the test
suite validates every sharding in CI, on any machine. On a real pod
slice, drop the ``set_device("cpu")`` line and the same code lays the
mesh over the physical chips.

    python examples/hybrid_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np


def main():
    import paddle_tpu as pt

    # the demo mesh is the virtual CPU one; flip BEFORE any array op
    # (on a real slice, remove this line)
    pt.set_device("cpu")
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.optimizer import AdamW

    from paddle_tpu.distributed.parallel.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)

    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
    s.sharding = True
    s.sharding_configs = {"stage": 2}      # ZeRO-2 over the dp axis
    fleet.init(strategy=s)

    pt.seed(0)
    # TP is explicit layer choice, exactly like the reference's
    # fleet.meta_parallel mpu layers: Column splits the output dim across
    # the mp axis, Row splits the input dim and reduces — XLA inserts the
    # collectives from the sharding annotations
    model = nn.Sequential(ColumnParallelLinear(64, 256), nn.ReLU(),
                          RowParallelLinear(256, 10))
    opt = AdamW(learning_rate=1e-3)
    step = fleet.distributed_model(
        model, opt, loss_fn=lambda out, b: F.cross_entropy(out, b[1]))

    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 64)).astype(np.float32)  # 32 % dp==0
    y = rng.integers(0, 10, 32)
    for i in range(10):
        loss = step((x, y))
        if i % 3 == 0:
            print(f"step {i}  loss {float(loss):.4f}")

    # the mesh placement is real: inspect the weight shardings
    for name, p in step.params.items():
        if getattr(p, "ndim", 0) == 2:
            print(f"param {name!r} sharding: {p.sharding.spec}")


if __name__ == "__main__":
    main()
