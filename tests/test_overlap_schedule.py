"""Overlap-scheduled gradient reduction (``distributed.overlap``) and the
ZeRO sharded-update path of ``DistributedTrainStep``.

The contract under test: ``overlap_grad_reduce=True`` changes the step's
SCHEDULE (bucketed reverse-backward collective placement + sharded
weight update at ``sharding_stage >= 1``) but never the values it
reduces: the bucket seams are ``optimization_barrier`` chains and
sharding constraints that pass values through untouched, so losses and
reduced gradients are asserted bitwise. What the optimizer then makes of
the same gradients is the same arithmetic compiled in another fusion,
and is held to the last place (``test_overlap_bitwise_parity``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import distributed as dist
from paddle_tpu.distributed import collective as C
from paddle_tpu.distributed.mesh import init_mesh, set_mesh
from paddle_tpu.distributed.overlap import (
    GradBucket, bucket_order, build_buckets, shard_first_free_dim,
    weight_update_specs)
from paddle_tpu.framework.jax_compat import shard_map
from paddle_tpu.optimizer import AdamW
from paddle_tpu.observability.registry import default_registry


@pytest.fixture
def mesh8():
    m = init_mesh(sdp=8)
    yield m
    set_mesh(None)


class MLP(nn.Layer):
    """fc3.bias has shape (4,) — indivisible by sdp=8, so it exercises
    the ZeRO fallback (replicated update for that one param)."""

    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 8)
        self.fc3 = nn.Linear(8, 4)

    def forward(self, x):
        return self.fc3(F.relu(self.fc2(F.relu(self.fc1(x)))))


def _mse(out, batch):
    return ((out - batch[1]) ** 2).mean()


def _data():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    y = rng.standard_normal((16, 4)).astype(np.float32)
    return x, y


def _make_step(stage, overlap, **kw):
    pt.seed(0)
    return dist.DistributedTrainStep(
        MLP(), AdamW(learning_rate=1e-2), loss_fn=_mse,
        sharding_stage=stage, overlap_grad_reduce=overlap,
        bucket_size_mb=0.001, **kw)   # tiny target -> several buckets


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}/"))
        elif hasattr(v, "shape"):
            out[f"{prefix}{k}"] = np.asarray(v)
    return out


def _assert_bitident(a, b):
    fa, fb = _flat(a), _flat(b)
    assert fa.keys() == fb.keys()
    for k in fa:
        np.testing.assert_array_equal(fa[k], fb[k], err_msg=k)


def _assert_last_place(a, b, ulps):
    """Leaf for leaf within ``ulps`` units in the last place of the
    leaf's largest element (a count of ulps of each element would call
    two roundings of a value near zero far apart)."""
    fa, fb = _flat(a), _flat(b)
    assert fa.keys() == fb.keys()
    for k in fa:
        if fa[k].dtype != np.float32:
            np.testing.assert_array_equal(fa[k], fb[k], err_msg=k)
            continue
        unit = np.finfo(np.float32).eps * np.abs(fa[k]).max(initial=0.0)
        np.testing.assert_allclose(fa[k], fb[k], rtol=0, atol=ulps * unit,
                                   err_msg=k)


# ------------------------------------------------------------ bucket logic
def test_bucket_order_is_reverse_backward():
    # grads materialise in reverse declaration order during backward —
    # the first-ready grads must land in the first bucket
    assert bucket_order(["fc1.w", "fc1.b", "fc2.w"]) == \
        ["fc2.w", "fc1.b", "fc1.w"]


def test_build_buckets_deterministic_and_covering():
    sizes = {f"p{i}": 100 * (i + 1) for i in range(7)}
    a = build_buckets(sizes, bucket_bytes=500)
    b = build_buckets(sizes, bucket_bytes=500)
    assert a == b                                   # deterministic
    names = [n for bk in a for n in bk.names]
    assert names == bucket_order(list(sizes))       # covering, in order
    assert all(isinstance(bk, GradBucket) for bk in a)
    assert [bk.index for bk in a] == list(range(len(a)))
    for bk in a:
        assert bk.bytes == sum(sizes[n] for n in bk.names)


def test_build_buckets_count_override():
    sizes = {f"p{i}": 128 for i in range(12)}
    assert len(build_buckets(sizes, bucket_bytes=128, bucket_count=3)) == 3
    assert len(build_buckets(sizes, bucket_bytes=10 ** 9,
                             bucket_count=1)) == 1
    # without the override the byte target rules: 12 singleton buckets
    assert len(build_buckets(sizes, bucket_bytes=128)) == 12


def test_shard_first_free_dim(mesh8):
    # first divisible free dim picked
    spec, ok = shard_first_free_dim(P(), (32, 4), "sdp", mesh8)
    assert ok and spec == P("sdp", None)
    # dim 0 indivisible -> falls through to dim 1
    spec, ok = shard_first_free_dim(P(), (4, 32), "sdp", mesh8)
    assert ok and spec == P(None, "sdp")
    # nothing divisible -> unchanged, not ok
    spec, ok = shard_first_free_dim(P(), (4,), "sdp", mesh8)
    assert not ok and spec == P(None)
    # axis already used by the param's own spec -> kept as-is
    spec, ok = shard_first_free_dim(P("sdp"), (32,), "sdp", mesh8)
    assert ok and spec == P("sdp")


def test_weight_update_specs_reports_fallbacks(mesh8):
    fell = []
    specs = weight_update_specs(
        {"a": P(), "b": P()}, {"a": (32, 8), "b": (3,)}, "sdp", mesh8,
        on_fallback=fell.append)
    assert specs["a"] == P("sdp", None)
    assert specs["b"] == P(None)
    assert fell == ["b"]


# --------------------------------------------------------- schedule surface
def test_collective_schedule_and_statusz(mesh8):
    step = _make_step(1, True)
    sched = step.collective_schedule()
    assert sched, "overlap step must expose its bucket schedule"
    names = [n for b in sched for n in b["params"]]
    assert names == bucket_order(list(step.params))
    sz = step.statusz()
    assert sz["overlap_grad_reduce"] and sz["sharding_stage"] == 1
    assert len(sz["buckets"]) == len(sched)
    # fc3.bias (4,) is indivisible by sdp=8 -> counted, surfaced, metered
    assert "fc3.bias" in sz["zero_fallback_params"]
    counters = default_registry().snapshot()["counters"]
    assert any(k.startswith("distributed.zero_fallback_params_total")
               and v >= 1 for k, v in counters.items())

    serial = _make_step(1, False)
    assert serial.collective_schedule() == []
    assert not serial.statusz()["overlap_grad_reduce"]


def test_bucket_count_knob_reaches_step(mesh8):
    step = _make_step(1, True, bucket_count=2)
    assert len(step.collective_schedule()) == 2


# ------------------------------------------------------------ step parity
@pytest.mark.parametrize("stage", [
    0, 1,
    pytest.param(2, marks=pytest.mark.slow),
    pytest.param(3, marks=pytest.mark.slow),
])
def test_overlap_bitwise_parity(mesh8, stage):
    """The bucketed schedule at every sharding stage is a RESCHEDULE of
    the serial program. Bitwise: the loss of each of three steps, and
    params and opt state after the first, which makes the reduced
    gradients the same bits (from zero moments AdamW's are ``(1 - beta) *
    g`` and ``(1 - beta) * g * g``, products that round once).

    After later steps params and moments agree to the last place, not
    to the bit, and no reduction is at fault: on the 8-device CPU mesh
    both programs hold ONE combined all-reduce with the same operands
    (jax 0.9's compiled text). The barrier keeps a weight gradient's
    transpose ahead of the update where the serial program sinks it into
    the update's fusion, so ``beta * m + (1 - beta) * g`` reaches LLVM
    with its products in another order and the one it contracts into the
    fused multiply-add (which XLA's CPU backend always allows) rounds
    differently: under one unit in the last place of a tensor's largest
    element after three steps at every stage (measured 1.06), held to
    four."""
    x, y = _data()
    serial = _make_step(stage, False)
    bucketed = _make_step(stage, True)
    for step in range(3):
        ls = serial((x, y))
        lb = bucketed((x, y))
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lb))
        if step == 0:
            _assert_bitident(serial.params, bucketed.params)
            _assert_bitident(serial.opt_state, bucketed.opt_state)
    _assert_last_place(serial.params, bucketed.params, ulps=4)
    _assert_last_place(serial.opt_state, bucketed.opt_state, ulps=4)


def test_overlap_grad_accum_parity(mesh8):
    """Gradient merge composes with the bucketed schedule: the sharded
    accumulator feeds the same update as the serial one."""
    x, y = _data()
    serial = _make_step(1, False, grad_accum_steps=2)
    bucketed = _make_step(1, True, grad_accum_steps=2)
    for _ in range(4):                        # two full accumulation cycles
        ls = serial((x, y))
        lb = bucketed((x, y))
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lb))
    _assert_bitident(serial.params, bucketed.params)
    _assert_bitident(serial.opt_state, bucketed.opt_state)


def test_scaler_rollback_restores_sharded_opt_state(mesh8):
    """A watchdog-poisoned step under the bucketed+ZeRO schedule must
    roll back to EXACTLY the pre-step sharded state (params, moments,
    and scale all bit-identical)."""
    from paddle_tpu.amp import GradScaler

    x, y = _data()
    step = _make_step(1, True,
                      scaler=GradScaler(init_loss_scaling=2.0 ** 10,
                                        use_dynamic_loss_scaling=True))
    loss, ok, found = step.watchdog_call((x, y))
    assert bool(ok) and np.isfinite(float(loss))
    before_p = {k: np.asarray(v) for k, v in step.params.items()}
    before_o = _flat(step.opt_state)
    step.inject_anomaly()
    loss, ok, found = step.watchdog_call((x, y))
    assert not bool(ok)
    _assert_bitident(step.params, before_p)
    _assert_bitident(step.opt_state, before_o)


@pytest.mark.slow
def test_overlap_state_reshards_across_dp_resize(mesh8):
    """PR 6 elastic path: a checkpoint written by the bucketed+ZeRO step
    on sdp=8 resumes on sdp=4 (set_state_dict re-places every leaf onto
    the new mesh's declared shardings) and keeps training parity."""
    x, y = _data()
    big = _make_step(1, True)
    ref = _make_step(1, True)
    for _ in range(2):
        big((x, y))
        ref((x, y))
    sd = jax.tree.map(np.asarray, big.state_dict())
    set_mesh(None)
    init_mesh(sdp=4)
    small = _make_step(1, True)
    small.set_state_dict(sd)
    for k, v in small.params.items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(ref.params[k]))
    l_small = float(small((x, y)))
    l_ref = float(ref((x, y)))
    assert np.isfinite(l_small)
    # across topologies the reduction tree changes: parity is numeric
    np.testing.assert_allclose(l_small, l_ref, rtol=1e-5, atol=1e-6)


# ------------------------------------------------- explicit-SPMD analogue
def test_all_reduce_buckets_matches_mapped_all_reduce(mesh8):
    xs = [jnp.arange(8.0) + i for i in range(3)]

    def bucketed(*vs):
        return tuple(C.all_reduce_buckets(vs, group="sdp"))

    def mapped(*vs):
        return tuple(C.all_reduce(v, group="sdp") for v in vs)

    specs = (P("sdp"),) * 3
    fb = shard_map(bucketed, mesh=mesh8, in_specs=specs, out_specs=specs)
    fm = shard_map(mapped, mesh=mesh8, in_specs=specs, out_specs=specs)
    for got, want in zip(fb(*xs), fm(*xs)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
