"""Recompile-proof input pipeline: shape bucketing, tail padding, async
device prefetch, compile-cache accounting, retrace guard.

Acceptance anchor (ISSUE 2): a CPU fit loop over a ragged dataset with 3
sequence lengths compiles <= (1 + #buckets) programs with stabilization on
(vs one compile per distinct shape off), asserted via ``cache_stats()``;
the prefetch iterator demonstrably overlaps and shuts down leak-free.
"""
import gc
import itertools
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework import compile_cache
from paddle_tpu.framework.jit import TrainStep
from paddle_tpu.io import (DataLoader, Dataset, PaddedBatcher, bucket_for,
                           default_collate_fn, prefetch_to_device)
from paddle_tpu.io.dataloader import _PrefetchIterator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ fixtures
LENGTHS = (12, 20, 28)
BUCKETS = (16, 32)


class RaggedDataset(Dataset):
    """(ids[L], label): lengths in blocks of 8 samples (two batches of 4),
    22 samples total -> ragged tail batch of 2."""

    def __len__(self):
        return 22

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        L = LENGTHS[min(i // 8, len(LENGTHS) - 1)]
        return (np.asarray(rng.integers(1, 64, L), np.int64),
                np.int64(i % 4))


class TinyClassifier(nn.Layer):
    def __init__(self):
        super().__init__()
        self.embed = nn.Embedding(64, 16)
        self.head = nn.Linear(16, 4)

    def forward(self, ids):
        return self.head(self.embed(ids).mean(axis=1))


# ------------------------------------------------- collate fn satellites
class TestDefaultCollate:
    def test_bool_scalars_stay_bool(self):
        out = default_collate_fn([True, False, True])
        assert out.dtype == np.bool_
        np.testing.assert_array_equal(out, [True, False, True])

    def test_numpy_bool_scalars_stay_bool(self):
        out = default_collate_fn([np.bool_(True), np.bool_(False)])
        assert out.dtype == np.bool_

    def test_numpy_generic_preserves_dtype(self):
        out = default_collate_fn([np.int16(1), np.int16(2)])
        assert out.dtype == np.int16
        out = default_collate_fn([np.float16(0.5), np.float16(1.5)])
        assert out.dtype == np.float16

    def test_empty_batch_raises_value_error(self):
        with pytest.raises(ValueError, match="empty batch"):
            default_collate_fn([])

    def test_python_numbers_unchanged(self):
        assert default_collate_fn([1, 2, 3]).dtype.kind == "i"
        assert default_collate_fn([1.0, 2.0]).dtype.kind == "f"


# ------------------------------------------------------- shape bucketing
class TestBucketing:
    def test_bucket_for_smallest_fit(self):
        assert bucket_for(1, (16, 32)) == 16
        assert bucket_for(16, (16, 32)) == 16
        assert bucket_for(17, (16, 32)) == 32
        assert bucket_for(32, (16, 32)) == 32

    def test_bucket_for_overflow_ladder(self):
        # beyond the top bucket: next multiple of it (bounded shape set)
        assert bucket_for(33, (16, 32)) == 64
        assert bucket_for(65, (16, 32)) == 96

    def test_bucket_for_order_independent(self):
        for L in range(1, 70):
            assert bucket_for(L, (32, 16)) == bucket_for(L, (16, 32))

    def test_bucket_for_deterministic(self):
        sigs = {bucket_for(L, BUCKETS) for L in LENGTHS}
        assert sigs == {16, 32}
        # same length -> same bucket, every time
        assert all(bucket_for(20, BUCKETS) == 32 for _ in range(10))

    def test_bucket_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            bucket_for(5, (0, 16))

    def test_batch_level_bucket_is_max_sample(self):
        b = PaddedBatcher(batch_size=2, pad_batches=False,
                          length_buckets=BUCKETS)
        out = b([(np.zeros(12, np.int64), np.int64(0)),
                 (np.zeros(20, np.int64), np.int64(1))])
        assert out[0].shape == (2, 32)  # 20 -> bucket 32 rules the batch

    def test_length_fields_protects_fixed_size_features(self):
        # (ids[L], soft_label[10]): only field 0 carries the seq axis;
        # without length_fields the 10-vector would be padded to the bucket
        b = PaddedBatcher(batch_size=2, pad_batches=False,
                          length_buckets=(16,), length_fields=(0,))
        out = b([(np.zeros(12, np.int64), np.ones(10, np.float32)),
                 (np.zeros(9, np.int64), np.ones(10, np.float32))])
        assert out[0].shape == (2, 16)
        assert out[1].shape == (2, 10)  # untouched


# ----------------------------------------------------- tail-batch padding
class TestTailPadding:
    def test_tail_padded_and_masked(self):
        loader = DataLoader(RaggedDataset(), batch_size=4, shuffle=False,
                            pad_batches=True, length_buckets=BUCKETS)
        batches = list(loader)
        assert len(batches) == 6
        shapes = {b[0].shape for b in batches}
        assert shapes == {(4, 16), (4, 32)}  # every batch full-size
        # all non-tail masks fully valid
        for b in batches[:-1]:
            np.testing.assert_array_equal(b[-1], [True] * 4)
        ids, label, mask = batches[-1]
        np.testing.assert_array_equal(mask, [True, True, False, False])
        assert mask.dtype == np.bool_
        # filler rows repeat the last REAL sample (finite losses, no junk)
        np.testing.assert_array_equal(ids[2], ids[1])
        np.testing.assert_array_equal(ids[3], ids[1])
        assert label[2] == label[1]

    def test_mask_emitted_for_every_batch(self):
        # batch structure must be shape-stable: the mask is appended even
        # when nothing was padded
        loader = DataLoader(RaggedDataset(), batch_size=2, shuffle=False,
                            pad_batches=True, length_buckets=(32,))
        for b in loader:
            assert len(b) == 3 and b[-1].dtype == np.bool_

    def test_sequence_padding_zero_filled(self):
        b = PaddedBatcher(batch_size=4, pad_batches=True,
                          length_buckets=(16,), pad_value=0)
        out = b([(np.ones(10, np.int64), np.int64(1))])
        ids, label, mask = out
        assert ids.shape == (4, 16)
        np.testing.assert_array_equal(ids[0, 10:], np.zeros(6, np.int64))
        np.testing.assert_array_equal(mask, [True, False, False, False])

    def test_empty_batch_raises(self):
        with pytest.raises(ValueError):
            PaddedBatcher(batch_size=4)([])

    def test_padding_through_worker_processes(self):
        loader = DataLoader(RaggedDataset(), batch_size=4, shuffle=False,
                            num_workers=2, pad_batches=True,
                            length_buckets=BUCKETS)
        shapes = {b[0].shape for b in loader}
        assert shapes == {(4, 16), (4, 32)}

    def test_drop_last_needs_no_padding(self):
        loader = DataLoader(RaggedDataset(), batch_size=4, shuffle=False,
                            drop_last=True, pad_batches=True,
                            length_buckets=BUCKETS)
        batches = list(loader)
        assert len(batches) == 5
        assert all(bool(b[-1].all()) for b in batches)


# -------------------------------------------------- prefetch iterator
class TestPrefetchIterator:
    def test_values_and_order(self):
        it = _PrefetchIterator(iter(range(10)), depth=3)
        assert list(it) == list(range(10))

    def test_overlap_producer_runs_ahead(self):
        """Producer timestamps precede consumer step completion — the
        pipeline actually overlaps production with consumption."""
        produced = {}

        def stamp(x):
            produced[x] = time.perf_counter()
            return x

        it = _PrefetchIterator(iter(range(5)), depth=2, transform=stamp)
        completed = {}
        for x in it:
            time.sleep(0.03)  # simulated device step
            completed[x] = time.perf_counter()
        for n in range(1, 5):
            assert produced[n] < completed[n - 1], (
                f"batch {n} was not produced while batch {n - 1} was "
                f"still being consumed")

    def test_error_delivered_promptly(self):
        """A producer exception surfaces on the NEXT __next__, not after
        the queued batches drain."""

        def gen():
            yield 1
            yield 2
            raise RuntimeError("producer boom")

        it = _PrefetchIterator(gen(), depth=8)
        deadline = time.monotonic() + 5.0
        while it._state.err is None and time.monotonic() < deadline:
            time.sleep(0.01)  # let the producer run to its exception
        with pytest.raises(RuntimeError, match="producer boom"):
            next(it)  # queued 1, 2 must NOT be yielded first
        with pytest.raises(StopIteration):
            next(it)

    def test_error_midstream(self):
        """Items consumed before the failure flow normally; the error
        arrives on the next request after it happens. The gate makes the
        ordering deterministic (no race between consume and fail)."""
        gate = threading.Event()

        def gen():
            yield "ok"
            gate.wait(5.0)
            raise ValueError("later")

        it = _PrefetchIterator(gen(), depth=1)
        assert next(it) == "ok"
        gate.set()
        with pytest.raises(ValueError, match="later"):
            next(it)

    def test_close_unblocks_and_joins(self):
        # infinite producer parked on the bounded queue
        it = _PrefetchIterator(itertools.count(), depth=2)
        assert next(it) == 0
        th = it._thread
        it.close()
        assert not th.is_alive()
        with pytest.raises(StopIteration):
            next(it)
        it.close()  # idempotent

    def test_abandoned_iterator_does_not_leak_thread(self):
        it = _PrefetchIterator(itertools.count(), depth=2)
        next(it)
        th = it._thread
        del it
        gc.collect()
        th.join(timeout=5.0)
        assert not th.is_alive()

    def test_exhaustion_joins_thread(self):
        it = _PrefetchIterator(iter(range(3)), depth=2)
        list(it)
        it._thread.join(timeout=5.0)
        assert not it._thread.is_alive()

    def test_stats_track_stall(self):
        it = _PrefetchIterator(iter(range(4)), depth=2)
        list(it)
        s = it.stats()
        assert s["batches"] == 4
        assert s["consumer_stall_s"] >= 0.0


# -------------------------------------------------- device prefetch
class TestDevicePrefetch:
    def test_values_on_device(self):
        import jax

        batches = [(np.full((2, 3), i, np.float32), np.int64(i))
                   for i in range(4)]
        it = prefetch_to_device(iter(batches), depth=2)
        out = list(it)
        assert len(out) == 4
        for i, (x, y) in enumerate(out):
            assert isinstance(x, jax.Array)
            np.testing.assert_array_equal(np.asarray(x), batches[i][0])
            assert int(y) == i
        it.close()

    def test_sharded_landing(self):
        """With a sharding, batches land in their GSPMD layout directly
        (make_array_from_process_local_data; 8 virtual CPU devices)."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        sh = NamedSharding(mesh, PartitionSpec("dp"))
        batches = [np.arange(16, dtype=np.float32).reshape(8, 2) + i
                   for i in range(3)]
        it = prefetch_to_device(iter(batches), depth=2, sharding=sh)
        out = list(it)
        assert len(out) == 3
        for i, x in enumerate(out):
            np.testing.assert_array_equal(np.asarray(x), batches[i])
            assert x.sharding.is_equivalent_to(sh, x.ndim)

    def test_mesh_spec_spelling(self):
        import jax
        from jax.sharding import Mesh, PartitionSpec

        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        it = prefetch_to_device(iter([np.zeros((8, 2), np.float32)]),
                                mesh=mesh, spec=PartitionSpec("dp"))
        (x,) = list(it)
        assert {d.id for d in x.sharding.device_set} == {
            d.id for d in jax.devices()}

    def test_sharded_landing_clips_spec_for_low_rank_mask(self):
        # (ids[B,S], label[B], mask[B]) under a rank-2 spec: the rank-1
        # riders take the clipped spec instead of crashing
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        sh = NamedSharding(mesh, PartitionSpec("dp", None))
        loader = DataLoader(RaggedDataset(), batch_size=8, shuffle=False,
                            pad_batches=True, length_buckets=(32,))
        it = prefetch_to_device(iter(loader), depth=2, sharding=sh)
        batches = list(it)
        assert len(batches) == 3
        ids, label, mask = batches[-1]
        assert ids.sharding.is_equivalent_to(sh, ids.ndim)
        assert len(mask.shape) == 1 and len(label.shape) == 1
        assert np.asarray(mask).sum() == 6  # 22 = 8+8+6 real rows

    def test_mesh_without_spec_rejected(self):
        # a replicated default would silently diverge on multi-host
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        with pytest.raises(ValueError, match="spec"):
            prefetch_to_device(iter([np.zeros(4)]), mesh=mesh)

    def test_through_dataloader(self):
        loader = DataLoader(RaggedDataset(), batch_size=4, shuffle=False,
                            pad_batches=True, length_buckets=BUCKETS)
        it = prefetch_to_device(iter(loader), depth=2)
        n = 0
        for ids, label, mask in it:
            assert ids.shape[1] in BUCKETS
            n += 1
        assert n == 6


# ------------------------------------------- compile cache + retrace guard
def _make_step():
    class M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 2)

        def forward(self, x):
            return self.fc(x).mean()

    return TrainStep(M(), pt.optimizer.SGD(learning_rate=0.1))


class TestCompileCache:
    def test_cache_stats_counts_traces_and_hits(self):
        step = _make_step()
        x = np.ones((4, 8), np.float32)
        step(x)
        step(x)
        step(x)
        s = step.cache_stats()
        assert s["compiles"] == 1
        assert s["calls"] == 3
        assert s["cache_hits"] == 2
        assert "float32(4, 8)" in s["last_trace_signature"]

    def test_new_shape_is_new_compile(self):
        step = _make_step()
        step(np.ones((4, 8), np.float32))
        step(np.ones((2, 8), np.float32))
        s = step.cache_stats()
        assert s["compiles"] == 2
        assert len(s["signatures"]) == 2

    def test_retrace_guard_catches_shape_change(self):
        step = _make_step()
        step(np.ones((4, 8), np.float32))  # warmup
        with compile_cache.retrace_guard(max_compiles=0):
            step(np.ones((4, 8), np.float32))  # cached: fine
            with pytest.raises(compile_cache.RetraceError,
                               match="pad/bucket"):
                step(np.ones((3, 8), np.float32))  # injected shape change

    def test_retrace_guard_budget(self):
        step = _make_step()
        with compile_cache.retrace_guard(max_compiles=1):
            step(np.ones((4, 8), np.float32))  # the one budgeted compile

    def test_retrace_guard_warn_mode(self):
        step = _make_step()
        step(np.ones((4, 8), np.float32))
        with pytest.warns(RuntimeWarning, match="retrace_guard"):
            with compile_cache.retrace_guard(max_compiles=0, action="warn"):
                step(np.ones((5, 8), np.float32))

    def test_guard_removed_after_exit(self):
        step = _make_step()
        with compile_cache.retrace_guard(max_compiles=0):
            pass
        step(np.ones((4, 8), np.float32))  # no guard active: fine

    def test_jit_function_stats(self):
        import jax.numpy as jnp

        from paddle_tpu.framework.jit import jit

        @jit
        def f(x):
            return jnp.sum(x * 2)

        f(np.ones(4, np.float32))
        f(np.ones(4, np.float32))
        assert f.cache_stats()["compiles"] == 1
        assert f.cache_stats()["calls"] == 2

    def test_global_stats_aggregate(self):
        step = _make_step()
        step(np.ones((4, 8), np.float32))
        g = compile_cache.cache_stats()
        assert g["compiles"] >= 1
        assert step._cc_name in g["functions"]

    def test_persistent_cache_wiring(self, tmp_path, monkeypatch):
        import jax

        # an explicit directory is honoured only when the environment
        # names none (tests/test_chip_smoke.py covers the other direction)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        old = jax.config.jax_compilation_cache_dir
        try:
            d = compile_cache.enable_persistent_cache(
                str(tmp_path / "xla_cache"))
            assert os.path.isdir(d)
            assert jax.config.jax_compilation_cache_dir == d
            assert compile_cache.persistent_cache_dir() == d
        finally:
            jax.config.update("jax_compilation_cache_dir", old)

    def test_persistent_cache_flags_exist(self):
        flags = pt.get_flags(["FLAGS_persistent_compile_cache",
                              "FLAGS_compile_cache_dir"])
        assert flags["FLAGS_persistent_compile_cache"] is False


# --------------------------------------------- the acceptance fit loop
class TestFitShapeStability:
    def _fit(self, stabilize):
        pt.seed(0)
        from paddle_tpu.hapi import Model

        model = Model(TinyClassifier())
        model.prepare(
            optimizer=pt.optimizer.SGD(learning_rate=0.1),
            loss=lambda logits, label: F.cross_entropy(logits, label))
        model.fit(RaggedDataset(), batch_size=4, epochs=2, verbose=0,
                  shuffle=False, pad_batches=stabilize,
                  length_buckets=BUCKETS if stabilize else None)
        return model._train_step.cache_stats()

    def test_stabilized_compiles_at_most_one_per_bucket(self):
        s = self._fit(stabilize=True)
        assert s["compiles"] <= 1 + len(BUCKETS), s
        assert s["calls"] == 12  # 6 batches x 2 epochs
        assert s["cache_hits"] >= s["calls"] - (1 + len(BUCKETS))

    def test_unstabilized_compiles_once_per_shape(self):
        s = self._fit(stabilize=False)
        # shapes: (4,12), (4,20), (4,28), ragged tail (2,28)
        assert s["compiles"] == 4, s

    def test_fit_with_device_prefetch(self):
        pt.seed(0)
        from paddle_tpu.hapi import Model

        model = Model(TinyClassifier())
        model.prepare(
            optimizer=pt.optimizer.SGD(learning_rate=0.1),
            loss=lambda logits, label: F.cross_entropy(logits, label))
        hist = model.fit(RaggedDataset(), batch_size=4, epochs=1, verbose=0,
                         shuffle=False, pad_batches=True,
                         length_buckets=BUCKETS, prefetch_depth=2)
        assert model._train_step.cache_stats()["compiles"] <= 1 + len(BUCKETS)
        # no leaked prefetch threads
        gc.collect()
        stragglers = [t for t in threading.enumerate()
                      if t is not threading.main_thread() and t.daemon
                      and "Thread-" in t.name and not t.is_alive()]
        assert not stragglers


# ------------------------------------------------------- tool smoke test
def _load_retrace_report():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "retrace_report", os.path.join(REPO, "tools", "retrace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestRetraceReportTool:
    """In-process (a subprocess would spend ~15s just re-importing jax;
    main() is argv-driven either way)."""

    def test_stabilized_within_budget(self, capsys):
        tool = _load_retrace_report()
        rc = tool.main(["--epochs", "1"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "OK:" in out
        assert "trace signature" in out
        assert "train" in out  # per-row kind labels (train/prefill/decode)

    def test_unstabilized_busts_budget(self, capsys):
        tool = _load_retrace_report()
        rc = tool.main(["--epochs", "1", "--no-stabilize", "--budget", "2"])
        assert rc == 1
        assert "FAIL:" in capsys.readouterr().err
