"""What both regime harnesses share: resolving names from data files,
building the seeded model, reading the program's counters, tracing a
slice, and watching the garbage collector."""
from __future__ import annotations

import contextlib
import gc
import importlib
import sys
import tempfile
import time


def log(msg: str) -> None:
    """Log lines go to stderr: stdout carries the result line alone."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def resolve(spec: str):
    """``"package.module:attr"`` -> the attribute."""
    module, _, attr = spec.partition(":")
    obj = importlib.import_module(module)
    return getattr(obj, attr) if attr else obj


def seeded_rng(*ints):
    """A numpy generator keyed by whole numbers of any size or sign
    (``--seed`` passes 2**31); further ints select independent streams."""
    import numpy as np

    return np.random.default_rng([int(i) & 0xFFFFFFFFFFFFFFFF for i in ints])


def fold_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program's generator takes a
    non-negative int32."""
    return int(seed) % (2 ** 31 - 1)


def build_model(config: dict, overrides: dict, seed: int):
    """The configuration's model on seeded random weights, through the
    program's own constructor (its initializers draw from the global
    generator that ``paddle_tpu.seed`` sets)."""
    import paddle_tpu

    kw = dict(config["config"])
    kw.update(config.get("run", {}))
    kw.update(overrides or {})
    paddle_tpu.seed(fold_seed(seed))
    cfg = resolve(config["model"]["config_class"])(**kw)
    return resolve(config["model"]["model_class"])(cfg)


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip so far, as the runtime counts
    them. Read when the measured work has ended and before the reference
    check, whose float32 logits are the benchmark's, not the program's."""
    import jax

    # a backend that keeps no such count (the CPU of the rehearsal) gives 0
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:chips])


def program_counters() -> dict:
    """Compiles as the program counts them: executables jax asked the
    backend or the persistent cache for (eager ops included), and traces
    of the instrumented step/prefill/decode programs."""
    from paddle_tpu.framework import compile_cache

    return {"backend": compile_cache.backend_compile_stats(),
            "traces": compile_cache.cache_stats()["compiles"]}


def compiled_inside(opened: dict, closed: dict) -> int:
    """Executables requested plus programs traced between two readings of
    :func:`program_counters`; a measured window must show 0."""
    return ((closed["backend"]["requests"] - opened["backend"]["requests"])
            + (closed["traces"] - opened["traces"]))


def _start_trace(log_dir: str) -> None:
    """Device events only. The profiler's Python and host tracers hook
    every call of the serve loop and the clients; with them on, a traced
    serving slice showed the host turn at twice its untraced length, and
    the idle share with it. Only the device planes are read here."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)


def warm_up_profiler() -> None:
    """Start and stop the profiler once during set-up: its first start in
    a process takes seconds and holds everything up, a later one less."""
    import jax

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        _start_trace(d)
        jax.profiler.stop_trace()
    log(f"profiler warmed up in {time.perf_counter() - t0:.1f} s")


@contextlib.contextmanager
def traced_slice(holder: dict):
    """Profile what runs inside the ``with`` and leave the reduced trace
    in ``holder["trace"]``. The trace files live in a temporary directory
    (under ``TMPDIR``) that is gone when this returns."""
    import jax

    from . import trace_reduce

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        _start_trace(d)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        holder["trace"] = trace_reduce.reduce_trace(
            trace_reduce.newest_xplane(d))
        log(f"trace reduced in {time.perf_counter() - t0:.1f} s: window "
            f"{holder['trace']['window_s']:.3f} s, busy "
            f"{holder['trace']['busy_s']:.3f} s")


class GcWatch:
    """Before a window: collect, then ``gc.freeze()`` so that the model,
    the compiled programs and the drawn traffic never get walked again;
    during it: time every collector pass and keep those over 20 ms."""

    def __init__(self):
        self.pauses_ms = []
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            ms = (time.perf_counter() - self._t0) * 1e3
            if ms > 20.0:
                self.pauses_ms.append((info.get("generation"), round(ms, 1)))

    def __enter__(self):
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)
        gc.unfreeze()
        return False
