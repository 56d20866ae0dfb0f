"""Compile accounting + persistent XLA compilation cache.

On TPU the executor is XLA, so the silent killer of steady-state
throughput is the *retrace*: a novel input shape/dtype re-runs tracing and
backend compilation (seconds to minutes) in the middle of what should be a
microseconds dispatch. This module makes that cost visible and bounded:

- every ``framework.jit`` / ``TrainStep`` / ``EvalStep`` program is
  *instrumented*: each trace (== each distinct compiled specialization)
  bumps a counter keyed by the function's registered name and records the
  abstract ``(shape, dtype)`` signature that caused it;
- :func:`cache_stats` exposes compiles / calls / cache hits / the last
  trace signature, per function and in aggregate — the number BENCH and
  the tier-1 tests assert on;
- :func:`retrace_guard` is a context manager for the steady state: after
  warmup, wrap the training loop and any recompile beyond the declared
  budget warns or raises :class:`RetraceError` *at trace time*, naming the
  offending function and signature;
- :func:`enable_persistent_cache` wires jax's persistent compilation cache
  (at ``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed path in the
  checkout), so a restarted process pays tracing but not backend
  compilation;
- :func:`watched_jit` / :func:`program_scopes` keep, for the programs
  built through it (the serving engine's two, ``TrainStep``'s step), the
  executables they ran and say which ``jax.named_scope`` each of their
  instructions belongs to: what turns a device trace into time by scope
  (:mod:`paddle_tpu.observability.scopes`).

Trace count is the retrace signal, not XLA's internal executable cache:
a trace is exactly one new specialization from the framework's point of
view, and it is observable portably (the Python body runs once per trace).
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import os
import json
import threading
import time
import warnings
import weakref
from typing import Any, Callable, Dict, Optional

__all__ = [
    "RetraceError", "cache_stats", "reset_stats", "instrument",
    "register_name", "retrace_guard", "enable_persistent_cache",
    "backend_compile_stats", "initialize_from_flags", "watched_jit",
    "program_scopes", "export_program_scopes", "programs_with_scopes",
]


class RetraceError(RuntimeError):
    """An XLA recompile happened inside a :func:`retrace_guard` window."""


class _Entry:
    __slots__ = ("compiles", "calls", "signatures", "last_trace_signature")

    def __init__(self):
        self.compiles = 0
        self.calls = 0
        self.signatures: Dict[str, int] = {}
        self.last_trace_signature: Optional[str] = None

    def as_dict(self) -> dict:
        return {"compiles": self.compiles, "calls": self.calls,
                "cache_hits": max(self.calls - self.compiles, 0),
                "signatures": dict(self.signatures),
                "last_trace_signature": self.last_trace_signature}


_lock = threading.RLock()
_entries: Dict[str, _Entry] = {}
_name_serial = itertools.count()
_guards: list = []  # active retrace_guard frames (innermost last)
_last_trace_signature: Optional[str] = None


def register_name(base: str) -> str:
    """A unique stats key (``base`` + serial) for per-instance tracking."""
    return f"{base}#{next(_name_serial)}"


def _leaf_sig(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}{tuple(shape)}"
    return repr(x)


def abstract_signature(args, kwargs) -> str:
    """shape/dtype signature of a call — stable across values, sensitive to
    exactly what forces a retrace (shapes, dtypes, static values)."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    return "(" + ", ".join(_leaf_sig(leaf) for leaf in leaves) + ")"


def _entry(name: str) -> _Entry:
    with _lock:
        e = _entries.get(name)
        if e is None:
            e = _entries[name] = _Entry()
        return e


def record_trace(name: str, signature: str) -> None:
    """Called from inside a traced body: one new specialization exists."""
    global _last_trace_signature
    with _lock:
        e = _entry(name)
        e.compiles += 1
        e.signatures[signature] = e.signatures.get(signature, 0) + 1
        e.last_trace_signature = signature
        _last_trace_signature = signature
        guards = list(_guards)
    try:
        # compiles are rare and exactly what a crash postmortem wants:
        # land each one in the flight-recorder ring and the trace buffer
        # (host-side bookkeeping only — the trace itself is already paying
        # seconds; telemetry failures must never break it)
        from ..observability import flight as _flight
        from ..observability import tracing as _tracing

        _flight.note("compile", corr=_tracing.current(), program=name,
                     signature=signature[:200])
        _tracing.record_event("compile", program=name)
    except Exception:
        pass
    for g in guards:
        g._on_trace(name, signature)


def record_call(name: str) -> None:
    with _lock:
        _entry(name).calls += 1
        prog = _programs.get(_base(name))
        pending = prog is not None and prog.name == name and prog.pending
    if pending:
        _keep_executables(prog)


def cache_stats(name: Optional[str] = None) -> dict:
    """Compile/call counters.

    ``cache_stats()`` aggregates every instrumented program:
    ``{"compiles", "calls", "cache_hits", "last_trace_signature",
    "functions": {name: per-function dict}}``. ``cache_stats(name)``
    returns one function's dict (zeros if it never ran).
    """
    with _lock:
        if name is not None:
            e = _entries.get(name)
            return e.as_dict() if e is not None else _Entry().as_dict()
        compiles = sum(e.compiles for e in _entries.values())
        calls = sum(e.calls for e in _entries.values())
        return {"compiles": compiles, "calls": calls,
                "cache_hits": max(calls - compiles, 0),
                "last_trace_signature": _last_trace_signature,
                "functions": {n: e.as_dict() for n, e in _entries.items()}}


def reset_stats() -> None:
    with _lock:
        _entries.clear()
        global _last_trace_signature
        _last_trace_signature = None


def instrument(fn: Callable, name: Optional[str] = None) -> Callable:
    """Wrap ``fn`` for ``jax.jit`` so each TRACE is recorded.

    The wrapper's body executes exactly once per specialization (that is
    what tracing is), so it is the portable retrace probe. The trace runs
    under a ``compile`` profiler span; pair with :func:`record_call` at the
    dispatch site for hit-rate accounting. The stats key is attached as
    ``wrapped.__cc_name__``.
    """
    key = name or register_name(getattr(fn, "__name__", "jit_fn"))

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        from ..profiler import RecordEvent

        record_trace(key, abstract_signature(args, kwargs))
        with RecordEvent("compile"):
            return fn(*args, **kwargs)

    wrapped.__cc_name__ = key
    return wrapped



# ------------------------------------------- executables by named scope
class _Program:
    """A watched program: its jitted callable (weakly: it closes over its
    engine or step), the specializations traced and not kept yet, and the
    executables kept, each with its scope map once somebody asked."""
    __slots__ = ("name", "kind", "module", "jitted", "pending", "kept",
                 "keep_s")

    def __init__(self, name: str, kind: str, module: str):
        self.name, self.kind, self.module = name, kind, module
        self.jitted = lambda: None  # a weakref once the jit exists
        self.pending: list = []     # (args, kwargs) of abstract leaves
        self.kept: list = []        # [Compiled, scope map or None]
        self.keep_s = 0.0           # what keeping them cost the callers


#: the newest watched program of each name's base (``register_name`` adds
#: a serial per instance): a second server replaces the first one's entry
_programs: Dict[str, _Program] = {}


def _base(name: str) -> str:
    return name.split("#", 1)[0]


def watched_jit(fn: Callable, name: str, kind: str, **jit_kwargs):
    """``jax.jit(instrument(fn, name), **jit_kwargs)`` that also keeps
    what :func:`program_scopes` needs: the executable of every
    specialization it is traced for. ``kind`` names the program in a table
    of device time (``"decode"``, ``"prefill"``, ``"train"``).

    Nothing is held that holds ``fn`` (the engine, the step, their
    parameters). The trace leaves its abstract arguments behind; the
    first :func:`record_call` of ``name`` after it takes
    ``jitted.lower(*abstract).compile()``, which in jax 0.9 answers from
    the caches the call itself filled (no second trace, no backend
    compile: milliseconds), and keeps that ``Compiled``. Where it would
    NOT answer from them (arguments committed to a sharding that the
    abstract ones do not carry, a mesh) the lowering is dropped and the
    program has no map: nothing is ever compiled for the map's sake. A
    step costs :func:`record_call` one more test."""
    import jax

    # "jit__decode_fn": what a profiler calls a run of it, less the hash
    prog = _Program(name, kind, "jit_" + getattr(fn, "__name__", ""))
    traced = instrument(fn, name)

    def abstract(x):
        # a static value (no shape) stays as it is
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        weak = getattr(getattr(x, "aval", None), "weak_type", False)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=weak)

    @functools.wraps(traced)
    def noting(*args, **kwargs):
        spec = jax.tree.map(abstract, (args, kwargs))
        with _lock:
            prog.pending.append(spec)
        return traced(*args, **kwargs)

    jitted = jax.jit(noting, **jit_kwargs)
    prog.jitted = weakref.ref(jitted)
    with _lock:
        _programs[_base(name)] = prog
    return jitted


def _keep_executables(prog: _Program) -> None:
    from ..distributed.mesh import get_mesh

    with _lock:
        pending, prog.pending = prog.pending, []
    jitted, mesh = prog.jitted(), get_mesh()
    if jitted is None or (mesh is not None and mesh.size > 1):
        return      # sharded arguments: the abstract ones would not match
    t0 = time.perf_counter()
    for spec in pending:
        args, kwargs = spec[:2]
        lowered = jitted.lower(*args, **kwargs)
        # the call's own executable, or none: `MeshComputation.compile`
        # keeps what it compiled, so an executable there means the call
        # went through this very lowering
        if getattr(getattr(lowered, "_lowering", None), "_executable",
                   None) is not None:
            with _lock:
                prog.kept.append([lowered.compile(), None])
        elif len(spec) == 2:
            # traced by a `lower()` that no call has followed yet: the
            # next call of the program gets one more look
            with _lock:
                prog.pending.append(spec + ("again",))
        else:
            warnings.warn(
                f"{prog.name}: the lowering for its abstract arguments is "
                f"not the one the call compiled; no scope map is kept",
                RuntimeWarning, stacklevel=3)
    prog.keep_s += time.perf_counter() - t0


def program_scopes() -> Dict[str, dict]:
    """``{"<program name>@<i>": {"kind", "module", "keep_s", "ops": {event
    key: op_name}}}`` for every executable kept of the watched programs
    (the i-th specialization of the program registered under that name):
    which ``jax.named_scope`` path each instruction of the optimized HLO
    belongs to, keyed as a profiler's device event of it is
    (:func:`paddle_tpu.observability.scopes.event_key`); ``module`` is the
    name a profiler gives a run of the program, less its hash; ``keep_s``
    is what keeping the program's executables cost its callers, all told.

    The text is parsed when first asked for and the map kept. Asking
    traces nothing and compiles nothing (the executables were kept by
    :func:`watched_jit`'s hook, outside any window), so it is safe
    inside a :func:`retrace_guard` and between two readings of
    :func:`cache_stats`; it works after the engine or the step is gone.

    Trap: jax leaves op metadata out of the persistent cache's key unless
    told otherwise, so an executable loaded from a cache directory that
    another tree wrote carries THAT tree's scope names, and a join against
    a trace books time under names this tree does not have, or under
    ``unscoped``. :func:`enable_persistent_cache` tells it otherwise; a
    cache turned on through jax's own configuration is the case to
    mind."""
    from ..observability.scopes import parse_hlo_scopes

    with _lock:
        progs = list(_programs.values())
    out = {}
    for prog in progs:
        for i, slot in enumerate(list(prog.kept)):
            if slot[1] is None:
                slot[1] = parse_hlo_scopes(slot[0].as_text())
            out[f"{prog.name}@{i}"] = {"kind": prog.kind,
                                       "module": prog.module,
                                       "keep_s": prog.keep_s,
                                       "ops": slot[1]}
    return out


def export_program_scopes(path: str) -> int:
    """Write :func:`program_scopes` to ``path`` as JSON (what
    ``tools/trace_view.py --scopes`` reads beside a profile of this
    process); returns the number of executables written."""
    scopes = program_scopes()
    with open(path, "w") as f:
        json.dump(scopes, f)
    return len(scopes)


def programs_with_scopes() -> int:
    """How many executables :func:`program_scopes` would describe."""
    with _lock:
        return sum(len(p.kept) for p in _programs.values())


class _Guard:
    def __init__(self, max_compiles: int, action: str, label: str):
        self.max_compiles = int(max_compiles)
        self.action = action
        self.label = label
        self.seen: list = []  # (name, signature) of traces in the window

    def _on_trace(self, name: str, signature: str):
        self.seen.append((name, signature))
        if len(self.seen) <= self.max_compiles:
            return
        msg = (f"retrace_guard({self.label}): {len(self.seen)} compile(s) "
               f"inside a window budgeted for {self.max_compiles}; "
               f"latest: {name} traced for {signature}. An unstable input "
               f"shape is recompiling the step — pad/bucket the pipeline "
               f"(DataLoader(pad_batches=..., length_buckets=...)).")
        if self.action == "raise":
            raise RetraceError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


@contextlib.contextmanager
def retrace_guard(max_compiles: int = 0, action: str = "raise",
                  label: str = "steady-state"):
    """Bound compiles inside the ``with`` block.

    Enter it AFTER warmup: any trace of an instrumented program beyond
    ``max_compiles`` raises :class:`RetraceError` (``action="raise"``) or
    emits a ``RuntimeWarning`` (``action="warn"``) the moment it happens,
    naming the function and the shape signature that caused it.
    """
    if action not in ("raise", "warn"):
        raise ValueError(f"action must be 'raise' or 'warn', got {action!r}")
    g = _Guard(max_compiles, action, label)
    with _lock:
        _guards.append(g)
    try:
        yield g
    finally:
        with _lock:
            _guards.remove(g)


# ------------------------------------------------- persistent XLA cache
_persistent_dir: Optional[str] = None

#: where the cache lives when nobody says otherwise: a fixed path next to
#: the package (the checkout root), so two runs of the same command from
#: the same tree share it; listed in ``.gitignore``
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache(cache_dir: Optional[str] = None,
                            min_compile_secs: Optional[float] = None) -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ONE rule places the cache, and this function is the only code that
    applies it:

    - ``JAX_COMPILATION_CACHE_DIR`` set in the environment => jax has
      already adopted that directory at import; it is the cache and this
      function sets no other (``cache_dir`` and ``FLAGS_compile_cache_dir``
      both lose to it);
    - otherwise ``cache_dir``, else ``FLAGS_compile_cache_dir``, else
      :data:`DEFAULT_CACHE_DIR` (``<checkout>/.jax_cache``).

    Subsequent processes that compile an identical program (same HLO,
    flags, backend) load the executable from disk instead of recompiling.
    Call it before the first compile of the process: jax decides once
    whether the cache is in use. Safe to call repeatedly.
    """
    global _persistent_dir
    from . import flags

    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    else:
        cache_dir = os.path.abspath(os.path.expanduser(
            cache_dir or flags.flag("FLAGS_compile_cache_dir")
            or DEFAULT_CACHE_DIR))
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if min_compile_secs is None:
        min_compile_secs = flags.flag(
            "FLAGS_persistent_cache_min_compile_secs")
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax leaves op metadata (the named scopes, file and line) out of the
    # cache's key by default: an executable another tree wrote would then
    # come back with that tree's scope names in it, and program_scopes()
    # would book device time under them
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    _persistent_dir = cache_dir
    _listen_for_backend_compiles()
    return cache_dir


# what jax itself reports about the persistent cache (jax.monitoring):
# every executable it needs is one request; a request the cache answers
# is a hit; the rest are real backend compiles
_backend = {"requests": 0, "hits": 0, "seconds": 0.0, "listening": False}
_COMPILE_DURATION_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")


def _listen_for_backend_compiles() -> None:
    import jax

    with _lock:
        if _backend["listening"]:
            return
        _backend["listening"] = True

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            with _lock:
                _backend["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            with _lock:
                _backend["hits"] += 1

    def on_duration(event, seconds, **_):
        if event in _COMPILE_DURATION_EVENTS:
            with _lock:
                _backend["seconds"] += seconds

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def backend_compile_stats() -> dict:
    """Process totals since :func:`enable_persistent_cache`: executables
    jax asked for (``requests``), how many the persistent cache answered
    (``persistent_hits``), how many the backend really compiled
    (``backend_compiles``) and the seconds spent tracing, lowering and
    compiling or loading them (``compile_seconds``). Unlike
    :func:`cache_stats` — which counts traces of instrumented programs —
    this sees every executable, eager ops included; callers diff two
    readings to attribute a phase."""
    with _lock:
        return {"requests": _backend["requests"],
                "persistent_hits": _backend["hits"],
                "backend_compiles": _backend["requests"] - _backend["hits"],
                "compile_seconds": round(_backend["seconds"], 3)}


def persistent_cache_dir() -> Optional[str]:
    """The directory wired by :func:`enable_persistent_cache`, else None."""
    return _persistent_dir


def initialize_from_flags() -> None:
    """Honor ``FLAGS_persistent_compile_cache`` at import (env-settable:
    ``FLAGS_persistent_compile_cache=1 python train.py``)."""
    from . import flags

    if flags.flag("FLAGS_persistent_compile_cache"):
        enable_persistent_cache()


initialize_from_flags()
