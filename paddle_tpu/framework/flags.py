"""Global flag registry — ``paddle.set_flags``/``get_flags`` analogue.

Reference parity: the 72 exported gflags in
``paddle/fluid/platform/flags.cc`` surfaced to Python through
``global_value_getter_setter.cc``. TPU-native: flags that exist to steer
hand-managed CUDA memory/streams are accepted but inert (XLA owns those
decisions); the live ones gate framework behavior (nan/inf checking, log
verbosity, deterministic ops). Flags initialize from ``FLAGS_*`` env vars,
same as the reference.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Union

_DEFAULTS: Dict[str, Any] = {
    # live flags (consumed by the framework)
    "FLAGS_check_nan_inf": False,          # per-step numeric checks (TrainStep)
    "FLAGS_profile_host_events": True,     # host RecordEvent capture (profiler)
    # persistent XLA compile cache (framework/compile_cache.py): warm
    # processes skip backend compilation for programs already on disk
    "FLAGS_persistent_compile_cache": False,
    "FLAGS_compile_cache_dir": "",         # "" -> <checkout>/.jax_cache
    "FLAGS_persistent_cache_min_compile_secs": 0.0,
    # accepted-but-inert (XLA/jax own these concerns on TPU; XLA:TPU is
    # deterministic by default, verbosity goes through absl/glog env)
    "FLAGS_v": 0,
    "FLAGS_deterministic": False,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_use_autotune": True,
    "FLAGS_sync_nccl_allreduce": False,
    "FLAGS_cudnn_deterministic": False,
}

_flags: Dict[str, Any] = {}


def _coerce(default: Any, raw: str) -> Any:
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def _init() -> None:
    for name, default in _DEFAULTS.items():
        raw = os.environ.get(name)
        _flags[name] = _coerce(default, raw) if raw is not None else default


_init()


def set_flags(flags: Dict[str, Any]) -> None:
    """``paddle.set_flags({'FLAGS_check_nan_inf': 1})``."""
    for name, value in flags.items():
        if name not in _flags:
            raise ValueError(f"unknown flag {name!r}; known: {sorted(_flags)}")
        default = _DEFAULTS[name]
        if isinstance(default, bool) and not isinstance(value, bool):
            value = bool(value)
        _flags[name] = value


def get_flags(flags: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    if flags is None:
        return dict(_flags)
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        if name not in _flags:
            raise ValueError(f"unknown flag {name!r}")
        out[name] = _flags[name]
    return out


def flag(name: str) -> Any:
    """Fast internal accessor."""
    return _flags[name]
