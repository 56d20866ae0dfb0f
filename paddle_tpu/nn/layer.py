"""Layer: the module base class.

Reference parity: ``python/paddle/fluid/dygraph/layers.py`` (Layer with
sublayers/parameters/buffers/hooks/state_dict). TPU-native twist: a Layer is
*also* a functional program — :func:`functional_call` runs a layer with an
explicit parameter/buffer pytree and returns updated buffers, which is what a
``jit``-compiled train step differentiates. Eager forward (outside jit) works
directly on the stored arrays, giving the reference's dygraph feel.

No autograd tape exists here: the reference's 21k-LoC eager GradNode engine
(``paddle/fluid/eager/``) is replaced by ``jax.grad`` over
:func:`functional_call`.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.dtype import convert_dtype, get_default_dtype
from ..framework import random as framework_random


# --------------------------------------------------------------------- RNG
class RNGContext:
    """Named deterministic key streams for functional calls.

    The analogue of the reference's ``RNGStatesTracker``
    (``fleet/meta_parallel/parallel_layers/random.py:32``): each named stream
    (e.g. "dropout", "global") yields keys by folding an incrementing counter
    into a base key, so a traced forward is deterministic given the base keys.
    """

    def __init__(self, rngs: Dict[str, Any]):
        self._base = dict(rngs)
        self._counters: Dict[str, int] = {}

    def next(self, name: str = "dropout"):
        base = self._base.get(name)
        if base is None:
            base = self._base.get("default")
        if base is None:
            return None
        c = self._counters.get(name, 0)
        self._counters[name] = c + 1
        return jax.random.fold_in(base, c)


_rng_ctx_stack: List[RNGContext] = []


@contextlib.contextmanager
def rng_context(rngs: Dict[str, Any]):
    ctx = RNGContext(rngs)
    _rng_ctx_stack.append(ctx)
    try:
        yield ctx
    finally:
        _rng_ctx_stack.pop()


def take_rng_key(name: str = "dropout"):
    """Key for stochastic layers: functional stream when inside a
    functional_call, global stateful generator otherwise (eager)."""
    if _rng_ctx_stack:
        key = _rng_ctx_stack[-1].next(name)
        if key is not None:
            return key
        raise RuntimeError(
            f"layer requested rng stream {name!r} inside a functional call, "
            f"but no key was provided via rngs="
        )
    return framework_random.next_key()


# --------------------------------------------------------------------- Layer
class Parameter:
    """Marker wrapper: assigning a ``Parameter`` to a Layer attribute registers
    it in ``_parameters`` (the role the reference's ``EagerParamBase`` subclass
    check plays in ``Layer.__setattr__``, ``layers.py``). The stored value is
    always the raw ``jax.Array``; this wrapper exists only at assignment time.
    """

    __slots__ = ("value", "trainable")

    def __init__(self, value, trainable: bool = True):
        self.value = jnp.asarray(value)
        self.trainable = trainable


class Layer:
    """Base class for all neural network layers."""

    def __init__(self, name_scope: Optional[str] = None, dtype=None):
        # use object.__setattr__ to avoid recursion before dicts exist
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        self.training = True
        self._dtype = convert_dtype(dtype) or get_default_dtype()
        # per-parameter PartitionSpec-like tuples (local names); collected
        # tree-wide by paddle_tpu.distributed.shard.param_shardings()
        self._param_shardings: Dict[str, tuple] = {}
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._hook_id = 0
        self._name_scope = name_scope or type(self).__name__.lower()

    # ------------------------------------------------------------- attributes
    def __setattr__(self, name: str, value: Any):
        params = self.__dict__.get("_parameters")
        subs = self.__dict__.get("_sub_layers")
        bufs = self.__dict__.get("_buffers")
        if isinstance(value, Layer):
            if subs is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            subs[name] = value
            self.__dict__.pop(name, None)
            return
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value.value
            self.__dict__.pop(name, None)
            return
        if params is not None and name in params:
            if value is None:
                del params[name]
                object.__setattr__(self, name, None)
            else:
                params[name] = jnp.asarray(value)
            return
        if bufs is not None and name in bufs:
            bufs[name] = jnp.asarray(value)
            return
        if subs is not None and name in subs:
            if value is None:
                del subs[name]
            else:
                subs[name] = value
            if not isinstance(value, Layer):
                object.__setattr__(self, name, value)
            return
        object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        # only called when normal lookup fails
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name: str):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # ------------------------------------------------------------- creation
    def create_parameter(
        self,
        shape,
        dtype=None,
        attr=None,
        is_bias: bool = False,
        default_initializer=None,
    ):
        """Create (and return) a parameter array. Mirrors
        ``Layer.create_parameter`` (reference ``layers.py``); ParamAttr is
        reduced to optional initializer/name."""
        from .initializer import Constant, XavierUniform, _resolve_initializer

        dtype = convert_dtype(dtype) or self._dtype
        init = _resolve_initializer(attr, default_initializer,
                                    is_bias=is_bias)
        if init is None:
            init = Constant(0.0) if is_bias else XavierUniform()
        key = framework_random.next_key()
        return Parameter(init(key, tuple(shape), dtype))

    def add_parameter(self, name: str, parameter):
        if parameter is None:
            self._parameters[name] = None
        elif isinstance(parameter, Parameter):
            self._parameters[name] = parameter.value
        else:
            self._parameters[name] = jnp.asarray(parameter)
        self.__dict__.pop(name, None)
        return self._parameters.get(name)

    def register_buffer(self, name: str, tensor, persistable: bool = True):
        self._buffers[name] = None if tensor is None else jnp.asarray(tensor)
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        self.__dict__.pop(name, None)
        return self._buffers.get(name)

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        return sublayer

    def set_param_sharding(self, name: str, spec: tuple):
        """Declare how parameter ``name`` (local) shards over mesh axes,
        e.g. ``("mp", None)`` for a vocab-sharded embedding. GSPMD inserts
        the collectives the reference writes by hand in mp_layers.py."""
        self._param_shardings[name] = tuple(spec)

    def named_param_shardings(self, prefix: str = ""):
        for name, spec in self._param_shardings.items():
            yield (f"{prefix}.{name}" if prefix else name), spec
        for sname, sub in self._sub_layers.items():
            if sub is None:
                continue
            sp = f"{prefix}.{sname}" if prefix else sname
            yield from sub.named_param_shardings(prefix=sp)

    # ------------------------------------------------------------- traversal
    def named_sublayers(self, prefix: str = "", include_self: bool = False) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield p, sub
            yield from sub.named_sublayers(prefix=p)

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        for sub in self._sub_layers.values():
            if sub is not None:
                yield sub

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def named_parameters(self, prefix: str = "", include_sublayers: bool = True):
        for name, p in self._parameters.items():
            if p is not None:
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for sname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sp = f"{prefix}.{sname}" if prefix else sname
                yield from sub.named_parameters(prefix=sp)

    def parameters(self, include_sublayers: bool = True) -> List[Any]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for sname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sp = f"{prefix}.{sname}" if prefix else sname
                yield from sub.named_buffers(prefix=sp)

    def buffers(self, include_sublayers: bool = True) -> List[Any]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for sub in self.children():
            sub.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------- mode
    def train(self) -> "Layer":
        self.training = True
        for sub in self.children():
            sub.train()
        return self

    def eval(self) -> "Layer":
        self.training = False
        for sub in self.children():
            sub.eval()
        return self

    # ------------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook) -> "HookRemoveHelper":
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> "HookRemoveHelper":
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # ------------------------------------------------------------- state dict
    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "") -> "OrderedDict[str, Any]":
        out = OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            out[name] = p
        for name, b in self._named_persistable_buffers(prefix=structured_name_prefix.rstrip(".")):
            out[name] = b
        return out

    def _named_persistable_buffers(self, prefix: str = ""):
        for name, b in self._buffers.items():
            if b is not None and name not in self._non_persistable_buffer_names:
                yield (f"{prefix}.{name}" if prefix else name), b
        for sname, sub in self._sub_layers.items():
            if sub is None:
                continue
            sp = f"{prefix}.{sname}" if prefix else sname
            yield from sub._named_persistable_buffers(prefix=sp)

    def set_state_dict(self, state_dict: Dict[str, Any], use_structured_name: bool = True):
        missing, unexpected = [], []
        consumed = set()
        for name, _ in list(self.named_parameters()) + list(self.named_buffers()):
            if name in state_dict:
                self._set_by_path(name, jnp.asarray(state_dict[name]))
                consumed.add(name)
            else:
                missing.append(name)
        unexpected = [k for k in state_dict if k not in consumed]
        return missing, unexpected

    load_dict = set_state_dict

    def _owner_of(self, path: str):
        """``(layer, leaf name)`` of the parameter or buffer at ``path``."""
        parts = path.split(".")
        layer = self
        for p in parts[:-1]:
            layer = layer._sub_layers[p]
        return layer, parts[-1]

    def _set_by_path(self, path: str, value):
        layer, leaf = self._owner_of(path)
        if leaf in layer._parameters:
            layer._parameters[leaf] = value
        elif leaf in layer._buffers:
            layer._buffers[leaf] = value
        else:
            raise KeyError(f"no parameter or buffer named {path}")

    def _get_by_path(self, path: str):
        layer, leaf = self._owner_of(path)
        if leaf in layer._parameters:
            return layer._parameters[leaf]
        return layer._buffers[leaf]

    # ------------------------------------------------------------- dtype
    #: names of a layer's own parameters that a cast of the model leaves
    #: in the type they were made in (a recurrence's float32 constants)
    keeps_dtype = ()

    def to(self, dtype=None):
        if dtype is not None:
            d = convert_dtype(dtype)
            # by name, one at a time: a list of the parameters would keep
            # every old array alive until the last is cast (both copies
            # of a 2.7B-parameter model: 16 GB on a 16 GB chip)
            for name in [n for n, _ in self.named_parameters()]:
                owner, leaf = self._owner_of(name)
                if leaf in owner.keeps_dtype:
                    continue
                p = self._get_by_path(name)
                if jnp.issubdtype(p.dtype, np.floating):
                    self._set_by_path(name, p.astype(d))
        return self

    astype = to

    def float(self):
        return self.to("float32")

    def bfloat16(self):
        return self.to("bfloat16")

    # ------------------------------------------------------------- call
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            res = hook(self, args)
            if res is not None:
                args = res if isinstance(res, tuple) else (res,)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, args, out)
            if res is not None:
                out = res
        return out

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = "\n  ".join(sub_repr)
            lines.append(f"({name}): {sub_repr}")
        body = ""
        if extra or lines:
            body = "\n  " + "\n  ".join(([extra] if extra else []) + lines) + "\n"
        return f"{type(self).__name__}({body})"


class HookRemoveHelper:
    def __init__(self, hooks: Dict[int, Callable], hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


# -------------------------------------------------------- functional bridge
def param_state(layer: Layer) -> Dict[str, Any]:
    """Trainable parameter pytree (flat path->array dict)."""
    return dict(layer.named_parameters())


def buffer_state(layer: Layer) -> Dict[str, Any]:
    """Mutable non-trainable state pytree (BN stats, counters, ...)."""
    return dict(layer.named_buffers())


def functional_call(
    layer: Layer,
    params: Dict[str, Any],
    buffers: Optional[Dict[str, Any]],
    *args,
    rngs: Optional[Dict[str, Any]] = None,
    **kwargs,
):
    """Run ``layer`` with explicit state; returns ``(out, new_buffers)``.

    This is the jit/grad entry point: ``params``/``buffers`` may be tracers.
    The layer's stored arrays are swapped in-place for the duration of the
    call and restored afterwards (single-threaded trace-time mutation, same
    trick as flax.nnx's merge/split).
    """
    saved = {}
    for name in list(params) + list(buffers or {}):
        saved[name] = layer._get_by_path(name)
    try:
        for name, v in params.items():
            layer._set_by_path(name, v)
        for name, v in (buffers or {}).items():
            layer._set_by_path(name, v)
        # rngs=None inherits any ambient rng context (nested functional calls)
        ctx = rng_context(rngs) if rngs is not None else contextlib.nullcontext()
        with ctx:
            out = layer(*args, **kwargs)
        new_buffers = {name: layer._get_by_path(name) for name in (buffers or {})}
    finally:
        for name, v in saved.items():
            layer._set_by_path(name, v)
    return out, new_buffers
