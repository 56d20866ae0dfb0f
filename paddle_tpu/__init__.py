"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities (reference: xuewujiao/Paddle; see SURVEY.md for the blueprint).

Public surface mirrors ``paddle.*``: tensor ops at top level, ``nn``,
``optimizer``, ``amp``, ``io``, ``distributed``, ``vision``. Tensors are
plain ``jax.Array``; execution is eager op-by-op (dygraph feel) or compiled
via ``paddle_tpu.jit``/``TrainStep`` (XLA = the executor).
"""
from __future__ import annotations

import jax as _jax_cfg

# paddle-parity numerics: f32 matmul/conv accumulate in f32 (reference CUDA
# kernels are true fp32). bf16 model paths are unaffected — that's the
# MXU-native fast path either way.
_jax_cfg.config.update("jax_default_matmul_precision", "float32")

# ops become the top-level tensor API (paddle.add, paddle.matmul, ...)
from .ops import *  # noqa: F401,F403
from .framework.dtype import (  # noqa: F401
    bfloat16, bool_, complex64, complex128, convert_dtype, dtype_name,
    finfo, float16, float32, float64, get_default_dtype, iinfo, int8, int16,
    int32, int64, is_complex, is_floating_point, is_integer,
    set_default_dtype, uint8,
)
from .framework.random import (  # noqa: F401
    default_generator, get_rng_state, next_key, seed, set_rng_state,
)
from .framework.io import load, save  # noqa: F401
from .framework.compat import (  # noqa: F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, LazyGuard, NPUPlace, TPUPlace,
    array_length, array_read, array_write, batch, check_shape,
    create_array, create_parameter, disable_signal_handler, disable_static,
    dtype, enable_static, in_dynamic_mode, index_add_, is_grad_enabled,
    set_grad_enabled,
)
from .framework.random import (  # noqa: F401
    get_rng_state as get_cuda_rng_state,  # device RNG collapses to one
    set_rng_state as set_cuda_rng_state,
)
from .framework.flags import get_flags, set_flags  # noqa: F401
from .framework.debugging import check_numerics  # noqa: F401
from .framework.jit import EvalStep, TrainStep  # noqa: F401

from . import nn  # noqa: F401
from . import geometric  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import vision  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import strings  # noqa: F401
from . import utils  # noqa: F401
from . import incubate  # noqa: F401
from . import quantization  # noqa: F401
from . import optimizer  # noqa: F401
from . import metric  # noqa: F401
from . import callbacks  # noqa: F401
from .hapi import InputSpec, Model, flops, summary  # noqa: F401
# paddle.jit module parity (to_static/save/load); the bare compile decorator
# stays available as paddle_tpu.jit.to_static and framework.jit.jit
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import inference  # noqa: F401
from . import profiler  # noqa: F401
from . import eager  # noqa: F401  (Tensor.backward dygraph facade)
from . import autograd  # noqa: F401  (PyLayer / hooks / backward)
# self-healing training (numerics watchdog / auto-rollback / preemption);
# imported late: the supervisor pulls in distributed.checkpoint
from .framework.supervisor import (  # noqa: F401
    RecoveryPolicy, TrainingPreempted, TrainingSupervisor,
)

# autodiff: the reference's eager GradNode engine collapses to jax.grad
import jax as _jax


def grad(outputs, *args, **kwargs):
    """Dual-form ``paddle.grad``: with a CALLABLE first argument this is
    ``jax.grad`` (the TPU-native functional transform); with tensors it is
    the reference's imperative partial-grad —
    ``grad(outputs, inputs, grad_outputs=None, ...)`` over the eager tape
    (``python/paddle/fluid/dygraph/base.py:468``), returning grads without
    touching ``.grad``."""
    if callable(outputs) and not isinstance(outputs, eager.Tensor):
        return _jax.grad(outputs, *args, **kwargs)
    return eager.grad(outputs, *args, **kwargs)


value_and_grad = _jax.value_and_grad


def no_grad(fn=None):
    """Decorator/context for API parity. JAX only differentiates what is
    explicitly wrapped in grad(), so this is a no-op marker (plus
    lax.stop_gradient for in-graph use)."""
    import contextlib

    if fn is None:
        return contextlib.nullcontext()
    return fn


def stop_gradient(x):
    return _jax.lax.stop_gradient(x)


class ParamAttr:
    """Parameter attribute bundle (reference ``python/paddle/fluid/param_attr.py``).
    Reduced to the fields that matter functionally."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip


def set_device(device: str = "tpu"):
    """``paddle.set_device`` analogue: selects the JAX platform in-process
    (e.g. ``set_device("cpu")`` for host-simulated meshes). A backend that
    is already up is dropped, which invalidates its arrays — call this
    before creating any. From a shell, ``JAX_PLATFORMS=cpu`` before the
    first ``import jax`` does the same job."""
    import jax
    from jax.extend.backend import clear_backends

    want = device.split(":")[0]
    if want in ("gpu", "cuda"):
        raise ValueError("this build is TPU/CPU only (no CUDA symbols)")
    jax.config.update("jax_platforms", want)
    clear_backends()
    return f"{jax.default_backend()}:0"


def get_device():
    import jax

    return f"{jax.default_backend()}:0"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def device_count() -> int:
    import jax

    return len(jax.devices())


__version__ = "0.1.0"

# late aliases (kept last: `bool` would shadow the builtin above)
from .eager import Tensor  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402

bool = bool_  # noqa: F401,A001  — paddle.bool dtype name


def __getattr__(name):
    # lazy subpackages: serving pulls the generation/KV-cache stack,
    # which plain `import paddle_tpu` users (every subprocess test, the
    # launcher workers) shouldn't pay for
    if name == "serving":
        import importlib

        return importlib.import_module(".serving", __name__)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
