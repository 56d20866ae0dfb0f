"""Compiled execution.

The reference runs programs through ``InterpreterCore`` (instruction list +
threadpool, ``paddle/fluid/framework/new_executor/interpretercore.cc``). On
TPU the executor *is* XLA: a train/eval step is traced once, compiled, and
cached keyed on shapes/shardings. This module packages that as:

- :func:`jit` — paddle.jit.to_static analogue for plain functions/Layers.
- :class:`TrainStep` — whole-step compilation: forward + loss + backward +
  optimizer update in ONE XLA program with donated buffers (the analogue of
  the reference's fused optimizer pass + executor pipeline).
- :class:`EvalStep` — inference-only compiled step.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import compile_cache
from . import random as framework_random
from ..nn.layer import Layer, buffer_state, functional_call, param_state


DEFAULT_RNG_STREAMS = ("dropout", "rrelu", "gumbel", "default")


def _grad_dtype(dtype):
    """Accumulate low-precision grads in f32 (gradient-merge accumulators)."""
    return jnp.float32 if dtype in (jnp.bfloat16, jnp.float16) else dtype


def accumulate_grads(accum, grads):
    """Gradient-merge accumulate (no-op when accumulation is off)."""
    if accum is None:
        return None
    return jax.tree.map(lambda a, g: a + g.astype(a.dtype), accum, grads)


def merge_accumulated(accum, grads, k_steps, avg):
    """Finish a gradient-merge window: returns (grads_for_update,
    reset_accum). ``grads`` supplies the target dtypes."""
    if accum is None:
        return grads, None
    k = float(k_steps)
    merged = jax.tree.map(
        lambda a, g: (a / k if avg else a).astype(g.dtype), accum, grads)
    return merged, jax.tree.map(jnp.zeros_like, accum)


def resolve_inputs_fn(inputs_fn, loss_fn):
    """Default batch->model-inputs mapping shared by TrainStep and
    DistributedTrainStep: with a loss_fn, (inputs, labels) tuples feed the
    model their first element; otherwise the whole batch is the input."""
    if inputs_fn is not None:
        return inputs_fn
    if loss_fn is not None:
        return lambda b: b[0] if isinstance(b, (tuple, list)) else b
    return lambda b: b


def split_rng_streams(key, streams=DEFAULT_RNG_STREAMS):
    return dict(zip(streams, jax.random.split(key, len(streams))))


def jit(fn=None, *, static_argnums=(), static_argnames=(), donate_argnums=()):
    """``paddle.jit.to_static`` analogue. Accepts a function or a Layer.

    For a Layer, returns a compiled callable closed over the layer's current
    state (params become compile-time constants refreshed per call via
    functional_call — use TrainStep for training).
    """
    if fn is None:
        return functools.partial(jit, static_argnums=static_argnums,
                                 static_argnames=static_argnames,
                                 donate_argnums=donate_argnums)
    if isinstance(fn, Layer):
        layer = fn
        cc_name = compile_cache.register_name(
            f"jit:{type(layer).__name__}")

        def _run(p, b, *args, **kwargs):
            out, _ = functional_call(layer, p, b, *args, **kwargs)
            return out

        _compiled = jax.jit(compile_cache.instrument(_run, cc_name))

        def wrapped(*args, **kwargs):
            compile_cache.record_call(cc_name)
            return _compiled(param_state(layer), buffer_state(layer),
                             *args, **kwargs)

        wrapped.__wrapped_layer__ = layer
        wrapped.__cc_name__ = cc_name
        wrapped.cache_stats = lambda: compile_cache.cache_stats(cc_name)
        return wrapped
    cc_name = compile_cache.register_name(
        f"jit:{getattr(fn, '__name__', 'fn')}")
    compiled = jax.jit(compile_cache.instrument(fn, cc_name),
                       static_argnums=static_argnums,
                       static_argnames=static_argnames,
                       donate_argnums=donate_argnums)

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        compile_cache.record_call(cc_name)
        return dispatch.__jit__(*args, **kwargs)

    dispatch.__jit__ = compiled   # escape hatch: .lower()/.eval_shape()
    dispatch.__cc_name__ = cc_name
    dispatch.cache_stats = lambda: compile_cache.cache_stats(cc_name)
    return dispatch


def finite_guard(grads, new_state, old_state, extra_ok=None):
    """In-graph NaN/Inf gate for FLAGS_check_nan_inf: returns
    ``(ok, selected_state)`` where each leaf of ``new_state`` is kept only
    if every grad and every updated param is finite — otherwise the old
    leaf survives. Keeping the selection in-graph means a bad batch can be
    caught *without* corrupting donated buffers (the reference's per-op
    scan aborts before the update; here the update is predicated instead).

    ``new_state``/``old_state`` are matching tuples of pytrees; the first
    tree is the params (checked), the rest (buffers/opt state) are selected
    alongside. ``extra_ok`` folds an additional scalar condition (e.g. a
    finite loss) into the gate.
    """
    from .debugging import tree_all_finite

    ok = tree_all_finite(grads) & tree_all_finite(new_state[0])
    if extra_ok is not None:
        ok = ok & extra_ok

    def sel(n, o):
        return jnp.where(ok, n, o)

    selected = tuple(jax.tree.map(sel, n, o)
                     for n, o in zip(new_state, old_state))
    return ok, selected


def raise_if_bad_step(ok, loss) -> None:
    """Host-side companion to :func:`finite_guard`."""
    if not bool(ok):
        raise FloatingPointError(
            f"NaN/Inf detected in gradients or updated parameters "
            f"(FLAGS_check_nan_inf); update skipped, state preserved. "
            f"loss={float(loss)}")


def scaler_guard(loss, found, scaler_state, new_state, old_state):
    """In-graph GradScaler epilogue shared by TrainStep and
    DistributedTrainStep (ONE implementation, so the sharded and
    single-device skip/grow semantics cannot drift). ``found`` is
    ``unscale_and_check``'s nonfinite-grads flag; this classifies the
    step, predicates the update, and advances the scale.

    Classification: a nonfinite *loss* — or nonfinite UPDATED params under
    finite grads (optimizer-side blowup) — is a data/numerics **anomaly**;
    nonfinite grads under a finite loss are ordinary scale-overflow, but
    only while ``scale > 1``: at scale 1 there is no scaling left to blame,
    so persistent NaN grads escalate to the watchdog instead of silently
    skipping updates forever. Both cases keep the old state, and ONLY the
    benign overflow drives the backoff schedule — a poisoned batch must
    not walk the scale down.

    Returns ``(selected_state, new_scaler_state, ok, found_inf)`` where
    ``ok = ~anomaly`` and ``found_inf`` flags benign scaler skips only.
    """
    from ..amp.grad_scaler import update_scale
    from .debugging import tree_all_finite

    # the params term applies only under FINITE grads: overflowed grads
    # trivially produce nonfinite candidate params, and that case is the
    # ordinary overflow being classified right above it
    anomaly = (~jnp.isfinite(loss)
               | (found & (scaler_state["scale"] <= 1.0))
               | (~found & ~tree_all_finite(new_state[0])))
    bad = found | anomaly
    found_inf = found & ~anomaly

    def keep_old(n, o):
        return jax.tree.map(lambda a, b: jnp.where(bad, b, a), n, o)

    selected = tuple(keep_old(n, o) for n, o in zip(new_state, old_state))
    return selected, update_scale(scaler_state, found_inf), ~anomaly, \
        found_inf


class StepSeams:
    """Host-side seams shared by TrainStep and DistributedTrainStep: the
    step counter / gradient-accumulation window, the traced NaN-poison
    input, and GradScaler resolution — one implementation so the sharded
    and single-device paths cannot drift."""

    def _init_seams(self, scaler, grad_accum_steps: int) -> None:
        self.scaler = scaler if (scaler is not None
                                 and getattr(scaler, "enable", True)) else None
        if self.scaler is not None and grad_accum_steps > 1:
            raise ValueError(
                "GradScaler with grad_accum_steps > 1 is not supported: the "
                "scale could change mid-accumulation window")
        # deterministic numerics-fault seam: the NEXT step's loss is
        # multiplied by this traced scalar (1.0 = no-op; NaN = poisoned
        # batch). Being a regular input, flipping it never retraces — the
        # chaos harness drives it through fault_point("train.data").
        self._pending_poison = np.float32(1.0)

    def inject_anomaly(self):
        """Poison the NEXT step's loss (and hence grads) with NaN — the
        deterministic fault-injection seam the chaos harness drives through
        ``fault_point("train.data")``. The in-graph guard still protects
        the state; the watchdog observes the anomaly. (Distributed: the
        poison scalar is replicated, so every host sees the same anomaly
        at the same step.)"""
        self._pending_poison = np.float32("nan")

    def _take_poison(self):
        p, self._pending_poison = self._pending_poison, np.float32(1.0)
        return p

    def _next_count(self):
        count = np.uint32(self._count)
        self._count += 1
        do_update = (self.grad_accum_steps <= 1
                     or self._count % self.grad_accum_steps == 0)
        return count, do_update

    def _step_span(self):
        """The per-step host span both step classes dispatch under — ONE
        name ("step"), because ``tools/bench_profile.py``'s overlap
        breakdown classifies recorder spans by it; a drifted name would
        silently empty the breakdown."""
        from ..profiler import RecordEvent

        return RecordEvent("step")


class TrainStep(StepSeams):
    """One-call training: ``loss = step(batch)``.

    ``loss_fn(outputs, batch) -> scalar`` or pass ``model_loss=True`` when the
    model's forward already returns the loss. The compiled program:
    forward -> grad -> (optional grad transforms) -> optimizer update,
    with params/buffers/opt_state donated (in-place buffer reuse in HBM).

    With ``scaler`` (an :class:`paddle_tpu.amp.GradScaler`), dynamic loss
    scaling is fused into the program: the loss is scaled before the
    backward pass, grads unscaled, the update skipped in-graph on overflow
    and the scale grown/backed off — no per-step host sync. Overflow flags
    surface lazily and are pulled into the scaler's host counters
    (``skipped_step_count``/``last_overflow_step``) on read.
    """

    # hapi's step also returns the model outputs for train-time metrics;
    # the flag keeps one _step body for both (the extra output would pin an
    # extra HBM buffer for callers that never read it)
    _return_out = False

    def __init__(self, model: Layer, optimizer, loss_fn: Optional[Callable] = None,
                 inputs_fn: Optional[Callable] = None,
                 grad_transform: Optional[Callable] = None, donate: bool = True,
                 rng_streams=DEFAULT_RNG_STREAMS, grad_accum_steps: int = 1,
                 grad_accum_avg: bool = True, scaler=None,
                 trainable: Optional[Callable[[str], bool]] = None):
        """``grad_accum_steps`` (k>1) enables gradient merge (reference
        ``fleet/meta_optimizers/gradient_merge_optimizer.py``): each call
        accumulates grads; every k-th call applies one optimizer update with
        the sum (mean when ``grad_accum_avg``). k calls on batch B equal one
        k=1 call on batch k*B.

        ``trainable`` (a predicate on parameter paths) freezes everything
        it rejects: frozen params ride the BUFFERS pytree — still explicit
        jit inputs (a base-weight reload never serves stale compile-time
        constants), still donated, still in ``state_dict()`` for
        crash-resume — but excluded from grad and from ``optimizer.init``,
        so optimizer state scales with the trainable subset (the
        ``Model.fit(lora=...)`` adapter path: rank-sized, not
        model-sized)."""
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.inputs_fn = resolve_inputs_fn(inputs_fn, loss_fn)
        self.grad_transform = grad_transform
        self._trainable = trainable
        # copy: the step donates its buffers; the Layer must keep valid arrays
        all_params = jax.tree.map(lambda x: jnp.array(x, copy=True), param_state(model))
        self.params, frozen = self._split_trainable(all_params)
        self.buffers = jax.tree.map(lambda x: jnp.array(x, copy=True), buffer_state(model))
        self.buffers.update(frozen)
        self.opt_state = optimizer.init(self.params)
        self._rng_streams = tuple(rng_streams)
        # materialized once, here: every step then takes the base key as
        # a finished buffer and never waits on the program that made it
        # tpu-lint: disable=R1(one-time construction readback; the key is a finished buffer before the first step)
        self._base_key = jax.block_until_ready(framework_random.next_key())
        self._count = 0
        self.grad_accum_steps = int(grad_accum_steps)
        self.grad_accum_avg = grad_accum_avg
        self._grad_accum = None
        if self.grad_accum_steps > 1:
            self._grad_accum = jax.tree.map(
                lambda x: jnp.zeros(x.shape, _grad_dtype(x.dtype)), self.params)
        self._init_seams(scaler, self.grad_accum_steps)
        self.scaler_state = (jax.tree.map(jnp.asarray, dict(self.scaler.state))
                             if self.scaler is not None else None)
        donate_argnums = (0, 1, 2, 3) if donate else ()
        # retrace accounting: every new shape specialization of the step is
        # recorded under this key (see framework/compile_cache.py)
        self._cc_name = compile_cache.register_name(
            f"{type(self).__name__}:{type(model).__name__}")
        self._traced = compile_cache.instrument(self._step, self._cc_name)
        # two specializations when accumulating: accumulate-only / apply;
        # watched, so that program_scopes() can say what scope each
        # instruction of the step belongs to
        self._compiled = compile_cache.watched_jit(
            self._step, self._cc_name, "train",
            donate_argnums=donate_argnums, static_argnames=("do_update",))
        # FLAGS_check_nan_inf / watchdog variant: also reduces grads/params
        # finiteness in-graph (framework/debugging.py) — compiled on first use
        self._compiled_checked = None
        self._donate_argnums = donate_argnums

    def _split_trainable(self, all_params):
        """``(trainable, frozen)`` split of a flat param dict per the
        ``trainable`` predicate (everything/nothing when None)."""
        if self._trainable is None:
            return all_params, {}
        params = {k: v for k, v in all_params.items() if self._trainable(k)}
        frozen = {k: v for k, v in all_params.items()
                  if not self._trainable(k)}
        if not params:
            raise ValueError(
                "the trainable= predicate selected no parameters — "
                "nothing to optimize (for LoRA: apply_lora(model, config) "
                "before building the step)")
        return params, frozen

    def _step(self, params, buffers, opt_state, accum, scaler_state, batch,
              key, count, poison, with_check=False, do_update=True):
        # fold_in runs INSIDE the compiled step: a per-step key computed
        # outside would be a second program dispatch per step whose result
        # this one waits on. `count` arrives as a host numpy scalar, so a
        # step is exactly one dispatch with no device-side dependency.
        rngs = split_rng_streams(jax.random.fold_in(key, count),
                                 self._rng_streams)
        use_scaler = scaler_state is not None

        def compute_loss(p):
            inputs = self.inputs_fn(batch)
            if not isinstance(inputs, (tuple, list)):
                inputs = (inputs,)
            out, new_buf = functional_call(self.model, p, buffers, *inputs, rngs=rngs)
            raw = out if self.loss_fn is None else self.loss_fn(out, batch)
            loss = jnp.asarray(raw, jnp.float32) * poison
            scaled = loss * scaler_state["scale"] if use_scaler else loss
            return scaled, (new_buf, out, loss)

        (_, (new_buffers, out, loss)), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params)
        extras = (out,) if self._return_out else ()
        accum = accumulate_grads(accum, grads)
        if not do_update:
            return (loss, *extras, params, new_buffers, opt_state, accum,
                    scaler_state)
        grads, accum = merge_accumulated(accum, grads, self.grad_accum_steps,
                                         self.grad_accum_avg)
        if self.grad_transform is not None:
            grads = self.grad_transform(grads)
        if use_scaler:
            from ..amp.grad_scaler import unscale_and_check

            grads, found = unscale_and_check(grads, scaler_state)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = self.optimizer.update(
                    grads, opt_state, params)
            (new_params, new_buffers, new_opt_state), new_scaler_state, \
                ok, found_inf = scaler_guard(
                    loss, found, scaler_state,
                    (new_params, new_buffers, new_opt_state),
                    (params, buffers, opt_state))
            return (loss, *extras, new_params, new_buffers, new_opt_state,
                    accum, new_scaler_state, ok, found_inf)
        with jax.named_scope("optimizer"):
            new_params, new_opt_state = self.optimizer.update(
                grads, opt_state, params)
        if with_check:
            ok, (new_params, new_buffers, new_opt_state) = finite_guard(
                grads, (new_params, new_buffers, new_opt_state),
                (params, buffers, opt_state), extra_ok=jnp.isfinite(loss))
            return (loss, *extras, new_params, new_buffers, new_opt_state,
                    accum, scaler_state, ok, jnp.zeros((), jnp.bool_))
        return (loss, *extras, new_params, new_buffers, new_opt_state, accum,
                scaler_state)

    def _checked_compiled(self):
        if self._compiled_checked is None:
            self._compiled_checked = jax.jit(
                functools.partial(self._traced, with_check=True),
                donate_argnums=self._donate_argnums)
        return self._compiled_checked

    def cache_stats(self) -> dict:
        """Compile/call counters for this step's program: ``{"compiles",
        "calls", "cache_hits", "signatures", "last_trace_signature"}``."""
        return compile_cache.cache_stats(self._cc_name)

    def lower(self, batch):
        """The update program ``step(batch)`` dispatches, lowered but not
        run (a ``jax.stages.Lowered``; ``.as_text()`` is its StableHLO) —
        how a caller sees which kernels the step really contains. Advances
        no counter and touches no state."""
        # spelled exactly like the dispatch sites (_plain_call passes
        # do_update by keyword, the scaler path omits it) so this reuses
        # their trace instead of recording a second one
        kw = {} if self.scaler_state is not None else {"do_update": True}
        return self._compiled.lower(
            self.params, self.buffers, self.opt_state, self._grad_accum,
            self.scaler_state, batch, self._base_key,
            np.uint32(self._count), np.float32(1.0), **kw)

    def _checked_call(self, batch, count, poison):
        """Dispatch one update step through the flag-returning program.
        Returns ``(loss, *extras, ok, found_inf)`` with flags LAZY (device
        scalars, no host sync) and state stored back on self."""
        n = 1 + len(("out",) if self._return_out else ())
        if self.scaler_state is not None:
            outs = self._compiled(self.params, self.buffers, self.opt_state,
                                  self._grad_accum, self.scaler_state, batch,
                                  self._base_key, count, poison)
            (self.params, self.buffers, self.opt_state, self._grad_accum,
             self.scaler_state) = outs[n:n + 5]
            ok, found = outs[n + 5], outs[n + 6]
            if self.scaler is not None:
                self.scaler._note_step(found)
                # mirror the (lazy) updated scale so get_loss_scaling() and
                # state_dict() on the scaler object stay truthful
                self.scaler.state = dict(self.scaler_state)
        else:
            outs = self._checked_compiled()(
                self.params, self.buffers, self.opt_state, self._grad_accum,
                None, batch, self._base_key, count, poison)
            (self.params, self.buffers, self.opt_state,
             self._grad_accum) = outs[n:n + 4]
            ok, found = outs[n + 5], outs[n + 6]
        return (*outs[:n], ok, found)

    def _plain_call(self, batch, count, poison, do_update):
        n = 1 + len(("out",) if self._return_out else ())
        outs = self._compiled(self.params, self.buffers, self.opt_state,
                              self._grad_accum, None, batch, self._base_key,
                              count, poison, do_update=do_update)
        (self.params, self.buffers, self.opt_state,
         self._grad_accum) = outs[n:n + 4]
        return outs[:n]

    def watchdog_call(self, batch):
        """One step through the checked program: ``(loss, ok, found_inf)``
        with all three LAZY (the numerics watchdog batches the host sync
        every ``check_interval`` steps). ``ok``/``found_inf`` are ``None``
        on accumulate-only calls (no update happened to check)."""
        count, do_update = self._next_count()
        compile_cache.record_call(self._cc_name)
        poison = self._take_poison()
        with self._step_span():
            if not do_update:
                (loss,) = self._plain_call(batch, count, poison, False)
                return loss, None, None
            loss, ok, found = self._checked_call(batch, count, poison)
            return loss, ok, found

    def __call__(self, batch):
        from . import flags

        count, do_update = self._next_count()
        compile_cache.record_call(self._cc_name)
        poison = self._take_poison()
        with self._step_span():
            if do_update and (self.scaler_state is not None
                              or flags.flag("FLAGS_check_nan_inf")):
                loss, ok, found = self._checked_call(batch, count, poison)
                if flags.flag("FLAGS_check_nan_inf"):
                    raise_if_bad_step(ok, loss)
                return loss
            (loss,) = self._plain_call(batch, count, poison, do_update)
            return loss

    # ----------------------------------------------------------- state sync
    def sync_to_model(self):
        """Write the step's current params/buffers back into the Layer
        (for checkpointing / eval through the eager path)."""
        for name, v in self.params.items():
            self.model._set_by_path(name, v)
        for name, v in self.buffers.items():
            self.model._set_by_path(name, v)
        return self.model

    def load_from_model(self):
        self.params, frozen = self._split_trainable(param_state(self.model))
        self.buffers = buffer_state(self.model)
        self.buffers.update(frozen)
        return self

    def state_dict(self):
        sd = {"params": self.params, "buffers": self.buffers,
              "opt_state": self.opt_state, "count": self._count,
              # the per-step RNG is fold_in(base_key, count): restoring BOTH
              # makes a resumed run's dropout streams bit-identical
              "base_key": np.asarray(jax.random.key_data(self._base_key))}
        if self._grad_accum is not None:
            sd["grad_accum"] = self._grad_accum
        if self.scaler_state is not None:
            sd["scaler_state"] = self.scaler_state
        return sd

    def set_state_dict(self, sd):
        # restored leaves are often host numpy (framework_io / checkpoint
        # load): move them to device arrays so the donated dispatch path
        # sees the same avals as a live run (no donation warnings/copies)
        def dev(tree):
            return jax.tree.map(jnp.asarray, tree)

        self.params = dev(sd["params"])
        self.buffers = dev(sd["buffers"])
        self.opt_state = dev(sd["opt_state"])
        self._count = int(sd.get("count", 0))
        if sd.get("base_key") is not None:
            self._base_key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(sd["base_key"]), jnp.uint32))
        if "grad_accum" in sd:
            self._grad_accum = dev(sd["grad_accum"])
        if "scaler_state" in sd and self.scaler_state is not None:
            self.scaler_state = dev(sd["scaler_state"])


class EvalStep:
    def __init__(self, model: Layer):
        self.model = model
        self._cc_name = compile_cache.register_name(
            f"EvalStep:{type(model).__name__}")

        def _run(params, buffers, *args):
            out, _ = functional_call(model, params, buffers, *args)
            return out

        self._compiled = jax.jit(
            compile_cache.instrument(_run, self._cc_name))

    def cache_stats(self) -> dict:
        return compile_cache.cache_stats(self._cc_name)

    def __call__(self, *args):
        compile_cache.record_call(self._cc_name)
        return self._compiled(param_state(self.model), buffer_state(self.model), *args)
