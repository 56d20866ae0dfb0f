"""Sharding utilities + the distributed train step.

This is the replacement for the reference's whole program-rewrite stack:
``sharding_optimizer.py`` / ``tensor_parallel_optimizer.py`` meta-optimizers
and the ``c_*`` collective insertion passes collapse into: (1) parameter
PartitionSpecs declared by layers (or by policy here), (2) one ``jax.jit``
with in/out shardings, (3) GSPMD.

ZeRO mapping (reference ``group_sharded_parallel`` levels, SURVEY §2.3):
- os   (stage 1): optimizer state sharded over "sdp"
- os_g (stage 2): + gradient reduce-scatter (weight-update sharding)
- p_g_os (stage 3): + parameters sharded over "sdp" (gathered on use)
"""
from __future__ import annotations

import logging
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..nn.layer import Layer, buffer_state, functional_call, param_state
from ..framework import random as framework_random
from ..framework.jit import StepSeams
from .mesh import get_mesh, require_mesh
from .overlap import (build_buckets, bucketed_reduce, shard_first_free_dim,
                      weight_update_specs)

P = PartitionSpec


def _filter_spec(spec: tuple, mesh) -> PartitionSpec:
    """Drop axes absent from the mesh (so tp-annotated models run on a
    dp-only mesh etc.)."""
    out = []
    for s in spec:
        if s is None:
            out.append(None)
        elif isinstance(s, (tuple, list)):
            kept = [a for a in s if a in mesh.shape]
            out.append(tuple(kept) if kept else None)
        else:
            out.append(s if s in mesh.shape else None)
    return PartitionSpec(*out)


def param_specs(model: Layer, mesh=None, zero3_axis: Optional[str] = None,
                min_zero3_size: int = 2 ** 16) -> Dict[str, PartitionSpec]:
    """PartitionSpec per parameter path: layer-declared (TP) specs first,
    then optional ZeRO-3 sharding of remaining large params over
    ``zero3_axis`` (largest dim divisible by the axis size)."""
    mesh = mesh or require_mesh()
    declared = dict(model.named_param_shardings())
    specs: Dict[str, PartitionSpec] = {}
    for name, p in model.named_parameters():
        if name in declared:
            specs[name] = _filter_spec(declared[name], mesh)
            continue
        spec = [None] * p.ndim
        if zero3_axis and zero3_axis in mesh.shape and p.size >= min_zero3_size:
            ax_size = mesh.shape[zero3_axis]
            # pick the largest divisible dim
            cand = sorted(range(p.ndim), key=lambda i: -p.shape[i])
            for i in cand:
                if p.shape[i] % ax_size == 0:
                    spec[i] = zero3_axis
                    break
        specs[name] = PartitionSpec(*spec)
    return specs


def buffer_specs(model: Layer, mesh=None) -> Dict[str, PartitionSpec]:
    mesh = mesh or require_mesh()
    return {name: PartitionSpec() for name, _ in model.named_buffers()}


def put_global(x, sharding: NamedSharding):
    """Place a host value onto ``sharding``, valid on meshes spanning
    multiple processes.

    Single-process: plain ``device_put``. Multi-process: ``device_put``
    onto a non-addressable sharding first runs a broadcast to assert every
    process passed the same value — a collective per leaf, and one the CPU
    backend may not even implement — so the global array is assembled with
    ``make_array_from_callback`` instead: each process materialises only
    its addressable shards, no communication. The multi-controller data
    contract (every process passes the same global value) is assumed, the
    same contract ``device_put`` would have verified.
    """
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def shard_params(params: Dict[str, Any], specs: Dict[str, PartitionSpec], mesh=None):
    """Scatter each param to its NamedSharding (host->mesh). Goes through
    numpy so the result never aliases the input buffer (the train step
    donates its params; the source Layer must stay valid)."""
    mesh = mesh or require_mesh()
    return {name: put_global(np.asarray(p), NamedSharding(mesh, specs.get(name, PartitionSpec())))
            for name, p in params.items()}


def opt_state_specs(opt_state, params_specs: Dict[str, PartitionSpec],
                    shard_axis: Optional[str] = None, mesh=None,
                    on_fallback: Optional[Callable[[str], None]] = None):
    """Specs for optimizer state: moment slots inherit their parameter's
    spec; with ``shard_axis`` (ZeRO-1/2 weight-update sharding, cf.
    "Automatic Cross-Replica Sharding" in PAPERS.md) unsharded dims of the
    slots are additionally sharded over that axis — by the SAME dim rule
    as ``overlap.weight_update_specs`` (one shared helper), so the param
    shard and its moment shards always land on the same dim.

    A slot with no ``shard_axis``-divisible dim stays at its base spec —
    a silently REPLICATED piece of a nominally sharded update; each such
    param path is reported once through ``on_fallback`` so callers can
    count it instead of shipping a mis-sharded run invisibly."""
    mesh = mesh or require_mesh()

    def spec_for(path_key, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return PartitionSpec()
        base = params_specs.get(path_key)
        if base is None:
            return PartitionSpec()
        if shard_axis and shard_axis in mesh.shape:
            spec, ok = shard_first_free_dim(list(base), leaf.shape,
                                            shard_axis, mesh)
            if not ok and on_fallback is not None:
                on_fallback(path_key)
            return spec
        spec = list(base) + [None] * (leaf.ndim - len(list(base)))
        return PartitionSpec(*spec)

    out = {}
    for slot, val in opt_state.items():
        if isinstance(val, dict) and slot != "step":
            out[slot] = {k: spec_for(k, v) for k, v in val.items()}
        elif hasattr(val, "ndim"):
            out[slot] = PartitionSpec()
        else:
            out[slot] = None
    return out


class DistributedTrainStep(StepSeams):
    """pjit'd hybrid-parallel train step.

    Composition by configuration (the ``DistributedStrategy`` analogue):
      - data parallel: batch sharded over ("dp", "sdp")
      - tensor parallel: layer-declared "mp" specs
      - ZeRO: ``sharding_stage`` 1/2 -> opt-state (+grad) sharded over "sdp";
        3 -> params too
      - overlap: ``overlap_grad_reduce=True`` -> bucketed gradient
        reduction in reverse-backward order (``overlap.build_buckets`` /
        ``bucketed_reduce``) + the weight update computed on each
        replica's ``sdp`` shard under ``sharding_stage >= 1`` (default
        off => the serial schedule, bit-identical to before the knob
        existed)
      - recompute: wrap blocks with paddle_tpu.distributed.recompute
      - sp/pp: see sequence_parallel.py / pipeline.py
    """

    def __init__(self, model: Layer, optimizer, loss_fn=None, inputs_fn=None,
                 mesh=None, batch_axes=("dp", "sdp"), sharding_stage: int = 0,
                 grad_transform=None, donate: bool = True,
                 grad_accum_steps: int = 1, grad_accum_avg: bool = True,
                 scaler=None, overlap_grad_reduce: bool = False,
                 bucket_size_mb: Optional[float] = None,
                 bucket_count: Optional[int] = None):
        from ..framework.jit import (DEFAULT_RNG_STREAMS, _grad_dtype,
                                     resolve_inputs_fn)

        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.inputs_fn = resolve_inputs_fn(inputs_fn, loss_fn)
        self.grad_transform = grad_transform
        self.mesh = mesh or require_mesh()
        self.batch_axes = batch_axes

        # the public ZeRO entry (group_sharded_parallel) tags the optimizer
        # with the USER's requested stage; it must not be silently
        # downgraded by a caller's heuristic default (Engine passes 2)
        sharding_stage = max(sharding_stage,
                             getattr(optimizer, "_group_sharded_stage", 0))
        self.sharding_stage = sharding_stage
        zero3 = "sdp" if sharding_stage >= 3 else None
        self.specs = param_specs(model, self.mesh, zero3_axis=zero3)
        self.params = shard_params(param_state(model), self.specs, self.mesh)
        self.buffers = {k: put_global(np.asarray(v), NamedSharding(self.mesh, P()))
                        for k, v in buffer_state(model).items()}
        opt_state = optimizer.init(self.params)
        shard_axis = "sdp" if sharding_stage >= 1 else None
        # every param whose update stays replicated because no dim divides
        # the sdp axis — counted, logged, and surfaced in statusz() so a
        # mis-sharded run is visible instead of silently replicated
        self.zero_fallback_params: list = []

        def _note_fallback(name):
            if name not in self.zero_fallback_params:
                self.zero_fallback_params.append(name)

        self.opt_specs = opt_state_specs(opt_state, self.specs, shard_axis,
                                         self.mesh,
                                         on_fallback=_note_fallback)
        self.opt_state = self._shard_opt_state(opt_state)

        # ---- overlap schedule (ROADMAP item 1): bucketed grad reduction
        # in reverse-backward order + ZeRO weight-update sharding. All of
        # it is OFF by default; the serial path below is untouched.
        self.overlap_grad_reduce = bool(overlap_grad_reduce)
        if bucket_size_mb is None:
            # ported DataParallel scripts carry their comm_buffer_size
            # (MB) — honor it as the bucket size hint
            bucket_size_mb = getattr(model, "_comm_buffer_mb", None) or 25.0
        self.bucket_size_mb = float(bucket_size_mb)
        self.update_specs = weight_update_specs(
            self.specs, {k: v.shape for k, v in self.params.items()},
            shard_axis, self.mesh, on_fallback=_note_fallback)
        self._sharded_update = bool(self.overlap_grad_reduce and shard_axis)
        self._reduce_specs = (self.update_specs if self._sharded_update
                              else self.specs)
        self._buckets = None
        if self.overlap_grad_reduce:
            sizes = {k: int(v.size) * int(jnp.dtype(v.dtype).itemsize)
                     for k, v in self.params.items()}
            self._buckets = build_buckets(
                sizes, int(self.bucket_size_mb * 2 ** 20), bucket_count)
        if self.zero_fallback_params and shard_axis:
            from ..observability.registry import default_registry

            reg = default_registry()
            reg.inc("distributed.zero_fallback_params_total",
                    len(self.zero_fallback_params))
            reg.set_gauge("distributed.zero_fallback_params",
                          len(self.zero_fallback_params),
                          step=type(model).__name__,
                          stage=str(sharding_stage))
            logging.getLogger(__name__).warning(
                "ZeRO stage %d: %d param(s) have no sdp-divisible dim; "
                "their update stays REPLICATED: %s", sharding_stage,
                len(self.zero_fallback_params),
                ", ".join(self.zero_fallback_params[:8])
                + ("..." if len(self.zero_fallback_params) > 8 else ""))

        batch_spec = PartitionSpec(tuple(a for a in batch_axes if a in self.mesh.shape) or None)
        self._batch_sharding = NamedSharding(self.mesh, batch_spec)
        # tpu-lint: disable=R1(one-time construction readback; see TrainStep.__init__ — the key is a finished buffer before the first step)
        self._base_key = jax.block_until_ready(framework_random.next_key())
        self._count = 0
        self._rng_streams = DEFAULT_RNG_STREAMS
        # gradient merge (reference gradient_merge_optimizer.py): accumulator
        # sharded like the grads it receives — the param specs on the
        # serial path, the reduce-scattered update specs under the overlap
        # schedule (so accumulation happens on each replica's shard and
        # the sdp memory win extends to the accumulator)
        self.grad_accum_steps = int(grad_accum_steps)
        self.grad_accum_avg = grad_accum_avg
        self._grad_accum = None
        if self.grad_accum_steps > 1:
            self._grad_accum = {
                k: put_global(
                    np.zeros(v.shape, _grad_dtype(v.dtype)),
                    NamedSharding(self.mesh, self._reduce_specs[k]))
                for k, v in self.params.items()}
        self._init_seams(scaler, self.grad_accum_steps)
        # scale state is replicated: every device applies the same skip/grow
        # decision, so the rolled-back state stays consistent across shards
        self.scaler_state = (
            {k: put_global(np.asarray(v), NamedSharding(self.mesh, P()))
             for k, v in dict(self.scaler.state).items()}
            if self.scaler is not None else None)
        donate_argnums = (0, 1, 2, 3) if donate else ()
        from ..framework import compile_cache

        self._cc_name = compile_cache.register_name(
            f"DistributedTrainStep:{type(model).__name__}")
        self._traced = compile_cache.instrument(self._step, self._cc_name)
        self._compiled = jax.jit(self._traced, donate_argnums=donate_argnums,
                                 static_argnames=("do_update",))
        self._donate_argnums = donate_argnums
        self._compiled_checked = None
        # silent-data-corruption defense (distributed/integrity.py):
        # None = off, and the traced programs stay bit-identical to a
        # build without the feature (with_fp is never passed)
        self._integrity = None
        self._fp_compiled = None
        self._last_fp = None

    def enable_integrity(self, vote_axis="dp"):
        """Turn on in-program cross-replica fingerprints (``None``
        disables). The checked/scaler step specializations are rebuilt so
        they emit an extra lazy ``uint32[vote_size, 1 + n_buckets]``
        output; :meth:`take_fingerprint` hands it to the supervisor's
        :class:`~paddle_tpu.distributed.integrity.IntegrityMonitor`
        without forcing a host sync. Returns the checker (or ``None``)."""
        from .integrity import IntegrityChecker

        if vote_axis is None:
            self._integrity = None
        else:
            self._integrity = IntegrityChecker(
                self.mesh, vote_axis, param_specs=self.specs,
                opt_specs=self.opt_specs, grad_specs=self._reduce_specs,
                buckets=self._buckets)
        self._compiled_checked = None
        self._fp_compiled = None
        self._last_fp = None
        return self._integrity

    def take_fingerprint(self):
        """The last checked call's lazy fingerprint array (once)."""
        fp, self._last_fp = self._last_fp, None
        return fp

    def _checked_compiled(self):
        import functools

        if self._compiled_checked is None:
            kwargs = ({"with_check": True, "with_fp": True}
                      if self._integrity is not None
                      else {"with_check": True})
            self._compiled_checked = jax.jit(
                functools.partial(self._traced, **kwargs),
                donate_argnums=self._donate_argnums)
        return self._compiled_checked

    def _scaler_compiled(self):
        import functools

        if self._integrity is None:
            return self._compiled
        if self._fp_compiled is None:
            self._fp_compiled = jax.jit(
                functools.partial(self._traced, with_fp=True),
                donate_argnums=self._donate_argnums)
        return self._fp_compiled

    def cache_stats(self) -> dict:
        from ..framework import compile_cache

        return compile_cache.cache_stats(self._cc_name)

    def collective_schedule(self) -> list:
        """The bucketed reduction schedule as plain dicts (``[]`` on the
        serial path) — what ``bench_profile --overlap`` names its
        per-bucket collective spans after."""
        return [b.to_dict() for b in (self._buckets or [])]

    def statusz(self) -> dict:
        """Introspection snapshot of the sharding/overlap configuration —
        the training-side ``/statusz`` handle. A nonzero
        ``zero_fallback_params`` under ``sharding_stage >= 1`` means that
        many updates silently run replicated (no sdp-divisible dim)."""
        return {
            "sharding_stage": self.sharding_stage,
            "overlap_grad_reduce": self.overlap_grad_reduce,
            "bucket_size_mb": self.bucket_size_mb,
            "buckets": self.collective_schedule(),
            "params": len(self.params),
            "zero_fallback_params": list(self.zero_fallback_params),
            "grad_accum_steps": self.grad_accum_steps,
        }

    def _shard_opt_state(self, opt_state):
        out = {}
        for slot, val in opt_state.items():
            spec = self.opt_specs.get(slot)
            if isinstance(val, dict) and isinstance(spec, dict):
                out[slot] = {k: put_global(v, NamedSharding(self.mesh, spec[k]))
                             for k, v in val.items()}
            elif hasattr(val, "ndim"):
                out[slot] = put_global(val, NamedSharding(self.mesh, P()))
            else:
                out[slot] = val
        return out

    def _step(self, params, buffers, opt_state, accum, scaler_state, batch,
              key, count, poison, with_check=False, do_update=True,
              with_fp=False):
        from ..framework.jit import (accumulate_grads, finite_guard,
                                     merge_accumulated, split_rng_streams)

        # fold_in inside the program: one dispatch per step, not two
        # (see framework/jit.py _step)
        rngs = split_rng_streams(jax.random.fold_in(key, count),
                                 self._rng_streams)
        use_scaler = scaler_state is not None

        def compute_loss(p):
            # keep params at their declared shardings inside the traced fn
            p = {k: jax.lax.with_sharding_constraint(v, NamedSharding(self.mesh, self.specs[k]))
                 for k, v in p.items()}
            inputs = self.inputs_fn(batch)
            if not isinstance(inputs, (tuple, list)):
                inputs = (inputs,)
            out, new_buf = functional_call(self.model, p, buffers, *inputs, rngs=rngs)
            raw = out if self.loss_fn is None else self.loss_fn(out, batch)
            loss = jnp.asarray(raw, jnp.float32) * poison
            scaled = loss * scaler_state["scale"] if use_scaler else loss
            return scaled, (new_buf, loss)

        (_, (new_buffers, loss)), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params)
        if self._buckets:
            # overlap schedule: pin each reverse-backward-ordered bucket
            # of grads to its reduction placement (reduce-scattered over
            # sdp under sharding_stage >= 1) as its own schedulable unit,
            # so XLA's latency-hiding scheduler issues bucket k's
            # collective while bucket k+1's grads are still being
            # computed. Placement only — values are untouched.
            grads = bucketed_reduce(grads, self._buckets,
                                    self._reduce_specs, self.mesh)
        accum = accumulate_grads(accum, grads)
        if not do_update:
            return loss, params, new_buffers, opt_state, accum, scaler_state
        grads, accum = merge_accumulated(accum, grads, self.grad_accum_steps,
                                         self.grad_accum_avg)
        if self.grad_transform is not None:
            grads = self.grad_transform(grads)
        if use_scaler:
            from ..amp.grad_scaler import unscale_and_check

            grads, found = unscale_and_check(grads, scaler_state)
        upd_params = params
        if self._sharded_update:
            # ZeRO weight-update sharding (arXiv:2004.13336): constrain
            # the update's param input to the sdp-sharded update specs so
            # the whole optimizer computation runs on each replica's
            # shard (grads and moments already live there); the param
            # constraint right below is the all-gather back.
            upd_params = {k: jax.lax.with_sharding_constraint(
                v, NamedSharding(self.mesh, self.update_specs[k]))
                for k, v in params.items()}
        new_params, new_opt_state = self.optimizer.update(grads, opt_state,
                                                          upd_params)
        new_params = {k: jax.lax.with_sharding_constraint(
            v, NamedSharding(self.mesh, self.specs[k])) for k, v in new_params.items()}
        if use_scaler:
            from ..framework.jit import scaler_guard

            # the skip/grow decision is a replicated scalar, so every shard
            # of the GSPMD state takes the same branch — rollback-consistent
            (new_params, new_buffers, new_opt_state), new_scaler_state, \
                ok, found_inf = scaler_guard(
                    loss, found, scaler_state,
                    (new_params, new_buffers, new_opt_state),
                    (params, buffers, opt_state))
            out = (loss, new_params, new_buffers, new_opt_state, accum,
                   new_scaler_state, ok, found_inf)
            if with_fp:
                # fingerprint the GUARDED state the step actually keeps
                out += (self._integrity.fingerprints(
                    new_params, new_opt_state, grads),)
            return out
        if with_check:
            ok, (new_params, new_buffers, new_opt_state) = finite_guard(
                grads, (new_params, new_buffers, new_opt_state),
                (params, buffers, opt_state), extra_ok=jnp.isfinite(loss))
            out = (loss, new_params, new_buffers, new_opt_state, accum,
                   scaler_state, ok, jnp.zeros((), jnp.bool_))
            if with_fp:
                out += (self._integrity.fingerprints(
                    new_params, new_opt_state, grads),)
            return out
        return loss, new_params, new_buffers, new_opt_state, accum, scaler_state

    def _put_batch(self, batch):
        def put(x):
            if not (hasattr(x, "ndim") or isinstance(x, (np.ndarray, list))):
                return x
            if isinstance(x, jax.Array):
                # already on device (prefetch pipeline): reshard in place —
                # np.asarray here would block on a D2H copy (and raise
                # outright for non-addressable multi-process batches)
                return jax.device_put(x, self._batch_sharding)
            return put_global(np.asarray(x), self._batch_sharding)

        return jax.tree.map(put, batch)

    def _checked_call(self, batch, count, poison):
        if self.scaler_state is not None:
            out = self._scaler_compiled()(
                self.params, self.buffers, self.opt_state,
                self._grad_accum, self.scaler_state, batch,
                self._base_key, count, poison)
            if self._integrity is not None:
                *out, self._last_fp = out
            (loss, self.params, self.buffers, self.opt_state,
             self._grad_accum, self.scaler_state, ok, found) = out
            if self.scaler is not None:
                self.scaler._note_step(found)
                self.scaler.state = dict(self.scaler_state)
            return loss, ok, found
        out = self._checked_compiled()(self.params, self.buffers,
                                       self.opt_state, self._grad_accum,
                                       None, batch, self._base_key, count,
                                       poison)
        if self._integrity is not None:
            *out, self._last_fp = out
        (loss, self.params, self.buffers, self.opt_state, self._grad_accum,
         _, ok, found) = out
        return loss, ok, found

    def watchdog_call(self, batch):
        """``(loss, ok, found_inf)``, flags LAZY (no host sync); ``None``
        flags on accumulate-only calls. See TrainStep.watchdog_call."""
        from ..framework import compile_cache

        batch = self._put_batch(batch)
        count, do_update = self._next_count()
        compile_cache.record_call(self._cc_name)
        poison = self._take_poison()
        with self.mesh, self._step_span():
            if not do_update:
                loss, self.params, self.buffers, self.opt_state, \
                    self._grad_accum, _ = \
                    self._compiled(self.params, self.buffers, self.opt_state,
                                   self._grad_accum, None, batch,
                                   self._base_key, count, poison,
                                   do_update=False)
                return loss, None, None
            return self._checked_call(batch, count, poison)

    def __call__(self, batch):
        from ..framework import compile_cache, flags
        from ..framework.jit import raise_if_bad_step

        batch = self._put_batch(batch)
        count, do_update = self._next_count()
        compile_cache.record_call(self._cc_name)
        poison = self._take_poison()
        with self.mesh, self._step_span():
            if do_update and (self.scaler_state is not None
                              or flags.flag("FLAGS_check_nan_inf")):
                loss, ok, found = self._checked_call(batch, count, poison)
                if flags.flag("FLAGS_check_nan_inf"):
                    raise_if_bad_step(ok, loss)
                return loss
            loss, self.params, self.buffers, self.opt_state, \
                self._grad_accum, _ = \
                self._compiled(self.params, self.buffers, self.opt_state,
                               self._grad_accum, None, batch, self._base_key,
                               count, poison, do_update=do_update)
        return loss

    def sync_to_model(self):
        for name, v in self.params.items():
            self.model._set_by_path(name, v)
        for name, v in self.buffers.items():
            self.model._set_by_path(name, v)
        return self.model

    def state_dict(self):
        sd = {"params": self.params, "buffers": self.buffers,
              "opt_state": self.opt_state, "count": self._count,
              "base_key": np.asarray(jax.random.key_data(self._base_key))}
        if self._grad_accum is not None:
            sd["grad_accum"] = self._grad_accum
        if self.scaler_state is not None:
            sd["scaler_state"] = self.scaler_state
        return sd

    def state_shardings(self):
        """Flat ``{checkpoint key: NamedSharding}`` matching
        :meth:`state_dict`'s layout, for
        ``distributed.checkpoint.load_state(shardings=...)`` — each process
        materialises only its addressable shards, the multi-host resume
        path (reference: fleet ``load_persistables`` +
        ``python/paddle/distributed/fleet/utils/fs.py`` shard merge)."""
        out = {}
        for k, spec in self.specs.items():
            out[f"params/{k}"] = NamedSharding(self.mesh, spec)
        for k in self.buffers:
            out[f"buffers/{k}"] = NamedSharding(self.mesh, P())
        for slot, spec in self.opt_specs.items():
            if isinstance(spec, dict):
                for k, s in spec.items():
                    out[f"opt_state/{slot}/{k}"] = NamedSharding(self.mesh, s)
            elif spec is not None:
                out[f"opt_state/{slot}"] = NamedSharding(self.mesh, P())
        if self._grad_accum is not None:
            for k, spec in self._reduce_specs.items():
                out[f"grad_accum/{k}"] = NamedSharding(self.mesh, spec)
        out["base_key"] = NamedSharding(self.mesh, P())
        if self.scaler_state is not None:
            for k in self.scaler_state:
                out[f"scaler_state/{k}"] = NamedSharding(self.mesh, P())
        return out

    def set_state_dict(self, state):
        """Restore from a state tree (plain numpy from ``load_state``, or
        global arrays from a sharded load): every leaf is placed onto this
        step's declared sharding, so a checkpoint resumes correctly on a
        different topology too."""
        def put(v, sharding):
            if isinstance(v, jax.Array) and v.sharding == sharding:
                return v
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                # already a global array on another sharding: reshard
                return jax.device_put(v, sharding)
            return put_global(np.asarray(v), sharding)

        self.params = {k: put(state["params"][k],
                              NamedSharding(self.mesh, self.specs[k]))
                       for k in self.params}
        self.buffers = {k: put(state["buffers"][k],
                               NamedSharding(self.mesh, P()))
                        for k in self.buffers}
        new_opt = {}
        for slot, val in self.opt_state.items():
            spec = self.opt_specs.get(slot)
            sval = state["opt_state"][slot]
            if isinstance(val, dict) and isinstance(spec, dict):
                new_opt[slot] = {k: put(sval[k],
                                        NamedSharding(self.mesh, spec[k]))
                                 for k in val}
            elif hasattr(val, "ndim"):
                new_opt[slot] = put(sval, NamedSharding(self.mesh, P()))
            else:
                new_opt[slot] = sval
        self.opt_state = new_opt
        self._count = int(state.get("count", self._count))
        if state.get("base_key") is not None:
            self._base_key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(state["base_key"]), jnp.uint32))
        if self._grad_accum is not None and "grad_accum" in state:
            self._grad_accum = {
                k: put(state["grad_accum"][k],
                       NamedSharding(self.mesh, self._reduce_specs[k]))
                for k in self._grad_accum}
        if self.scaler_state is not None and "scaler_state" in state:
            self.scaler_state = {
                k: put(state["scaler_state"][k], NamedSharding(self.mesh, P()))
                for k in self.scaler_state}
