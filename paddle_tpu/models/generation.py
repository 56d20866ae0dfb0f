"""Compiled KV-cache generation engine: O(1)-compile autoregressive decode.

Serving a decoder LM naively is the worst case for an XLA backend twice
over: full-sequence forwards redo O(L^2) attention per emitted token, and
every grown sequence length is a novel shape, so N tokens trace N programs
— the exact recompile storm ``framework.compile_cache.retrace_guard`` was
built to catch. This module fixes both with a strict shape discipline:

- the KV cache (:mod:`.kv_cache`) is PREALLOCATED — its shape never
  changes while decoding, only a position scalar advances;
- **prefill** runs the prompt (right-padded up to the smallest PR-2 style
  length bucket) through the flash-eligible block-local attention path and
  writes the prompt's K/V into the cache: one compile per *bucket*, not
  per prompt length;
- **decode** is a single-token step: cached dot-product attention against
  the full cache under a position mask, RoPE/position tables indexed at a
  *traced* position scalar — exactly ONE compile total, reused for every
  position of every request of the same batch geometry.

Generating N tokens therefore costs ``#buckets + 1`` XLA programs instead
of O(N). Sampling (greedy / temperature / top-k / top-p, per-sequence EOS
early-stop via a done-mask — no shape change) runs inside the compiled
steps; the driver is a plain Python loop (no ``lax.while_loop``: the two
jitted steps with donated cache buffers are the whole program, and the
loop stays debuggable/interruptible). Both steps are
``compile_cache``-instrumented (``generate:prefill:*`` /
``generate:decode:*`` keys) and the loop runs under a ``decode``
RecordEvent span.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import compile_cache
from ..framework import random as framework_random
from ..nn.layer import buffer_state, functional_call, param_state
from ..io.batching import bucket_for
from ..observability import tracing as _tracing
from .kv_cache import (cache_geometry, constrain_cache, init_cache,
                       normalize_kv_dtype)

__all__ = ["GenerationEngine", "generate", "sample_logits", "filter_logits",
           "sample_logits_rows", "sample_branch", "per_row_keys",
           "DEFAULT_PREFILL_BUCKETS"]

# prompt lengths round up to the smallest of these (clipped to the
# model's max_length) — the serving analogue of DataLoader length_buckets
DEFAULT_PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)


# -------------------------------------------------------------- sampling
def filter_logits(logits, temperature=1.0, top_k: int = 0, top_p=1.0,
                  use_top_p: Optional[bool] = None):
    """The temperature/top-k/top-p transform :func:`sample_logits` draws
    from, returned as float32 logits [..., V] (``-inf`` on filtered
    entries). Factored out so speculative verification can materialize
    the EXACT sampling distribution — ``softmax(filter_logits(...))`` is
    the p (and q) of the acceptance rule — instead of approximating it.

    ``top_k``/``use_top_p`` are static (``top_k`` feeds
    ``ops.search.topk``, whose k is a compile-time constant; nucleus
    filtering costs an O(V log V) sort, so it compiles in only when
    requested); ``temperature``/``top_p`` may be traced scalars, so
    sweeping their VALUES does NOT recompile."""
    from ..ops.search import topk as ops_topk

    l = logits.astype(jnp.float32) / jnp.maximum(
        jnp.asarray(temperature, jnp.float32), 1e-6)
    if top_k and top_k > 0:
        vals, _ = ops_topk(l, min(int(top_k), l.shape[-1]), axis=-1)
        kth = vals[..., -1:]
        l = jnp.where(l < kth, -jnp.inf, l)
    if use_top_p is None:  # eager convenience: decide from the value
        if isinstance(top_p, jax.core.Tracer):
            # under trace the value is unknowable: deciding here would
            # concretize the tracer (ConcretizationTypeError deep in jax);
            # traced callers must pick the sampling graph statically
            raise ValueError(
                "top_p is traced but use_top_p was not given; pass "
                "use_top_p= explicitly (it selects the compiled sampling "
                "graph and must be static)")
        use_top_p = float(top_p) < 1.0
    if use_top_p:
        top_p = jnp.asarray(top_p, jnp.float32)
        # nucleus: keep the smallest prefix of the sorted distribution
        # whose EXCLUSIVE cumulative mass is < top_p (top-1 always stays)
        sorted_l = jnp.sort(l, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                         keepdims=True)
        # top_p >= 1.0 must be an EXACT no-op (cumsum rounding could
        # otherwise mask a tail token): the serving engine compiles the
        # filter in unconditionally and relies on value-level equality
        # with the unfiltered solo graph
        l = jnp.where(top_p >= 1.0, l, jnp.where(l < cutoff, -jnp.inf, l))
    return l


@jax.named_scope("sample")
def sample_logits(logits, key=None, temperature=1.0, top_k: int = 0,
                  top_p=1.0, greedy: bool = False,
                  use_top_p: Optional[bool] = None):
    """Batched next-token selection on ``logits`` [B, V]: categorical
    draw over :func:`filter_logits` (or argmax under ``greedy``).
    ``greedy``/``top_k``/``use_top_p`` are static; ``temperature``/
    ``top_p`` may be traced scalars (value sweeps don't recompile)."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = filter_logits(logits, temperature, top_k, top_p, use_top_p)
    return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)


def per_row_keys(key, batch: int, position=None):
    """Derive one PRNG key per batch row from a base ``key``: fold in the
    (possibly traced) ``position`` first, then the row index. Two
    properties the sampled paths rely on:

    - *steps differ*: the position fold gives every decode step fresh
      randomness under a fixed seed;
    - *rows differ*: the row fold gives every row its own stream, so
      identical prompts in one batch sample independent continuations.

    Row 0's key is the derivation the continuous-batching engine replays
    per slot, which is why a served request's sampled tokens match a solo
    batch-1 ``generate()`` with the same seed."""
    k = key if position is None else jax.random.fold_in(key, position)
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        k, jnp.arange(batch, dtype=jnp.uint32))


def sample_branch(live, greedy_mask, top_p):
    """What a batch's live rows ask of the sampler, as one number: 0 when
    every live row is greedy (or none is live), 1 when some live row
    samples and none of those with ``top_p < 1``, 2 when one does.
    :func:`sample_logits_rows` switches on it inside the program and the
    serving engine counts it on its host vectors (``[B]`` arrays of jax
    or of numpy alike): one test, so the count says what the program
    ran."""
    samples = live & ~greedy_mask
    return (samples.any().astype("int32")
            + (samples & (top_p < 1.0)).any().astype("int32"))


@jax.named_scope("sample")
def sample_logits_rows(logits, row_keys, temperature=1.0, top_k: int = 0,
                       top_p=1.0, *, use_top_p: Optional[bool] = None,
                       greedy_mask=None, live=None):
    """Next-token selection on ``logits`` [B, V] with one key PER ROW.

    ``temperature``/``top_p`` may be scalars or per-row ``[B]`` vectors
    (traced — sweeping values never recompiles); ``top_k`` stays static.
    ``greedy_mask`` ([B] bool, may be traced) selects argmax per row — a
    mixed greedy/sampled batch is ONE program, which is what lets the
    serving decode step hold heterogeneous requests.

    ``use_top_p`` as in :func:`filter_logits`: a bool where the caller
    knows at trace time (the offline engines' one ``top_p`` a batch);
    ``None`` decides from the values, here a step at a time. The program
    then takes ONE ``lax.switch`` on :func:`sample_branch` over the rows
    that are ``live`` ([B] bool, ``None`` = all; a serving batch's free
    slots keep their last request's settings and must not count):
    argmax alone, the categorical draw without the nucleus filter, or the
    whole graph. The switch is taken once, on the batch (under a
    ``vmap`` a ``cond`` lowers to a ``select`` that runs both sides), so
    a step whose live rows are all greedy runs no divide, no O(V log V)
    sort, no softmax and draws no random bits. Every branch gives every live
    row the token the whole graph would: a greedy row is
    ``argmax(logits)`` in all three, and ``top_p >= 1`` is an exact
    no-op of the filter by value."""
    B = logits.shape[0]
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    tp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    # the filter on the batch as [B, V], not under vmap as [B, 1, V]: in a
    # conditional's branch the TPU compiler tiles the latter one row a tile
    # (T(1,128) where the open graph gets T(8,128)) and the sort over the
    # vocabulary takes eight times as long (20 ms against 2.6 at
    # [48, 50304]: PERF.md section 6, PR 33)
    t, p = temp[:, None], tp[:, None]
    if use_top_p is not None:
        return _draw_rows(filter_logits(logits, t, top_k, p, use_top_p),
                          row_keys, logits, greedy_mask)
    ones = jnp.ones((B,), bool)
    index = sample_branch(
        ones if live is None else jnp.asarray(live),
        ~ones if greedy_mask is None else jnp.asarray(greedy_mask), tp)
    return jax.lax.switch(index, (
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32),
        lambda: _draw_rows(filter_logits(logits, t, top_k, p, False),
                           row_keys, logits, greedy_mask),
        lambda: _draw_rows(filter_logits(logits, t, top_k, p, True),
                           row_keys, logits, greedy_mask)))


def _draw_rows(filtered, row_keys, logits, greedy_mask):
    """One categorical draw a row over its ``filtered`` logits ``[B, V]``,
    argmax of ``logits`` where ``greedy_mask`` says so. A row's draw has
    the ``[1, V]`` of a batch-1 :func:`sample_logits`, so its key gives
    the bits it gives there."""
    sampled = jax.vmap(lambda k, row: jax.random.categorical(
        k, row[None], axis=-1)[0])(row_keys, filtered).astype(jnp.int32)
    if greedy_mask is None:
        return sampled
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.asarray(greedy_mask), greedy_tok, sampled)


# ---------------------------------------------------------------- engine
class GenerationEngine:
    """The two compiled steps + the Python driver loop for one model.

    Built lazily by :func:`generate` and cached on the model, so repeated
    calls reuse the jitted programs (jax re-specializes only on a novel
    batch/bucket geometry). ``cache_stats()`` exposes the compile counters
    of both steps — the number the decode bench and the tier-1 retrace
    test assert on.
    """

    def __init__(self, model, max_length: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 kv_dtype=None):
        self.model = model
        self.spec = spec = model.cache_spec()
        self.kv_dtype = normalize_kv_dtype(kv_dtype)
        self.max_length, self.prefill_buckets = cache_geometry(
            spec, max_length, prefill_buckets or DEFAULT_PREFILL_BUCKETS)
        model_name = type(model).__name__
        self._cc_prefill = compile_cache.register_name(
            f"generate:prefill:{model_name}")
        self._cc_decode = compile_cache.register_name(
            f"generate:decode:{model_name}")
        # donation keeps the cache in-place in HBM (one resident copy per
        # request); CPU's PJRT ignores donation and warns, so skip there
        donate = (2,) if jax.default_backend() != "cpu" else ()
        statics = ("top_k", "greedy", "use_top_p")
        self._prefill_compiled = jax.jit(
            compile_cache.instrument(self._prefill_fn, self._cc_prefill),
            donate_argnums=donate, static_argnames=statics)
        self._decode_compiled = jax.jit(
            compile_cache.instrument(self._decode_fn, self._cc_decode),
            donate_argnums=donate, static_argnames=statics)

    # The step bodies run under functional_call so params/buffers are
    # explicit jit inputs (weight updates between calls don't retrace).
    def _prefill_fn(self, params, buffers, cache, ids, last_index, key,
                    eos_id, temperature, top_p, *, top_k, greedy,
                    use_top_p):
        with jax.named_scope("prefill"):
            (logits, cache), _ = functional_call(
                self.model, params, buffers, ids, cache=cache,
                position_offset=0, gather_last=last_index)
        cache = constrain_cache(cache)
        logits = logits[:, 0, :]
        if greedy:
            next_tok = sample_logits(logits, None, greedy=True)
        else:
            # one key per row (not one shared key): identical prompts in a
            # batch must sample independent first tokens
            rows = per_row_keys(key, logits.shape[0])
            next_tok = sample_logits_rows(logits, rows, temperature, top_k,
                                          top_p, use_top_p=use_top_p)
        done = next_tok == eos_id
        return next_tok, done, jnp.all(done), cache

    def _decode_fn(self, params, buffers, cache, token, pos, key, done,
                   eos_id, temperature, top_p, *, top_k, greedy,
                   use_top_p):
        with jax.named_scope("decode"):
            (logits, cache), _ = functional_call(
                self.model, params, buffers, token, cache=cache,
                position_offset=pos)
        cache = constrain_cache(cache)
        logits = logits[:, -1, :]
        if greedy:
            next_tok = sample_logits(logits, None, greedy=True)
        else:
            # fold the traced position THEN the row index into the key:
            # every (step, row) pair draws from its own stream
            rows = per_row_keys(key, logits.shape[0], position=pos)
            next_tok = sample_logits_rows(logits, rows, temperature, top_k,
                                          top_p, use_top_p=use_top_p)
        # finished sequences keep emitting eos (or 0) — the done-mask is
        # the early-stop mechanism; shapes never change
        fill = jnp.maximum(eos_id, 0).astype(jnp.int32)
        next_tok = jnp.where(done, fill, next_tok)
        done = done | (next_tok == eos_id)
        return next_tok, done, jnp.all(done), cache

    def cache_stats(self) -> dict:
        """``{"prefill": {...}, "decode": {...}}`` compile/call counters
        (see ``framework.compile_cache.cache_stats``)."""
        return {"prefill": compile_cache.cache_stats(self._cc_prefill),
                "decode": compile_cache.cache_stats(self._cc_decode)}

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 seed: Optional[int] = None,
                 return_stats: bool = False,
                 done_check_interval: int = 4):
        """Autoregressively extend ``input_ids`` [B, prompt_len].

        Returns the GENERATED ids ``[B, n]`` (``n <= max_new_tokens``;
        the loop stops early once every sequence hit ``eos_token_id``,
        and finished rows are filled with eos). With ``return_stats``
        also returns ``{"ttft_s", "total_s", "new_tokens",
        "tokens_per_sec", "decode_tokens_per_sec", "compile_stats"}``.

        ``done_check_interval``: the all-done early-stop flag is read on
        the host (a device round-trip that serializes dispatch) only every
        k-th decode step; any overshoot columns — all rows were already
        done, so they contain only eos fill — are trimmed on the host
        afterwards, so the OUTPUT is identical to checking every step
        (``done_check_interval=1`` restores the per-step check).
        """
        from ..profiler import RecordEvent

        ids = np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        B, prompt_len = ids.shape
        if prompt_len < 1:
            raise ValueError("generate needs a non-empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the prefill "
                             "step always emits the first token)")
        if prompt_len + max_new_tokens > self.max_length:
            raise ValueError(
                f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
                f"exceeds the cache's max_length {self.max_length}; build "
                f"the engine with a larger max_length")
        bucket = min(bucket_for(prompt_len, self.prefill_buckets),
                     self.max_length)
        ids_p = np.zeros((B, bucket), np.int32)
        ids_p[:, :prompt_len] = ids
        greedy = not do_sample
        if do_sample and seed is None:
            key = framework_random.next_key()
        else:
            # fixed key: unused under greedy, deterministic under seed
            key = jax.random.PRNGKey(0 if seed is None else int(seed))
        eos_id = np.int32(-1 if eos_token_id is None else eos_token_id)
        temp = np.float32(temperature)
        top_p_ = np.float32(top_p)
        # static: nucleus filtering is an O(V log V) sort per step, so it
        # compiles in only when requested (top_p VALUES in (0,1) still
        # sweep without recompiling)
        use_top_p = bool(top_p < 1.0)

        # generation must trace the eval graph (dropout off) regardless of
        # the model's current mode; the flag is read at trace time only
        was_training = self.model.training
        self.model.eval()
        try:
            params = param_state(self.model)
            buffers = buffer_state(self.model)
            cache = init_cache(self.model, B, self.max_length,
                               kv_dtype=self.kv_dtype)
            tokens = []
            dones = []
            interval = max(1, int(done_check_interval))
            # request-scoped tracing: host-side wall-clock spans at the
            # existing dispatch points only (zero extra device syncs).
            # The enabled flag is read ONCE — the per-token branch below
            # is a plain bool check when tracing is off.
            trace_on = _tracing.enabled()
            corr = _tracing.current() if trace_on else None
            if trace_on and corr is None:
                corr = _tracing.new_correlation_id("gen")
            t0 = time.perf_counter()
            t0_wall = time.time()
            with RecordEvent("decode"):
                compile_cache.record_call(self._cc_prefill)
                tok, done, all_done, cache = self._prefill_compiled(
                    params, buffers, cache, ids_p,
                    np.int32(prompt_len - 1), key, eos_id, temp, top_p_,
                    top_k=int(top_k), greedy=greedy, use_top_p=use_top_p)
                tokens.append(tok)
                dones.append(done)
                # tpu-lint: disable=R1(honest TTFT — the metric is "token READY", not "dispatch returned")
                jax.block_until_ready(tok)
                ttft = time.perf_counter() - t0
                if trace_on:
                    t_wall = time.time()
                    _tracing.record_span(
                        "prefill", t0_wall, t_wall, corr=corr,
                        tags={"bucket": bucket, "batch": B})
                pos = prompt_len
                # the early-stop host read serializes dispatch (one device
                # round-trip per token) — only pay it when an eos id makes
                # stopping possible at all, and then only every
                # ``interval``-th step; overshoot columns are trimmed below
                check_done = eos_token_id is not None
                for i in range(max_new_tokens - 1):
                    # tpu-lint: disable=R1(interval-batched early-stop read — one sync per done_check_interval steps, overshoot trimmed below)
                    if check_done and i % interval == 0 and bool(all_done):
                        break
                    compile_cache.record_call(self._cc_decode)
                    tok, done, all_done, cache = self._decode_compiled(
                        params, buffers, cache, tok[:, None],
                        np.int32(pos), key, done, eos_id, temp, top_p_,
                        top_k=int(top_k), greedy=greedy,
                        use_top_p=use_top_p)
                    tokens.append(tok)
                    dones.append(done)
                    if trace_on:
                        now_wall = time.time()
                        _tracing.record_span("decode_step", t_wall,
                                             now_wall, corr=corr)
                        t_wall = now_wall
                    pos += 1
            out = np.stack([np.asarray(t) for t in tokens], axis=1)
            if check_done and out.shape[1] > 1:
                # trim the overshoot: columns past the first all-done one
                # are pure eos fill (the done-mask holds finished rows), so
                # the result equals a per-step-checked run
                col_done = np.stack([np.asarray(d) for d in dones],
                                    axis=1).all(axis=0)
                if col_done.any():
                    out = out[:, :int(col_done.argmax()) + 1]
            total = time.perf_counter() - t0
        finally:
            if was_training:
                self.model.train()
        if not return_stats:
            return out
        n = out.shape[1]
        stats = {
            "ttft_s": ttft,
            "total_s": total,
            "new_tokens": n,
            "tokens_per_sec": B * n / max(total, 1e-9),
            "decode_tokens_per_sec": (B * (n - 1) / max(total - ttft, 1e-9)
                                      if n > 1 else 0.0),
            "prefill_bucket": bucket,
            "compile_stats": self.cache_stats(),
        }
        return out, stats


def _engine_for(model, max_length, prefill_buckets,
                kv_dtype=None) -> GenerationEngine:
    """One engine per (max_length, buckets, kv_dtype) geometry, cached on
    the model instance so repeated ``generate()`` calls reuse the
    compiled steps."""
    engines = model.__dict__.setdefault("_generation_engines", {})
    key = (max_length,
           tuple(prefill_buckets) if prefill_buckets else None,
           normalize_kv_dtype(kv_dtype))
    if key not in engines:
        engines[key] = GenerationEngine(model, max_length=max_length,
                                        prefill_buckets=prefill_buckets,
                                        kv_dtype=kv_dtype)
    return engines[key]


def generate(model, input_ids, max_new_tokens: int = 32, *,
             max_length: Optional[int] = None,
             prefill_buckets: Optional[Sequence[int]] = None,
             kv_dtype=None, **sampling_kwargs):
    """Module-level entry point surfaced as ``model.generate(...)`` on
    :class:`~paddle_tpu.models.gpt.GPTForCausalLM` /
    :class:`~paddle_tpu.models.llama.LlamaForCausalLM` and
    ``hapi.Model.generate``. See :meth:`GenerationEngine.generate` for the
    sampling knobs."""
    engine = _engine_for(model, max_length, prefill_buckets, kv_dtype)
    return engine.generate(input_ids, max_new_tokens, **sampling_kwargs)
