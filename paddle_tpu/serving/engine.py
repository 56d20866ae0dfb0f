"""Slot-based continuous batcher: a fixed-shape decode batch under an
open request stream.

``models.generation.GenerationEngine`` serves one CLOSED batch: every
request in it prefills together and the batch drains together, so a
request arriving mid-decode waits out the whole batch and finished rows
burn decode FLOPs as eos filler. This engine keeps the SAME fixed cache
shape ``[B, max_length, n_kv_heads, head_dim]`` but treats the batch
dimension as ``B`` independent *slots*:

- **admit** runs the existing bucketed prefill at batch 1 and — inside
  the same compiled program — writes its keys and values into the live
  batch's row at a *traced* slot index (``kv_cache.cache_row_view``) and
  samples the request's first token. One program per prefill bucket, for
  every slot. (With a prefix pool the slot's row is assembled from pool blocks
  and scattered in, ``kv_cache.scatter_cache_rows``.)
- **launch** / **collect** (``step`` is one after the other) advance ALL
  slots one token with a *vector* of per-slot
  positions (the ``[B]`` ``position_offset`` path through
  ``kv_cache.cached_attention`` / ``update_kv_cache``, on a TPU both
  through a kernel: a slot's new row lands by direct copy and only the
  blocks its positions fill are read; and the models' position
  tables), per-slot PRNG keys / eos ids / sampling params, and a
  traced greedy mask. Exactly ONE compiled program, regardless of which
  requests currently share the batch. What a step hands the next (each
  slot's new token and done flag) stays on the device: ``launch()``
  feeds the last launch's two output vectors straight into the program,
  and what the host alone knows (an admission's first token, a freed
  slot, a request that its length ends) goes in as an override the
  program merges first thing. So a second launch can be made while the
  first still runs, and ``collect()`` reads a launch back one behind
  (``InferenceServer``'s loop does exactly that).

Steady state therefore holds at ``#prefill_buckets + 1`` compiled
programs — the generation engine's compile discipline, now under
multi-tenant traffic. Freed slots are reusable immediately. Of an entry
indexed by position (keys and values, a latent pair) a stale row is
harmless: the per-row position mask never lets a query see beyond its own
request's frontier, and every position is rewritten before it first
becomes visible. A launch still in flight when a slot is admitted into
decodes filler there; the prefill program is enqueued behind it and
takes ``live_cache`` from it by donation, so the order of the writes
into the row (the filler step's, then the admission's whole row, state
entries included) is the device's queue order. Of a STATE entry (a
recurrent mixer's scan state and convolution window,
``kv_cache.write_state``) nothing is masked, and a
freed slot's state goes on being advanced by every filler step; so an
admission starts from zeros whatever the row held and overwrites both
leaves of the row whole, with the state after exactly the prompt's tokens:
the prompt's true length reaches the mixers of every prefill program
through ``gather_last`` (``lm_utils.cached_lm_forward`` opens
``block_length``), so the bucket's pads do not move it. ``reset()`` builds
the cache anew, zeros.

Per-request sampled streams are *placement-invariant*: slot keys fold
``(position, row=0)`` exactly like a solo batch-1 ``generate()``, so a
request's tokens don't depend on which slot it landed in or who shares
the batch.
"""
from __future__ import annotations

import collections
import contextlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import compile_cache
from ..io.batching import bucket_for
from ..models.generation import (DEFAULT_PREFILL_BUCKETS, per_row_keys,
                                 sample_branch, sample_logits_rows)
from ..models.kv_cache import (cache_entries, cache_entry_kind,
                               cache_geometry, cache_nbytes,
                               cache_paths, cache_row_buffers,
                               cache_row_view, cache_split_nbytes,
                               constrain_cache, gather_cache_blocks,
                               init_cache, normalize_kv_dtype,
                               scatter_cache_blocks, scatter_cache_rows,
                               state_entries)
from ..lora import adapter_rows as _adapter_rows_ctx
from ..lora.store import AdapterStore, normalize_adapter_id
from ..nn.layer import buffer_state, functional_call, param_state
from ..nn.layers.expert_ffn import expert_load
from .metrics import LoopClock
from .prefix_cache import BlockPool

__all__ = ["ContinuousBatchingEngine", "SlotEvent"]


@dataclass
class SlotEvent:
    """One slot's outcome of a decode step (host-side)."""

    slot: int
    token: int
    done: bool


@dataclass
class _Launch:
    """A decode step the device was handed and the host has not read
    back: its two output vectors, the request that was live in each slot
    when it was launched, and the tags of the launch's span."""

    tok: jax.Array
    done: jax.Array
    live: Dict[int, object]
    tags: Optional[dict]
    #: False once the host knows it has ended on the device (an
    #: admission's read-back waited it out): a launch behind it overlaps
    #: nothing
    running: bool = True


class ContinuousBatchingEngine:
    """The compiled slot-scatter prefill + vector-position decode pair and
    the host-side slot table for one model.

    ``top_k`` is an engine-level static (it changes the compiled
    sampling graph); everything else — temperature, top_p value,
    greedy-vs-sample, eos id, seed — is per-request and traced, so a
    heterogeneous batch still runs the single decode program, which
    samples what the step's live slots ask for and no more
    (``generation.sample_logits_rows``: an all-greedy step takes an
    argmax; the nucleus filter's sort runs only in a step where a live
    slot set ``top_p < 1``).

    ``prefix_cache`` (None | BlockPool | True | byte budget | kwargs
    dict) switches admission to the paged-pool program: matched prompt
    blocks are copied out of the pool in-program and only the novel
    suffix is prefilled, at the cost of the suffix forward running the
    chunked-continuation attention path instead of the block-local
    (flash-eligible) prefill. Default None keeps the PR 4 admit program
    bit-for-bit.

    ``adapter_store`` (a :class:`~paddle_tpu.lora.AdapterStore` built on
    the SAME LoRA-applied model) turns on batched multi-tenant decode:
    each slot carries a traced page-stack row, the prefill/decode
    programs gather that row's ``(A, B)`` pages in-program and apply the
    low-rank delta per slot (row 0 = the zero adapter = base model), so
    one compiled program family serves every tenant. Loading/evicting a
    tenant is a store buffer update — never a recompile — and with a
    prefix cache attached, each tenant's K/V blocks live under its own
    digest namespace (adapter-modified projections make cross-tenant
    reuse numerically wrong).
    """

    def __init__(self, model, slots: int = 4,
                 max_length: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 top_k: int = 0,
                 prefix_cache=None, adapter_store=None, kv_dtype=None):
        if slots < 1:
            raise ValueError(f"need at least one slot, got {slots}")
        self.model = model
        self.spec = spec = model.cache_spec()
        self.kv_dtype = normalize_kv_dtype(kv_dtype)
        self.slots = int(slots)
        self.max_length, self.prefill_buckets = cache_geometry(
            spec, max_length, prefill_buckets or DEFAULT_PREFILL_BUCKETS)
        self.top_k = int(top_k)
        self.pool = self._normalize_pool(prefix_cache)
        self.store = self._normalize_store(adapter_store)
        #: whose time admit() and step() divide at their device waits.
        #: The engine's own books nothing; an InferenceServer's loop
        #: thread puts its clock here (``ServingMetrics`` counters).
        self.clock = LoopClock()
        model_name = type(model).__name__
        self._cc_prefill = compile_cache.register_name(
            f"serve:prefill:{model_name}")
        self._cc_decode = compile_cache.register_name(
            f"serve:decode:{model_name}")
        #: how the decode program writes a step's keys and values ("dma"
        #: or "scatter") and reads the cache for attention ("kernel" or
        #: "xla"): known once it has been traced
        self._cache_write: Optional[str] = None
        self._cache_read: Optional[str] = None
        #: ``(expert layers, experts)`` as the model states them beside
        #: its ``cache_spec()``, None for a model without expert FFNs:
        #: the shape of the decode program's expert-load counters
        #: (:meth:`expert_load`)
        shape = getattr(model, "expert_load_shape", lambda: (0, 0))()
        self._expert_shape = tuple(shape) if shape[0] else None
        on_device = jax.default_backend() != "cpu"
        lora = self.store is not None
        if self.pool is not None:
            # cache hit or miss, every admission runs the SAME pooled
            # program family (one per suffix bucket): n_matched is traced
            # (0 on a miss), so the compile budget stays #buckets + 1.
            # The adapter page stacks ride as extra NON-donated inputs
            # (the store keeps serving every later dispatch) — still one
            # program per bucket, adapters or not.
            donate = (2, 3) if on_device else ()
            prefill = self._prefill_pool_lora_fn if lora \
                else self._prefill_pool_fn
        else:
            donate = (2,) if on_device else ()
            prefill = self._prefill_lora_fn if lora else self._prefill_fn
        # watched: the executables stay behind for program_scopes(), which
        # says what scope each instruction of a device trace belongs to
        self._prefill_compiled = compile_cache.watched_jit(
            prefill, self._cc_prefill, "prefill", donate_argnums=donate)
        self._decode_compiled = compile_cache.watched_jit(
            self._decode_lora_fn if lora else self._decode_fn,
            self._cc_decode, "decode",
            donate_argnums=(2,) if on_device else ())
        self.reset()

    def _normalize_pool(self, prefix_cache) -> Optional[BlockPool]:
        """Accept the serving-layer spellings of "give me a prefix
        cache": ``None``/``False``/``0`` (off — the PR 4 admit program,
        bit-identical), a ready :class:`BlockPool`, ``True`` (defaults),
        a positive int/float byte budget, or a kwargs dict for
        :class:`BlockPool`. A zero budget means OFF, not a one-block
        pool — configs spell "disabled" as 0."""
        if prefix_cache is None or prefix_cache is False:
            return None
        if isinstance(prefix_cache, (int, float)) and not isinstance(
                prefix_cache, bool) and prefix_cache <= 0:
            return None
        if isinstance(prefix_cache, BlockPool):
            prefix_cache.compatible_with(self.spec, self.max_length,
                                         kv_dtype=self.kv_dtype)
            owner = getattr(prefix_cache, "_owner", None)
            if owner is not None and owner is not self:
                # each admit program DONATES the pool tensors; a second
                # engine dispatching against the same pool would read
                # buffers the first one already consumed
                raise ValueError(
                    "this BlockPool is already attached to another "
                    "engine; build one pool per replica")
            prefix_cache._owner = self
            return prefix_cache
        kwargs = {}
        if isinstance(prefix_cache, dict):
            kwargs = dict(prefix_cache)
        elif prefix_cache is not True:
            kwargs = {"max_bytes": int(prefix_cache)}
        kwargs.setdefault("max_length", self.max_length)
        kwargs.setdefault("kv_dtype", self.kv_dtype)
        pool = BlockPool(self.model, **kwargs)
        # same geometry gate as the ready-pool branch: an explicit
        # kwargs max_length larger than the engine cache would otherwise
        # only surface as a reshape error inside the admit program
        pool.compatible_with(self.spec, self.max_length,
                             kv_dtype=self.kv_dtype)
        pool._owner = self
        return pool

    def _normalize_store(self, adapter_store) -> Optional[AdapterStore]:
        """An :class:`AdapterStore` must wrap THIS engine's model
        instance: the compiled programs reach the adapter hooks through
        the model's injected layers, and the store's page geometry is
        derived from exactly those layers."""
        if adapter_store is None:
            return None
        if not isinstance(adapter_store, AdapterStore):
            raise TypeError(
                f"adapter_store must be a paddle_tpu.lora.AdapterStore, "
                f"got {type(adapter_store).__name__}")
        if adapter_store.model is not self.model:
            raise ValueError(
                "this AdapterStore was built for a different model "
                "instance; build the store on the engine's model "
                "(AdapterStore(model, ...))")
        owner = getattr(adapter_store, "_owner", None)
        if owner is not None and owner is not self:
            # pins are engine-lifecycle state: a shared store would let
            # one replica's crash-recovery release_all() void ANOTHER
            # replica's live pins, making its rows evictable mid-stream
            # (same sharing hazard BlockPool guards with _owner)
            raise ValueError(
                "this AdapterStore is already attached to another "
                "engine; build one store per replica")
        adapter_store._owner = self
        return adapter_store

    # ------------------------------------------------------------- state
    def reset(self) -> None:
        """(Re)build the live batch: fresh cache, all slots free, weights
        re-snapshotted, launches not read back forgotten. Also the
        crash-recovery path — a fault mid-step may leave donated buffers
        half-written, so recovery starts clean."""
        self._params = param_state(self.model)
        self._buffers = buffer_state(self.model)
        self.live_cache = init_cache(self.model, self.slots, self.max_length,
                                     kv_dtype=self.kv_dtype)
        positional, state = cache_split_nbytes(self.spec, self.live_cache)
        self._bytes_per_token = positional // (self.slots * self.max_length)
        #: bytes of recurrent state a slot holds whatever its length, from
        #: the leaves as allocated; None for a model without state entries
        self.state_bytes_per_slot: Optional[int] = (
            state // self.slots if state_entries(self.spec) else None)
        if self.pool is not None:
            self.pool.reset()
        if self.store is not None:
            # every live request is about to be requeued: the pins this
            # engine held on their page rows are void (the pages
            # themselves survive — the store is never donated)
            self.store.release_all()
        B = self.slots
        # device-resident, carried through the decode program and read
        # back only on request; never donated, so that a snapshot on
        # another thread can fetch the array it holds while the loop
        # dispatches the next step
        self._expert_load = None if self._expert_shape is None else {
            "steps": jnp.zeros((), jnp.int32),
            "tokens_per_expert": jnp.zeros(self._expert_shape, jnp.int32),
            "experts_touched_steps": jnp.zeros(self._expert_shape[:1],
                                               jnp.int32)}
        self._adapter_slots = np.zeros(B, np.int32)
        self._positions = np.zeros(B, np.int32)
        # what the last launch handed the next, on the device, and what
        # the host puts in its place where it knows better (`_override`:
        # a slot admitted, freed or ended by its length since)
        self._carry = (jnp.zeros(B, jnp.int32), jnp.ones(B, bool))
        self._override = np.ones(B, bool)
        self._tokens = np.zeros(B, np.int32)
        #: slots the next launch does not count live: free, ended on eos
        #: (read back) or by length (known ahead: `_owed` tokens to go)
        self._done = np.ones(B, bool)
        self._owed = np.zeros(B, np.int32)
        #: launches not read back yet, oldest first; two at most
        self._flights: collections.deque = collections.deque()
        self._keys = np.zeros((B, 2), np.uint32)
        self._eos = np.full(B, -1, np.int32)
        self._temp = np.ones(B, np.float32)
        self._top_p = np.ones(B, np.float32)
        self._greedy = np.ones(B, bool)
        #: what the last launch had to serve: the slots live in it and
        #: the cached positions their queries read (each slot's position,
        #: the new token's own key included)
        self.step_load = (0, 0)
        #: which branch of the sampler the last launch took
        #: (``generation.sample_branch`` on the vectors it was given)
        self.step_sample_branch = 0
        self.requests: List[Optional[object]] = [None] * B

    def sync_weights(self) -> None:
        """Re-snapshot the model's parameters/buffers (e.g. after a fit
        loop updated them). Shape-stable, so no recompile."""
        self._params = param_state(self.model)
        self._buffers = buffer_state(self.model)

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.requests) if r is None]

    @property
    def active_count(self) -> int:
        return sum(r is not None for r in self.requests)

    @property
    def live_count(self) -> int:
        """Slots the next launch would decode for: occupied, not ended
        on eos, and owed a token beyond those of the launches in flight."""
        return int(self.slots - self._done.sum())

    @property
    def in_flight(self) -> int:
        """Launches not collected yet (0, 1 or 2)."""
        return len(self._flights)

    def drop_flights(self) -> None:
        """Forget the launches not read back (their slots' requests have
        been released or are about to be)."""
        self._flights.clear()

    def occupancy(self) -> float:
        return self.active_count / self.slots

    # ----------------------------------------------------- compiled fns
    @contextlib.contextmanager
    def _eval_mode(self):
        """Serving must trace the EVAL graph (dropout off) even if the
        model is mid-fit; the flag is read at trace time only, so every
        dispatch site (a novel bucket may trace at any admit) flips it
        and restores — same discipline as GenerationEngine.generate."""
        was_training = self.model.training
        self.model.eval()
        try:
            yield
        finally:
            if was_training:
                self.model.train()

    def cache_bytes_per_slot(self) -> int:
        """HBM bytes one slot's KV occupies in the live batch — the
        number the ``kv_dtype="int8"`` halving claim is asserted on. A
        slot's recurrent state, where the model has any, is in it."""
        return cache_nbytes(self.live_cache) // self.slots

    def _prefill_fn(self, params, buffers, live_cache, ids, slot,
                    last_index, key, eos_id, temperature, top_p, greedy):
        """Bucketed batch-1 prefill that writes its keys and values
        straight into row ``slot`` of the live batch (``cache_row_view``):
        no single-slot cache exists, inside this program or out of it, so
        admission costs one compile per bucket — not per bucket per slot,
        and no separate scatter program. Positions past the bucket keep
        what the slot's last request left there, behind the position
        mask like the rest of a reused slot."""
        row = cache_row_view(live_cache, slot)
        with jax.named_scope("prefill"):
            (logits, row), _ = functional_call(
                self.model, params, buffers, ids, cache=row,
                position_offset=0, gather_last=last_index)
        live_cache = cache_row_buffers(row)
        logits = logits[:, 0, :]
        rows = per_row_keys(key, 1)
        next_tok = sample_logits_rows(
            logits, rows, temperature, self.top_k, top_p,
            greedy_mask=jnp.asarray(greedy).reshape(1))
        live_cache = constrain_cache(live_cache)
        done = next_tok[0] == eos_id
        return next_tok[0], done, live_cache

    def _prefill_pool_fn(self, params, buffers, live_cache, pool, ids, slot,
                         last_index, n_matched, read_idx, write_idx, key,
                         eos_id, temperature, top_p, greedy):
        """The paged-pool admit program: ONE fused dispatch copies the
        matched prefix blocks out of the pool, prefills only the novel
        suffix at the (traced) matched offset, scatters the assembled
        slot cache into the live batch, and writes the prompt's new full
        blocks back into the pool.

        Every per-request quantity — the matched length, the block
        read/write rows (padded to ``max_length // block_tokens``, dump
        row 0 where unused), the slot — is traced, so a hit and a miss
        of any length run the SAME program per suffix bucket. The suffix
        forward attends through ``cached_attention``'s chunked-
        continuation path (multi-token queries against the full cache at
        a traced offset), which is what makes the prefix K/V reusable
        without re-running its FLOPs."""
        slot_cache = gather_cache_blocks(pool, read_idx, self.max_length)
        with jax.named_scope("prefill"):
            (logits, slot_cache), _ = functional_call(
                self.model, params, buffers, ids, cache=slot_cache,
                position_offset=n_matched, gather_last=last_index)
        logits = logits[:, 0, :]
        rows = per_row_keys(key, 1)
        next_tok = sample_logits_rows(
            logits, rows, temperature, self.top_k, top_p,
            greedy_mask=jnp.asarray(greedy).reshape(1))
        pool = scatter_cache_blocks(pool, slot_cache, write_idx)
        live_cache = scatter_cache_rows(live_cache, slot_cache, slot)
        live_cache = constrain_cache(live_cache)
        done = next_tok[0] == eos_id
        return next_tok[0], done, live_cache, pool

    # Adapter variants: same bodies, traced under an adapter-rows context
    # — the per-row (A, B) gather happens in-program, so WHICH tenants
    # occupy the batch is data. One extra program input (the page
    # stacks), zero extra programs.
    def _prefill_lora_fn(self, params, buffers, live_cache, pages, row,
                         *rest):
        with _adapter_rows_ctx(pages, row):
            return self._prefill_fn(params, buffers, live_cache, *rest)

    def _prefill_pool_lora_fn(self, params, buffers, live_cache, pool,
                              pages, row, *rest):
        with _adapter_rows_ctx(pages, row):
            return self._prefill_pool_fn(params, buffers, live_cache,
                                         pool, *rest)

    def _decode_lora_fn(self, params, buffers, live_cache, pages, rows,
                        *rest):
        with _adapter_rows_ctx(pages, rows):
            return self._decode_fn(params, buffers, live_cache, *rest)

    def _decode_fn(self, params, buffers, live_cache, carry, override,
                   tokens, positions, keys, done, eos, temperature, top_p,
                   greedy_mask, load=None):
        """``carry`` is the launch before's ``(next_tok, done)``, still on
        the device; where ``override`` is set the host's ``tokens`` and
        ``done`` take their place (:meth:`launch`). ``load`` (None for a
        model without experts: no leaf, the same
        program as before it existed) is the running expert load, summed
        here over the slots that are live in this step (``~done``: a free
        slot decodes filler and is not counted) and handed back."""
        tokens = jnp.where(override, tokens, carry[0])[:, None]
        done = jnp.where(override, done, carry[1])
        with contextlib.ExitStack() as stack:
            stack.enter_context(jax.named_scope("decode"))
            paths = stack.enter_context(cache_paths())
            tally = (None if load is None
                     else stack.enter_context(expert_load(~done)))
            (logits, live_cache), _ = functional_call(
                self.model, params, buffers, tokens, cache=live_cache,
                position_offset=positions)
        self._cache_write = "dma" if paths["write"] == {"dma"} else "scatter"
        self._cache_read = "kernel" if paths["read"] == {"kernel"} else "xla"
        if load is not None:
            load = {
                "steps": load["steps"] + 1,
                "tokens_per_expert": load["tokens_per_expert"]
                + jnp.stack([per_expert for per_expert, _ in tally]),
                "experts_touched_steps": load["experts_touched_steps"]
                + jnp.stack([touched for _, touched in tally])}
        live_cache = constrain_cache(live_cache)
        logits = logits[:, -1, :]
        # per-slot streams: each slot replays the batch-1 generate() key
        # derivation (per_row_keys at batch=1 — ONE shared definition), so
        # a served request's sampled tokens are identical to a solo run
        # with the same seed no matter its slot or batch companions
        step_keys = jax.vmap(
            lambda k, p: per_row_keys(k, 1, position=p)[0])(keys, positions)
        next_tok = sample_logits_rows(
            logits, step_keys, temperature, self.top_k, top_p,
            greedy_mask=greedy_mask, live=~done)
        fill = jnp.maximum(eos, 0)
        next_tok = jnp.where(done, fill, next_tok)
        done = done | (next_tok == eos)
        return next_tok, done, live_cache, load

    # -------------------------------------------------------- host API
    def bucket_for_prompt(self, prompt_len: int) -> int:
        return min(bucket_for(prompt_len, self.prefill_buckets),
                   self.max_length)

    def validate(self, prompt_len: int, max_new_tokens: int) -> None:
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt_len + max_new_tokens > self.max_length:
            raise ValueError(
                f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
                f"exceeds the engine's max_length {self.max_length}")

    def warmup(self, max_new_tokens: int = 2) -> dict:
        """Compile every program this engine can ever dispatch — one
        prefill per bucket plus the shared decode step — by pushing one
        dummy greedy request per bucket through :meth:`admit` +
        :meth:`step` on an idle engine. With the persistent compile
        cache enabled (``framework.compile_cache.enable_persistent_cache``)
        the traced programs deserialize from disk instead of
        recompiling, so a freshly spawned replica boots WARM: its first
        real request pays dispatch cost, not compile cost. The prefix
        pool is reset afterwards so the dummy prompt's blocks never
        match real traffic. ``max_new_tokens=1`` warms the prefill
        programs ONLY — a disaggregated prefill replica serves nothing
        but single-token requests, so its decode program must never be
        traced (#buckets programs, not #buckets+1). Returns the compile
        counts the warmup actually incurred (all zeros on a warm
        persistent cache)."""
        from .scheduler import Request

        if self.requests[0] is not None:
            raise RuntimeError("warmup() needs an idle engine — run it "
                               "before admitting traffic")
        before_p = compile_cache.cache_stats(self._cc_prefill)["compiles"]
        before_d = compile_cache.cache_stats(self._cc_decode)["compiles"]
        mnt = max(1, int(max_new_tokens))
        seen = set()
        for b in self.prefill_buckets:
            L = max(1, min(int(b), self.max_length - mnt))
            bucket = self.bucket_for_prompt(L)
            if bucket in seen:
                continue
            seen.add(bucket)
            prompt = (np.arange(L, dtype=np.int32) % 97) + 1
            req = Request(prompt=prompt, max_new_tokens=mnt, greedy=True,
                          seed=0)
            self.admit(req, 0)
            if mnt > 1:
                self.step()  # the first step compiles the decode program
            self.release(0)
        if self.pool is not None:
            self.pool.reset()
        return {
            "buckets": sorted(seen),
            "prefill_compiles":
                compile_cache.cache_stats(self._cc_prefill)["compiles"]
                - before_p,
            "decode_compiles":
                compile_cache.cache_stats(self._cc_decode)["compiles"]
                - before_d,
        }

    def _request_key(self, request) -> np.ndarray:
        seed = getattr(request, "seed", None)
        if seed is None:
            # fresh randomness per request — matching solo
            # generate(do_sample=True, seed=None); two unseeded requests
            # with the same prompt must NOT sample identical streams
            from ..framework import random as framework_random

            return np.asarray(
                jax.random.key_data(framework_random.next_key()),
                np.uint32)
        return np.asarray(jax.random.PRNGKey(int(seed)), np.uint32)

    def _plan_hit(self, prompt: np.ndarray, L: int, salt: bytes = b""):
        """Pin the longest usable pool match for ``prompt`` and plan the
        block writes. The match shrinks (block granularity) until
        ``matched + suffix_bucket`` fits the cache — the suffix write
        window must never clamp against the cache end. ``salt``
        namespaces the digest chain per adapter: a tenant only ever hits
        K/V its own adapter computed."""
        hit = self.pool.lookup(prompt, salt=salt)
        # everything between the lookup (which PINS the matched blocks)
        # and handing (hit, plan) to the caller runs under an abort
        # guard: a raise out of trim/plan_store would otherwise leak
        # the pins forever (tpu_lint R9 — the pool becomes unevictable)
        try:
            matched = hit.tokens
            while (matched > 0
                   and matched + self.bucket_for_prompt(L - matched)
                   > self.max_length):
                matched -= self.pool.block_tokens
            if matched != hit.tokens:
                hit = self.pool.trim(hit, matched)
            plan = self.pool.plan_store(prompt, matched,
                                        digests=hit.digests, salt=salt)
        except Exception:
            self.pool.abort(hit)
            raise
        return hit, plan

    def admit(self, request, slot: int) -> Tuple[int, bool, int]:
        """Prefill ``request`` into free ``slot``; returns the first
        sampled token, whether the request finished at prefill (eos on
        the first token), and how many prompt tokens were served from
        the prefix cache (0 without a pool). The live batch keeps
        decoding other slots' requests before/after this call — only
        this call itself runs the prefill program, which the device takes
        up behind the launch in flight, if there is one: the read-back
        of the first token waits that launch out too.

        Two boundaries of ``self.clock`` lie inside: the prefill's
        dispatch has returned (``admit_wait`` begins: the first token's
        read-back) and the token is on the host (``admit_host`` again,
        the admission's tail, inside the caller's span)."""
        if self.requests[slot] is not None:
            raise RuntimeError(f"slot {slot} is occupied")
        prompt = np.asarray(request.prompt, np.int32).ravel()
        L = int(prompt.shape[0])
        self.validate(L, int(request.max_new_tokens))
        adapter_id = normalize_adapter_id(
            getattr(request, "adapter_id", None))
        if adapter_id is not None and self.store is None:
            raise ValueError(
                f"request names adapter {adapter_id!r} but this engine "
                f"has no adapter_store")
        key = self._request_key(request)
        eos = np.int32(-1 if request.eos_token_id is None
                       else request.eos_token_id)
        temp = np.float32(request.temperature)
        top_p = np.float32(request.top_p)
        greedy = np.bool_(request.greedy)
        a_row, a_salt = 0, b""
        if self.store is not None:
            # host-side resolve BEFORE any dispatch: an unknown adapter
            # or a pinned-out store fails only this request (AdapterError
            # — the server catches it without an engine reset). On a
            # cold tenant this stages its pages into a stack row — a
            # buffer update, never a recompile. Acquired LAST so every
            # raise after the pin is owned by the try below; the digest
            # salt rides along ATOMICALLY so a concurrent adapter update
            # can't stamp these pages' K/V into the new version's
            # namespace.
            a_row, a_salt = self.store.acquire(adapter_id, with_salt=True)
        hit_tokens = 0
        try:
            lora_args = () if self.store is None else (
                self.store.tensors, np.asarray([a_row], np.int32))
            with self._eval_mode():
                compile_cache.record_call(self._cc_prefill)
                if self.pool is None:
                    bucket = self.bucket_for_prompt(L)
                    ids_p = np.zeros((1, bucket), np.int32)
                    ids_p[0, :L] = prompt
                    tok, done0, self.live_cache = self._prefill_compiled(
                        self._params, self._buffers, self.live_cache,
                        *lora_args, ids_p,
                        np.int32(slot), np.int32(L - 1), key, eos, temp,
                        top_p, greedy)
                else:
                    # device_lock spans plan -> dispatch -> commit: the
                    # dispatch DONATES pool.tensors and commit rebinds
                    # them, so a migration export/import on an rpc
                    # thread (serving.disagg) must never interleave —
                    # it would read invalidated buffers or scatter into
                    # tensors the adopt is about to replace
                    with self.pool.device_lock:
                        hit, plan = self._plan_hit(prompt, L, salt=a_salt)
                        # the abort guard starts the statement AFTER the
                        # pins land: a raise anywhere before the commit —
                        # bucket planning as much as the dispatch itself —
                        # must release them (tpu_lint R9)
                        try:
                            hit_tokens = hit.tokens
                            suffix = L - hit_tokens
                            bucket = self.bucket_for_prompt(suffix)
                            ids_p = np.zeros((1, bucket), np.int32)
                            ids_p[0, :suffix] = prompt[hit_tokens:]
                            tok, done0, self.live_cache, tensors = (
                                self._prefill_compiled(
                                    self._params, self._buffers,
                                    self.live_cache, self.pool.tensors,
                                    *lora_args, ids_p, np.int32(slot),
                                    np.int32(suffix - 1),
                                    np.int32(hit_tokens),
                                    hit.read_idx, plan.write_idx, key, eos,
                                    temp, top_p, greedy))
                        except Exception:
                            # dispatch never completed: unpin + free the
                            # plan's rows (a post-dispatch device fault
                            # instead goes through reset(), which
                            # rebuilds the pool tensors)
                            self.pool.abort(hit, plan)
                            raise
                        self.pool.commit(hit, plan, tensors)
        except Exception:
            if self.store is not None:
                # the request never reached a slot: its page pin is void
                self.store.release(a_row)
            raise
        clock = self.clock
        clock.enter("admit_wait", "serve.prefill.wait",
                    getattr(request, "corr_id", None))
        # ONE batched transfer for both scalars — two np.asarray reads
        # here cost two serialized device round-trips per admission.
        # tpu-lint: disable=R1(admission's single batched sync point — the first token must reach the client now)
        first_h, fin_h = jax.device_get((tok, done0))
        clock.enter("admit_host")
        for flight in self._flights:    # the prefill ran behind them
            flight.running = False
        first = int(first_h)
        fin = bool(fin_h)
        self.requests[slot] = request
        self._adapter_slots[slot] = a_row
        self._positions[slot] = L
        self._override[slot] = True
        self._tokens[slot] = first
        self._owed[slot] = int(request.max_new_tokens) - 1
        self._done[slot] = fin or self._owed[slot] == 0
        self._keys[slot] = key
        self._eos[slot] = eos
        self._temp[slot] = request.temperature
        self._top_p[slot] = request.top_p
        self._greedy[slot] = request.greedy
        return first, fin, hit_tokens

    def _decode_inputs(self) -> tuple:
        """What a launch hands the decode program behind the weights and
        the cache, in its order. The host's vectors go as copies: the
        runtime may read an argument after the call has returned, and
        admit(), release() and the next launch write into them while the
        program is still queued."""
        lora_args = () if self.store is None else (
            self.store.tensors, self._adapter_slots.copy())
        host = (self._override, self._tokens, self._positions, self._keys,
                self._done, self._eos, self._temp, self._top_p, self._greedy)
        return (*lora_args, self._carry, *(v.copy() for v in host),
                self._expert_load)

    def launch(self) -> bool:
        """Hand the device one decode iteration over the WHOLE live
        batch and return without waiting for it: the first half of a
        step. Its tokens and done flags stay on the device for the next
        launch and are on their way to the host for :meth:`collect`.
        Live in it are the occupied slots that have not ended: on eos,
        as far as a ``collect()`` has shown, or by length, which the
        host knows ahead (a request complete with the launches in flight
        is not decoded for again, and its slot decodes masked filler
        like a free one). A live slot's position rises by one; what the
        launch had to serve is in ``step_load`` and
        ``step_sample_branch``. One launch may be made ahead of the one
        not collected yet, and no more. Returns whether it was made
        ahead: behind a launch the host does not know to have ended.

        The caller has opened the ``decode_dispatch`` span of
        ``self.clock``; its tags are kept for the spans of the launch's
        ``collect()``."""
        if len(self._flights) > 1:
            raise RuntimeError("two launches are in flight: collect() the "
                               "older before launching again")
        ahead = bool(self._flights) and self._flights[-1].running
        live = ~self._done
        self.step_sample_branch = int(sample_branch(
            live, self._greedy, self._top_p))
        with self._eval_mode():
            compile_cache.record_call(self._cc_decode)
            tok, done, self.live_cache, self._expert_load = (
                self._decode_compiled(
                    self._params, self._buffers, self.live_cache,
                    *self._decode_inputs()))
        # the copy to the host starts when the program ends, not when
        # collect() asks
        tok.copy_to_host_async()
        done.copy_to_host_async()
        self._carry = (tok, done)
        slots = np.flatnonzero(live).tolist()
        self._flights.append(_Launch(
            tok, done, {i: self.requests[i] for i in slots},
            self.clock.tags))
        self._positions[live] += 1
        self._owed[live] -= 1
        self.step_load = (len(slots), int(self._positions[live].sum()))
        # what this launch completes by length is done for the next one,
        # and only the host knows: the one override it leaves behind
        self._override = live & (self._owed == 0)
        self._done |= self._override
        return ahead

    def collect(self) -> List[SlotEvent]:
        """Read the oldest launch back: the second half of a step.
        Returns one event per slot that was live in it (its new token
        and whether that was its eos), less the slots whose request has
        been released or replaced since. The per-launch host read of
        ``[B]`` tokens is what streams results out — continuous
        batching's equivalent of the generate() loop's done-check. A
        slot that ends on eos here was still counted live in a launch
        made ahead of this read: the device's own done carry made it
        decode filler there, and the event is dropped.

        Two boundaries of ``self.clock`` lie inside: the wait for the
        launch begins (``decode_wait``) and the tokens are on the host
        (``emit`` begins); their spans carry the tags of the launch's
        ``decode_dispatch`` span."""
        clock = self.clock
        flight = self._flights.popleft()
        clock.enter("decode_wait", "serve.decode.wait", tags=flight.tags)
        # one batched transfer for the whole [B] step readback (token +
        # done vectors) instead of two serialized np.array round-trips
        # tpu-lint: disable=R1(the per-step [B]-token readback IS the streaming output; one batched transfer per decode step, asked for at the launch)
        toks, dns = jax.device_get((flight.tok, flight.done))
        clock.enter("emit", "serve.emit", tags=flight.tags)
        events: List[SlotEvent] = []
        for i, req in flight.live.items():
            if self.requests[i] is not req:
                continue
            ev = SlotEvent(i, int(toks[i]), bool(dns[i]))
            events.append(ev)
            if ev.done:
                self._done[i] = True
                for ahead in self._flights:
                    ahead.live.pop(i, None)
        return events

    def step(self) -> List[SlotEvent]:
        """One decode iteration, launched and read back at once: the
        synchronous use of the two halves, for an engine driven by
        hand."""
        if self._flights:
            raise RuntimeError("a launch is in flight: collect() it first")
        self.launch()
        return self.collect()

    def release(self, slot: int) -> None:
        """Free ``slot`` immediately — no batch drain. The stale cache
        rows stay; the position mask keeps them invisible to whoever is
        admitted next, and a recurrent state (which the filler steps go
        on advancing) is overwritten whole by the next admission. The
        slot's adapter-page pin drops with it (the
        freed slot decodes as the zero adapter)."""
        self.requests[slot] = None
        self._override[slot] = True
        self._done[slot] = True
        self._positions[slot] = 0
        self._tokens[slot] = 0
        if self.store is not None:
            self.store.release(int(self._adapter_slots[slot]))
            self._adapter_slots[slot] = 0

    def expert_load(self) -> Optional[dict]:
        """How the decode steps so far spread their live slots' tokens
        over the experts, None for a model without an expert FFN:
        ``steps`` counted, ``tokens_per_expert`` [expert layer][expert]
        tokens routed there and ``experts_touched_steps`` [expert layer]
        experts that at least one live token picked, both summed over the
        steps. The counters live on the device, in int32 (a step adds at
        most ``slots * top_k`` to one: years at any rate a chip decodes
        at), and THIS read is their only trip to the host: it waits for
        the step in flight, so call it for a snapshot, not a step."""
        load = self._expert_load
        if load is None:
            return None
        # tpu-lint: disable=R1(a snapshot's read, on the caller's thread; no step reads these back)
        host = jax.device_get(load)
        return {"steps": int(host["steps"]),
                "tokens_per_expert": host["tokens_per_expert"].tolist(),
                "experts_touched_steps":
                    host["experts_touched_steps"].tolist()}

    def cache_stats(self) -> dict:
        """Compile/call counters of the two serving programs — steady
        state must hold at ``#buckets_used`` prefill + 1 decode — and the
        live cache's geometry: its entries (one per layer application
        that writes keys and values) and the bytes a token holds in all
        of them. ``cache_write`` is the way the decode program lands a
        step's keys and values (``kv_cache.update_kv_cache``: ``"dma"``
        or ``"scatter"``) and ``cache_read`` the way it reads the cache
        for attention (``kv_cache.cached_attention``: ``"kernel"``, by
        position, or ``"xla"``, the whole leaf under a mask), both None
        until it has been traced. ``cache_entry`` is what an entry holds:
        ``"kv"`` (keys and values per head), ``"latent"`` (one
        compressed vector and one shared rotated key a position) or
        ``"kv+state"``: then ``cache_entries`` counts the entries
        indexed by position alone, and ``state_entries`` and
        ``state_bytes_per_slot`` (what a slot holds whatever its length,
        as allocated) stand beside them. ``programs_with_scopes`` counts
        the executables the process could describe instruction by
        instruction (``compile_cache.program_scopes()``)."""
        state = self.state_bytes_per_slot
        return {"prefill": compile_cache.cache_stats(self._cc_prefill),
                "decode": compile_cache.cache_stats(self._cc_decode),
                "cache_write": self._cache_write,
                "cache_read": self._cache_read,
                "cache_entries": cache_entries(self.spec),
                "cache_entry": cache_entry_kind(self.spec),
                "cache_bytes_per_token": self._bytes_per_token,
                "programs_with_scopes":
                    compile_cache.programs_with_scopes(),
                **({} if state is None else {
                    "state_entries": state_entries(self.spec),
                    "state_bytes_per_slot": state})}
