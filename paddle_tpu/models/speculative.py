"""Draft-model speculative decoding: K tokens per target dispatch.

The PR 3 engine emits exactly one token per compiled decode dispatch —
optimal in programs, not in tokens. This module multiplies the tokens
per dispatch with the classic draft/verify split (Leviathan et al.,
arXiv:2211.17192): a small DRAFT model proposes ``K`` tokens
autoregressively, the TARGET model scores all ``K`` (plus the pending
token) in ONE batched forward over an ``[B, K+1]`` window, and exact
rejection sampling keeps the emitted stream distribution-identical to
solo target decoding:

- accept draft token ``d_i`` with probability ``min(1, p(d_i)/q(d_i))``
  (``p`` = target's filtered sampling distribution, ``q`` = draft's);
- on the first rejection, resample from the residual
  ``max(p - q, 0)`` renormalized;
- when all ``K`` survive, a bonus token is sampled from the target's
  ``K+1``-th distribution — so every verify dispatch emits between 1 and
  ``K + 1`` tokens.

Under greedy decoding the rule degenerates to ``d_i == argmax(p_i)`` and
the output is TOKEN-IDENTICAL to solo greedy target decode (the parity
gate tier-1 asserts). Under sampling, equivalence is distributional, so
determinism is pinned by fixed-seed acceptance-trace replay instead: the
per-(step, row) PRNG fold discipline of PR 4 extends here with one named
stream per random decision (draft proposal / accept / resample / bonus),
each folded at the token's absolute POSITION then row — two runs with
the same seed replay the same acceptance trace exactly.

Shape discipline (the compile-budget story):

- both caches are preallocated pytrees; all round state (positions,
  pending tokens, done mask) is ``[B]`` vectors — rows accept different
  counts per round, so every row sits at its OWN position (the PR 8
  continuous-batching machinery: per-row windowed cache writes, per-row
  mask frontiers, per-row position-table gathers);
- the whole round — K-step draft chain AND the ``[B, K+1]`` target
  verify — is FUSED into ONE compiled program: a round costs exactly
  ONE dispatch for up to ``K + 1`` tokens, against ``K + 1`` solo
  dispatches for the same tokens, and the draft distributions never
  cross a program boundary. The chain's first window is the two-token
  pair ``[prev, pending]`` (so the draft cache never misses ``prev``'s
  KV — in particular ``d_K``'s after an all-accept round); later steps
  feed one token each.

The steady-state program family is therefore ``#buckets`` target
prefills + ``#buckets`` draft prefills + 1 decode round — the named
budget line ``retrace_report.py --generate`` learns.

``build_draft_model`` gives the zero-training default draft: the first
``n`` decoder blocks of the target with shared embeddings/final norm
(and tied head), weight-copied — agreement comes from the shallow
truncation, cost from ``n / num_layers``. Quantization composes on both
axes: ``kv_dtype="int8"`` halves either cache, and a PTQ'd draft
(``quantization.PTQ`` over the parallel projections) drops draft weight
traffic.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import compile_cache
from ..framework import random as framework_random
from ..nn.layer import buffer_state, functional_call, param_state
from ..io.batching import bucket_for
from .generation import (filter_logits, per_row_keys, sample_logits,
                         sample_logits_rows, DEFAULT_PREFILL_BUCKETS)
from .kv_cache import (cache_geometry, constrain_cache, init_cache,
                       normalize_kv_dtype, refuse_state_entries)

__all__ = ["SpeculativeEngine", "build_draft_model"]

# named PRNG streams: every random decision folds (stream, position, row)
_STREAM_DRAFT = 101
_STREAM_ACCEPT = 102
_STREAM_RESAMPLE = 103
_STREAM_BONUS = 104


def _keys_at(key, stream: int, positions):
    """One PRNG key per row: fold the stream tag, then each row's
    (traced) absolute ``position``, then the row index — the speculative
    extension of :func:`~paddle_tpu.models.generation.per_row_keys`."""
    base = jax.random.fold_in(key, stream)
    rows = jnp.arange(positions.shape[0], dtype=jnp.uint32)

    def one(p, r):
        return jax.random.fold_in(jax.random.fold_in(base, p), r)

    return jax.vmap(one)(positions, rows)


def build_draft_model(model, num_layers: int = 1):
    """Weight-copied truncated draft for a :class:`GPTForCausalLM`-family
    target: same config with only the first ``num_layers`` decoder
    blocks, embeddings/final-norm (and the tied head riding them) copied
    from the target. No training needed — on a peaked target the shallow
    stack already agrees on most next tokens, at ``num_layers /
    target_layers`` of the FLOPs."""
    cfg = dataclasses.replace(model.cfg, num_layers=int(num_layers))
    draft = type(model)(cfg)
    # copy every parameter the truncated config retains (block 0..n-1,
    # embeddings, ln_f); set_state_dict ignores the dropped deep blocks
    draft.set_state_dict(dict(model.state_dict()))
    draft.eval()
    return draft


class SpeculativeEngine:
    """Draft/verify decode loop over a (target, draft) model pair.

    Mirrors :class:`~paddle_tpu.models.generation.GenerationEngine`'s
    construction contract (max_length validation, prefill buckets,
    ``compile_cache``-instrumented steps, ``kv_dtype``), plus ``k``: the
    number of draft proposals per verify dispatch.
    """

    def __init__(self, model, draft_model, k: int = 4,
                 max_length: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 kv_dtype=None, draft_kv_dtype=None):
        if int(k) < 1:
            raise ValueError("speculative k must be >= 1")
        self.model = model
        self.draft_model = draft_model
        self.k = int(k)
        self.spec = spec = model.cache_spec()
        self.dspec = dspec = draft_model.cache_spec()
        for who, its in (("target", spec), ("draft", dspec)):
            refuse_state_entries(
                its, f"speculative decoding (the {who} model)",
                "a rejected draft would need the state rolled back to the "
                "last accepted token, which no one keeps yet")
        self.kv_dtype = normalize_kv_dtype(kv_dtype)
        self.draft_kv_dtype = normalize_kv_dtype(
            kv_dtype if draft_kv_dtype is None else draft_kv_dtype)
        buckets = prefill_buckets or DEFAULT_PREFILL_BUCKETS
        self.max_length, self.prefill_buckets = cache_geometry(
            spec, max_length, buckets, "target's")
        cache_geometry(dspec, self.max_length, buckets, "DRAFT's")
        name = f"{type(model).__name__}+{type(draft_model).__name__}"
        self._cc = {
            kind: compile_cache.register_name(f"speculative:{kind}:{name}")
            for kind in ("target_prefill", "draft_prefill", "decode_round")}
        on_device = jax.default_backend() != "cpu"
        statics = ("top_k", "greedy", "use_top_p")
        self._target_prefill = jax.jit(
            compile_cache.instrument(self._target_prefill_fn,
                                     self._cc["target_prefill"]),
            donate_argnums=(2,) if on_device else (),
            static_argnames=statics)
        self._draft_prefill = jax.jit(
            compile_cache.instrument(self._draft_prefill_fn,
                                     self._cc["draft_prefill"]),
            donate_argnums=(2,) if on_device else ())
        # the whole round — K-step draft chain AND the [B, K+1] verify —
        # is ONE compiled program: a single dispatch per round, and the
        # draft distributions Q never cross a program boundary (greedy
        # mode dead-code-eliminates them entirely)
        self._round = jax.jit(
            compile_cache.instrument(self._round_fn,
                                     self._cc["decode_round"]),
            donate_argnums=(2, 5) if on_device else (),
            static_argnames=statics)

    # ------------------------------------------------------ compiled steps
    def _target_prefill_fn(self, params, buffers, cache, ids, last_index,
                           key, eos_id, temperature, top_p, *, top_k,
                           greedy, use_top_p):
        """Identical derivation to GenerationEngine._prefill_fn (same
        per-row key fold), so the pending first token matches a solo run
        with the same seed."""
        (logits, cache), _ = functional_call(
            self.model, params, buffers, ids, cache=cache,
            position_offset=0, gather_last=last_index)
        cache = constrain_cache(cache)
        logits = logits[:, 0, :]
        if greedy:
            tok = sample_logits(logits, None, greedy=True)
        else:
            rows = per_row_keys(key, logits.shape[0])
            tok = sample_logits_rows(logits, rows, temperature, top_k,
                                     top_p, use_top_p=use_top_p)
        return tok, tok == eos_id, cache

    def _draft_prefill_fn(self, dparams, dbuffers, dcache, ids,
                          last_index):
        """Prompt KV into the draft cache; the head projection collapses
        to the one gathered position (logits discarded)."""
        (_, dcache), _ = functional_call(
            self.draft_model, dparams, dbuffers, ids, cache=dcache,
            position_offset=0, gather_last=last_index)
        return constrain_cache(dcache)

    def _draft_chain_fn(self, dparams, dbuffers, dcache, prev, pend, pos,
                        key, temperature, top_p, *, top_k, greedy,
                        use_top_p):
        """Propose all ``K`` draft tokens in ONE compiled program (the
        loop unrolls at trace time — one dispatch per round, not per
        token). The FIRST window is the two-token pair ``[prev, pend]``
        at ``[pos - 1, pos]``: refeeding ``prev`` costs one extra row of
        attention but guarantees its KV is in the draft cache — in
        particular ``d_K``'s, which an all-accept round hands back as
        the next ``prev`` without any step having fed it. Every later
        step feeds just the newest draft token (its KV lands as a side
        effect), so the chain costs ``K + 1`` draft token-passes, not
        ``2K``. Step ``j`` samples the token at position ``pos + j + 1``
        from the draft stream. Returns ``(D [B, K], Q [B, K, V],
        dcache)``."""
        D, Q = [], []
        cur = pend
        for j in range(self.k):
            if j == 0:
                toks = jnp.stack([prev, pend], axis=1)
                offset = pos - 1
            else:
                toks = cur[:, None]
                offset = pos + j
            (logits, dcache), _ = functional_call(
                self.draft_model, dparams, dbuffers, toks, cache=dcache,
                position_offset=offset)
            dcache = constrain_cache(dcache)
            logits = logits[:, -1, :]
            if greedy:
                d = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                q = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            else:
                f = filter_logits(logits, temperature, top_k, top_p,
                                  use_top_p)
                q = jax.nn.softmax(f, axis=-1)
                dk = _keys_at(key, _STREAM_DRAFT, pos + j + 1)
                d = jax.vmap(
                    lambda dk, ll: jax.random.categorical(dk, ll)
                )(dk, f).astype(jnp.int32)
            D.append(d)
            Q.append(q)
            cur = d
        return jnp.stack(D, axis=1), jnp.stack(Q, axis=1), dcache

    def _verify_fn(self, params, buffers, cache, pend, pos, D, Q,
                   key, done, eos_id, temperature, top_p, *, top_k,
                   greedy, use_top_p):
        """Score the ``[B, K+1]`` window ``[pending, d_1..d_K]`` in one
        target forward and run the exact accept/resample/bonus rule.
        ``D [B, K]`` / ``Q [B, K, V]`` are the draft chain's proposals
        and per-step sampling distributions.

        Returns ``(out [B, K+1], n_emit [B], new_prev, new_pending,
        new_pos, new_done, all_done, cache)`` — ``out[:, :n_emit]`` are
        the committed tokens (eos-trimmed), positions/pending state
        advance by the per-row acceptance count. Done rows freeze: their
        window rewrites the same cache positions each round (never
        visible — the PR 8 frontier invariant) and emit nothing.
        """
        K = self.k
        toks = jnp.concatenate([pend[:, None], D], axis=1)   # [B, K+1]
        (logits, cache), _ = functional_call(
            self.model, params, buffers, toks, cache=cache,
            position_offset=pos)
        cache = constrain_cache(cache)
        B = D.shape[0]
        cols = jnp.arange(K + 1, dtype=jnp.int32)
        if greedy:
            tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
            accept = D == tgt[:, :K]
        else:
            f = filter_logits(logits, temperature, top_k, top_p, use_top_p)
            p = jax.nn.softmax(f, axis=-1)                   # [B, K+1, V]
            p_d = jnp.take_along_axis(p[:, :K], D[..., None],
                                      axis=-1)[..., 0]
            q_d = jnp.take_along_axis(Q, D[..., None], axis=-1)[..., 0]
            # u < p/q, drawn per (position, row) from the accept stream
            dpos = pos[:, None] + 1 + jnp.arange(K, dtype=jnp.int32)
            base = jax.random.fold_in(key, _STREAM_ACCEPT)
            rows = jnp.arange(B, dtype=jnp.uint32)

            def ukey(p_, r):
                return jax.random.fold_in(jax.random.fold_in(base, p_), r)

            ukeys = jax.vmap(jax.vmap(ukey, in_axes=(0, None)),
                             in_axes=(0, 0))(dpos, rows)     # [B, K] keys
            u = jax.vmap(jax.vmap(
                lambda uk: jax.random.uniform(uk, ())))(ukeys)
            accept = u * q_d < p_d
        cum = jnp.cumprod(accept.astype(jnp.int32), axis=1)
        n_acc = jnp.sum(cum, axis=1)                         # [B] in 0..K
        r = jnp.minimum(n_acc, K - 1)                        # gather index
        if greedy:
            tok_rej = jnp.take_along_axis(tgt[:, :K], r[:, None],
                                          axis=1)[:, 0]
            tok_bonus = tgt[:, K]
        else:
            pr = jnp.take_along_axis(p[:, :K], r[:, None, None],
                                     axis=1)[:, 0]           # [B, V]
            qr = jnp.take_along_axis(Q, r[:, None, None], axis=1)[:, 0]
            fr = jnp.take_along_axis(f[:, :K], r[:, None, None],
                                     axis=1)[:, 0]
            res = jnp.maximum(pr - qr, 0.0)
            res_sum = jnp.sum(res, axis=-1, keepdims=True)
            # residual mass 0 means p == q at this position — resampling
            # from p itself (the filtered target logits) is then exact
            safe_log = jnp.where(res > 0,
                                 jnp.log(jnp.maximum(res, 1e-38)),
                                 -jnp.inf)
            resample_logits = jnp.where(res_sum > 0, safe_log, fr)
            rkeys = _keys_at(key, _STREAM_RESAMPLE, pos + 1 + n_acc)
            tok_rej = jax.vmap(
                lambda rk, ll: jax.random.categorical(rk, ll)
            )(rkeys, resample_logits).astype(jnp.int32)
            bkeys = _keys_at(key, _STREAM_BONUS, pos + K + 1)
            tok_bonus = jax.vmap(
                lambda bk, ll: jax.random.categorical(bk, ll)
            )(bkeys, f[:, K]).astype(jnp.int32)
        next_tok = jnp.where(n_acc == K, tok_bonus, tok_rej)
        pad = jnp.concatenate(
            [D, jnp.zeros((B, 1), jnp.int32)], axis=1)       # [B, K+1]
        out = jnp.where(cols[None, :] == n_acc[:, None],
                        next_tok[:, None], pad)
        n_emit = n_acc + 1
        # eos inside the emitted prefix ends the row there
        is_eos = (out == eos_id) & (cols[None, :] < n_emit[:, None])
        any_eos = jnp.any(is_eos, axis=1)
        first_eos = jnp.argmax(is_eos, axis=1)
        n_emit = jnp.where(any_eos, first_eos + 1, n_emit)
        new_done = done | any_eos
        n_emit = jnp.where(done, 0, n_emit)
        new_pos = jnp.where(new_done, pos, pos + n_acc + 1)
        new_prev = jnp.take_along_axis(toks, n_acc[:, None], axis=1)[:, 0]
        return (out, n_emit, new_prev, next_tok, new_pos, new_done,
                jnp.all(new_done), cache)

    def _round_fn(self, params, buffers, cache, dparams, dbuffers, dcache,
                  prev, pend, pos, key, done, eos_id, temperature, top_p,
                  *, top_k, greedy, use_top_p):
        """One fused decode round: the K-step draft chain feeds straight
        into the verify window without leaving the program. Under greedy
        the verify ignores ``Q``, so XLA eliminates the draft softmax
        stack outright."""
        D, Q, dcache = self._draft_chain_fn(
            dparams, dbuffers, dcache, prev, pend, pos, key, temperature,
            top_p, top_k=top_k, greedy=greedy, use_top_p=use_top_p)
        (out, n_emit, new_prev, next_tok, new_pos, new_done, _all_done,
         cache) = self._verify_fn(
            params, buffers, cache, pend, pos, D, Q, key, done, eos_id,
            temperature, top_p, top_k=top_k, greedy=greedy,
            use_top_p=use_top_p)
        # everything the host consumes per round rides ONE int32 blob
        # [B, K+3] — tokens | n_emit | done — a single device->host
        # transfer at the round boundary instead of three
        host = jnp.concatenate(
            [out, n_emit[:, None], new_done.astype(jnp.int32)[:, None]],
            axis=1)
        return host, new_prev, next_tok, new_pos, cache, dcache

    # ------------------------------------------------------------- driver
    def cache_stats(self) -> dict:
        return {kind: compile_cache.cache_stats(cc)
                for kind, cc in self._cc.items()}

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 seed: Optional[int] = None,
                 return_stats: bool = False):
        """Speculatively extend ``input_ids`` [B, prompt_len]; same
        return contract as :meth:`GenerationEngine.generate`. With
        ``return_stats`` the stats dict additionally carries
        ``acceptance_rate``, ``tokens_per_target_dispatch``, ``rounds``,
        ``dispatches`` and the per-round ``acceptance_trace`` (a [rounds,
        B] emit-count array — the fixed-seed replay artifact)."""
        from ..profiler import RecordEvent

        K = self.k
        ids = np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        B, prompt_len = ids.shape
        if prompt_len < 1:
            raise ValueError("generate needs a non-empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt_len + max_new_tokens + K > self.max_length:
            raise ValueError(
                f"prompt_len {prompt_len} + max_new_tokens "
                f"{max_new_tokens} + k {K} exceeds max_length "
                f"{self.max_length}: the last verify window must fit in "
                f"the cache; build the engine with a larger max_length "
                f"or smaller k")
        bucket = min(bucket_for(prompt_len, self.prefill_buckets),
                     self.max_length)
        ids_p = np.zeros((B, bucket), np.int32)
        ids_p[:, :prompt_len] = ids
        greedy = not do_sample
        if do_sample and seed is None:
            key = framework_random.next_key()
        else:
            key = jax.random.PRNGKey(0 if seed is None else int(seed))
        eos_id = np.int32(-1 if eos_token_id is None else eos_token_id)
        temp = np.float32(temperature)
        top_p_ = np.float32(top_p)
        use_top_p = bool(top_p < 1.0)

        was_training = (self.model.training, self.draft_model.training)
        self.model.eval()
        self.draft_model.eval()
        try:
            params = param_state(self.model)
            buffers = buffer_state(self.model)
            dparams = param_state(self.draft_model)
            dbuffers = buffer_state(self.draft_model)
            cache = init_cache(self.model, B, self.max_length,
                               kv_dtype=self.kv_dtype)
            dcache = init_cache(self.draft_model, B, self.max_length,
                                kv_dtype=self.draft_kv_dtype)
            emitted = [[] for _ in range(B)]
            trace = []
            proposed = accepted = 0
            dispatches = 0
            rounds = 0
            t0 = time.perf_counter()
            with RecordEvent("speculative_decode"):
                compile_cache.record_call(self._cc["target_prefill"])
                tok, _eos_dev, cache = self._target_prefill(
                    params, buffers, cache, ids_p,
                    np.int32(prompt_len - 1), key, eos_id, temp, top_p_,
                    top_k=int(top_k), greedy=greedy, use_top_p=use_top_p)
                compile_cache.record_call(self._cc["draft_prefill"])
                dcache = self._draft_prefill(dparams, dbuffers, dcache,
                                             ids_p, np.int32(prompt_len - 1))
                dispatches += 2
                # tpu-lint: disable=R1(honest TTFT — the metric is "token READY", not "dispatch returned")
                first = np.asarray(tok)
                ttft = time.perf_counter() - t0
                done_h = (first == int(eos_id)) | (max_new_tokens == 1)
                for i in range(B):
                    emitted[i].append(int(first[i]))
                # device round state: prev/pending tokens + per-row
                # positions (prev = last prompt token @ prompt_len - 1,
                # pending @ prompt_len)
                prev = jnp.asarray(ids[:, -1].astype(np.int32))
                pend = tok
                pos = jnp.full((B,), prompt_len, jnp.int32)
                while not done_h.all():
                    # ONE dispatch per round: draft the chain
                    # [prev, pend, d_1, .., d_K] and verify it in the
                    # same compiled program
                    compile_cache.record_call(self._cc["decode_round"])
                    (host, prev, pend, pos, cache, dcache) = self._round(
                        params, buffers, cache, dparams, dbuffers, dcache,
                        prev, pend, pos, key, jnp.asarray(done_h), eos_id,
                        temp, top_p_, top_k=int(top_k), greedy=greedy,
                        use_top_p=use_top_p)
                    dispatches += 1
                    rounds += 1
                    # tpu-lint: disable=R1(round-boundary readback — this round's tokens/counts/done ride ONE batched transfer)
                    blob = np.asarray(host)
                    out_h = blob[:, :K + 1]
                    n_emit_h = blob[:, K + 1]
                    trace.append(n_emit_h.copy())
                    for i in range(B):
                        if done_h[i]:
                            continue
                        room = max_new_tokens - len(emitted[i])
                        take = min(int(n_emit_h[i]), room)
                        emitted[i].extend(int(t) for t in
                                          out_h[i, :take])
                        proposed += K
                        accepted += min(int(n_emit_h[i]) - 1, take)
                    done_h = blob[:, K + 2].astype(bool) | np.array(
                        [len(e) >= max_new_tokens for e in emitted])
            total = time.perf_counter() - t0
        finally:
            if was_training[0]:
                self.model.train()
            if was_training[1]:
                self.draft_model.train()
        fill = int(max(eos_id, 0))
        n = max(len(e) for e in emitted)
        out_arr = np.full((B, n), fill, np.int32)
        for i, e in enumerate(emitted):
            out_arr[i, :len(e)] = e
        if not return_stats:
            return out_arr
        new_tokens = sum(len(e) for e in emitted)
        stats = {
            "ttft_s": ttft,
            "total_s": total,
            "new_tokens": n,
            "tokens_per_sec": new_tokens / max(total, 1e-9),
            "decode_tokens_per_sec": ((new_tokens - B) /
                                      max(total - ttft, 1e-9)
                                      if n > 1 else 0.0),
            "prefill_bucket": bucket,
            "rounds": rounds,
            "dispatches": dispatches,
            "k": K,
            "acceptance_rate": accepted / max(proposed, 1),
            "tokens_per_target_dispatch": new_tokens / max(rounds + 1, 1),
            "acceptance_trace": (np.stack(trace, axis=0) if trace
                                 else np.zeros((0, B), np.int32)),
            "compile_stats": self.cache_stats(),
        }
        return out_arr, stats
