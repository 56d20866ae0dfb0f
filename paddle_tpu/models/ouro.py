"""Ouro looped decoder ("LoopLM", ByteDance 2025): one stack of layers
applied ``total_ut_steps`` times to every token, with the same weights at
every step, and a learned exit gate that turns the steps' states into a
distribution over where to stop.

    x = E[ids]
    for t in range(T):                       # recurrent steps
        for l in range(L):                   # sandwich-normalised block
            x = x + RMSNorm(Attn_l(RMSNorm(x; g1_l)); g2_l)
            x = x + RMSNorm(MLP_l(RMSNorm(x; g3_l)); g4_l)
        x = h_t = RMSNorm(x; g_final)        # step t + 1 starts from h_t
        lam_t = sigmoid(w_gate . h_t + b_gate)
    p_t = lam_t prod_{j<t} (1 - lam_j),  p_{T-1} = prod_{j<T-1} (1 - lam_j)
    logits = W_head h_{T-1}                  # early_exit_threshold 1.0

The attention, the SwiGLU MLP and the rotary code are ``llama.py``'s; this
file adds the four-norm block, the loop and the gate. The key of a
position differs from step to step (its input does), so the KV cache has
``T * L`` entries, not ``L``: each layer's ``(k, v)`` leaves stack its T
steps on an axis after the batch's, ``[B, T, S, Hkv, D]``, entry ``(t,
l)`` at ``cache[l][...][:, t]``, and the steps are ONE loop in the program
(``lax.scan`` over ``t``): a decode program of L layer applications, not
``T * L``.

Every step runs for every token: an exit before the last step (threshold
below 1) would make a step's cost differ between the slots of one decode
batch, which the serving engine cannot schedule yet, so the config
refuses it. The loss with labels is the shifted cross entropy of the
last step's logits; the paper's objective over the exit distribution is
not implemented.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..distributed.parallel.mp_layers import VocabParallelEmbedding
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..nn.layers.common import Linear
from ..nn.layers.norm import RMSNorm
from .llama import (LlamaAttention, LlamaConfig, LlamaForCausalLM,
                    LlamaMLP)
from .lm_utils import DecoderBlockList, constrain_seq

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "ouro_tiny"]


@dataclass
class OuroConfig(LlamaConfig):
    """``LlamaConfig`` (whose attention and MLP read it) plus the loop.
    Defaults are Ouro-2.6B's published sizes."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    intermediate_size: int = 5632
    max_position_embeddings: int = 65536
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be >= 1")
        if self.early_exit_threshold != 1.0:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold}: only "
                f"1.0 (every step runs for every token) is supported")


def ouro_tiny(**overrides) -> OuroConfig:
    cfg = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
               intermediate_size=160, max_position_embeddings=256,
               total_ut_steps=3, use_flash_attention=False)
    cfg.update(overrides)
    return OuroConfig(**cfg)


class OuroBlock(Layer):
    """Sandwich-normalised block: a norm before AND after each of the
    attention and the MLP, the residual added after the second.

    The second norm makes every branch write a vector of its gain's
    length into the stream, whatever the projections' scale: the
    ``1 / sqrt(2 L)`` that this repo's decoders put on the initial
    ``o_proj`` and ``down_proj`` so that 2 L branches add up to the length
    of what they started from is undone by it. So the gain starts there
    instead of at 1. At 1 a step's 96 branches bury its input (the state
    turns by 1.2 of its length a step), freshly initialised weights
    amplify a perturbation 33 times over four steps, and no bfloat16
    program comes within a fifth of the logits' spread of its float32
    reference (PERF.md, PR 27); at ``1 / sqrt(2 L)`` the gain of a
    perturbation is 3, the same at every step."""

    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        branch = Constant(1.0 / math.sqrt(2 * cfg.num_layers))
        norm = lambda gain=None: RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps, weight_attr=gain)
        self.input_layernorm = norm()
        self.self_attn = LlamaAttention(cfg)
        self.input_layernorm_2 = norm(branch)
        self.post_attention_layernorm = norm()
        self.mlp = LlamaMLP(cfg)
        self.post_attention_layernorm_2 = norm(branch)

    def forward(self, x, cache=None, position_offset=0, cache_entry=None):
        """``x`` is the float32 residual stream (see :class:`OuroModel`);
        the projections compute in their weights' type."""
        compute = self.self_attn.q_proj.weight.dtype
        a = self.self_attn(self.input_layernorm(x).astype(compute),
                           cache=cache, position_offset=position_offset,
                           cache_entry=cache_entry)
        if cache is not None:
            a, cache = a
        x = x + self.input_layernorm_2(a.astype(x.dtype))
        m = self.mlp(self.post_attention_layernorm(x).astype(compute))
        x = x + self.post_attention_layernorm_2(m.astype(x.dtype))
        x = constrain_seq(x, self.cfg)
        return x if cache is None else (x, cache)


class OuroModel(Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        init = Normal(0.0, cfg.initializer_range)
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        self.layers = DecoderBlockList(cfg, OuroBlock)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.early_exit_gate = Linear(cfg.hidden_size, 1, weight_attr=init)

    def forward(self, input_ids, cache=None, position_offset=0,
                exit_gates=False):
        """Final hidden states ``h_{T-1}`` [B, L, H] float32, with the
        updated cache when one is given; with ``exit_gates`` also every
        step's gate ``lam`` as [T, B, L] float32.

        The residual stream is float32 whatever the weights are: what a
        branch adds is a tenth of the stream's length at initialisation,
        and in bfloat16 the sum's rounding (2**-9 of the stream) is 3 % of
        it, 384 times a token. Measured at 48 layers x 4 steps: it halves
        the logits' distance from the float32 reference (PERF.md PR 27)."""
        with jax.named_scope("embed"):
            x = constrain_seq(self.embed_tokens(input_ids), self.cfg)
            x = x.astype(jnp.float32)

        def step(carry, t):
            x, cache = carry
            if cache is None:
                x = self.layers(x)
            else:
                x, cache = self.layers(x, caches=cache,
                                       position_offset=position_offset,
                                       cache_entry=t)
            x = self.norm(x)
            lam = None
            if exit_gates:
                with jax.named_scope("exit_gate"):
                    lam = jax.nn.sigmoid(
                        self.early_exit_gate(x)[..., 0].astype(jnp.float32))
            return (x, cache), lam

        # around the loop, not inside its body: what the compiler hoists
        # out of the body (a weight re-laid once a token for all the
        # passes) is the loop's too
        with jax.named_scope("ut_step"):
            (x, cache), lam = jax.lax.scan(
                step, (x, cache),
                jnp.arange(self.cfg.total_ut_steps, dtype=jnp.int32))
        out = x if cache is None else (x, cache)
        return (out, lam) if exit_gates else out


class OuroForCausalLM(LlamaForCausalLM):
    """LM head model; :class:`LlamaForCausalLM` over the looped backbone
    (its contract: logits, the loss directly when labels are given,
    ``(logits, cache)`` on the cached path, ``generate()``,
    ``lora_spec()``), and :meth:`exit_pdf`."""

    backbone_cls = OuroModel

    def _logits(self, h):
        # the backbone's stream is float32; the head computes in its
        # weights' type like every other projection
        return super()._logits(h.astype(self.model.embed_tokens.weight.dtype))

    def cache_spec(self) -> dict:
        """KV-cache geometry for ``models.kv_cache.init_cache``: one
        entry per (step, layer), a layer's steps stacked in each row of
        its leaves."""
        T = self.cfg.total_ut_steps
        return dict(super().cache_spec(), entry_stack=T,
                    cache_entries=T * self.cfg.num_layers)

    def exit_pdf(self, input_ids):
        """The exit distribution [B, L, T] over the recurrent steps:
        ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last step taking what
        is left, so that it sums to 1."""
        _, lam = self.model(input_ids, exit_gates=True)
        stay = jnp.cumprod(1.0 - lam[:-1], axis=0)           # [T-1, B, L]
        before = jnp.concatenate([jnp.ones_like(lam[:1]), stay], axis=0)
        pdf = jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], axis=0)
        return jnp.moveaxis(pdf, 0, -1)
