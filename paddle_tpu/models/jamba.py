"""Jamba hybrid decoder (AI21, arXiv:2403.19887; ``model_type`` jamba):
Mamba-1 layers with an attention layer every ``attn_layer_period``, a dense
SwiGLU after each, RMSNorm before both, a tied head.

Layer ``i`` is an ATTENTION layer where ``i % attn_layer_period ==
attn_layer_offset`` and a MAMBA layer otherwise. Per token ``t`` (``rms(x,
g) = x rsqrt(mean x^2 + eps) g``; ``d = mamba_expand * hidden_size`` inner
channels, ``n = mamba_d_state`` states, ``K = mamba_d_conv``):

    x <- x + Mixer_i(rms(x, g_in));   x <- x + W_down(silu(W_gate y) * W_up y),  y = rms(x, g_ff)
    logits = E rms(x, g_f)                                  (E the embedding: tied)

    Mamba:  [u' | z] = x W_in
            u_t = silu(sum_k w_c[k] * u'_{t-(K-1)+k} + b_c)   (u' before the sequence is 0)
            [dl | B | C] = u_t W_x;  dl, B, C <- rms(., g_dl), rms(., g_B), rms(., g_C)
            D_t = softplus(dl W_dt + b_dt);   A = -exp(A_log)
            h_t[s, c] = exp(D_t[c] A[s, c]) h_{t-1}[s, c] + D_t[c] u_t[c] B_t[s],   h_{-1} = 0
            y_t[c] = sum_s h_t[s, c] C_t[s] + D[c] u_t[c];   out_t = (y_t * silu(z_t)) W_out

    Attention: ``llama.py``'s grouped-query attention WITHOUT positions
    (``rope_theta=None``): no rotary, no table; the Mamba layers carry
    the order.

What a slot carries between tokens is ``h_t`` (float32) and the last ``K -
1`` inputs ``u'`` of the convolution: a STATE entry of the cache
(:mod:`.kv_cache`), beside the ``(k, v)`` entries of the attention layers.
The mixer reaches it through ``lm_utils.scan_with_state`` alone.

The residual stream is float32 whatever the weights' type, as
``ouro.py``'s and ``xing.py``'s are, the branches add to it in float32 and
the logits leave the tied head in float32: bfloat16 activations through
56 sublayers of freshly drawn weights stand a few hundredths of the
logits' spread from the float32 reference's (every sublayer grows a
perturbation, eightfold in all), and each rounding taken out is distance
a greedy token does not fall short by (PERF.md section 6, PR 34). The
recurrence (``D_t``'s projection and softplus, ``exp(D_t A)``, the state,
the sum over it) is float32 at full matmul precision whatever the
weights' type; ``A_log`` and ``D`` stay float32 parameters under a cast
of the model (``Layer.keeps_dtype``); the window is ``cfg.dtype``. The inner
width lies on the lanes everywhere: ``A_log`` is ``[n, d]`` and the
convolution's weight ``[K, d]`` (a checkpoint's ``[d, n]`` and ``[d, 1,
K]`` transposed). Every parameter is drawn in ``cfg.dtype`` from the
start, as ``xing.py``'s are. ``num_experts`` is 1 in the published 3B
model: every FFN is dense and the ``expert_layer_*`` keys select nothing;
more is refused. Initial values the published ``config.json`` is silent on
are Mamba's own (arXiv:2312.00752) and are listed in
``benchmarks/configs/ai21-jamba2-3b.json`` under ``assumed``.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..distributed.parallel.mp_layers import VocabParallelEmbedding
from ..nn.initializer import Constant, Initializer, Normal, Uniform
from ..nn.layer import Layer
from ..nn.layers.common import Linear
from ..nn.layers.norm import RMSNorm
from .llama import (LlamaAttention, LlamaConfig, LlamaForCausalLM, LlamaMLP,
                    born_as)
from .lm_utils import DecoderBlockList, scan_with_state

__all__ = ["JambaConfig", "JambaModel", "JambaForCausalLM", "MambaMixer",
           "jamba_tiny"]

_HIGHEST = jax.lax.Precision.HIGHEST
# Mamba's published initialisation of the step size: softplus(b_dt) is
# log-uniform in [DT_MIN, DT_MAX]
DT_MIN, DT_MAX = 1e-3, 1e-1


@dataclass
class JambaConfig(LlamaConfig):
    """``LlamaConfig`` (whose attention, SwiGLU, head and loss read it)
    plus the layer pattern and the Mamba mixer, under the published
    names. Defaults are AI21-Jamba2-3B's."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    intermediate_size: int = 8192
    max_position_embeddings: int = 262144
    rope_theta: float = None               # no positional encoding at all
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_experts: int = 1
    num_experts_per_tok: int = 1
    expert_layer_period: int = 2           # select nothing at one expert
    expert_layer_offset: int = 1

    def __post_init__(self):
        super().__post_init__()
        if self.num_experts != 1 or self.num_experts_per_tok != 1:
            raise ValueError("only num_experts = 1 (a dense FFN in every "
                             "layer) is supported")
        if not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError("only mamba_conv_bias true and mamba_proj_bias "
                             "false are supported")
        if self.rope_theta is not None:
            raise ValueError("this family's attention has no positional "
                             "encoding: rope_theta must be None")

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention_layer(self, index: int) -> bool:
        return index % self.attn_layer_period == self.attn_layer_offset


def jamba_tiny(**overrides) -> JambaConfig:
    """Both mixer kinds at a size the CPU tests afford: four layers with
    attention at index 1, inner width 64, 8 states, step rank 8."""
    cfg = dict(vocab_size=256, hidden_size=32, num_layers=4, num_heads=4,
               num_kv_heads=1, intermediate_size=64,
               max_position_embeddings=256, attn_layer_period=4,
               attn_layer_offset=1, mamba_d_state=8, mamba_dt_rank=8)
    cfg.update(overrides)
    return JambaConfig(**cfg)


def _project(x, weight):
    """``x @ weight`` in the weight's type with a FLOAT32 result: between
    two matmuls of a branch nothing is rounded to bfloat16 but the next
    matmul's own input (gate and up of the SwiGLU, the mixer's gate ``z``
    and every branch's output went through bfloat16 once more each, and
    the served logits stood a third farther from the float32 reference's
    for it: PERF.md section 6, PR 34)."""
    return jnp.matmul(x.astype(weight.dtype), weight,
                      preferred_element_type=jnp.float32)


class JambaMLP(LlamaMLP):
    """``LlamaMLP``'s parameters and SwiGLU, float32 between its matmuls
    (:func:`_project`)."""

    @jax.named_scope("mlp")
    def forward(self, x):
        gate = _project(x, self.gate_proj.weight)
        up = _project(x, self.up_proj.weight)
        return _project(jax.nn.silu(gate) * up, self.down_proj.weight)


class _StateLog(Initializer):
    """``A_log[s, c] = ln(s + 1)``: state ``s`` decays at rate ``s + 1``."""

    def __call__(self, key, shape, dtype):
        n, d = shape
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
            (n, d)).astype(dtype)


class _InverseSoftplusOfLogUniform(Initializer):
    """``b`` such that ``softplus(b)`` is log-uniform in ``[lo, hi]``."""

    def __init__(self, lo: float, hi: float):
        self.lo, self.hi = lo, hi

    def __call__(self, key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class MambaMixer(Layer):
    """The Mamba-1 mixer with Jamba's three inner norms; names as the
    family's checkpoints."""

    keeps_dtype = ("A_log", "D")    # float32 through Layer.to / amp.decorate

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        C, d = cfg.hidden_size, cfg.mamba_d_inner
        n, K, r = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
        init = Normal(0.0, cfg.initializer_range)
        out_init = Normal(0.0, cfg.initializer_range
                          / math.sqrt(2 * cfg.num_layers))
        self.in_proj = Linear(C, 2 * d, weight_attr=init, bias_attr=False)
        self.conv_weight = self.create_parameter(
            (K, d), attr=Uniform(-K ** -0.5, K ** -0.5))
        self.conv_bias = self.create_parameter(
            (d,), attr=Uniform(-K ** -0.5, K ** -0.5))
        self.x_proj = Linear(d, r + 2 * n, weight_attr=init, bias_attr=False)
        norm = lambda width: RMSNorm(width, epsilon=cfg.rms_norm_eps)
        self.dt_layernorm, self.b_layernorm, self.c_layernorm = (
            norm(r), norm(n), norm(n))
        self.dt_proj = Linear(
            r, d, weight_attr=Uniform(-r ** -0.5, r ** -0.5),
            bias_attr=_InverseSoftplusOfLogUniform(DT_MIN, DT_MAX))
        self.A_log = self.create_parameter((n, d), dtype="float32",
                                           attr=_StateLog())
        self.D = self.create_parameter((d,), dtype="float32",
                                       attr=Constant(1.0))
        self.out_proj = Linear(d, C, weight_attr=out_init, bias_attr=False)

    def _ssm_params(self, u):
        """``(delta [B, L, d], B [B, L, n], C [B, L, n])`` float32 of the
        convolved inputs ``u`` [B, L, d] float32."""
        cfg = self.cfg
        r, n = cfg.mamba_dt_rank, cfg.mamba_d_state
        f32 = jnp.float32
        dbc = _project(u, self.x_proj.weight)
        dl = self.dt_layernorm(dbc[..., :r])
        Bm = self.b_layernorm(dbc[..., r:r + n])
        Cm = self.c_layernorm(dbc[..., r + n:])
        delta = jax.nn.softplus(
            jnp.matmul(dl.astype(f32), self.dt_proj.weight.astype(f32),
                       precision=_HIGHEST)
            + self.dt_proj.bias.astype(f32))
        return delta, Bm.astype(f32), Cm.astype(f32)

    @jax.named_scope("mamba")
    def forward(self, x, cache=None, position_offset=0):
        d = self.cfg.mamba_d_inner
        with jax.named_scope("in_proj"):
            uz = _project(x, self.in_proj.weight)
            # the convolution's inputs in the window's type: what a
            # decode step reads back is what prefill convolved
            u_pre, z = uz[..., :d].astype(x.dtype), uz[..., d:]
        y, cache = scan_with_state(
            u_pre, self.conv_weight, self.conv_bias, self._ssm_params,
            -jnp.exp(self.A_log.astype(jnp.float32)),
            self.D.astype(jnp.float32), cache, position_offset)
        with jax.named_scope("out_proj"):
            out = _project(y * jax.nn.silu(z), self.out_proj.weight)
        return out if cache is None else (out, cache)


class JambaBlock(Layer):
    def __init__(self, cfg: JambaConfig, index: int):
        super().__init__()
        self.cfg = cfg
        norm = lambda: RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.input_layernorm = norm()
        # named as the family's checkpoints name them
        self.mixer_name, mixer_cls = (
            ("self_attn", LlamaAttention) if cfg.is_attention_layer(index)
            else ("mamba", MambaMixer))
        setattr(self, self.mixer_name, mixer_cls(cfg))
        self.pre_ff_layernorm = norm()
        self.feed_forward = JambaMLP(cfg)

    def forward(self, x, cache=None, position_offset=0):
        """``x`` [B, L, C]: the float32 residual stream."""
        compute = self.feed_forward.down_proj.weight.dtype
        a = getattr(self, self.mixer_name)(
            self.input_layernorm(x).astype(compute), cache=cache,
            position_offset=position_offset)
        if cache is not None:
            a, cache = a
        x = x + a
        x = x + self.feed_forward(self.pre_ff_layernorm(x).astype(compute))
        return x if cache is None else (x, cache)


class JambaModel(Layer):
    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=Normal(0.0, cfg.initializer_range))
        index = itertools.count()       # a per-layer pattern of mixers
        self.layers = DecoderBlockList(
            cfg, lambda cfg: JambaBlock(cfg, next(index)))
        self.final_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, cache=None, position_offset=0):
        """Final hidden states [B, L, C] float32, with the updated cache
        when one is given."""
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids).astype(jnp.float32)
        if cache is None:
            x = self.layers(x)
        else:
            x, cache = self.layers(x, caches=cache,
                                   position_offset=position_offset)
        with jax.named_scope("final_norm"):
            x = self.final_layernorm(x)
        return x if cache is None else (x, cache)


class JambaForCausalLM(LlamaForCausalLM):
    """LM head model; :class:`LlamaForCausalLM`'s contract (tied head,
    ``generate``) over the hybrid backbone."""

    backbone_cls = JambaModel

    def __init__(self, cfg: JambaConfig):
        with born_as(cfg.dtype):
            super().__init__(cfg)

    @jax.named_scope("lm_head")
    def _logits(self, h):
        """Float32 logits from the tied matrix: in bfloat16 the largest
        logits of 65536 lie 2^-6 to 2^-5 apart, so near-ties round to
        ties and a greedy token falls short of the float32 reference's
        best by that spacing alone."""
        w = self.model.embed_tokens.weight
        return jnp.matmul(h.astype(w.dtype), w.T,
                          preferred_element_type=jnp.float32)

    def cache_spec(self) -> dict:
        """Cache geometry for ``models.kv_cache``: entry by entry,
        ``"kv"`` for an attention layer (one key/value head) and
        ``"state"`` for a Mamba layer: ``(h [.., d_state, d_inner]
        float32, window [.., d_conv - 1, d_inner])``."""
        cfg = self.cfg
        return {"num_layers": cfg.num_layers,
                "entry_kinds": tuple(
                    "kv" if cfg.is_attention_layer(i) else "state"
                    for i in range(cfg.num_layers)),
                "num_kv_heads": cfg.num_kv_heads,
                "head_dim": cfg.hidden_size // cfg.num_heads,
                "state": (cfg.mamba_d_state, cfg.mamba_d_conv - 1,
                          cfg.mamba_d_inner),
                "max_length": cfg.max_position_embeddings,
                "dtype": cfg.dtype}

    def lora_spec(self) -> dict:
        """The attention layers' projections: the mixers and the SwiGLU
        multiply by their weights directly (:func:`_project`)."""
        return {"target_modules": ("q_proj", "k_proj", "v_proj", "o_proj")}
