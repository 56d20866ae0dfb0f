"""Xing4.0 sparse decoder (XingChen-AGI, 2026): multi-head latent attention,
a dropless expert FFN with a shared expert behind a per-layer pattern, and
a residual path of ``hc_mult`` streams mixed by a doubly stochastic matrix
(manifold-constrained hyper-connections, arXiv:2512.24880).

Per token, with ``n = hc_mult`` streams ``X`` [n, C] (float32):

    X_0 = [E[id]] * n
    for each block, for F in (attention, FFN), each with its own g, phi, alpha, b:
        x~ = vec(X);  m = (x~ phi) * rsqrt(mean x~^2 + eps)           # n^2 + 2n numbers
        H_pre = sigmoid(a_pre m[:n] + b[:n]);  H_post = 2 sigmoid(a_post m[n:2n] + b[n:2n])
        M = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), lo, hi))
        hc_sinkhorn_iters times:  M /= rowsum M + hc_eps;  M /= colsum M + hc_eps
        h = sum_i H_pre[i] X_i;  y = F(rms(h, g));  X'_j = sum_i M[j, i] X_i + H_post[j] y
    logits = W_head rms(sum_i X_i, g_f)

Attention is latent (``lm_utils.attend_with_latent_cache``): the query
through a rank-``q_lora_rank`` bottleneck, keys and values decompressed
from one normed ``kv_lora_rank`` vector a position plus one rotated key of
``qk_rope_head_dim`` that all heads share (YaRN-scaled frequencies). The
cache holds that pair and nothing per head; decode attends in the latent
space. The FFN of the first ``first_k_dense_replace`` blocks is
``llama.py``'s SwiGLU, of the others ``nn.layers.expert_ffn.ExpertFFN``.

Everything that decides routing or mixing (router scores, the mixers, the
Sinkhorn steps, the streams themselves) is float32 at full matmul
precision whatever the weights' type; the projections compute in their
weights' type. Every parameter is drawn in ``cfg.dtype`` from the start:
the published size born in float32 and cast afterwards would not fit the
chip it is served from.

The config's multi-token-prediction layer is not part of the served
forward pass and is not built. Where the published ``config.json`` is
silent (how the streams start and are read out, where the clamp and
``hc_eps`` enter, every initial value of the mixers) the choices are this
file's, listed in ``benchmarks/configs/xing4.0-29b-a4b.json`` under
``assumed``.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.parallel.mp_layers import VocabParallelEmbedding
from ..nn.initializer import Constant, Initializer, Normal
from ..nn.layer import Layer
from ..nn.layers.common import Linear
from ..nn.layers.expert_ffn import ExpertFFN
from ..nn.layers.norm import RMSNorm
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaMLP, born_as,
                    rotary_embed)
from .lm_utils import (DecoderBlockList, attend_with_latent_cache,
                       latent_block_attention)

__all__ = ["XingConfig", "XingModel", "XingForCausalLM", "xing_tiny",
           "yarn_inv_freq", "sinkhorn"]

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass
class XingConfig(LlamaConfig):
    """``LlamaConfig`` (whose SwiGLU, head and loss read it) plus the
    latent attention, the experts and the streams. Defaults are
    Xing4.0-29B-A4B's published sizes; ``intermediate_size`` is the
    dense layers' width."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    num_heads: int = 32
    intermediate_size: int = 9216
    max_position_embeddings: int = 262144
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    use_flash_attention: bool = False      # key width 192: no flash shape
    # latent attention
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_scaling: Optional[dict] = None    # YaRN: factor, beta_fast, ...
    # experts
    first_k_dense_replace: int = 2
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1024
    n_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
    # streams
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    def __post_init__(self):
        super().__post_init__()
        if self.hc_mult < 1:
            raise ValueError("hc_mult must be >= 1")
        if self.scoring_func != "sigmoid" or not self.norm_topk_prob:
            raise ValueError(
                f"scoring_func {self.scoring_func!r}, norm_topk_prob "
                f"{self.norm_topk_prob}: only sigmoid scores normalised "
                f"over the picked experts are supported")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)
        rs = self.rope_scaling
        if rs and rs.get("mscale", 1) != rs.get("mscale_all_dim", 0):
            # YaRN would scale cos and sin by the ratio of the two mscales
            raise ValueError("rope_scaling with mscale != mscale_all_dim "
                             "is not supported")

    @property
    def attention_scale(self) -> float:
        """``(nope + rope) ** -0.5``, times YaRN's ``mscale ** 2`` (``0.1
        mscale_all_dim ln(factor) + 1``) where the frequencies are scaled."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim"):
            scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return scale


def xing_tiny(**overrides) -> XingConfig:
    """Every mechanism on at a size the CPU tests afford: 4 streams, 8
    experts top 2 and a shared one, one dense and two expert blocks,
    latent 16 + rotated 8, YaRN past 32 positions."""
    cfg = dict(vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
               intermediate_size=160, max_position_embeddings=256,
               q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16,
               rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                             "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                             "original_max_position_embeddings": 32},
               first_k_dense_replace=1, n_routed_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=32,
               hc_mult=4, hc_sinkhorn_iters=20)
    cfg.update(overrides)
    return XingConfig(**cfg)


# ------------------------------------------------------------------ rotary
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rope_scaling: Optional[dict]):
    """Rotary frequencies [dim / 2] (float32 numpy): ``theta ** (-2 i /
    dim)``, or under YaRN a blend of those and the same over ``factor``,
    by a linear ramp between the dimensions that make ``beta_fast`` and
    ``beta_slow`` rotations over the original context."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rope_scaling:
        return extra.astype(np.float32)
    rs = rope_scaling
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / rs["factor"] * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


# --------------------------------------------------------------- attention
class LatentAttention(Layer):
    """Multi-head latent attention; names as the family's checkpoints."""

    def __init__(self, cfg: XingConfig):
        super().__init__()
        self.cfg = cfg
        H, C = cfg.num_heads, cfg.hidden_size
        init = Normal(0.0, cfg.initializer_range)
        out_init = Normal(0.0, cfg.initializer_range
                          / math.sqrt(2 * cfg.num_layers))
        lin = lambda i, o, w=init: Linear(i, o, weight_attr=w,
                                          bias_attr=False)
        self.q_a_proj = lin(C, cfg.q_lora_rank)
        self.q_a_layernorm = RMSNorm(cfg.q_lora_rank,
                                     epsilon=cfg.rms_norm_eps)
        self.q_b_proj = lin(cfg.q_lora_rank,
                            H * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
        self.kv_a_proj_with_mqa = lin(C, cfg.kv_lora_rank
                                      + cfg.qk_rope_head_dim)
        self.kv_a_layernorm = RMSNorm(cfg.kv_lora_rank,
                                      epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = lin(cfg.kv_lora_rank,
                             H * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = lin(H * cfg.v_head_dim, C, out_init)
        self._inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                       cfg.rope_scaling)

    @jax.named_scope("attention")
    def forward(self, x, cache=None, position_offset=0):
        cfg = self.cfg
        B, L, _ = x.shape
        H, N, R = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        q = q.reshape(B, L, H, N + R)
        q_nope, q_rope = q[..., :N], q[..., N:]
        ckr = self.kv_a_proj_with_mqa(x)
        c = self.kv_a_layernorm(ckr[..., :cfg.kv_lora_rank])
        k_rope = ckr[..., cfg.kv_lora_rank:].reshape(B, L, 1, R)
        # the cache stores the POST-rotation shared key
        q_rope, k_rope = rotary_embed(q_rope, k_rope, cfg.rope_theta,
                                      position_offset,
                                      inv_freq=self._inv_freq)
        w = self.kv_b_proj.weight.reshape(cfg.kv_lora_rank, H,
                                          N + cfg.v_head_dim)
        w_uk, w_uv = w[..., :N], w[..., N:]
        if cache is None:
            out = latent_block_attention(q_nope, q_rope, c, k_rope, w_uk,
                                         w_uv, cfg.attention_scale)
        else:
            out, cache = attend_with_latent_cache(
                q_nope, q_rope, c, k_rope, w_uk, w_uv, cache,
                position_offset, cfg.attention_scale)
        out = self.o_proj(out.reshape(B, L, H * cfg.v_head_dim))
        return out if cache is None else (out, cache)


# ----------------------------------------------------------------- streams
def sinkhorn(m, iters: int, eps: float):
    """``iters`` row-then-column normalisations of positive matrices given
    as ``m[j][i]``, a list of rows of same-shaped arrays (one matrix an
    element): every row sum and then every column sum brought to 1,
    which converges on a doubly stochastic matrix. Returned in the same
    form. Written on the n * n entries as separate arrays, sums as adds:
    nothing but elementwise work on one shape, which the compiler keeps
    in one fusion a loop trip (as reductions and broadcasts over a ``[n,
    n, ...]`` array the 20 steps were 74 launches a sublayer on the TPU).
    Five steps a trip: unrolled whole, the 800 operations of a sublayer
    take the CPU's compiler over a minute a program."""
    n = len(m)

    def step(_, m):
        rows = [sum(m[j]) + eps for j in range(n)]
        m = [[m[j][i] / rows[j] for i in range(n)] for j in range(n)]
        cols = [sum(m[j][i] for j in range(n)) + eps for i in range(n)]
        return [[m[j][i] / cols[i] for i in range(n)] for j in range(n)]

    return jax.lax.fori_loop(0, iters, step, m,
                             unroll=5 if iters % 5 == 0 else 1)


# Initial values the source gives none of (assumed, and listed so in
# benchmarks/configs/xing4.0-29b-a4b.json). The mixers: every gate's
# ``alpha`` 1, so that the dynamic part matters from the first token, and
# ``_RES_DIAG`` on the diagonal of the residual matrix's bias: ``M`` starts
# with about 0.6 on the diagonal, neither the identity nor uniform.
_ALPHA = 1.0
_RES_DIAG = 2.0
# The routed experts of a layer start akin (``ExpertFFN(own_share=)``): one
# drawn expert, and 1/32 of a draw of each expert's own, so the routed
# branch weighs what the shared expert and the attention weigh (its four
# picks add up in step: twice one expert) and WHICH expert a token takes
# moves its output by 1/32 of that. Top-k routing is discontinuous: where
# a token's k-th and (k+1)-th scores lie within 4e-3, bfloat16 rounding
# upstream of the router picks the other expert now and then (most
# positions of a 7-layer model have such a layer; float32 routing inputs
# do not change it), and between experts drawn apart one such pick moved
# that position's logits by up to 0.57 of their spread (rms; 2.6 by the
# largest) and a served token 2.2 under the reference's best, where a
# benchmark's comparison with the float32 reference allows every token
# 0.25 (PERF.md section 6, PR 32: the chip's readings at 1, 1/16, 1/32).
# Which expert a row goes through is held to the reference where the
# inputs are equal and no tie can fall two ways (``chip_smoke.py:
# expert_ffn_check``, experts drawn apart; ``tests/test_xing.py``).
_EXPERT_OWN_SHARE = 1.0 / 32


class _DiagBias(Initializer):
    """The mixer's bias at birth: zeros for the two gates, ``diag`` on the
    diagonal of the residual matrix's part."""

    def __init__(self, n: int, diag: float):
        self.n, self.diag = n, diag

    def __call__(self, key, shape, dtype):
        n = self.n
        b = np.zeros(n * n + 2 * n, np.float32)
        b[2 * n:] = (self.diag * np.eye(n, dtype=np.float32)).reshape(-1)
        return jnp.asarray(b, dtype)


class StreamMixer(Layer):
    """One sublayer's connection to the ``n`` streams: what it reads
    (``H_pre``), how its output is spread (``H_post``) and how the streams
    mix meanwhile (``M``, doubly stochastic), all functions of the token's
    own streams. Token axes are kept LAST in the mixer's arithmetic
    (``m`` is ``[n * n + 2 n, B, L]``), so the small per-token matrices
    lie along the lanes instead of padding a tile each."""

    def __init__(self, cfg: XingConfig):
        super().__init__()
        self.cfg = cfg
        n, C = cfg.hc_mult, cfg.hidden_size
        # phi ~ N(0, 1 / (n C)): m's entries start at unit variance
        self.phi = self.create_parameter(
            (n * C, n * n + 2 * n), attr=Normal(0.0, (n * C) ** -0.5))
        self.alpha = self.create_parameter(
            (3,), attr=Constant(_ALPHA))                   # pre, post, res
        self.bias = self.create_parameter(
            (n * n + 2 * n,), attr=_DiagBias(n, _RES_DIAG))

    @jax.named_scope("streams")
    def pre(self, X):
        """``(h [B, L, C], (H_post [n, B, L], M))`` of streams ``X`` [B, L,
        n, C] float32; ``M[j][i]`` [B, L] as :func:`sinkhorn` returns it."""
        cfg = self.cfg
        n = cfg.hc_mult
        B, L = X.shape[:2]
        f32 = jnp.float32
        with jax.named_scope("hc_pre"):
            flat = X.reshape(B, L, -1)
            inv = jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1)
                                + cfg.rms_norm_eps)                # [B, L]
            m = jnp.einsum("blk,kj->jbl", flat, self.phi.astype(f32),
                           precision=_HIGHEST) * inv
            a = self.alpha.astype(f32)
            b = self.bias.astype(f32)[:, None, None]
            h_pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
            h_post = 2.0 * jax.nn.sigmoid(a[1] * m[n:2 * n] + b[n:2 * n])
            h = sum(h_pre[i][..., None] * X[:, :, i] for i in range(n))
        with jax.named_scope("sinkhorn"):
            res = jnp.clip(a[2] * m[2 * n:] + b[2 * n:],
                           cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max)
            M = sinkhorn([[jnp.exp(res[j * n + i]) for i in range(n)]
                          for j in range(n)],
                         cfg.hc_sinkhorn_iters, cfg.hc_eps)
        return h, (h_post, M)

    @jax.named_scope("streams")
    def post(self, X, y, mix):
        """``X'_j = sum_i M[j, i] X_i + H_post[j] y``."""
        h_post, M = mix
        n = self.cfg.hc_mult
        y = y.astype(jnp.float32)
        with jax.named_scope("hc_post"):
            return jnp.stack(
                [sum(M[j][i][..., None] * X[:, :, i] for i in range(n))
                 + h_post[j][..., None] * y for j in range(n)], axis=2)


class XingBlock(Layer):
    def __init__(self, cfg: XingConfig, index: int):
        super().__init__()
        self.cfg = cfg
        norm = lambda: RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.attn_hc = StreamMixer(cfg)
        self.input_layernorm = norm()
        self.self_attn = LatentAttention(cfg)
        self.ffn_hc = StreamMixer(cfg)
        self.post_attention_layernorm = norm()
        if index < cfg.first_k_dense_replace:
            self.mlp = LlamaMLP(cfg)
        else:
            self.mlp = ExpertFFN(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size,
                experts_held=cfg.experts_held,
                routed_scaling_factor=cfg.routed_scaling_factor,
                init_std=cfg.initializer_range,
                out_init_std=(cfg.initializer_range
                              / math.sqrt(2 * cfg.num_layers)),
                own_share=_EXPERT_OWN_SHARE)

    def forward(self, X, cache=None, position_offset=0):
        """``X`` [B, L, n, C]: the float32 streams."""
        compute = self.self_attn.o_proj.weight.dtype
        h, mix = self.attn_hc.pre(X)
        a = self.self_attn(self.input_layernorm(h).astype(compute),
                           cache=cache, position_offset=position_offset)
        if cache is not None:
            a, cache = a
        X = self.attn_hc.post(X, a, mix)
        h, mix = self.ffn_hc.pre(X)
        X = self.ffn_hc.post(
            X, self.mlp(self.post_attention_layernorm(h).astype(compute)),
            mix)
        return X if cache is None else (X, cache)


class XingModel(Layer):
    def __init__(self, cfg: XingConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=Normal(0.0, cfg.initializer_range))
        index = itertools.count()       # a per-layer pattern: dense first
        self.layers = DecoderBlockList(
            cfg, lambda cfg: XingBlock(cfg, next(index)))
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, cache=None, position_offset=0):
        """Final hidden states [B, L, C] float32 (the streams summed,
        normed), with the updated cache when one is given."""
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids).astype(jnp.float32)
            X = jnp.broadcast_to(
                x[:, :, None, :],
                x.shape[:2] + (self.cfg.hc_mult, x.shape[-1]))
        if cache is None:
            X = self.layers(X)
        else:
            X, cache = self.layers(X, caches=cache,
                                   position_offset=position_offset)
        with jax.named_scope("final_norm"):
            h = self.norm(jnp.sum(X, axis=2))
        return h if cache is None else (h, cache)


class XingForCausalLM(LlamaForCausalLM):
    """LM head model; :class:`LlamaForCausalLM`'s contract over the
    latent, sparse, multi-stream backbone."""

    backbone_cls = XingModel

    def __init__(self, cfg: XingConfig):
        with born_as(cfg.dtype):
            super().__init__(cfg)

    def _logits(self, h):
        return super()._logits(h.astype(self.model.embed_tokens.weight.dtype))

    def cache_spec(self) -> dict:
        """Cache geometry for ``models.kv_cache``: one LATENT entry a
        layer, ``(c [.., 1, kv_lora_rank], k_r [.., 1, qk_rope_head_dim])``."""
        cfg = self.cfg
        return {"num_layers": cfg.num_layers,
                "cache_entries": cfg.num_layers,
                "num_kv_heads": 1,
                "head_dim": cfg.kv_lora_rank,
                "latent": (cfg.kv_lora_rank, cfg.qk_rope_head_dim),
                "max_length": cfg.max_position_embeddings,
                "dtype": cfg.dtype}

    def expert_load_shape(self) -> Tuple[int, int]:
        """``(expert layers, experts routed over)``: what a serving
        engine's expert-load counters are shaped by."""
        cfg = self.cfg
        return (cfg.num_layers - min(cfg.first_k_dense_replace,
                                     cfg.num_layers), cfg.n_routed_experts)

    def lora_spec(self) -> dict:
        return {"target_modules": ("q_a_proj", "q_b_proj",
                                   "kv_a_proj_with_mqa", "kv_b_proj",
                                   "o_proj")}
