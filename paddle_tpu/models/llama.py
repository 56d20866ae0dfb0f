"""Llama decoder-only family: RoPE + RMSNorm + SwiGLU + GQA.

Reference parity: BASELINE.md lists "ERNIE-3.0 / Llama-2-7B, v5p-64,
sharding-stage3 (ZeRO-3-equivalent) pretrain" as a target config; the
reference trains such models through PaddleNLP on the same fleet
machinery as GPT. Here the family is written once against the
TP-annotated layers (``distributed/parallel/mp_layers.py``) and composes
with ZeRO (``distributed/shard.py`` stage 3), sequence parallel, flash
attention, recompute, and the chunked LM loss — the exact knobs the
GPT flagship uses.

TPU-first notes: the attention layer computes its rotary angles in the
program from the positions it is given (a table for every position a
config allows would ride in each program as a constant: 2 x 33.5 MB at
65536 positions of head 128); GQA repeats K/V heads to the query head
count before attention so the Pallas flash kernel (equal-head layout)
serves grouped queries unchanged.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.parallel.mp_layers import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
    parallel_matmul,
)
from ..framework.dtype import get_default_dtype, set_default_dtype
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..nn.layers.norm import RMSNorm
from .lm_utils import (attend_with_cache, causal_attention,
                       constrain_seq as _constrain_seq, repeat_kv)

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "LlamaAttention",
           "LlamaMLP", "rotary_embed", "born_as", "llama_tiny",
           "llama2_7b", "llama_loss_fn", "llama_flops_per_token"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None = MHA; < num_heads = GQA
    intermediate_size: Optional[int] = None  # default: llama 8/3 rule
    max_position_embeddings: int = 4096
    rope_theta: Optional[float] = 10000.0  # None: attention without positions
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False  # llama unties
    use_recompute: bool = False
    recompute_policy: str = None
    use_flash_attention: bool = True
    sequence_parallel: bool = False
    loss_chunk: int = 0
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size is None:
            # llama MLP sizing: 2/3 * 4h rounded up to a multiple of 256
            inter = int(8 * self.hidden_size / 3)
            self.intermediate_size = -(-inter // 256) * 256
        assert self.num_heads % self.num_kv_heads == 0


def llama_tiny(**overrides) -> "LlamaConfig":
    cfg = dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
               num_kv_heads=2, max_position_embeddings=256)
    cfg.update(overrides)
    return LlamaConfig(**cfg)


def llama2_7b(**overrides) -> "LlamaConfig":
    """Llama-2-7B: the BASELINE.md sharding-stage3 target config."""
    cfg = dict(vocab_size=32000, hidden_size=4096, num_layers=32,
               num_heads=32, num_kv_heads=32, intermediate_size=11008,
               max_position_embeddings=4096)
    cfg.update(overrides)
    return LlamaConfig(**cfg)


# ------------------------------------------------------------------ rotary
def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary_embed(q, k, theta: float, position_offset=0, inv_freq=None):
    """Rotary position embedding on [B, L, H, D] (llama rotate-half
    convention; ``q`` and ``k`` may differ in heads). ``position_offset``
    may be a scalar or a per-row ``[B]`` vector (continuous-batching
    decode: each slot rotates at its own position), traced or not. The
    angles of the ``L`` positions are computed here, in float32, so a
    program carries ``D/2`` constants whatever the model's position
    limit: ``theta ** (-2 i / D)``, or ``inv_freq`` [D/2] where a model
    scales its frequencies (YaRN)."""
    L, D = q.shape[1], q.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float32) / D))
    pos = (jnp.asarray(position_offset, jnp.int32).reshape(-1, 1)
           + jnp.arange(L, dtype=jnp.int32)[None, :])        # [1|B, L]
    freqs = pos[..., None].astype(jnp.float32) * inv_freq    # [1|B, L, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]
    c, s = jnp.cos(emb).astype(q.dtype), jnp.sin(emb).astype(q.dtype)
    return q * c + _rotate_half(q) * s, k * c + _rotate_half(k) * s


# GQA head repetition now lives in lm_utils (shared with the KV-cache
# decode path); the private name stays for existing callers
_repeat_kv = repeat_kv


@contextlib.contextmanager
def born_as(dtype):
    """Parameters made inside are drawn in ``dtype`` (``Layer.__init__``
    reads the default type): a model of billions of parameters born in
    float32 and cast afterwards would not fit the chip it is served from."""
    before = get_default_dtype()
    set_default_dtype(dtype)
    try:
        yield
    finally:
        set_default_dtype(before)


# ------------------------------------------------------------------ layers
class LlamaAttention(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.head_dim = cfg.hidden_size // cfg.num_heads
        init = Normal(0.0, cfg.initializer_range)
        out_init = Normal(0.0, cfg.initializer_range
                          / math.sqrt(2 * cfg.num_layers))
        kv_out = cfg.num_kv_heads * self.head_dim
        self.q_proj = ColumnParallelLinear(
            cfg.hidden_size, cfg.hidden_size, weight_attr=init,
            has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(
            cfg.hidden_size, kv_out, weight_attr=init,
            has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(
            cfg.hidden_size, kv_out, weight_attr=init,
            has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, weight_attr=out_init,
            has_bias=False, input_is_parallel=True)

    @jax.named_scope("attention")
    def forward(self, x, cache=None, position_offset=0, cache_entry=None):
        B, L, _ = x.shape
        cfg = self.cfg
        q = self.q_proj(x).reshape(B, L, cfg.num_heads, self.head_dim)
        k = self.k_proj(x).reshape(B, L, cfg.num_kv_heads, self.head_dim)
        v = self.v_proj(x).reshape(B, L, cfg.num_kv_heads, self.head_dim)
        # RoPE turns by position_offset (traced for cached decode steps),
        # so the cache stores POST-rotation keys; a family whose other
        # layers carry the order (jamba.py) says rope_theta=None
        if cfg.rope_theta is not None:
            q, k = rotary_embed(q, k, cfg.rope_theta, position_offset)
        if cache is not None:
            out, cache = attend_with_cache(
                q, k, v, cache, position_offset,
                use_flash=cfg.use_flash_attention, entry=cache_entry)
            return self.o_proj(out.reshape(B, L, cfg.hidden_size)), cache
        groups = cfg.num_heads // cfg.num_kv_heads
        k, v = _repeat_kv(k, groups), _repeat_kv(v, groups)
        out = causal_attention(q, k, v, dropout_p=0.0,
                               training=self.training,
                               use_flash=cfg.use_flash_attention)
        return self.o_proj(out.reshape(B, L, cfg.hidden_size))


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        out_init = Normal(0.0, cfg.initializer_range
                          / math.sqrt(2 * cfg.num_layers))
        self.gate_proj = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, weight_attr=init,
            has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, weight_attr=init,
            has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(
            cfg.intermediate_size, cfg.hidden_size, weight_attr=out_init,
            has_bias=False, input_is_parallel=True)

    @jax.named_scope("mlp")
    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cache=None, position_offset=0):
        if cache is not None:
            a, cache = self.self_attn(self.input_layernorm(x), cache=cache,
                                      position_offset=position_offset)
            x = x + a
            x = x + self.mlp(self.post_attention_layernorm(x))
            return _constrain_seq(x, self.cfg), cache
        x = x + self.self_attn(self.input_layernorm(x))
        x = x + self.mlp(self.post_attention_layernorm(x))
        return _constrain_seq(x, self.cfg)


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        from .lm_utils import DecoderBlockList

        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=Normal(0.0, cfg.initializer_range))
        self.layers = DecoderBlockList(cfg, LlamaBlock)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, cache=None, position_offset=0):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        x = _constrain_seq(x, self.cfg)
        if cache is not None:
            x, cache = self.layers(x, caches=cache,
                                   position_offset=position_offset)
        else:
            x = self.layers(x)
        with jax.named_scope("final_norm"):
            x = self.norm(x)
        return x if cache is None else (x, cache)


class LlamaForCausalLM(Layer):
    """LM head model; same contract as :class:`GPTForCausalLM` (logits, or
    the loss directly when labels are given, chunk-fused when
    ``cfg.loss_chunk > 0``)."""

    backbone_cls = LlamaModel   # a family on these blocks sets its own

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = self.backbone_cls(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size,
                weight_attr=Normal(0.0, cfg.initializer_range),
                has_bias=False, gather_output=False)
        self.parallel_ce = ParallelCrossEntropy()

    @jax.named_scope("lm_head")
    def _logits(self, h):
        if self.cfg.tie_word_embeddings:
            return parallel_matmul(h, self.model.embed_tokens.weight,
                                   transpose_y=True)
        return self.lm_head(h)

    def cache_spec(self) -> dict:
        """Static KV-cache geometry for ``models.kv_cache.init_cache``
        (GQA: the cache stores ``num_kv_heads``, not ``num_heads``)."""
        return {"num_layers": self.cfg.num_layers,
                "cache_entries": self.cfg.num_layers,
                "num_kv_heads": self.cfg.num_kv_heads,
                "head_dim": self.cfg.hidden_size // self.cfg.num_heads,
                "max_length": self.cfg.max_position_embeddings,
                "dtype": self.cfg.dtype}

    def lora_spec(self) -> dict:
        """Default LoRA injection surface for ``paddle_tpu.lora``: the
        split attention projections + the SwiGLU MLP projections of
        every block (``LoraConfig(target_modules=None)`` resolves to
        this)."""
        return {"target_modules": ("q_proj", "k_proj", "v_proj", "o_proj",
                                   "gate_proj", "up_proj", "down_proj")}

    def forward(self, input_ids, labels=None, cache=None, position_offset=0,
                gather_last=None):
        if cache is not None or gather_last is not None:
            from .lm_utils import cached_lm_forward

            return cached_lm_forward(self.model, self._logits, input_ids,
                                     cache, position_offset, gather_last)
        if labels is not None and self.cfg.loss_chunk:
            from .lm_utils import chunked_lm_loss

            return chunked_lm_loss(self.model(input_ids), labels,
                                   self._logits, self.parallel_ce,
                                   chunk=self.cfg.loss_chunk)
        logits = self._logits(self.model(input_ids))
        if labels is None:
            return logits
        return self.loss(logits, labels)

    def generate(self, input_ids, max_new_tokens=32, **kwargs):
        """Compiled KV-cache generation — see
        :func:`paddle_tpu.models.generation.generate`."""
        from .generation import generate

        return generate(self, input_ids, max_new_tokens, **kwargs)

    def loss(self, logits, labels):
        shift_logits = logits[:, :-1, :]
        shift_labels = jnp.asarray(labels)[:, 1:]
        return jnp.mean(self.parallel_ce(shift_logits, shift_labels))


def llama_loss_fn(model: LlamaForCausalLM):
    def loss_fn(outputs, batch):
        return model.loss(outputs, batch[1])

    return loss_fn


def llama_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """6ND + attention term (PaLM formula), GQA-aware."""
    head_dim = cfg.hidden_size // cfg.num_heads
    kv = cfg.num_kv_heads * head_dim
    n_params = (
        cfg.vocab_size * cfg.hidden_size
        * (1 if cfg.tie_word_embeddings else 2)
        + cfg.num_layers * (
            cfg.hidden_size * cfg.hidden_size * 2      # q + o
            + cfg.hidden_size * kv * 2                  # k + v
            + 3 * cfg.hidden_size * cfg.intermediate_size  # swiglu
            + 2 * cfg.hidden_size))                     # rmsnorm
    attn = 12 * cfg.num_layers * cfg.hidden_size * seq_len
    return 6.0 * n_params + attn
