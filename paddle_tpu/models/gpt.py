"""GPT decoder-only language model — the flagship pretrain config.

Reference parity: the BASELINE north-star is PaddleNLP's GPT-3 1.3B hybrid
DP+MP pretrain (BASELINE.md). The reference implements the parallel pieces as
hand-written collective layers (``fleet/layers/mpu/mp_layers.py``) plus fused
CUDA attention (``paddle/fluid/operators/fused/fused_attention_op.cu``); here
the same model is written once against TP-annotated layers and GSPMD derives
the collectives, while attention dispatches to the Pallas flash kernel on TPU.

Parallelism knobs (all composable, set on :class:`GPTConfig`):
- ``mp``: tensor parallel via Column/RowParallelLinear + VocabParallelEmbedding
- ``dp``/``sdp``: batch sharding + ZeRO via DistributedTrainStep
- ``sp``: sequence parallel — activations sharded over the sequence dim
  between blocks (Ulysses/ring attention in ``parallel/sequence_parallel.py``)
- ``recompute``: activation checkpointing per block (jax.checkpoint)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..nn.layers.norm import LayerNorm
from ..nn.layers.common import Dropout
from ..distributed.parallel.mp_layers import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
    parallel_matmul,
)
from ..distributed.parallel.recompute import recompute_wrap


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: Optional[int] = None  # default 4*hidden
    max_position_embeddings: int = 2048
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_recompute: bool = False
    # recompute only the attention sublayer (drops the [B, H, L, L] softmax
    # stash from saved activations; MLP activations stay resident). The
    # cheap middle ground between no-remat and per-block remat on chips
    # where the XLA attention path is used
    recompute_attn_only: bool = False
    # jax.checkpoint policy name for recompute (see parallel/recompute.py
    # POLICIES): "save_dots_no_batch" keeps matmul outputs and recomputes
    # only elementwise/norm ops — a fraction of full-remat's FLOP cost
    recompute_policy: str = None
    use_flash_attention: bool = True
    sequence_parallel: bool = False  # shard activations over "sp" between blocks
    # fused head+CE over sequence chunks of this size (0 = off): the full
    # [B, L, vocab] logits never materialize (see chunked_lm_loss)
    loss_chunk: int = 0
    dtype: str = "float32"

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size


def gpt_tiny(**overrides) -> "GPTConfig":
    cfg = dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
               max_position_embeddings=256)
    cfg.update(overrides)
    return GPTConfig(**cfg)


def gpt_1p3b(**overrides) -> "GPTConfig":
    """GPT-3 1.3B: the BASELINE.md v5p-32 target config."""
    cfg = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
               max_position_embeddings=2048)
    cfg.update(overrides)
    return GPTConfig(**cfg)


# shared decoder plumbing lives in lm_utils; legacy names kept for callers
from .lm_utils import (attend_with_cache, causal_attention,  # noqa: E402
                       constrain_seq as _constrain_seq)


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        init = Normal(0.0, cfg.initializer_range)
        # fused qkv, column-split over mp (each mp shard owns whole heads)
        self.qkv_proj = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, weight_attr=init,
            has_bias=True, gather_output=False)
        self.out_proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size,
            weight_attr=Normal(0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)),
            has_bias=True, input_is_parallel=True)

    @jax.named_scope("attention")
    def forward(self, x, cache=None, position_offset=0):
        B, L, _ = x.shape
        qkv = self.qkv_proj(x)  # [B, L, 3*H*D] (mp-sharded feature dim)
        qkv = qkv.reshape(B, L, 3, self.num_heads, self.head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cache is not None:
            out, cache = attend_with_cache(
                q, k, v, cache, position_offset,
                use_flash=self.cfg.use_flash_attention)
            out = out.reshape(B, L, self.num_heads * self.head_dim)
            return self.out_proj(out), cache
        out = causal_attention(
            q, k, v, dropout_p=self.cfg.attention_dropout_prob,
            training=self.training, use_flash=self.cfg.use_flash_attention)
        out = out.reshape(B, L, self.num_heads * self.head_dim)
        return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        self.fc_in = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, weight_attr=init,
            has_bias=True, gather_output=False)
        self.fc_out = RowParallelLinear(
            cfg.intermediate_size, cfg.hidden_size,
            weight_attr=Normal(0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)),
            has_bias=True, input_is_parallel=True)

    @jax.named_scope("mlp")
    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTBlock(Layer):
    """Pre-LN transformer decoder block."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.ln_1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln_2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.mlp = GPTMLP(cfg)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, cache=None, position_offset=0):
        if cache is not None:
            a, cache = self.attn(self.ln_1(x), cache=cache,
                                 position_offset=position_offset)
            x = x + self.dropout(a)
            x = x + self.dropout(self.mlp(self.ln_2(x)))
            return _constrain_seq(x, self.cfg), cache
        attn = self.attn
        if self.cfg.recompute_attn_only and not self.cfg.use_recompute:
            attn = recompute_wrap(self.attn)
        x = x + self.dropout(attn(self.ln_1(x)))
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        return _constrain_seq(x, self.cfg)


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=Normal(0.0, cfg.initializer_range))
        self.position_embeddings = self.create_parameter(
            (cfg.max_position_embeddings, cfg.hidden_size),
            default_initializer=Normal(0.0, cfg.initializer_range))
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    @jax.named_scope("embed")
    def forward(self, input_ids, position_offset=0):
        L = input_ids.shape[1]
        h = self.word_embeddings(input_ids)
        if getattr(position_offset, "ndim", 0) == 1:
            # per-row offsets [B] (continuous-batching decode: every slot
            # sits at its own position): gather rows [B, L, H]
            idx = (jnp.asarray(position_offset, jnp.int32)[:, None]
                   + jnp.arange(L, dtype=jnp.int32)[None, :])
            pos = jnp.take(self.position_embeddings, idx, axis=0)
        else:
            pos = jax.lax.dynamic_slice_in_dim(
                self.position_embeddings, position_offset, L, axis=0)
        return self.dropout(h + pos)


class GPTModel(Layer):
    """Embeddings + N decoder blocks + final LN. Returns hidden states."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.h = _BlockList(cfg)
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids, cache=None, position_offset=0):
        x = self.embeddings(input_ids, position_offset=position_offset)
        x = _constrain_seq(x, self.cfg)
        if cache is not None:
            x, cache = self.h(x, caches=cache,
                              position_offset=position_offset)
        else:
            x = self.h(x)
        with jax.named_scope("final_norm"):
            x = self.ln_f(x)
        return x if cache is None else (x, cache)


def _BlockList(cfg: GPTConfig):
    from .lm_utils import DecoderBlockList

    return DecoderBlockList(cfg, GPTBlock)


class GPTForCausalLM(Layer):
    """LM head model. ``forward`` returns logits; ``loss`` computes shifted
    next-token cross entropy (the pretrain objective)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size,
                weight_attr=Normal(0.0, cfg.initializer_range),
                has_bias=False, gather_output=False)
        self.parallel_ce = ParallelCrossEntropy()

    def _head_weight(self):
        if self.cfg.tie_word_embeddings:
            return self.gpt.embeddings.word_embeddings.weight
        return None

    @jax.named_scope("lm_head")
    def _logits(self, h):
        if self.cfg.tie_word_embeddings:
            return parallel_matmul(h, self._head_weight(), transpose_y=True)
        return self.lm_head(h)

    def cache_spec(self) -> dict:
        """Static KV-cache geometry for ``models.kv_cache.init_cache``."""
        return {"num_layers": self.cfg.num_layers,
                "cache_entries": self.cfg.num_layers,
                "num_kv_heads": self.cfg.num_heads,
                "head_dim": self.cfg.hidden_size // self.cfg.num_heads,
                "max_length": self.cfg.max_position_embeddings,
                "dtype": self.cfg.dtype}

    def lora_spec(self) -> dict:
        """Default LoRA injection surface for ``paddle_tpu.lora``: the
        fused attention projections + both MLP projections of every
        block (``LoraConfig(target_modules=None)`` resolves to this)."""
        return {"target_modules": ("qkv_proj", "out_proj",
                                   "fc_in", "fc_out")}

    def forward(self, input_ids, labels=None, cache=None, position_offset=0,
                gather_last=None):
        """Logits when ``labels`` is None; otherwise the LM loss directly —
        via the memory-fused chunked path when ``cfg.loss_chunk > 0`` (the
        full [B, L, vocab] logits tensor never exists; see
        ``chunked_lm_loss``).

        With ``cache`` (per-layer ``(k, v)`` pairs from
        ``models.kv_cache.init_cache``) runs the cached-decode path and
        returns ``(logits, new_cache)``. ``gather_last`` (a traced scalar
        index) slices the hidden states to that single position BEFORE the
        head projection, so serving never materializes [B, L, vocab]."""
        if cache is not None or gather_last is not None:
            from .lm_utils import cached_lm_forward

            return cached_lm_forward(self.gpt, self._logits, input_ids,
                                     cache, position_offset, gather_last)
        if labels is not None and self.cfg.loss_chunk:
            return self.chunked_lm_loss(self.gpt(input_ids), labels,
                                        chunk=self.cfg.loss_chunk)
        logits = self._logits(self.gpt(input_ids))
        if labels is None:
            return logits
        return self.loss(logits, labels)

    def generate(self, input_ids, max_new_tokens=32, **kwargs):
        """Compiled KV-cache generation — see
        :func:`paddle_tpu.models.generation.generate`."""
        from .generation import generate

        return generate(self, input_ids, max_new_tokens, **kwargs)

    @jax.named_scope("loss_head")
    def loss(self, logits, labels):
        """Shifted LM loss: predict token t+1 from prefix ..t."""
        shift_logits = logits[:, :-1, :]
        shift_labels = jnp.asarray(labels)[:, 1:]
        per_tok = self.parallel_ce(shift_logits, shift_labels)
        return jnp.mean(per_tok)

    def chunked_lm_loss(self, h, labels, chunk=256):
        """Head-projection + softmax-CE fused over sequence chunks: the
        [B, L, vocab] logits tensor (the single largest HBM allocation in
        GPT pretrain — e.g. 1.5 GB per materialization at B=16, L=1024,
        V=50304) is never formed. Shared machinery in
        :func:`..models.lm_utils.chunked_lm_loss`."""
        from .lm_utils import chunked_lm_loss

        w = self._head_weight()

        def logits_fn(h_c):
            if self.cfg.tie_word_embeddings:
                return parallel_matmul(h_c, w, transpose_y=True)
            return self.lm_head(h_c)

        return chunked_lm_loss(h, labels, logits_fn, self.parallel_ce,
                               chunk=chunk)

    def forward_with_loss(self, input_ids, labels):
        return self.forward(input_ids, labels)


def gpt_loss_fn(model: GPTForCausalLM):
    """loss_fn for TrainStep/DistributedTrainStep on (input_ids, labels)
    batches."""

    def loss_fn(outputs, batch):
        return model.loss(outputs, batch[1])

    return loss_fn


def gpt_flops_per_token(cfg: GPTConfig, seq_len: int) -> float:
    """Model FLOPs per token for MFU accounting (fwd+bwd, 6ND + attention
    term — the standard PaLM-paper formula)."""
    n_params = (
        cfg.vocab_size * cfg.hidden_size  # embeddings (tied head reused)
        + cfg.max_position_embeddings * cfg.hidden_size
        + cfg.num_layers * (
            4 * cfg.hidden_size * cfg.hidden_size  # qkv + out
            + 2 * cfg.hidden_size * cfg.intermediate_size  # mlp
            + 4 * cfg.hidden_size)  # ln/bias approx
    )
    attn = 12 * cfg.num_layers * cfg.hidden_size * seq_len
    return 6.0 * n_params + attn
