"""Plain reference of the GPT decoder (Brown et al. 2020; pre-LN blocks,
learned positions, tanh GELU, tied head): forward pass and shifted
next-token loss in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
batching tricks, and nothing imported from the program.

``params`` is the flat ``{name: array}`` dict of the program's model
(``framework.jit.param_state``), in whatever float type the system holds
them; every leaf is upcast to float32 where it is used, so the reference
computes in float32 on exactly the weights the system computes with and
no second full-size copy of them is ever resident.

Departures from the paper: none in the mathematics. GPT-3 alternates
dense and locally banded sparse attention; ``models/gpt.py`` and this
file are dense in every layer, which the configuration files state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(_F32) + b.astype(_F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("num_heads", "eps"))
def _block(x, p, num_heads, eps):
    """One pre-LN block on ``x`` [B, L, H]; ``p`` holds the block's eight
    weights and four biases under the program's names."""
    B, L, H = x.shape
    D = H // num_heads
    h = _layer_norm(x, p["ln_1.weight"], p["ln_1.bias"], eps)
    qkv = h @ p["attn.qkv_proj.weight"].astype(_F32) \
        + p["attn.qkv_proj.bias"].astype(_F32)
    # the fused projection's columns run [q | k | v], each [heads, D]
    q, k, v = jnp.moveaxis(qkv.reshape(B, L, 3, num_heads, D), 2, 0)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(B, L, H) @ p["attn.out_proj.weight"].astype(_F32) \
        + p["attn.out_proj.bias"].astype(_F32)
    h = _layer_norm(x, p["ln_2.weight"], p["ln_2.bias"], eps)
    h = _gelu_tanh(h @ p["mlp.fc_in.weight"].astype(_F32)
                   + p["mlp.fc_in.bias"].astype(_F32))
    return x + h @ p["mlp.fc_out.weight"].astype(_F32) \
        + p["mlp.fc_out.bias"].astype(_F32)


@jax.jit
def _embed(ids, wte, wpe):
    L = ids.shape[1]
    return wte.astype(_F32)[ids] + wpe.astype(_F32)[:L][None]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, w, b, wte, eps):
    return _layer_norm(x, w, b, eps) @ wte.astype(_F32).T


def logits(params: dict, cfg: dict, ids) -> jax.Array:
    """``ids`` [B, L] int -> logits [B, L, vocab] float32."""
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(ids, jnp.int32),
                   params["gpt.embeddings.word_embeddings.weight"],
                   params["gpt.embeddings.position_embeddings"])
        for i in range(cfg["num_layers"]):
            prefix = f"gpt.h.{i}."
            p = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
            x = _block(x, p, num_heads=cfg["num_heads"], eps=eps)
        return _head(x, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
                     params["gpt.embeddings.word_embeddings.weight"], eps=eps)


def loss(params: dict, cfg: dict, ids, labels) -> float:
    """Mean next-token cross entropy: position t predicts ``labels[t+1]``.
    One sequence at a time, so the [L, vocab] logits of a single row are
    the largest thing alive."""
    total, count = 0.0, 0
    for row_ids, row_labels in zip(ids, labels):
        lg = logits(params, cfg, row_ids[None])[0, :-1]
        y = jnp.asarray(row_labels, jnp.int32)[1:]
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, y[:, None], axis=-1)[:, 0]
        total += float(jnp.sum(nll))
        count += int(y.shape[0])
    return total / count
