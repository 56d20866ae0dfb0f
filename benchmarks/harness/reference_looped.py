"""Plain reference of a looped decoder (a "universal transformer" stack:
``L`` sandwich-normalised blocks applied ``T`` times to every token with
the same weights, RMSNorm, rotary positions, SwiGLU, grouped-query causal
attention, an exit gate over the steps): forward pass, shifted next-token
loss and exit distribution in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no loop
primitive, no batching tricks, and nothing imported from the program.

    x = E[ids]
    for t in range(T):
        for l in range(L):
            x = x + rms(attn_l(rms(x, g1_l)), g2_l)
            x = x + rms(mlp_l(rms(x, g3_l)), g4_l)
        x = h_t = rms(x, g_final)
        lam_t = sigmoid(w_gate . h_t + b_gate)
    p_t = lam_t prod_{j<t}(1 - lam_j) for t < T-1;  p_{T-1} = prod_{j<T-1}(1 - lam_j)
    logits = W_head h_{t*}, t* the first t whose cumulative p reaches
    ``early_exit_threshold`` (the last step if none before it does)

``params`` is the flat ``{name: array}`` dict of the program's model
(``framework.jit.param_state``), in whatever float type the system holds
them; a layer's weights are upcast to float32 inside that layer's call, so
the reference computes in float32 on exactly the weights the system
computes with and no second full-size copy of them is ever resident.

Departures from the source (the published ``config.json`` fixes the sizes,
not the wiring; the configuration file lists these under ``assumed``):
the placement of the four norms of a block as above; the final norm
applied after every step and its output fed to the next; no bias in the
seven projections, a bias in the gate. The loss is the cross entropy of
the selected step's logits, not the source's training objective over the
exit distribution. Initial values are the program's, not this file's: it
computes with whatever weights and gains it is handed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g.astype(_F32)


def _rotate(x, cos, sin):
    """Rotary embedding on [B, L, heads, D], rotate-half convention: the
    pair (i, i + D/2) turns by ``position * theta ** (-2 i / D)``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def _angles(length: int, head_dim: int, theta: float):
    inv_freq = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64)
                         / head_dim)
    a = np.outer(np.arange(length, dtype=np.float64), inv_freq)
    a = np.concatenate([a, a], axis=-1)
    return np.cos(a).astype(np.float32), np.sin(a).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def _block(x, p, cos, sin, heads, kv_heads, eps):
    """One sandwich-normalised block on ``x`` [B, L, H]; ``p`` holds the
    block's seven projections and four gains under the program's names."""
    B, L, H = x.shape
    D = p["self_attn.q_proj.weight"].shape[1] // heads
    h = _rms(x, p["input_layernorm.weight"], eps)
    q = (h @ p["self_attn.q_proj.weight"].astype(_F32)).reshape(B, L, heads, D)
    k = (h @ p["self_attn.k_proj.weight"].astype(_F32)).reshape(
        B, L, kv_heads, D)
    v = (h @ p["self_attn.v_proj.weight"].astype(_F32)).reshape(
        B, L, kv_heads, D)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    # each key/value head serves heads // kv_heads query heads
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    a = a.reshape(B, L, heads * D) @ p["self_attn.o_proj.weight"].astype(_F32)
    x = x + _rms(a, p["input_layernorm_2.weight"], eps)
    h = _rms(x, p["post_attention_layernorm.weight"], eps)
    m = (jax.nn.silu(h @ p["mlp.gate_proj.weight"].astype(_F32))
         * (h @ p["mlp.up_proj.weight"].astype(_F32)))
    m = m @ p["mlp.down_proj.weight"].astype(_F32)
    return x + _rms(m, p["post_attention_layernorm_2.weight"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _end_of_step(x, g, w_gate, b_gate, eps):
    h = _rms(x, g, eps)
    lam = jax.nn.sigmoid(h @ w_gate.astype(_F32)[:, 0] + b_gate.astype(_F32)[0])
    return h, lam


def _steps(params: dict, cfg: dict, ids):
    """Every step's normalised state [T, B, L, H] and gate [T, B, L]."""
    heads = cfg["num_heads"]
    kv_heads = cfg.get("num_kv_heads") or heads
    eps = float(cfg["rms_norm_eps"])
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = _angles(ids.shape[1], cfg["hidden_size"] // heads,
                       float(cfg["rope_theta"]))
    x = params["model.embed_tokens.weight"].astype(_F32)[ids]
    states, gates = [], []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_layers"]):
            prefix = f"model.layers.{i}."
            p = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
            x = _block(x, p, cos, sin, heads=heads, kv_heads=kv_heads, eps=eps)
        x, lam = _end_of_step(x, params["model.norm.weight"],
                              params["model.early_exit_gate.weight"],
                              params["model.early_exit_gate.bias"], eps=eps)
        states.append(x)
        gates.append(lam)
    return jnp.stack(states), jnp.stack(gates)


def _pdf(gates):
    """[T, B, L] gates -> [T, B, L] exit distribution (sums to 1 over T)."""
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay], axis=0)
    return jnp.concatenate([gates[:-1] * before[:-1], before[-1:]], axis=0)


def exit_pdf(params: dict, cfg: dict, ids) -> jax.Array:
    """``ids`` [B, L] int -> exit distribution [B, L, T] float32."""
    with jax.default_matmul_precision("highest"):
        return jnp.moveaxis(_pdf(_steps(params, cfg, ids)[1]), 0, -1)


def logits(params: dict, cfg: dict, ids) -> jax.Array:
    """``ids`` [B, L] int -> logits [B, L, vocab] float32, of the step at
    which each position exits."""
    with jax.default_matmul_precision("highest"):
        states, gates = _steps(params, cfg, ids)
        # the cumulative probability only rises, so the first step that
        # reaches the threshold is the number of steps that fall short of
        # it; the last step's is 1 by construction (in floating point the
        # sum may fall an ulp short), so it is not asked and takes the rest
        short = jnp.cumsum(_pdf(gates), axis=0)[:-1] \
            < float(cfg.get("early_exit_threshold", 1.0))
        t_star = jnp.sum(short, axis=0)                        # [B, L]
        h = jnp.take_along_axis(states, t_star[None, :, :, None], axis=0)[0]
        if cfg.get("tie_word_embeddings"):
            return h @ params["model.embed_tokens.weight"].astype(_F32).T
        return h @ params["lm_head.weight"].astype(_F32)


def loss(params: dict, cfg: dict, ids, labels) -> jax.Array:
    """Mean next-token cross entropy: position t predicts ``labels[t+1]``."""
    lg = logits(params, cfg, ids)[:, :-1]
    y = jnp.asarray(labels, jnp.int32)[:, 1:]
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, y[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
