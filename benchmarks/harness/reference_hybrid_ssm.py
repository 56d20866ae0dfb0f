"""Plain reference of a hybrid state-space / attention decoder (AI21's Jamba
family as this repository reads its ``config.json``): logits in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
No kernel, no cache, no state entry, no bucket, no chunk, nothing imported
from the program. Layer ``i`` is an attention layer where ``i %
attn_layer_period == attn_layer_offset`` and a Mamba-1 layer otherwise;
every layer's FFN is a dense SwiGLU. Per token ``t`` (``rms(x, g) = x
rsqrt(mean x^2 + eps) g``; ``d = mamba_expand * hidden_size`` channels,
``n = mamba_d_state`` states, ``K = mamba_d_conv``):

    x <- x + Mixer_i(rms(x, g_in));   x <- x + W_down(silu(W_gate y) * W_up y),  y = rms(x, g_ff)
    logits = E rms(x, g_f)                                       (E the embedding: tied)

    Mamba:  [u' | z] = x W_in
            u_t = silu(sum_{k<K} w_c[k] * u'_{t-(K-1)+k} + b_c)        (u' before the sequence is 0)
            [dl | B | C] = u_t W_x;  dl, B, C <- rms(dl, g_dl), rms(B, g_B), rms(C, g_C)
            D_t = softplus(dl W_dt + b_dt);   A = -exp(A_log)
            h_t[s, c] = exp(D_t[c] A[s, c]) h_{t-1}[s, c] + D_t[c] u_t[c] B_t[s],    h_{-1} = 0
            y_t[c] = sum_s h_t[s, c] C_t[s] + D[c] u_t[c];   out_t = (y_t * silu(z_t)) W_out
    Attention: q = x W_q (H heads), k = x W_k, v = x W_v (Hkv heads, each
            shared by H / Hkv query heads), p = causal softmax(q . k / sqrt(head)),
            out = concat_h(p v) W_o.  No rotary, no position table.

The recurrence runs ONE POSITION AT A TIME (``lax.scan`` over ``t`` with
``h`` as the carry), exactly as the equations stand. Attention is one
causal softmax over the whole sequence, a block of ``BLOCK`` queries at a
time against all keys, so that an 8192-position pass fits beside the
resident model; everything per token runs on the whole sequence at once.

``params`` is the flat ``{name: array}`` dict of the program's model
(``framework.jit.param_state``) in whatever float type the system holds
it; a layer's weights are upcast to float32 inside the call that uses
them, so the reference computes in float32 on exactly the weights the
system computes with. The head is applied a slice of the vocabulary at a
time and collected on the host: ``logits`` returns a numpy array.

Departures and assumptions. The published ``config.json`` fixes the sizes
and the layer pattern. Stored with the inner width last, as the program
stores them: ``A_log`` is ``[n, d]`` and the convolution's weight ``[K,
d]`` (a checkpoint of the family holds ``[d, n]`` and ``[d, 1, K]``);
``conv_weight[k]`` multiplies the input ``K - 1 - k`` positions back. The
three inner norms use ``rms_norm_eps``. ``num_experts`` is 1: the
``expert_layer_*`` keys select nothing. No bias but the convolution's and
``dt_proj``'s. Initial values are the program's: this file computes with
whatever it is handed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
BLOCK = 512            # queries a call of the attention
VOCAB_SLICE = 16384    # columns of the head a call


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g.astype(_F32)


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def is_attention_layer(cfg: dict, index: int) -> bool:
    return (index % int(cfg["attn_layer_period"])
            == int(cfg["attn_layer_offset"]))


# ------------------------------------------------------------------- mamba
@functools.partial(jax.jit, static_argnames=("rank", "states", "eps"))
def mamba_mixer(x, p, rank, states, eps, h0=None):
    """The mixer on one sequence's normed inputs ``x`` [L, C] float32;
    returns ``(out [L, C], h_L [n, d], u' [L, d])``: the last two are
    what a cache would carry (the state after the last position and the
    convolution's inputs). ``h0`` defaults to zeros."""
    d, K = p["conv_bias"].shape[0], p["conv_weight"].shape[0]
    uz = x @ p["in_proj.weight"].astype(_F32)
    u_pre, z = uz[:, :d], uz[:, d:]
    padded = jnp.concatenate([jnp.zeros((K - 1, d), _F32), u_pre])
    w = p["conv_weight"].astype(_F32)
    u = jax.nn.silu(sum(w[k] * padded[k:k + x.shape[0]] for k in range(K))
                    + p["conv_bias"].astype(_F32))
    dbc = u @ p["x_proj.weight"].astype(_F32)
    dl = _rms(dbc[:, :rank], p["dt_layernorm.weight"], eps)
    Bm = _rms(dbc[:, rank:rank + states], p["b_layernorm.weight"], eps)
    Cm = _rms(dbc[:, rank + states:], p["c_layernorm.weight"], eps)
    delta = jax.nn.softplus(dl @ p["dt_proj.weight"].astype(_F32)
                            + p["dt_proj.bias"].astype(_F32))
    A = -jnp.exp(p["A_log"].astype(_F32))                      # [n, d]

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = jnp.exp(d_t[None, :] * A) * h + (d_t * u_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    h, y = jax.lax.scan(
        step, jnp.zeros((states, d), _F32) if h0 is None else h0,
        (u, delta, Bm, Cm))
    y = y + p["D"].astype(_F32) * u
    return (y * jax.nn.silu(z)) @ p["out_proj.weight"].astype(_F32), h, u_pre


# --------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnames=("heads", "kv_heads"))
def _qkv(x, p, heads, kv_heads):
    L = x.shape[0]
    return ((x @ p["q_proj.weight"].astype(_F32)).reshape(L, heads, -1),
            (x @ p["k_proj.weight"].astype(_F32)).reshape(L, kv_heads, -1),
            (x @ p["v_proj.weight"].astype(_F32)).reshape(L, kv_heads, -1))


@jax.jit
def _attend(q, first, k, v):
    """Queries ``q`` [P, H, D] at positions ``first ...`` against all keys
    [S, Hkv, D] under the causal mask, grouped: [P, H * D]."""
    P, H, D = q.shape
    S, Hkv = k.shape[0], k.shape[1]
    qg = q.reshape(P, Hkv, H // Hkv, D)
    s = jnp.einsum("pkgd,skd->kgps", qg, k) / jnp.sqrt(_F32(D))
    seen = jnp.arange(S)[None, :] <= (first + jnp.arange(P))[:, None]
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("kgps,skd->pkgd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(P, H * D)


def attention_mixer(x, p, cfg):
    heads = int(cfg["num_heads"])
    kv_heads = int(cfg.get("num_kv_heads") or heads)
    q, k, v = _qkv(x, p, heads=heads, kv_heads=kv_heads)
    o = jnp.concatenate([_attend(q[a:a + BLOCK], a, k, v)
                         for a in range(0, x.shape[0], BLOCK)])
    return _project(o, p["o_proj.weight"])


# ------------------------------------------------------------------- model
@jax.jit
def _project(x, w):
    return x @ w.astype(_F32)


@jax.jit
def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(_F32)) * (x @ up.astype(_F32))) \
        @ down.astype(_F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, eps):
    return _rms(x, g, eps)


@functools.partial(jax.jit, static_argnames=("first", "width"))
def _head_slice(h, w, first, width):
    return h @ jax.lax.slice_in_dim(w, first, first + width,
                                    axis=1).astype(_F32)


def forward(params: dict, cfg: dict, row, states=None):
    """Final normed hidden states [L, C] of one sequence ``row`` [L].
    ``states``, a list, receives per Mamba layer ``(h_L [n, d], u' [L,
    d])``: what a cache's state entry would hold after the sequence."""
    eps = float(cfg["rms_norm_eps"])
    x = params["model.embed_tokens.weight"][np.asarray(row)].astype(_F32)
    for i in range(int(cfg["num_layers"])):
        p = _sub(params, f"model.layers.{i}.")
        y = _norm(x, p["input_layernorm.weight"], eps=eps)
        if is_attention_layer(cfg, i):
            x = x + attention_mixer(y, _sub(p, "self_attn."), cfg)
        else:
            out, h, u_pre = mamba_mixer(
                y, _sub(p, "mamba."), rank=int(cfg["mamba_dt_rank"]),
                states=int(cfg["mamba_d_state"]), eps=eps)
            x = x + out
            if states is not None:
                states.append((np.asarray(h), np.asarray(u_pre)))
        x = x + _swiglu(_norm(x, p["pre_ff_layernorm.weight"], eps=eps),
                        p["feed_forward.gate_proj.weight"],
                        p["feed_forward.up_proj.weight"],
                        p["feed_forward.down_proj.weight"])
    return _norm(x, params["model.final_layernorm.weight"], eps=eps)


def logits(params: dict, cfg: dict, ids, states=None) -> np.ndarray:
    """``ids`` [B, L] int -> logits [B, L, vocab] float32, on the host.
    ``states``, a list, receives per sequence :func:`forward`'s list."""
    ids = np.asarray(ids, np.int32)
    w = (params["model.embed_tokens.weight"].T
         if cfg.get("tie_word_embeddings", True)
         else params["lm_head.weight"])
    vocab = w.shape[1]
    out = np.empty(ids.shape + (vocab,), np.float32)
    with jax.default_matmul_precision("highest"):
        for b, row in enumerate(ids):
            got = None if states is None else []
            h = forward(params, cfg, row, got)
            if states is not None:
                states.append(got)
            for first in range(0, vocab, VOCAB_SLICE):
                width = min(VOCAB_SLICE, vocab - first)
                out[b, :, first:first + width] = np.asarray(
                    _head_slice(h, w, first=first, width=width))
    return out
