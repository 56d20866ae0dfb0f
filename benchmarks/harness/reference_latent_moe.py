"""Plain reference of a latent-attention, sparse-expert decoder whose
residual path is ``n`` streams mixed by a doubly stochastic matrix
(Xing4.0-29B-A4B's forward pass as this repository reads it): logits in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
No kernel, no cache, no grouped matmul, no absorbed projection, nothing
imported from the program. Per token (``rms(x, g) = x rsqrt(mean x^2 + eps)
g``; n streams ``X`` [n, C]):

    X = [E[id]] * n
    for each block, for F in (attention, FFN) with its own g, phi, alpha, b:
        x~ = vec(X);  m = (x~ phi) rsqrt(mean x~^2 + eps)
        H_pre = sigmoid(a0 m[:n] + b[:n]);  H_post = 2 sigmoid(a1 m[n:2n] + b[n:2n])
        M = exp(clip(a2 mat(m[2n:]) + mat(b[2n:]), lo, hi))
        iters times: M /= rowsum M + hc_eps;  M /= colsum M + hc_eps
        h = sum_i H_pre[i] X_i;  y = F(rms(h, g));  X'_j = sum_i M[j,i] X_i + H_post[j] y
    logits = W_head rms(sum_i X_i, g_f)

    attention: c_q = rms(x W_dq, g_q);  [q_n | q_r] = c_q W_uq per head
               [c' | k_r'] = x W_dkv;  c = rms(c', g_kv);  q_r, k_r = rope(q_r, k_r')
               [k_n | v] = c W_ukv per head               # keys and values DECOMPRESSED
               p = causal softmax((q_n . k_n + q_r . k_r) s);  out = concat_h(p v) W_o
    FFN, leading layers: W_down(silu(W_gate x) * W_up x)
    FFN, the others: sigma = sigmoid(x W_g); the k largest of sigma + b_corr picked;
               w = sigma[picked] / (sum + 1e-20) * routed_scaling_factor
               y = sum over the experts e, ONE AT A TIME, of w_e(token) E_e(x), + E_shared(x)

``params`` is the flat ``{name: array}`` dict of the program's model
(``framework.jit.param_state``) in whatever float type the system holds
it; a layer's (an expert's, a slice of the head's) weights are upcast to
float32 inside the call that uses them, so the reference computes in
float32 on exactly the weights the system computes with and no second
copy of the model is ever resident. So that an 8192-position pass fits
beside an 11 GB model on a 16 GB chip, the streams are kept as blocks of
``BLOCK`` positions, everything per token runs a block at a time, a query
block attends to all keys under the causal mask, and the head is applied
a slice of the vocabulary at a time and collected on the host:
``logits`` returns a numpy array.

``cfg["experts_held"] = (first, count)``, where given, is the share of
the experts this chip holds: the others' part of the sum is left out, as
in the program.

Departures and assumptions (the published ``config.json`` fixes sizes and
the five ``hc_*`` / ``mhc_*`` numbers, not the wiring): the streams start
as ``n`` copies of the embedding and are read out by their sum; ``eps`` of
the mixer's normaliser is ``rms_norm_eps``; the clamp applies to the
exponent and ``hc_eps`` to each normaliser's denominator, rows first;
rotate-half rotary convention on the ``qk_rope_head_dim`` part; YaRN's
cos/sin factor ``mscale / mscale_all_dim`` is 1 and is not applied; no
bias in any projection; ``n_group = topk_group = 1`` (no grouping of the
experts); the multi-token-prediction layer is not part of the forward
pass. Initial values are the program's: this file computes with whatever
it is handed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
BLOCK = 256            # positions a call
VOCAB_SLICE = 16384    # columns of the head a call


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g.astype(_F32)


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


# ----------------------------------------------------------------- streams
@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "hc_eps",
                                             "lo", "hi"))
def _mix_pre(X, phi, alpha, bias, g, n, iters, eps, hc_eps, lo, hi):
    """Streams ``X`` [P, n, C] -> (rms(h, g) [P, C], H_post [P, n], M [P,
    n, n])."""
    flat = X.reshape(X.shape[0], -1)
    m = (flat @ phi.astype(_F32)) * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    a, b = alpha.astype(_F32), bias.astype(_F32)
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:], lo, hi))
    M = M.reshape(-1, n, n)                          # [P, row j, column i]
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=2, keepdims=True) + hc_eps)
        M = M / (jnp.sum(M, axis=1, keepdims=True) + hc_eps)
    h = jnp.einsum("pi,pic->pc", h_pre, X)
    return _rms(h, g, eps), h_post, M


@jax.jit
def _mix_post(X, y, h_post, M):
    return jnp.einsum("pji,pic->pjc", M, X) + h_post[:, :, None] * y[:, None]


def _mixer_args(p: dict, which: str, norm: str, cfg: dict):
    return (p[which + ".phi"], p[which + ".alpha"], p[which + ".bias"],
            p[norm + ".weight"]), dict(
        n=int(cfg["hc_mult"]), iters=int(cfg["hc_sinkhorn_iters"]),
        eps=float(cfg["rms_norm_eps"]), hc_eps=float(cfg["hc_eps"]),
        lo=float(cfg["mhc_h_res_clamp_min"]),
        hi=float(cfg["mhc_h_res_clamp_max"]))


# --------------------------------------------------------------- attention
def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(dim: int, theta: float, rs) -> np.ndarray:
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rs:
        return extra
    orig = rs["original_max_position_embeddings"]
    corr = lambda rot: (dim * math.log(orig / (rot * 2 * math.pi))
                        / (2 * math.log(theta)))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / rs["factor"] * ramp + extra * (1 - ramp)


def attention_scale(cfg: dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        s *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _angles(length: int, cfg: dict):
    a = np.outer(np.arange(length, dtype=np.float64),
                 inv_freq(cfg["qk_rope_head_dim"], float(cfg["rope_theta"]),
                          cfg.get("rope_scaling")))
    a = np.concatenate([a, a], axis=-1)
    return np.cos(a).astype(np.float32), np.sin(a).astype(np.float32)


def _rotate(x, cos, sin):
    """Rotate-half rotary embedding; ``cos``/``sin`` broadcast against x."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


@functools.partial(jax.jit, static_argnames=("rank", "eps"))
def _compress(x, w_dkv, g_kv, cos, sin, rank, eps):
    """Normed input [P, C] -> (c [P, rank], rotated shared key [P, R])."""
    ckr = x @ w_dkv.astype(_F32)
    return _rms(ckr[:, :rank], g_kv, eps), _rotate(ckr[:, rank:], cos, sin)


@functools.partial(jax.jit, static_argnames=("heads", "nope"))
def _decompress(c, w_ukv, heads, nope):
    kv = (c @ w_ukv.astype(_F32)).reshape(c.shape[0], heads, -1)
    return kv[..., :nope], kv[..., nope:]


@functools.partial(jax.jit, static_argnames=("heads", "nope", "eps", "scale"))
def _attend(x, first, p, k_nope, k_rope, v, cos, sin, heads, nope, eps,
            scale):
    """Queries of the block ``x`` [P, C] at positions ``first ...``
    against all keys [S, ...] under the causal mask."""
    P, S = x.shape[0], k_nope.shape[0]
    c_q = _rms(x @ p["q_a_proj.weight"].astype(_F32),
               p["q_a_layernorm.weight"], eps)
    q = (c_q @ p["q_b_proj.weight"].astype(_F32)).reshape(P, heads, -1)
    q_nope = q[..., :nope]
    q_rope = _rotate(q[..., nope:], cos[:, None], sin[:, None])
    s = (jnp.einsum("phd,shd->hps", q_nope, k_nope)
         + jnp.einsum("phr,sr->hps", q_rope, k_rope)) * scale
    seen = jnp.arange(S)[None, :] <= (first + jnp.arange(P))[:, None]
    s = jnp.where(seen[None], s, -jnp.inf)
    o = jnp.einsum("hps,shd->phd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(P, -1) @ p["o_proj.weight"].astype(_F32)


# --------------------------------------------------------------------- FFN
@jax.jit
def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(_F32)) * (x @ up.astype(_F32))) \
        @ down.astype(_F32)


@jax.jit
def _one_expert(acc, x, weight, gate, up, down):
    """``acc + weight[:, None] * E(x)`` for ONE expert's three matrices."""
    return acc + weight[:, None] * _swiglu(x, gate, up, down)


@functools.partial(jax.jit, static_argnames=("k", "factor"))
def route(x, w_g, b_corr, k, factor):
    """``(picked [T, k], weights [T, k], margin [T])`` of tokens ``x`` [T,
    C]; ``margin`` is what the k-th selecting score has over the (k+1)-th:
    how far the token is from picking another expert."""
    sigma = jax.nn.sigmoid(x @ w_g.astype(_F32))
    select = sigma + b_corr.astype(_F32)
    order = jnp.argsort(-select, axis=-1)
    picked = order[:, :k]
    ranked = jnp.take_along_axis(select, order[:, k - 1:k + 1], axis=-1)
    w = jnp.take_along_axis(sigma, picked, axis=-1)
    return (picked, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * factor,
            ranked[:, 0] - ranked[:, 1])


def expert_layer(p: dict, cfg: dict, x, routing=None):
    """The expert FFN on tokens ``x`` [T, C] float32: ``p`` the layer's
    parameters under the program's names (``router.*``, ``experts.*``,
    ``shared_expert.*``). The held experts (all unless
    ``cfg["experts_held"]``) are applied one at a time to every token,
    each weighed by what the token's routing gives it (zero where the
    token did not pick it). ``routing``, a list, receives ``(picked [T,
    k], margin [T])`` (:func:`route`)."""
    E = int(cfg["n_routed_experts"])
    picked, w, margin = route(
        x, p["router.weight"], p["router.e_score_correction_bias"],
        k=int(cfg["num_experts_per_tok"]),
        factor=float(cfg.get("routed_scaling_factor", 1.0)))
    if routing is not None:
        routing.append((np.asarray(picked), np.asarray(margin)))
    first, count = cfg.get("experts_held") or (0, E)
    y = jnp.zeros_like(x)
    for e in range(first, first + count):
        weight = jnp.sum(jnp.where(picked == e, w, 0.0), axis=-1)
        y = _one_expert(y, x, weight, p["experts.gate_proj"][e - first],
                        p["experts.up_proj"][e - first],
                        p["experts.down_proj"][e - first])
    if "shared_expert.gate_proj" in p:
        y = y + _swiglu(x, p["shared_expert.gate_proj"],
                        p["shared_expert.up_proj"],
                        p["shared_expert.down_proj"])
    return y


# ------------------------------------------------------------------- model
def _blocks(length: int):
    return [(a, min(a + BLOCK, length)) for a in range(0, length, BLOCK)]


def _layer(X: list, p: dict, cfg: dict, dense: bool, cos, sin, routing):
    """One block of the model on the streams ``X``, a list of [P, n, C]
    blocks of positions, replaced block by block."""
    heads, nope = int(cfg["num_heads"]), int(cfg["qk_nope_head_dim"])
    rank, eps = int(cfg["kv_lora_rank"]), float(cfg["rms_norm_eps"])
    length = sum(x.shape[0] for x in X)
    spans = _blocks(length)
    a = _sub(p, "self_attn.")
    # attention, pass 1: every position's compressed entry, then its keys
    # and values decompressed; pass 2: a query block at a time
    args, kw = _mixer_args(p, "attn_hc", "input_layernorm", cfg)
    entries = [_compress(_mix_pre(x, *args, **kw)[0],
                         a["kv_a_proj_with_mqa.weight"],
                         a["kv_a_layernorm.weight"], cos[s:e], sin[s:e],
                         rank=rank, eps=eps)
               for x, (s, e) in zip(X, spans)]
    c = jnp.concatenate([c for c, _ in entries])
    k_rope = jnp.concatenate([kr for _, kr in entries])
    k_nope, v = _decompress(c, a["kv_b_proj.weight"], heads=heads, nope=nope)
    for i, (s, e) in enumerate(spans):
        xn, h_post, M = _mix_pre(X[i], *args, **kw)
        y = _attend(xn, s, a, k_nope, k_rope, v, cos[s:e], sin[s:e],
                    heads=heads, nope=nope, eps=eps,
                    scale=attention_scale(cfg))
        X[i] = _mix_post(X[i], y, h_post, M)
    del k_nope, v
    args, kw = _mixer_args(p, "ffn_hc", "post_attention_layernorm", cfg)
    if dense:
        for i in range(len(X)):
            xn, h_post, M = _mix_pre(X[i], *args, **kw)
            y = _swiglu(xn, p["mlp.gate_proj.weight"],
                        p["mlp.up_proj.weight"], p["mlp.down_proj.weight"])
            X[i] = _mix_post(X[i], y, h_post, M)
        return
    # an expert's weights are upcast once for ALL positions
    pre = [_mix_pre(x, *args, **kw) for x in X]
    y = expert_layer(_sub(p, "mlp."), cfg,
                     jnp.concatenate([xn for xn, _, _ in pre]), routing)
    for i, (s, e) in enumerate(spans):
        X[i] = _mix_post(X[i], y[s:e], pre[i][1], pre[i][2])


@functools.partial(jax.jit, static_argnames=("eps",))
def _read_out(X, g, eps):
    return _rms(jnp.sum(X, axis=1), g, eps)


@functools.partial(jax.jit, static_argnames=("first", "width"))
def _head_slice(h, w, first, width):
    return h @ jax.lax.slice_in_dim(w, first, first + width,
                                    axis=1).astype(_F32)


def _forward(params: dict, cfg: dict, row, routing):
    """Final normed states [L, C] of one sequence ``row`` [L]; ``routing``
    (a list or None) receives every expert layer's, in layer order."""
    n, length = int(cfg["hc_mult"]), int(row.shape[0])
    cos, sin = _angles(length, cfg)
    emb = params["model.embed_tokens.weight"]
    X = [jnp.repeat(emb[row[s:e]].astype(_F32)[:, None], n, axis=1)
         for s, e in _blocks(length)]
    for i in range(int(cfg["num_layers"])):
        _layer(X, _sub(params, f"model.layers.{i}."), cfg,
               i < int(cfg["first_k_dense_replace"]), cos, sin, routing)
    return jnp.concatenate(
        [_read_out(x, params["model.norm.weight"],
                   eps=float(cfg["rms_norm_eps"])) for x in X])


def logits(params: dict, cfg: dict, ids, margins=None) -> np.ndarray:
    """``ids`` [B, L] int -> logits [B, L, vocab] float32, on the host.
    ``margins``, a list, receives per sequence a float array [expert
    layers, L]: what every position's last picked expert had over the
    first one left out (:func:`route`), so that a comparison can tell the
    positions where rounding alone may pick another expert."""
    ids = np.asarray(ids, np.int32)
    w = (params["model.embed_tokens.weight"].T
         if cfg.get("tie_word_embeddings") else params["lm_head.weight"])
    vocab = w.shape[1]
    out = np.empty(ids.shape + (vocab,), np.float32)
    with jax.default_matmul_precision("highest"):
        for b, row in enumerate(ids):
            routing = None if margins is None else []
            h = _forward(params, cfg, row, routing)
            if margins is not None:
                margins.append(np.stack([m for _, m in routing]))
            for first in range(0, vocab, VOCAB_SLICE):
                width = min(VOCAB_SLICE, vocab - first)
                out[b, :, first:first + width] = np.asarray(
                    _head_slice(h, w, first=first, width=width))
    return out


def picks(params: dict, cfg: dict, ids) -> list:
    """The experts every position picks: one int array [B, L, k] an
    expert layer, in layer order."""
    ids = np.asarray(ids, np.int32)
    rows = []
    with jax.default_matmul_precision("highest"):
        for row in ids:
            got = []
            _forward(params, cfg, row, got)
            rows.append([picked for picked, _ in got])
    return [np.stack(layer) for layer in zip(*rows)]
