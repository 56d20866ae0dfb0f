"""A token-choice expert FFN without drops, for decoder blocks.

    sigma = sigmoid(x W_g)                      # [E], float32
    picked = top_k(sigma + b_corr)              # b_corr selects, never weighs
    w = sigma[picked] / (sum sigma[picked] + 1e-20) * routed_scaling_factor
    y = sum_k w_k E_{picked_k}(x) + E_shared(x)
    E(x) = W_down(silu(W_gate x) * W_up x)

Every pick is computed, however skewed the routing: the (token, expert)
picks are sorted by expert, each expert's rows go through ONE grouped
matmul a projection over the stacked weights (``jax.lax.ragged_dot``,
which the TPU compiler lowers to a grouped-matmul kernel that visits the
groups that have rows), and the results are weighed and added back per
token. The same code serves a 2048-token prefill (about 128 rows an
expert at 64 experts, top 4) and a 32-slot decode step (about 2 rows an
expert, bandwidth-bound). There is no capacity and no auxiliary loss;
``distributed/parallel/moe.py`` is the capacity-drop layer.

The layer is told which contiguous range of experts it holds
(``experts_held=(first, count)``, all by default): it routes over ALL
``num_experts``, with the published router, and computes the part of the
result that its own experts give; what the others would add is left out,
here and nowhere stood in for. The shared expert is computed by every
holder alike.

Router arithmetic is float32 at full precision whatever the weights'
type: a near-tie among the top ``k`` that falls the other way swaps a
whole expert.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..initializer import Constant, Initializer, Normal
from ..layer import Layer

__all__ = ["ExpertFFN", "expert_load"]

_HIGHEST = jax.lax.Precision.HIGHEST

# Trace-time state, thread-local as kv_cache.cache_paths' is: the serving
# engine opens it around the trace of its decode program.
_LOAD = threading.local()


@contextlib.contextmanager
def expert_load(live):
    """Collect, from every :class:`ExpertFFN` applied under this context,
    ``(tokens_per_expert [E] int32, experts_touched [] int32)`` of the
    tokens that ``live`` (a traced bool ``[tokens]``, in the order the
    layer flattens them) marks: how many of them picked each expert, and
    how many experts at least one picked. Yields the list, one pair a
    layer in the order applied."""
    outer = getattr(_LOAD, "open", None)
    _LOAD.open = (live, [])
    try:
        yield _LOAD.open[1]
    finally:
        _LOAD.open = outer


class ExpertFFN(Layer):
    """``num_experts`` routed SwiGLU experts of width ``expert_width``,
    ``top_k`` a token, and ``shared_width`` > 0 for one shared SwiGLU
    expert every token passes through. Parameters (``dtype``, the
    default type when None): ``router.weight`` [H, E],
    ``router.e_score_correction_bias`` [E], ``experts.{gate,up}_proj`` [held,
    H, W], ``experts.down_proj`` [held, W, H], ``shared_expert.{gate,up,
    down}_proj``; normal with ``init_std``, the down projections with
    ``out_init_std``, routed and shared alike. ``own_share`` is how far
    the routed experts start apart: each is ``sqrt(1 - own_share ** 2)``
    of ONE expert drawn for the layer plus ``own_share`` of a draw of its
    own, at the same std: 1 draws every expert on its own, 0 makes them
    copies of one (sparse upcycling, arXiv:2212.05055)."""

    def __init__(self, hidden_size: int, expert_width: int, num_experts: int,
                 top_k: int, *, shared_width: int = 0,
                 experts_held: Optional[Tuple[int, int]] = None,
                 routed_scaling_factor: float = 1.0, init_std: float = 0.02,
                 out_init_std: Optional[float] = None,
                 own_share: float = 1.0, dtype=None):
        super().__init__(dtype=dtype)
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"experts_held {experts_held} is no range of "
                             f"the {num_experts} experts")
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.experts_held = (int(first), int(count))
        self.routed_scaling_factor = float(routed_scaling_factor)
        H, W = int(hidden_size), int(expert_width)
        if not 0.0 <= own_share <= 1.0:
            raise ValueError(f"own_share {own_share} is no share")
        out_std = init_std if out_init_std is None else out_init_std
        self.router = _Router(H, num_experts, init_std, dtype)
        self.experts = _Stacked(count, H, W, _Akin(init_std, own_share),
                                _Akin(out_std, own_share), dtype)
        self.shared_expert = (_Stacked(None, H, int(shared_width),
                                       Normal(0.0, init_std),
                                       Normal(0.0, out_std), dtype)
                              if shared_width else None)

    # ------------------------------------------------------------ routing
    def route(self, flat):
        """``(picked [T, k] int32, weights [T, k] float32)`` of tokens
        ``flat`` [T, H]."""
        logits = jnp.dot(flat.astype(jnp.float32),
                         self.router.weight.astype(jnp.float32),
                         precision=_HIGHEST)
        scores = jax.nn.sigmoid(logits)
        select = scores + self.router.e_score_correction_bias.astype(
            jnp.float32)
        _, picked = jax.lax.top_k(select, self.top_k)
        w = jnp.take_along_axis(scores, picked, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return picked.astype(jnp.int32), w * self.routed_scaling_factor

    def _tally(self, picked):
        open_ = getattr(_LOAD, "open", None)
        if open_ is None:
            return
        live, out = open_
        hot = jax.nn.one_hot(picked, self.num_experts, dtype=jnp.int32)
        per_expert = jnp.sum(hot * live.astype(jnp.int32)[:, None, None],
                             axis=(0, 1))
        out.append((per_expert, jnp.sum(per_expert > 0, dtype=jnp.int32)))

    # ------------------------------------------------------------ forward
    @jax.named_scope("moe")
    def forward(self, x):
        lead, H = x.shape[:-1], x.shape[-1]
        flat = x.reshape(-1, H)
        T, k = flat.shape[0], self.top_k
        first, count = self.experts_held
        whole = count == self.num_experts
        with jax.named_scope("router"):
            picked, w = self.route(flat)
            self._tally(picked)
        with jax.named_scope("dispatch"):
            local = picked.reshape(-1) - first                  # [T k]
            if not whole:
                held = (local >= 0) & (local < count)
                # picks of experts held elsewhere sort behind every group
                local = jnp.where(held, local, count)
            order = jnp.argsort(local, stable=True)
            back = jnp.argsort(order)
            sizes = jnp.sum(jax.nn.one_hot(local, count, dtype=jnp.int32),
                            axis=0)
            rows = flat[order // k]                             # [T k, H]
        with jax.named_scope("experts"):
            ys = self.experts(rows, sizes)
        with jax.named_scope("combine"):
            ys = ys[back].reshape(T, k, H).astype(jnp.float32)
            if not whole:
                # rows past the last group are no expert's output
                keep = held.reshape(T, k)
                ys = jnp.where(keep[..., None], ys, 0.0)
                w = jnp.where(keep, w, 0.0)
            y = jnp.sum(ys * w[..., None], axis=1)
        if self.shared_expert is not None:
            with jax.named_scope("shared_expert"):
                y = y + self.shared_expert(flat).astype(jnp.float32)
        return y.astype(x.dtype).reshape(*lead, H)


class _Router(Layer):
    def __init__(self, hidden_size, num_experts, init_std, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            (hidden_size, num_experts), attr=Normal(0.0, init_std))
        # noaux_tc: a bias on the scores that SELECT, kept out of the
        # weights; balancing moves it during training, zero at birth
        self.e_score_correction_bias = self.create_parameter(
            (num_experts,), attr=Constant(0.0))


class _Akin(Initializer):
    """Stacked weights ``[count, ...]``, normal with ``std``: ``sqrt(1 -
    own ** 2)`` of one draw that all ``count`` share plus ``own`` of a draw
    each."""

    def __init__(self, std: float, own: float):
        self.std, self.own = std, own

    def __call__(self, key, shape, dtype):
        if self.own == 1.0:
            return jax.random.normal(key, shape, dtype=dtype) * self.std
        shared, each = jax.random.split(key)
        one = jax.random.normal(shared, (1,) + tuple(shape[1:]), dtype=dtype)
        return ((1.0 - self.own ** 2) ** 0.5 * one + self.own
                * jax.random.normal(each, shape, dtype=dtype)) * self.std


class _Stacked(Layer):
    """``count`` SwiGLU experts as stacked weights (one plain expert where
    ``count`` is None), applied to rows grouped by expert; ``init`` draws
    the gate and up projections, ``out_init`` the down projection."""

    def __init__(self, count, hidden_size, width, init, out_init, dtype):
        super().__init__(dtype=dtype)
        lead = () if count is None else (count,)
        self.gate_proj = self.create_parameter(
            lead + (hidden_size, width), attr=init)
        self.up_proj = self.create_parameter(
            lead + (hidden_size, width), attr=init)
        self.down_proj = self.create_parameter(
            lead + (width, hidden_size), attr=out_init)

    def forward(self, rows, sizes=None):
        if sizes is None:
            dot = lambda a, w: jnp.dot(a, w)
        else:
            # bf16 operands state their own single-pass precision: left
            # to the process-wide "float32" default they would ask the
            # grouped-matmul kernel for an fp32 contraction, which Mosaic
            # refuses for bf16 ("Bad lhs type")
            precision = (jax.lax.Precision.DEFAULT
                         if rows.dtype == jnp.bfloat16 else None)
            dot = lambda a, w: jax.lax.ragged_dot(a, w, sizes,
                                                  precision=precision)
        h = jax.nn.silu(dot(rows, self.gate_proj)) * dot(rows, self.up_proj)
        return dot(h, self.down_proj)
