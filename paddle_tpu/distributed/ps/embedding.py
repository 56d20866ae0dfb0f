"""Sparse embedding lookup fused into jitted steps via host callbacks.

Reference parity: ``distributed_lookup_table``/``c_embedding`` +
``PSGPUWrapper::PullSparse``/``PushSparseGrad``
(``paddle/fluid/framework/fleet/ps_gpu_wrapper.h:157,170``) and the Python
``paddle.static.nn.sparse_embedding``. TPU-native: the pull is a
``jax.pure_callback`` into the host C++ table (dense [batch, dim] rows cross
PCIe, never the full table), and the push rides the backward pass as an
``io_callback`` inside a ``custom_vjp`` — the optimizer update happens
server-side in C++, so the embedding never appears in the jitted step's
parameter pytree. This is the reference's "hide the host↔device hop behind
the step" trick (``pre_build_thread`` pipelining) restated for XLA.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax._src.core import trace_state_clean

from ...nn.layer import Layer
from .table import MemorySparseTable, SparseAccessorConfig


def _tracing_active() -> bool:
    """True when called under ANY jax trace (jit or grad), even if every
    visible operand is a concrete closed-over array. Needed because a layer
    whose inputs are all closure constants still traces wrong: its host pull
    would bake stale rows into the compiled program and its push-vjp would
    be pruned."""
    return not trace_state_clean()


def make_lookup(table: MemorySparseTable):
    """Build a differentiable ``lookup(ids, anchor) -> f32[..., dim]`` bound
    to ``table``. Works eagerly and under ``jit``; backward pushes grads into
    the table (which applies its optimizer rule).

    ``anchor`` is a throwaway *differentiable* scalar: reverse-mode AD only
    visits a node on a path from a differentiated input, and ``ids`` is
    integer, so without the anchor the vjp (and therefore the grad push)
    would be dead-code-eliminated. Thread any trainable scalar through it
    (:class:`SparseEmbedding` registers one).
    """
    dim = table.embed_dim

    def _pull_host(ids):
        return table.pull(np.asarray(ids))

    def _push_host(ids, grads):
        table.push(np.asarray(ids), np.asarray(grads))
        return np.int32(0)

    @jax.custom_vjp
    def lookup(ids, anchor):
        del anchor  # connectivity only; numerically unused
        flat = ids.reshape(-1)
        # io_callback, not pure_callback: pull is effectful (initializes
        # missing keys, bumps the show counter that drives shrink eviction),
        # so it must run exactly once per step — pure callbacks may be
        # cached, elided, or re-executed under retracing/vmap.
        out = jax.experimental.io_callback(
            _pull_host,
            jax.ShapeDtypeStruct((flat.shape[0], dim), jnp.float32),
            flat, ordered=False)
        return out.reshape(ids.shape + (dim,))

    def fwd(ids, anchor):
        return lookup(ids, anchor), ids

    def bwd(ids, g):
        flat_ids = ids.reshape(-1)
        flat_g = g.reshape(-1, dim).astype(jnp.float32)
        jax.experimental.io_callback(
            _push_host, jax.ShapeDtypeStruct((), jnp.int32),
            flat_ids, flat_g, ordered=False)
        return (np.zeros(ids.shape, dtype=jax.dtypes.float0),
                jnp.zeros(()))

    lookup.defvjp(fwd, bwd)
    return lookup


class SparseEmbedding(Layer):
    """Embedding layer backed by a PS table instead of a dense parameter.

    Unlike :class:`paddle_tpu.nn.Embedding` (dense [vocab, dim] parameter on
    device), ids here are arbitrary int64 feature hashes — no vocab bound —
    and rows live host-side, the CTR/recsys regime the reference's HeterPS
    serves. The update is applied by the table on ``push`` during backward,
    so this layer contributes no entries to ``param_state``.
    """

    def __init__(self, embed_dim: int, table: MemorySparseTable = None,
                 **accessor_kw):
        super().__init__()
        if table is None:
            table = MemorySparseTable(
                SparseAccessorConfig(embed_dim=embed_dim, **accessor_kw))
        assert table.embed_dim == embed_dim
        self.table = table
        self.embed_dim = embed_dim
        self._lookup = make_lookup(table)
        # Differentiable anchor so the push-vjp survives AD pruning (see
        # make_lookup). Always receives zero gradient; numerically unused.
        from ...nn.initializer import Constant

        self.grad_anchor = self.create_parameter(
            (), default_initializer=Constant(0.0))

    def forward(self, ids):
        ids = jnp.asarray(ids)
        anchor_traced = isinstance(self.grad_anchor, jax.core.Tracer)
        in_trace = (anchor_traced or isinstance(ids, jax.core.Tracer)
                    or _tracing_active())
        if not in_trace:
            # Eager path: plain host pull, no callback machinery.
            # tpu-lint: disable=R1(eager branch — the in_trace check above proved ids is not a Tracer and no trace is active)
            rows = self.table.pull(np.asarray(ids).reshape(-1))
            return jnp.asarray(rows).reshape(ids.shape + (self.embed_dim,))
        if self.training and not anchor_traced:
            # Inside a jit/grad trace but grad_anchor is a plain array: the
            # push-vjp is unreachable from the differentiated inputs and AD
            # would silently prune it — the step would run, loss would move,
            # and the embedding would never train. Fail loudly instead.
            raise RuntimeError(
                "SparseEmbedding used inside a traced step, but its "
                "grad_anchor parameter is not among the traced/differentiated "
                "values, so embedding gradients would be silently dropped. "
                "Run the layer via functional_call/TrainStep with "
                "param_state(model) (which includes grad_anchor), or call "
                ".eval() on the layer for inference.")
        return self._lookup(ids, self.grad_anchor)

    def extra_repr(self):
        acc = getattr(self.table, "accessor", None)  # PsClient has none
        opt = f", optimizer={acc.optimizer}" if acc is not None else ""
        return f"embed_dim={self.embed_dim}{opt}"


class StagedPull:
    """Pull-before / push-after staging for training without in-graph
    callbacks — the reference's actual structure (``PSGPUWorker`` pulls via
    ``PullSparse`` before the program runs and pushes via ``PushSparseGrad``
    after it, ``ps_gpu_wrapper.h:157,170``), restated for XLA: the jitted
    step takes dense ``rows`` as a regular differentiable input; duplicate
    ids are deduplicated so row grads come back merged (the communicator's
    batched-merge, ``communicator.h:426``).

    Usage::

        staged = StagedPull(table)
        rows, inv, uniq = staged.pull(ids)          # host side
        loss, row_grads = step(params, rows, inv)   # jit: emb = rows[inv]
        staged.push(uniq, row_grads)                # host side, C++ update
    """

    def __init__(self, table: MemorySparseTable):
        self.table = table

    def pull(self, ids):
        ids = np.asarray(ids)
        uniq, inv = np.unique(ids.reshape(-1), return_inverse=True)
        rows = self.table.pull(uniq)
        return (jnp.asarray(rows), jnp.asarray(inv.reshape(ids.shape)),
                uniq)

    @staticmethod
    def lookup(rows, inv):
        """In-graph gather: embedding activations for the original ids."""
        return rows[inv]

    def push(self, uniq, row_grads) -> None:
        self.table.push(uniq, np.asarray(row_grads))
