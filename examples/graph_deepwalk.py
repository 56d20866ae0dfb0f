"""DeepWalk node embeddings on the native graph engine.

Builds a CSR graph in the C++ store, generates random-walk skip-gram
batches with negative samples on a host thread (the reference's
``GraphDataGenerator``/``pre_build_thread`` overlap pattern), and trains
embeddings with a jitted step.

    python examples/graph_deepwalk.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def main():
    from paddle_tpu.distributed.ps.graph import (GraphDataGenerator,
                                                 GraphTable)

    # ring-of-cliques graph: 8 cliques of 16 nodes, ring-linked
    rng = np.random.default_rng(0)
    src, dst = [], []
    n_cliques, k = 8, 16
    for c in range(n_cliques):
        base = c * k
        for i in range(k):
            for j in range(i + 1, k):
                src += [base + i, base + j]
                dst += [base + j, base + i]
        nxt = ((c + 1) % n_cliques) * k
        src += [base, nxt]
        dst += [nxt, base]
    g = GraphTable()
    g.add_edges(np.asarray(src, np.int64), np.asarray(dst, np.int64))
    g.build()
    n = n_cliques * k
    print(f"graph: {n} nodes, {len(src)} edges")

    dim = 32
    emb = jnp.asarray(rng.normal(size=(n, dim), scale=0.1), jnp.float32)

    @jax.jit
    def step(emb, centers, contexts, negatives):
        def loss_fn(e):
            ce, xe, ne = e[centers], e[contexts], e[negatives]
            pos = jnp.sum(ce * xe, -1)
            neg = jnp.einsum("bd,bkd->bk", ce, ne)
            return (jnp.mean(jax.nn.softplus(-pos))
                    + jnp.mean(jax.nn.softplus(neg)))
        loss, grad = jax.value_and_grad(loss_fn)(emb)
        # mean-reduced loss spreads each row's gradient over the batch, so
        # the embedding-table step wants a large lr
        return emb - 5.0 * grad, loss

    for epoch in range(30):
        gen = GraphDataGenerator(g, batch_size=1024, walk_len=8, window=2,
                                 num_neg=4, seed=epoch)
        for centers, contexts, negatives in gen:
            emb, loss = step(emb, centers, contexts, negatives)
        if epoch % 10 == 0 or epoch == 29:
            print(f"epoch {epoch:2d}  loss {float(loss):.4f}")

    # same-clique nodes should now be closer than cross-clique ones
    norm = emb / jnp.linalg.norm(emb, axis=-1, keepdims=True)
    same = float(jnp.mean(jnp.sum(norm[0] * norm[1:k], -1)))
    cross = float(jnp.mean(jnp.sum(norm[0] * norm[3 * k:4 * k], -1)))
    print(f"cosine same-clique {same:.3f} vs cross-clique {cross:.3f}")


if __name__ == "__main__":
    main()
