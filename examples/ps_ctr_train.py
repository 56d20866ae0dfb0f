"""CTR-style training with a parameter-server SparseEmbedding.

Feature ids are arbitrary int64 hashes (no vocab bound); rows live in a
host-side C++ sparse table and update via the lookup's custom-vjp push —
the HeterPS/PGLBox regime. The dense tower trains as normal jax params in
the SAME jitted step.

    python examples/ps_ctr_train.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def main():
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.ps import SparseEmbedding
    from paddle_tpu.nn.layer import buffer_state, functional_call, param_state

    class CTRModel(nn.Layer):
        def __init__(self, dim=16):
            super().__init__()
            self.emb = SparseEmbedding(dim, optimizer="adagrad",
                                       learning_rate=0.1, seed=0)
            self.fc1 = nn.Linear(2 * dim, 32)
            self.fc2 = nn.Linear(32, 1)

        def forward(self, user_ids, item_ids):
            u = self.emb(user_ids)
            v = self.emb(item_ids)
            h = jax.nn.relu(self.fc1(jnp.concatenate([u, v], -1)))
            return self.fc2(h)[:, 0]

    pt.seed(0)
    model = CTRModel()
    params = param_state(model)
    buffers = buffer_state(model)

    @jax.jit
    def train_step(params, user_ids, item_ids, labels):
        def loss_fn(p):
            logits, _ = functional_call(model, p, buffers, user_ids, item_ids)
            return jnp.mean(
                jnp.maximum(logits, 0) - logits * labels
                + jnp.log1p(jnp.exp(-jnp.abs(logits))))  # bce-with-logits
        loss, grads = jax.value_and_grad(loss_fn)(params)
        # dense tower SGD; the sparse rows already updated via push
        new_params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        return loss, new_params

    rng = np.random.default_rng(0)
    for step in range(60):
        # ids are hashes — sparse, unbounded, int64 (bucketed here so the
        # demo's table stays small)
        users = (rng.integers(0, 2**40, 512) % 500).astype(np.int64)
        items = (rng.integers(0, 2**40, 512) % 500).astype(np.int64)
        # synthetic click rule each id's embedding can encode directly
        labels = ((users % 3 == 0) & (items % 2 == 0)).astype(np.float32)
        loss, params = train_step(params, users, items, labels)
        if step % 10 == 0 or step == 59:
            print(f"step {step:3d}  loss {float(loss):.4f}  "
                  f"table rows {len(model.emb.table)}")


if __name__ == "__main__":
    main()
