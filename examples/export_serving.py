"""Train -> export StableHLO -> serve from Python (and plain C), plus
compiled KV-cache text generation.

``paddle_tpu.jit.save`` writes the reference's artifact pair: ``.pdmodel``
(serialized StableHLO — the portable IR, loadable under any XLA runtime)
and ``.pdiparams`` (weights). The Python ``Predictor`` serves it here;
``native/capi/infer_capi.h`` + ``tools/infer_demo.c`` serve the SAME
artifact from C with no Python. The second half demos the serving path
for decoder LMs: ``GPTForCausalLM.generate`` — O(1)-compile autoregressive
decode against a preallocated KV cache (``models/generation.py``).

    python examples/export_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.jit import InputSpec, save
    from paddle_tpu.optimizer import AdamW

    pt.seed(0)
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 3))
    step = pt.TrainStep(model, AdamW(learning_rate=1e-2),
                        loss_fn=lambda out, b: F.cross_entropy(out, b[1]))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    y = rng.integers(0, 3, 64)
    for _ in range(30):
        loss = step((x, y))
    print(f"trained to loss {float(loss):.4f}")
    step.sync_to_model()

    # export: dynamic batch via InputSpec(None, ...)
    save(model, "/tmp/demo_model",
         input_spec=[InputSpec(shape=[None, 8], dtype="float32")])
    print("exported /tmp/demo_model.pdmodel (+ .pdiparams)")

    pred = create_predictor(Config("/tmp/demo_model"))
    out = pred.run([x[:5]])[0]
    ref = np.asarray(model(pt.to_tensor(x[:5])))
    np.testing.assert_allclose(out, ref, rtol=1e-5)
    print("predictor output matches the eager model; batch is dynamic:",
          pred.run([x[:17]])[0].shape)

    generate_demo()


def generate_demo():
    """Batched autoregressive decode on gpt_tiny: #buckets_used + 1
    compiled programs total, per-token cost O(L) against the KV cache."""
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(0)
    lm = GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                 attention_dropout_prob=0.0,
                                 use_flash_attention=False))
    lm.eval()
    prompts = np.random.default_rng(0).integers(
        1, 1024, (2, 12)).astype(np.int32)
    tokens, stats = lm.generate(
        prompts, max_new_tokens=8, max_length=64, prefill_buckets=(16, 32),
        do_sample=True, temperature=0.9, top_k=40, seed=7, return_stats=True)
    cc = stats["compile_stats"]
    print(f"generated {tokens.shape[1]} tokens/seq for {tokens.shape[0]} "
          f"prompts: {tokens[0].tolist()} ...")
    print(f"decode engine: {cc['prefill']['compiles']} prefill + "
          f"{cc['decode']['compiles']} decode compile(s), "
          f"ttft {stats['ttft_s'] * 1e3:.1f} ms, "
          f"{stats['tokens_per_sec']:.0f} tokens/s")


if __name__ == "__main__":
    main()
