"""GPT pretraining with the fused TrainStep — the flagship workflow.

Runs a tiny config by default (CPU-friendly, seconds); ``--bench`` runs
the 350M-class configuration bench.py records on real TPU hardware.

    python examples/gpt_pretrain.py
    python examples/gpt_pretrain.py --bench   # needs a TPU-class chip
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu import amp
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.optimizer import AdamW


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", action="store_true",
                    help="350M-class TPU config instead of the tiny demo")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    if args.bench:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                        num_heads=16, max_position_embeddings=1024,
                        use_flash_attention=True, loss_chunk=256,
                        dtype="bfloat16")
        batch, seq = 8, 1024
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_position_embeddings=128)
        batch, seq = 4, 64

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=3e-4, weight_decay=0.01)
    if args.bench:
        # O2: bf16 compute, f32 master weights held by the optimizer
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    # forward(ids, labels) returns the shifted LM loss itself (chunked and
    # fused with the head projection when cfg.loss_chunk is set)
    step = pt.TrainStep(model, opt, loss_fn=None)

    # recompile-proof input pipeline: documents yield VARIABLE-length token
    # runs and the corpus size leaves a ragged tail batch — exactly the
    # stream that would retrace XLA once per novel shape. The loader's
    # pad_batches/length_buckets bound the shape set, and the async device
    # prefetch overlaps the host->HBM hop with the running step.
    rng = np.random.default_rng(0)
    n_docs = batch * args.steps + batch // 2      # ragged tail on purpose
    lengths = (seq // 2, seq)   # two buckets: enough to show the policy
                                # without a third demo-only XLA compile

    class TokenDocs(pt.io.Dataset):
        def __len__(self):
            return n_docs

        def __getitem__(self, i):
            L = lengths[(i // batch) % len(lengths)]
            ids = rng.integers(0, cfg.vocab_size, L).astype(np.int32)
            return ids, ids  # (input ids, labels)

    loader = pt.io.DataLoader(TokenDocs(), batch_size=batch, shuffle=False,
                              pad_batches=True,
                              length_buckets=lengths)
    t0 = time.perf_counter()
    tokens = 0
    i = 0
    prefetch = pt.io.prefetch_to_device(iter(loader), depth=2)
    from contextlib import ExitStack

    with ExitStack() as stack:  # guard + prefetch released on ANY exit
        stack.callback(prefetch.close)
        for ids_b, labels_b, valid in prefetch:
            loss = step((ids_b, labels_b))
            tokens += int(np.prod(ids_b.shape))
            if i % 5 == 0:
                print(f"step {i:4d}  loss {float(loss):.4f}  "
                      f"shape {tuple(ids_b.shape)}  "
                      f"valid {int(np.asarray(valid).sum())}")
            i += 1
            if i == len(lengths):
                # warmup traced one program per bucket; from here on any
                # recompile is a pipeline bug — fail loudly
                stack.enter_context(
                    pt.framework.compile_cache.retrace_guard(max_compiles=0))
    dt = time.perf_counter() - t0
    stats = step.cache_stats()
    print(f"{tokens / dt:,.0f} tokens/s (incl. compile) on {pt.get_device()}")
    print(f"compiled {stats['compiles']} program(s) over {stats['calls']} "
          f"steps (cache hits {stats['cache_hits']}); "
          f"h2d stall {prefetch.stats()['consumer_stall_s'] * 1e3:.0f}ms")

    # checkpoint + resume
    step.sync_to_model()
    pt.save(model.state_dict(), "/tmp/gpt_demo.pdparams")
    print("saved /tmp/gpt_demo.pdparams")


if __name__ == "__main__":
    main()
