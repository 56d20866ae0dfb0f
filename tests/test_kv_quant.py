"""int8 KV-cache quantization (paddle_tpu/quantization + the cache
pytree plumbing in models/generation.py and serving/engine.py).

What must hold:

1. **Round-trip bound** — per-head abs-max int8 quantization's error is
   at most half a quantization step (``scale / 2``), and all-zero heads
   dequantize to exact zero;
2. **Byte accounting** — a quantized cache pytree is at most half the
   full-precision cache's bytes (the HBM-per-slot halving claim);
3. **Checkpoint/reshard** — the scales leaf lives alongside the int8
   values in the cache pytree, so ``save_state``/``load_state(
   shardings=...)`` reshards both together with dtypes preserved;
4. **Adapter compatibility** — a zero-initialized LoRA adapter on a
   QUANTIZED base projection is a bitwise no-op (B = 0), so serving a
   quantized base with idle adapters changes nothing;
5. **Bounded drift** — teacher-forced decode logits through an int8
   cache stay within a small relative error of the full-precision path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.quantization import (is_quantized_kv, kv_dequantize,
                                     kv_quantize)


@pytest.fixture(scope="module")
def gpt_model():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    pt.seed(7)
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model, cfg


def test_roundtrip_error_within_half_step():
    x = np.random.default_rng(0).normal(
        0, 3.0, (2, 5, 3, 8)).astype(np.float32)
    q, scale = kv_quantize(jnp.asarray(x))
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert scale.dtype == jnp.float32 and scale.shape == (2, 5, 3, 1)
    deq = np.asarray(kv_dequantize(q, scale))
    # symmetric round-to-nearest: |err| <= scale / 2 per element
    bound = np.broadcast_to(np.asarray(scale) / 2 + 1e-7, x.shape)
    assert (np.abs(deq - x) <= bound).all()
    # relative error of the worst element stays small
    rel = np.abs(deq - x).max() / np.abs(x).max()
    assert rel < 0.01


def test_zero_head_dequantizes_to_exact_zero():
    x = jnp.zeros((1, 2, 2, 8), jnp.float32)
    q, scale = kv_quantize(x)
    assert (np.asarray(q) == 0).all()
    assert (np.asarray(kv_dequantize(q, scale)) == 0.0).all()


def test_is_quantized_kv_predicate():
    x = jnp.ones((1, 2, 2, 4), jnp.float32)
    assert is_quantized_kv(kv_quantize(x))
    assert not is_quantized_kv(x)
    assert not is_quantized_kv((x, x))   # fp pair is not a quant entry


def test_cache_pytree_bytes_halved(gpt_model):
    from paddle_tpu.models.kv_cache import cache_nbytes, init_cache

    model, _ = gpt_model
    full = cache_nbytes(init_cache(model, 4, 64))
    quant = cache_nbytes(init_cache(model, 4, 64, kv_dtype="int8"))
    assert quant <= full / 2, (
        f"int8 cache is {quant} bytes vs {full} full-precision — the "
        f"halving claim fails")


def test_serving_slot_bytes_halved(gpt_model):
    from paddle_tpu.serving.engine import ContinuousBatchingEngine

    model, _ = gpt_model
    full = ContinuousBatchingEngine(
        model, slots=2, max_length=64).cache_bytes_per_slot()
    quant = ContinuousBatchingEngine(
        model, slots=2, max_length=64,
        kv_dtype="int8").cache_bytes_per_slot()
    assert quant <= full / 2


def test_scales_reshard_alongside_cache(tmp_path):
    from paddle_tpu.distributed.checkpoint import load_state, save_state
    from paddle_tpu.distributed.mesh import init_mesh

    mesh = init_mesh(dp=2, mp=4)
    x = np.random.default_rng(1).normal(
        0, 1.0, (8, 16, 2, 8)).astype(np.float32)
    q, scale = kv_quantize(jnp.asarray(x))
    # the quantized pair shards over batch exactly like a fp cache leaf
    # (the trailing keepdim axis is why scales need no special casing)
    shard = NamedSharding(mesh, P("dp", None, None, None))
    state = {"k": jax.device_put(q, shard),
             "k_scale": jax.device_put(scale, shard)}
    d = str(tmp_path / "kv")
    save_state(state, d)
    # reload re-sliced onto a different axis layout: both leaves move
    # together, dtypes preserved
    target = NamedSharding(mesh, P("mp", None, None, None))
    out = load_state(d, shardings={"k": target, "k_scale": target})
    assert out["k"].dtype == jnp.int8
    assert out["k_scale"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out["k"]), np.asarray(q))
    np.testing.assert_array_equal(np.asarray(out["k_scale"]),
                                  np.asarray(scale))
    assert tuple(out["k"].sharding.spec) == ("mp", None, None, None)
    assert tuple(out["k_scale"].sharding.spec) == ("mp", None, None, None)
    # dequant after the round trip reproduces the pre-save values
    np.testing.assert_array_equal(
        np.asarray(kv_dequantize(out["k"], out["k_scale"])),
        np.asarray(kv_dequantize(q, scale)))


def test_zero_adapter_noop_on_quantized_base():
    from paddle_tpu.lora import LoraConfig, apply_lora
    from paddle_tpu.quantization import QAT
    import paddle_tpu.nn as nn

    class Head(nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(16, 8)

        def forward(self, x):
            return self.proj(x)

    pt.seed(0)
    model = QAT().quantize(Head())   # proj becomes QuantedLinear
    model.eval()
    x = jnp.asarray(np.random.default_rng(2).normal(
        0, 1.0, (3, 16)).astype(np.float32))
    base = np.asarray(model(x))
    apply_lora(model, LoraConfig(rank=4, target_modules=("proj",)))
    with_adapter = np.asarray(model(x))
    # lora_B starts at zero: injection must be BITWISE invisible even
    # through the fake-quant forward
    np.testing.assert_array_equal(base, with_adapter)


def test_quantized_cache_logit_drift_bounded(gpt_model):
    from paddle_tpu.models.kv_cache import init_cache
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     param_state)

    model, cfg = gpt_model
    params = param_state(model)
    buffers = buffer_state(model)
    ids = np.random.default_rng(3).integers(
        1, cfg.vocab_size, (2, 8)).astype(np.int32)
    full = init_cache(model, 2, 32)
    quant = init_cache(model, 2, 32, kv_dtype="int8")
    (lf, full), _ = functional_call(model, params, buffers,
                                    jnp.asarray(ids), cache=full,
                                    position_offset=0)
    (lq, quant), _ = functional_call(model, params, buffers,
                                     jnp.asarray(ids), cache=quant,
                                     position_offset=0)
    # prefill logits attend the un-quantized fresh block: bit-identical
    np.testing.assert_array_equal(np.asarray(lf[:, -1]),
                                  np.asarray(lq[:, -1]))
    # teacher-forced decode: replay the full-precision argmax chain
    # through both caches and bound the relative logit drift
    worst = 0.0
    tok = jnp.argmax(lf[:, -1], axis=-1).astype(jnp.int32)
    for step in range(4):
        (lf, full), _ = functional_call(
            model, params, buffers, tok[:, None], cache=full,
            position_offset=jnp.full((2,), 8 + step, jnp.int32))
        (lq, quant), _ = functional_call(
            model, params, buffers, tok[:, None], cache=quant,
            position_offset=jnp.full((2,), 8 + step, jnp.int32))
        a, b = np.asarray(lf[:, -1]), np.asarray(lq[:, -1])
        worst = max(worst, np.abs(a - b).max() / max(np.abs(a).max(),
                                                     1e-9))
        tok = jnp.argmax(lf[:, -1], axis=-1).astype(jnp.int32)
    assert worst < 0.05, f"int8 KV logit drift {worst} exceeds 5%"
