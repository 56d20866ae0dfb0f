"""``jax.named_scope`` on the model's parts: op metadata that names the
device operations of a profile by attention / MLP / loss head / optimizer
and prefill / decode / sample. The names must reach the programs the
benchmark runs, the train step and the serving decode step: here as they
are lowered; ``tests/test_program_scopes.py`` holds them in the optimized
text of the executables, instruction by instruction."""
import re

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.observability.scopes import scope_names
from paddle_tpu.optimizer import AdamW


def _scopes(lowered):
    """The scope names in a lowered program's op metadata: the segments of
    every ``loc("jit(f)/jit(main)/<scope>/.../<primitive>")`` as the
    program's one rule reads a path (``scope_names``: the transforms'
    wrappers, ``jvp(...)``, ``transpose(...)``, taken off)."""
    text = lowered.as_text(debug_info=True)
    found = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        found.update(scope_names(path))
    return found, text


@pytest.fixture(scope="module")
def cfg():
    return gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False, loss_chunk=8)


def test_train_step_names_attention_mlp_loss_head_and_optimizer(cfg):
    pt.seed(3)
    step = pt.TrainStep(GPTForCausalLM(cfg), AdamW(learning_rate=1e-3),
                        loss_fn=None, inputs_fn=lambda b: b)
    ids = np.ones((2, 16), np.int32)
    found, text = _scopes(step.lower((ids, ids)))
    assert {"embed", "block", "attention", "mlp", "final_norm", "loss_head",
            "optimizer"} <= found
    # metadata only: the program's text without it holds none of them
    plain = step.lower((ids, ids)).as_text()
    assert "loss_head" not in plain and "optimizer" not in plain


def test_decode_program_names_decode_sample_and_the_model_parts(cfg):
    from paddle_tpu.serving.engine import ContinuousBatchingEngine

    pt.seed(3)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = ContinuousBatchingEngine(m, slots=2, max_length=32,
                                   prefill_buckets=(16,))
    lowered = eng._decode_compiled.lower(
        eng._params, eng._buffers, eng.live_cache, *eng._decode_inputs())
    found, _ = _scopes(lowered)
    assert {"decode", "sample", "embed", "block", "attention", "cache_write",
            "cache_read", "mlp", "final_norm", "lm_head"} <= found
    assert "prefill" not in found
    lowered = eng._prefill_compiled.lower(
        eng._params, eng._buffers, eng.live_cache,
        np.zeros((1, 16), np.int32), np.int32(0), np.int32(3), eng._keys[0],
        np.int32(-1), np.float32(1), np.float32(1), np.bool_(True))
    found, _ = _scopes(lowered)
    # a prefill attends over its own block: it writes the cache and does
    # not read it
    assert {"prefill", "sample", "attention", "cache_write", "mlp"} <= found
    assert "decode" not in found and "cache_read" not in found


def test_generate_programs_name_prefill_decode_and_sample(cfg):
    from paddle_tpu.models.generation import GenerationEngine

    pt.seed(3)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = GenerationEngine(m, max_length=32, prefill_buckets=(16,))
    from paddle_tpu.models.kv_cache import init_cache
    from paddle_tpu.nn.layer import buffer_state, param_state

    args = (param_state(m), buffer_state(m), init_cache(m, 1, 32))
    kw = dict(top_k=0, greedy=False, use_top_p=True)
    key = np.zeros(2, np.uint32)
    pre = eng._prefill_compiled.lower(
        *args, np.zeros((1, 16), np.int32), np.int32(3), key, np.int32(-1),
        np.float32(1), np.float32(0.9), **kw)
    dec = eng._decode_compiled.lower(
        *args, np.zeros((1, 1), np.int32), np.int32(4), key,
        np.zeros(1, bool), np.int32(-1), np.float32(1), np.float32(0.9), **kw)
    assert {"prefill", "sample"} <= _scopes(pre)[0]
    assert {"decode", "sample"} <= _scopes(dec)[0]
