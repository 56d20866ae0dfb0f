"""Model zoo tests: GPT forward/loss/train-step, ResNet forward/train,
and the hybrid-parallel dryrun on the 8-device CPU mesh."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
import paddle_tpu as pt
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_loss_fn, gpt_tiny
from paddle_tpu.models.resnet import resnet18, resnet50
from paddle_tpu.framework.jit import TrainStep
from paddle_tpu.optimizer import AdamW, Momentum


def _ids(shape, vocab):
    return np.asarray(np.random.default_rng(0).integers(0, vocab, shape), np.int32)


def test_gpt_forward_shapes():
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = _ids((2, 16), cfg.vocab_size)
    logits = model(ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = model.loss(logits, ids)
    assert np.isfinite(float(loss))


def test_gpt_untied_head():
    cfg = gpt_tiny(tie_word_embeddings=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    logits = model(_ids((1, 8), cfg.vocab_size))
    assert logits.shape == (1, 8, cfg.vocab_size)


def test_gpt_train_loss_decreases():
    cfg = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                   max_position_embeddings=32,
                   hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    step = TrainStep(model, AdamW(learning_rate=1e-3),
                     loss_fn=gpt_loss_fn(model))
    ids = _ids((4, 16), cfg.vocab_size)
    losses = [float(step((ids, ids))) for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_gpt_recompute_matches():
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle_tpu.seed(7)
    m1 = GPTForCausalLM(cfg)
    ids = _ids((2, 16), cfg.vocab_size)
    m1.eval()
    base = np.asarray(m1(ids))
    m1.cfg.use_recompute = True
    m1.gpt.h.cfg.use_recompute = True
    rec = np.asarray(m1(ids))
    np.testing.assert_allclose(base, rec, rtol=1e-5, atol=1e-5)


def test_resnet18_forward():
    model = resnet18(num_classes=10)
    model.eval()
    x = np.random.default_rng(0).standard_normal((2, 3, 32, 32)).astype(np.float32)
    out = model(x)
    assert out.shape == (2, 10)


@pytest.mark.slow   # ~19s compile on the CI box; resnet18 covers tier-1
def test_resnet50_train_step():
    model = resnet50(num_classes=4)
    import paddle_tpu.nn.functional as F

    def loss_fn(out, batch):
        return F.cross_entropy(out, batch[1])

    step = TrainStep(model, Momentum(learning_rate=0.01), loss_fn=loss_fn)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 3, 32, 32)).astype(np.float32)
    y = np.asarray(rng.integers(0, 4, (2,)), np.int64)
    l0 = float(step((x, y)))
    l1 = float(step((x, y)))
    assert np.isfinite(l0) and np.isfinite(l1)


def test_graft_entry_single_chip():
    import importlib.util
    import jax

    spec = importlib.util.spec_from_file_location("__graft_entry__",
                                                  "/root/repo/__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.slow   # ~15s 8-device entry compile (tier-1 report)
def test_graft_entry_multichip():
    import importlib.util

    spec = importlib.util.spec_from_file_location("__graft_entry__",
                                                  "/root/repo/__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


# ------------------------------------------------------- BERT (round 3)
import jax
import jax.numpy as jnp


def test_bert_model_shapes_and_padding_mask():
    from paddle_tpu.models.bert import BertModel, bert_tiny

    paddle_tpu.seed(0)
    cfg = bert_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = BertModel(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(1, cfg.vocab_size, (2, 16))
    ids[1, 8:] = 0  # pad tail of row 1
    seq, pooled = model(jnp.asarray(ids))
    assert seq.shape == (2, 16, cfg.hidden_size)
    assert pooled.shape == (2, cfg.hidden_size)
    # padding must not influence non-pad positions: changing pad content
    # leaves row-1 valid outputs identical
    ids2 = ids.copy()
    ids2[1, 8:] = 7
    mask = (ids != 0).astype(np.float32)
    seq_a, _ = model(jnp.asarray(ids), attention_mask=jnp.asarray(mask))
    seq_b, _ = model(jnp.asarray(ids2), attention_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(seq_a[1, :8]),
                               np.asarray(seq_b[1, :8]), rtol=1e-5,
                               atol=1e-5)


def test_bert_finetune_trains():
    from paddle_tpu.framework.jit import TrainStep
    from paddle_tpu.models.bert import (BertForSequenceClassification,
                                        bert_tiny)
    from paddle_tpu.optimizer import AdamW

    paddle_tpu.seed(1)
    cfg = bert_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = BertForSequenceClassification(cfg, num_classes=2)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, cfg.vocab_size, (8, 12))
    labels = (ids.sum(1) % 2).astype(np.int64)
    step = TrainStep(model, AdamW(learning_rate=5e-4), loss_fn=None,
                     inputs_fn=lambda b: (b[0], None, None, b[1]))
    losses = [float(np.asarray(step((ids, labels)))) for _ in range(25)]
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_bert_pretraining_masked_lm():
    """MLM gathers only masked positions (no [B, L, vocab] logits) and the
    loss ignores -1 padded positions; tied decoder follows the embedding."""
    from paddle_tpu.models.bert import BertForPretraining, bert_tiny

    paddle_tpu.seed(2)
    cfg = bert_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = BertForPretraining(cfg)
    model.eval()
    rng = np.random.default_rng(3)
    ids = rng.integers(1, cfg.vocab_size, (2, 16))
    pos = np.asarray([[1, 5, -1], [2, 7, 9]], np.int64)
    lbl = np.asarray([[11, 22, -1], [33, 44, 55]], np.int64)
    nsp = np.asarray([0, 1], np.int64)
    loss = model(jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(lbl),
                 jnp.asarray(nsp))
    assert np.isfinite(float(loss))
    # padded mask slot is ignored: altering its label changes nothing
    lbl2 = lbl.copy(); lbl2[0, 2] = 99
    loss2 = model(jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(lbl2),
                  jnp.asarray(nsp))
    np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-6)
    # grads flow into the tied word embedding through the decoder
    from paddle_tpu.nn import functional_call, param_state

    params = param_state(model)

    def f(p):
        out, _ = functional_call(model, p, {}, jnp.asarray(ids),
                                 jnp.asarray(pos), jnp.asarray(lbl),
                                 jnp.asarray(nsp))
        return out

    g = jax.grad(f)(params)
    key = [k for k in g if "word_embeddings" in k][0]
    assert float(jnp.abs(g[key]).sum()) > 0


@pytest.mark.slow   # ~15s backbone+loss+nms compile (tier-1 report)
def test_yolov3_detector_end_to_end():
    """The PP-YOLOE-class pipeline: conv backbone -> 3-scale heads ->
    vectorized yolo_loss training signal -> yolo_box + matrix_nms
    inference."""
    from paddle_tpu.models.yolo import YOLOv3

    paddle_tpu.seed(0)
    model = YOLOv3(num_classes=4, width=8)
    model.eval()
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.normal(size=(2, 3, 64, 64)).astype(np.float32))
    heads = model(imgs)
    assert [h.shape[2] for h in heads] == [2, 4, 8]  # strides 32/16/8
    assert heads[0].shape[1] == 3 * (5 + 4)

    gt = np.zeros((2, 3, 4), np.float32)
    gt[:, 0] = [0.5, 0.5, 0.4, 0.4]
    lbl = np.zeros((2, 3), np.int64)
    loss0 = float(model.loss(imgs, jnp.asarray(gt), jnp.asarray(lbl)))
    assert np.isfinite(loss0)

    # a few grad steps on the loss reduce it (jit-compiled whole pipeline)
    from paddle_tpu.nn import functional_call, param_state, buffer_state
    from paddle_tpu.nn.layer import Layer

    class _Wrap(Layer):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, imgs, gt, lbl):
            return self.m.loss(imgs, gt, lbl)

    wrap = _Wrap(model)
    wparams = param_state(wrap)
    wbufs = buffer_state(wrap)

    @jax.jit
    def wstep(p, b):
        def f(p):
            l, nb = functional_call(wrap, p, b, imgs, jnp.asarray(gt),
                                    jnp.asarray(lbl))
            return l, nb
        (l, nb), g = jax.value_and_grad(f, has_aux=True)(p)
        return l, jax.tree.map(lambda w, gg: w - 1e-3 * gg, p, g), nb

    losses = []
    for _ in range(8):
        l, wparams, wbufs = wstep(wparams, wbufs)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses

    # inference path: decode + matrix NMS produce [R, 6] rows
    dets, num = model.predict(imgs, [[64, 64], [64, 64]],
                              conf_thresh=0.05, keep_top_k=10)
    dets = np.asarray(dets)
    assert dets.ndim == 2 and dets.shape[1] == 6
    assert len(np.asarray(num)) == 2


# ------------------------------------------------------------ llama
def test_llama_forward_shapes_and_gqa():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(0)
    cfg = llama_tiny()  # num_heads=4, num_kv_heads=2 -> GQA path
    assert cfg.num_kv_heads == 2
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    logits = model(jnp.asarray(ids, jnp.int32))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_rope_properties():
    from paddle_tpu.models.llama import rotary_embed

    q = jnp.asarray(np.random.default_rng(1).normal(size=(1, 8, 2, 16)),
                    jnp.float32)
    k = q + 0.0
    qr, kr = rotary_embed(q, k, 10000.0)
    # rotation preserves per-head norms
    np.testing.assert_allclose(np.linalg.norm(np.asarray(q), axis=-1),
                               np.linalg.norm(np.asarray(qr), axis=-1),
                               rtol=1e-5)
    # relative-position property: dot(q_i, k_j) depends only on i - j
    qr2, kr2 = rotary_embed(q, k, 10000.0, position_offset=7)
    d1 = np.einsum("blhd,bmhd->bhlm", np.asarray(qr), np.asarray(kr))
    d2 = np.einsum("blhd,bmhd->bhlm", np.asarray(qr2), np.asarray(kr2))
    np.testing.assert_allclose(d1, d2, rtol=1e-4, atol=1e-4)


def test_llama_train_loss_decreases():
    from paddle_tpu.framework.jit import TrainStep
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.optimizer import AdamW

    pt.seed(1)
    cfg = llama_tiny(vocab_size=128, use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    step = TrainStep(model, AdamW(learning_rate=1e-3), loss_fn=None)
    ids = np.random.default_rng(1).integers(0, 128, (4, 32)).astype(np.int32)
    losses = [float(np.asarray(step((ids, ids)))) for _ in range(12)]
    assert losses[-1] < losses[0], losses


def test_llama_chunked_loss_matches_full():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(2)
    cfg = llama_tiny(vocab_size=128, use_flash_attention=False)
    full = LlamaForCausalLM(cfg)
    ids = jnp.asarray(
        np.random.default_rng(2).integers(0, 128, (2, 24)), jnp.int32)
    ref = float(full(ids, labels=ids))
    cfg2 = llama_tiny(vocab_size=128, use_flash_attention=False,
                      loss_chunk=8)
    chunked = LlamaForCausalLM(cfg2)
    chunked.set_state_dict(full.state_dict())
    np.testing.assert_allclose(float(chunked(ids, labels=ids)), ref,
                               rtol=2e-5)


def test_llama_zero3_sharded_step():
    """The BASELINE row: llama-family pretrain under sharding stage 3
    (ZeRO-3) on the virtual mesh."""
    from paddle_tpu.distributed.mesh import init_mesh, mesh_scope, set_mesh
    from paddle_tpu.distributed.shard import DistributedTrainStep
    from paddle_tpu.models.llama import (LlamaForCausalLM, llama_loss_fn,
                                         llama_tiny)
    from paddle_tpu.optimizer import AdamW

    m = init_mesh(sdp=8)
    with mesh_scope(m):
        pt.seed(3)
        cfg = llama_tiny(vocab_size=128, use_flash_attention=False)
        model = LlamaForCausalLM(cfg)
        step = DistributedTrainStep(
            model, AdamW(learning_rate=1e-3), loss_fn=llama_loss_fn(model),
            mesh=m, batch_axes=("sdp",), sharding_stage=3)
        ids = np.random.default_rng(3).integers(0, 128, (8, 16)).astype(
            np.int32)
        l0 = float(np.asarray(step((ids, ids))))
        l1 = float(np.asarray(step((ids, ids))))
        assert np.isfinite(l0) and l1 < l0
    set_mesh(None)


# ------------------------------------------------------------ ernie
def test_ernie_task_embedding_changes_output():
    from paddle_tpu.models.ernie import ErnieModel, ernie_tiny

    pt.seed(4)
    model = ErnieModel(ernie_tiny())
    model.eval()
    ids = jnp.asarray(
        np.random.default_rng(4).integers(1, 1000, (2, 12)), jnp.int32)
    seq0, _ = model(ids, task_type_ids=jnp.zeros_like(ids))
    seq1, _ = model(ids, task_type_ids=jnp.ones_like(ids))
    assert not np.allclose(np.asarray(seq0), np.asarray(seq1))
    assert np.isfinite(np.asarray(seq0)).all()


def test_ernie_finetune_trains():
    from paddle_tpu.framework.jit import TrainStep
    from paddle_tpu.models.ernie import (ErnieForSequenceClassification,
                                         ernie_tiny)
    from paddle_tpu.optimizer import AdamW

    pt.seed(5)
    model = ErnieForSequenceClassification(ernie_tiny(), num_classes=2)
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 1000, (8, 16)).astype(np.int32)
    labels = (ids.sum(1) % 2).astype(np.int64)  # learnable from tokens
    import paddle_tpu.nn.functional as F

    step = TrainStep(model, AdamW(learning_rate=5e-4),
                     loss_fn=lambda out, b: F.cross_entropy(out, b[1]),
                     inputs_fn=lambda b: (b[0],))
    losses = [float(np.asarray(step((ids, labels)))) for _ in range(15)]
    assert losses[-1] < losses[0], losses


def test_ernie_pretraining_loss_runs():
    from paddle_tpu.models.ernie import ErnieForPretraining, ernie_tiny

    pt.seed(6)
    model = ErnieForPretraining(ernie_tiny())
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(1, 1000, (2, 16)), jnp.int32)
    pos = jnp.asarray([[1, 5, -1], [2, 7, 9]], jnp.int32)
    lbl = jnp.asarray(rng.integers(1, 1000, (2, 3)), jnp.int32)
    nsp = jnp.asarray([0, 1], jnp.int32)
    loss = model(ids, pos, lbl, nsp,
                 task_type_ids=jnp.zeros_like(ids))
    assert np.isfinite(float(loss))
