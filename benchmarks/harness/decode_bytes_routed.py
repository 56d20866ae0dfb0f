"""The least bytes one decode step of a SPARSE decoder must move between
the chip's memory and its cores: as :mod:`decode_bytes` counts them for a
dense one (every weight the step applies read once, the cached entries of
the positions its live slots attend over read once, the new token's
written), except that an expert's weights count only where a token of the
step was routed to it. Which experts a step touches is the traffic's and
the router's doing, not the program's, so it is an argument: the mean, per
expert layer, of the experts that at least one live slot picked, as the
program's counters give it (``ServingMetrics.snapshot()["moe"]``).
Untouched experts are not counted, so no program can move less and a
share computed from these bytes cannot pass 100 %.

A configuration names its function (``"decode_least_bytes_routed":
"harness.decode_bytes_routed:<function>"``); the reader of the share
(``layer_metrics/engine.decode_hbm_roofline.routed.py``) passes it the
configuration's ``config`` block, the bytes of one element, and the
step's load.
"""
from __future__ import annotations


def latent_moe_decoder(cfg: dict, itemsize: int, live_slots: float,
                       live_positions: float,
                       experts_touched: float) -> float:
    """A decoder of ``num_layers`` blocks with latent attention (a query
    bottleneck, one compressed key-value vector and one shared rotated
    key a position), ``hc_mult`` residual streams with two mixers a
    block, a SwiGLU of ``intermediate_size`` in the first
    ``first_k_dense_replace`` blocks and, in the others, a router over
    ``n_routed_experts`` experts of ``moe_intermediate_size`` with
    ``n_shared_experts`` shared; then the final norm and an untied head.

    - weights outside the routed experts: read once a step;
    - routed experts: ``experts_touched`` of them in each expert layer,
      three matrices each;
    - cache: a latent entry a layer, ``kv_lora_rank + qk_rope_head_dim``
      numbers a position; ``live_positions`` (the sum over the live slots
      of the positions their query attends over, its own included) are
      read and ``live_slots`` written in each;
    - the token's embedding row, the activations and the four float32
      streams are thousands of bytes against billions and are left out.
    """
    C, H = cfg["hidden_size"], cfg["num_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, q_rank, v = cfg["kv_lora_rank"], cfg["q_lora_rank"], cfg["v_head_dim"]
    n = cfg["hc_mult"]
    layers, dense = cfg["num_layers"], cfg["first_k_dense_replace"]
    attention = (C * q_rank + q_rank + q_rank * H * (nope + rope)
                 + C * (rank + rope) + rank + rank * H * (nope + v)
                 + H * v * C)
    mixers = 2 * (n * C * (n * n + 2 * n) + 3 + (n * n + 2 * n))
    every_layer = attention + mixers + 2 * C            # and two norm gains
    dense_ffn = 3 * C * cfg["intermediate_size"]
    expert = 3 * C * cfg["moe_intermediate_size"]
    sparse_ffn = (C * cfg["n_routed_experts"] + cfg["n_routed_experts"]
                  + cfg["n_shared_experts"] * expert
                  + experts_touched * expert)
    head = cfg["vocab_size"] * C + C
    weights = (layers * every_layer + dense * dense_ffn
               + (layers - dense) * sparse_ffn + head) * itemsize
    entry = (rank + rope) * itemsize
    cache = layers * entry * (live_positions + live_slots)
    return float(weights + cache)
