"""The least bytes one decode step of a serving engine must move between
the chip's memory and its cores, from a configuration's shapes and the
load the step served: every weight the step applies read once, and the
cached keys and values of the positions its live slots attend over read
once, the new token's written. What implements the step does not enter:
a program that reads the whole cache, or the weights once per slot, moves
more and shows a smaller share of the roofline; none can move less, so a
share computed from these bytes cannot pass 100 %.

A configuration names its function (``"decode_least_bytes":
"harness.decode_bytes:<function>"``); the reader of the share
(``layer_metrics/engine.decode_hbm_roofline.sat.py``) passes it the
configuration's ``config`` block, the bytes of one element, and the
step's load as the program's counters give it.
"""
from __future__ import annotations


def looped_decoder(cfg: dict, itemsize: int, live_slots: float,
                   live_positions: float) -> float:
    """A decoder whose ``num_layers`` blocks (four projections of
    attention, three of a gated MLP, four norm gains) run
    ``total_ut_steps`` times a token with the same weights, each run with
    cache entries of its own; then the final norm and an untied head.

    - weights: the memory holds one copy, but a step applies the layers
      ``total_ut_steps`` times and the batch of a decode step is far too
      small to keep 100 MB of a layer on the chip between two uses, so
      each application reads its layer again;
    - cache: ``live_positions`` is the sum over the live slots of the
      positions their query attends over (its own included); each is a
      key and a value in every one of the ``total_ut_steps * num_layers``
      entries; ``live_slots`` new keys and values are written to each;
    - the token's embedding row and the activations are thousands of
      bytes against billions and are left out.
    """
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    heads = cfg["num_heads"]
    kv_heads = cfg.get("num_kv_heads") or heads
    head_dim = h // heads
    steps, layers = cfg["total_ut_steps"], cfg["num_layers"]
    layer = (2 * h * heads * head_dim + 2 * h * kv_heads * head_dim
             + 3 * h * ffn + 4 * h)
    head = cfg["vocab_size"] * h + h
    weights = (steps * layers * layer + head) * itemsize
    entry = 2 * kv_heads * head_dim * itemsize      # a key and a value
    cache = steps * layers * entry * (live_positions + live_slots)
    return float(weights + cache)
