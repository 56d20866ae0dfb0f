"""The least bytes one decode step of a HYBRID state-space / attention
decoder must move between the chip's memory and its cores: as
:mod:`decode_bytes` counts them for a dense attention decoder (every
weight the step applies read once, the cached keys and values of the
positions its live slots attend over read once, the new token's written),
plus what the recurrent layers carry: each live slot's scan state and
convolution window read and written once a layer, whatever the slot's
position. A free slot's state is not counted (a program that advances it
moves more and shows a smaller share), so no program can move less and a
share computed from these bytes cannot pass 100 %.

A configuration names its function (``"decode_least_bytes":
"harness.decode_bytes_hybrid:<function>"``); the reader of the share
(``layer_metrics/engine.decode_hbm_roofline.sat.py``) passes it the
configuration's ``config`` block, the bytes of one element, and the
step's load as the program's counters give it.
"""
from __future__ import annotations

STATE_ITEMSIZE = 4      # the scan state is float32 whatever the weights are


def _shapes(cfg: dict):
    layers = cfg["num_layers"]
    attention = sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
                    for i in range(layers))
    return layers - attention, attention, cfg["mamba_expand"] * cfg["hidden_size"]


def state_bytes(cfg: dict, itemsize: int, live_slots: float) -> float:
    """The recurrent state's part of :func:`hybrid_ssm_decoder`: per Mamba
    layer and live slot the scan state ``[mamba_d_state, d]`` (float32)
    and the window of ``mamba_d_conv - 1`` inputs ``[.., d]`` (the
    weights' type), each read and written."""
    mamba, _, d = _shapes(cfg)
    slot = (cfg["mamba_d_state"] * d * STATE_ITEMSIZE
            + (cfg["mamba_d_conv"] - 1) * d * itemsize)
    return float(mamba * live_slots * 2 * slot)


def hybrid_ssm_decoder(cfg: dict, itemsize: int, live_slots: float,
                       live_positions: float) -> float:
    """A decoder of ``num_layers`` blocks, each a mixer and a SwiGLU of
    ``intermediate_size`` behind two norm gains: Mamba-1 mixers
    (``in_proj``, a depthwise convolution with bias, ``x_proj``, three
    inner norm gains, ``dt_proj`` with bias, ``A_log``, ``D``,
    ``out_proj``) but for the layers ``i % attn_layer_period ==
    attn_layer_offset``, which are grouped-query attention; then the final
    norm and a head TIED to the embedding.

    - weights: every parameter once; the tied matrix once, as the head
      (the token's embedding rows are thousands of bytes and left out);
      ``A_log`` and ``D`` at 4 bytes;
    - state: :func:`state_bytes`;
    - keys and values: per attention layer a key and a value of
      ``num_kv_heads`` heads a position; ``live_positions`` (the sum over
      the live slots of the positions their query attends over, its own
      included) are read and ``live_slots`` written.
    """
    C, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    heads = cfg["num_heads"]
    kv_heads = cfg.get("num_kv_heads") or heads
    head_dim = C // heads
    n, K, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    mamba, attention, d = _shapes(cfg)
    every_layer = 3 * C * ffn + 2 * C
    mamba_mixer = (C * 2 * d + K * d + d + d * (r + 2 * n) + r + 2 * n
                   + r * d + d + d * C) * itemsize \
        + (n * d + d) * STATE_ITEMSIZE
    attention_mixer = (2 * C * heads * head_dim
                       + 2 * C * kv_heads * head_dim) * itemsize
    head = cfg["vocab_size"] * C + C
    weights = ((mamba + attention) * every_layer + head) * itemsize \
        + mamba * mamba_mixer + attention * attention_mixer
    entry = 2 * kv_heads * head_dim * itemsize      # a key and a value
    cache = attention * entry * (live_positions + live_slots)
    return float(weights + state_bytes(cfg, itemsize, live_slots) + cache)
