"""The one traffic generator. A cell's ``traffic`` block is parameters
only: arrivals (open loop at a rate, Poisson or gamma-bursty; or a closed
loop of clients), prompt and output lengths (a distribution clipped to a
range), and how much of each prompt is shared. A new mix is a new data
file, never new code.

Steadiness rule: the *set* of request sizes and inter-arrival gaps of a
cell depends only on the cell's ``shape_seed`` and the window length;
``--seed`` decides the order they come in and the token values. Two runs
with different seeds therefore offer the same work in another order, and
every request is drawn before the window opens.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from .common import seeded_rng as _rng


@dataclasses.dataclass
class Req:
    due_s: float              # seconds after the window opens (open loop)
    prompt: np.ndarray        # int32 [prompt_len]
    max_new_tokens: int


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole token counts from ``spec``: ``{"dist": "lognormal",
    "median", "sigma"}``, ``{"dist": "uniform"}`` or ``{"dist": "fixed",
    "value"}``, clipped to ``[min, max]``."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"], n)
    elif dist == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", np.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def draw_gaps(arrivals: dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a renewal process, scaled so that they
    fill ``seconds`` exactly: every run of the cell then has the same
    ``n`` requests due inside its window. ``poisson`` gives exponential
    gaps; ``gamma`` with coefficient of variation ``cv`` gives bursts
    (cv 1 is Poisson again)."""
    process = arrivals["process"]
    if process == "poisson":
        g = rng.exponential(1.0, n)
    elif process == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = rng.gamma(shape, 1.0 / shape, n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g * (seconds / g.sum())


def _sizes(traffic: dict, n: int, rng: np.random.Generator):
    prompts = draw_lengths(traffic["prompt_tokens"], n, rng)
    outputs = draw_lengths(traffic["output_tokens"], n, rng)
    # a request must fit the cache: trim the answer, never the question
    outputs = np.minimum(outputs, traffic["max_total_tokens"] - prompts)
    if outputs.min() < 1:
        raise ValueError("prompt_tokens.max leaves no room under "
                         "max_total_tokens")
    return prompts, outputs


def _prompts(traffic: dict, lengths, vocab_size: int,
             rng: np.random.Generator) -> List[np.ndarray]:
    """Token values. ``shared_prefix: {"tokens": T, "groups": G}`` makes
    the first ``min(T, len - 1)`` tokens of each prompt one of ``G``
    seeded prefixes; without it every prompt is distinct."""
    shared = traffic.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = rng.integers(0, vocab_size,
                                (shared["groups"], shared["tokens"]),
                                dtype=np.int32)
    out = []
    for length in lengths:
        p = rng.integers(0, vocab_size, int(length), dtype=np.int32)
        if prefixes is not None:
            t = min(shared["tokens"], int(length) - 1)
            p[:t] = prefixes[rng.integers(0, shared["groups"])][:t]
        out.append(p)
    return out


def open_loop(traffic: dict, seconds: float, seed: int,
              vocab_size: int) -> List[Req]:
    """The requests due in a window of ``seconds``, in due order."""
    arrivals = traffic["arrivals"]
    n = max(1, int(round(arrivals["rate_rps"] * seconds)))
    shape = _rng(traffic["shape_seed"], n)
    gaps = draw_gaps(arrivals, n, seconds, shape)
    prompts, outputs = _sizes(traffic, n, shape)
    order = _rng(seed, 1)
    gaps = order.permutation(gaps)
    pick = order.permutation(n)
    # the first request is due half a gap in, the last half a gap from
    # the end: all n lie strictly inside the window
    due = np.cumsum(gaps) - gaps[0] / 2
    toks = _prompts(traffic, prompts[pick], vocab_size, _rng(seed, 2))
    return [Req(float(d), p, int(o))
            for d, p, o in zip(due, toks, outputs[pick])]


def closed_loop(traffic: dict, seed: int,
                vocab_size: int) -> List[List[Req]]:
    """One list of requests a client; a client sends its next when the
    last is answered and starts over if it ever reaches the end."""
    arrivals = traffic["arrivals"]
    clients = int(arrivals["clients"])
    per_client = int(arrivals["requests_per_client"])
    n = clients * per_client
    prompts, outputs = _sizes(traffic, n, _rng(traffic["shape_seed"], n))
    pick = _rng(seed, 1).permutation(n)
    toks = _prompts(traffic, prompts[pick], vocab_size, _rng(seed, 2))
    reqs = [Req(0.0, p, int(o)) for p, o in zip(toks, outputs[pick])]
    return [reqs[c * per_client:(c + 1) * per_client]
            for c in range(clients)]
