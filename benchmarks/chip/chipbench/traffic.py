"""The one traffic generator: requests from a mix's parameters and a seed.

Lengths and inter-arrival gaps come from fixed quantile grids of their
distributions (lognormal lengths clipped to the mix's range, exponential
gaps for Poisson arrivals), put in an order drawn once from the mix's own
``schedule_seed``. Every run therefore sends the same sizes at the same
times; the run's seed draws the prompt tokens (and, elsewhere, the
weights). The order is fixed because it alone moved the chat cell's
``ttft_p90_s`` from 1.9 s to 6.9 s between two seeds: a tail of queueing
behind a few long prompts that arrive close together.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Req:
    due_s: float  # offset from the start of the ramp (open loop; 0 closed)
    prompt: np.ndarray  # (P,) int32
    max_new: int


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths on the quantile grid of a clipped lognormal, shuffled."""
    z = np.array([NormalDist().inv_cdf(q) for q in _grid(n)])
    out = np.clip(
        np.rint(dist["median"] * np.exp(dist["sigma"] * z)), dist["min"], dist["max"]
    ).astype(np.int64)
    return rng.permutation(out)


def arrival_offsets(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """Poisson arrivals: shuffled exponential gaps on a quantile grid."""
    gaps = -np.log1p(-_grid(n)) / rate
    return np.cumsum(rng.permutation(gaps))


def build(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """Every request a run may send, in the order it sends them."""
    r_len, r_out, r_arr = (np.random.default_rng([mix["schedule_seed"], k]) for k in range(3))
    r_tok = np.random.default_rng([seed, 3])
    if mix["loop"] == "open":
        n = int(math.ceil(mix["rate_per_s"] * (mix["ramp_s"] + seconds))) + 2
        due = arrival_offsets(mix["rate_per_s"], n, r_arr)
    elif mix["loop"] == "closed":
        # Enough for every client to finish many requests in the window.
        n = max(4 * mix["clients"], 64 * int(math.ceil(seconds)))
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    p_len = lengths(mix["prompt_len"], n, r_len)
    o_len = lengths(mix["output_len"], n, r_out)
    return [
        Req(float(due[i]), r_tok.integers(0, vocab, size=int(p_len[i])).astype(np.int32),
            int(o_len[i]))
        for i in range(n)
    ]
