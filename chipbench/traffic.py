"""One general generator for every traffic mix.

A mix is a data file (``chipbench/traffic/<name>.json``) of parameters:
clipped log-normal input and output lengths and an arrival process. The
arithmetic is copied from the program's own samplers
(``serving/trace.py`` ``sample_lengths``, ``workloads/arrivals.py``
``PoissonProcess``), so that later changes to the program cannot move the
yardstick.

The set of sizes and the arrival times come from the mix's own
``size_seed`` and are the same for every run seed; the run seed only
shuffles which size lands on which arrival (inside the warm span and
inside the window separately) and draws the prompt tokens. Every seed
therefore offers the same work, in another order.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

_TOKENS = 0x544F4B      # rng stream of prompt tokens
_ORDER = 0x4F5244       # rng stream of the size shuffle


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    index: int
    due_s: float            # offset from the start of the warm span
    prompt: np.ndarray      # int32 token ids
    output_len: int
    in_window: bool


def lognormal_lengths(rng, n: int, mean: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """Clipped log-normal lengths whose unclipped mean is ``mean``."""
    mu = math.log(mean) - sigma ** 2 / 2.0
    return np.clip(rng.lognormal(mu, sigma, n).astype(int), lo, hi)


def arrival_times(rng, rate: float, horizon_s: float, kind: str) -> np.ndarray:
    """Arrival offsets in ``[0, horizon_s)`` of an open-loop process."""
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    n = int(rate * horizon_s * 2 + 64)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    if t[-1] < horizon_s:
        raise ValueError("arrival draw too short for the horizon")
    return t[t < horizon_s]


def plan(mix: dict, rate: float, warm_s: float, window_s: float, seed: int,
         vocab_size: int) -> List[Planned]:
    """The requests of one run: due offsets over the warm span and the
    window, sizes from the mix, tokens and order from ``seed``."""
    size_rng = np.random.default_rng(mix["size_seed"])
    due = arrival_times(size_rng, rate, warm_s + window_s, mix["arrival"])
    n = len(due)
    li, lo = mix["input_len"], mix["output_len"]
    ins = lognormal_lengths(size_rng, n, li["mean"], li["sigma"], li["min"],
                            li["max"])
    outs = lognormal_lengths(size_rng, n, lo["mean"], lo["sigma"], lo["min"],
                             lo["max"])
    in_window = due >= warm_s
    order = np.arange(n)
    shuffle = np.random.default_rng([_ORDER, int(seed)])
    for part in (~in_window, in_window):
        idx = np.nonzero(part)[0]
        order[idx] = idx[shuffle.permutation(len(idx))]
    tok = np.random.default_rng([_TOKENS, int(seed)])
    out = []
    for i in range(n):
        j = order[i]
        prompt = tok.integers(0, vocab_size, int(ins[j])).astype(np.int32)
        out.append(Planned(i, float(due[i]), prompt, int(outs[j]),
                           bool(in_window[i])))
    return out


def length_bounds(mix: dict) -> tuple:
    """(longest prompt, longest output) the mix can produce."""
    return mix["input_len"]["max"], mix["output_len"]["max"]
