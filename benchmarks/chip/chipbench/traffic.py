"""The one generator of traffic, driven by a mix's data file.

A training mix (``"kind": "train"``) names the batch and sequence length;
the trainer's own feed makes the rows.  A serving mix (``"kind": "serve"``)
names the engine's slots and capacity and two length distributions:

    "prompt": {"median": 2048, "sigma": 0.5, "min": 512, "max": 3840,
               "grid": 256}
    "output": {"median": 32, "sigma": 0.8, "min": 8, "max": 128}

Lengths are lognormal with that median and sigma, clipped to [min, max];
prompt lengths are then rounded up to a multiple of ``grid``.  So that
every seed gets the same work, the lengths are not drawn per seed: a
*deck* of ``deck`` requests takes its lengths at the deck's evenly spaced
quantiles, prompt and output lengths paired by a fixed permutation.  The
queue is that deck again and again, each copy in a fixed shuffled order, so
that a window that ends part-way through a copy does the same work for
every seed; prompt tokens are drawn from the seed uniformly over the
vocabulary.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantile_lengths(dist: dict, n: int) -> np.ndarray:
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    raw = np.exp(math.log(dist["median"]) + dist["sigma"] * np.asarray(z))
    out = np.clip(np.round(raw), dist["min"], dist["max"]).astype(np.int64)
    grid = dist.get("grid", 1)
    return np.minimum(-(-out // grid) * grid, dist["max"])


def deck(mix: dict) -> list:
    """The deck's (prompt_len, max_new_tokens) pairs, the same for every
    seed."""
    n = mix["deck"]
    prompts = _quantile_lengths(mix["prompt"], n)
    outputs = _quantile_lengths(mix["output"], n)
    pair = np.random.default_rng(0).permutation(n)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs[pair])]


def grid_lengths(mix: dict) -> list:
    """Every prompt length the mix can send."""
    p = mix["prompt"]
    g = p.get("grid", 1)
    return list(range(-(-p["min"] // g) * g, p["max"] + 1, g))


def requests(mix: dict, rng: np.random.Generator, n: int, vocab: int) -> list:
    """n requests as (prompt int32 array, max_new_tokens)."""
    d = deck(mix)
    order = np.random.default_rng(1)
    out = []
    while len(out) < n:
        for i in order.permutation(len(d)):
            p, o = d[i]
            out.append((rng.integers(0, vocab, p, dtype=np.int32), o))
    return out[:n]


def warmup_requests(mix: dict, rng: np.random.Generator, vocab: int) -> list:
    """One request of every prompt length on the grid, and at least one in
    every slot; two tokens each, so that each is prefilled, spliced into
    its slot and decoded once."""
    lengths = grid_lengths(mix)
    n = max(len(lengths), mix["slots"])
    return [(rng.integers(0, vocab, lengths[i % len(lengths)], dtype=np.int32),
             2) for i in range(n)]
