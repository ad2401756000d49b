"""Stratified request lengths: the n requests of a stretch take the n
evenly spaced quantiles of a distribution, so every seed offers the same
multiset of lengths (the same tokens, the same work) in another order."""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict

import numpy as np


def quantiles(spec: Dict, n: int) -> np.ndarray:
    """The n mid-point quantiles ((i + 0.5) / n) of ``spec``, clipped and
    rounded to whole tokens. ``spec`` is
    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b} or
    {"dist": "uniform", "min": a, "max": b}."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def paired(prompt: Dict, output: Dict, n: int, rng: np.random.Generator):
    """n (prompt_len, output_len) pairs: each side stratified, paired and
    ordered by permutations from the seed."""
    p = quantiles(prompt, n)[rng.permutation(n)]
    o = quantiles(output, n)[rng.permutation(n)]
    return p, o


def seeded(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))
