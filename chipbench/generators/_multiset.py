"""Fixed multisets and fixed orders. The SHAPE of the work (which
bucket, which budget, which gap, in which order) is fixed by the traffic
file and its ``schedule_seed``; ``--seed`` permutes prompt lengths only
among requests that pad to the same bucket, and draws the token values.
Two seeds therefore give the system the same sequence of dispatches."""

from __future__ import annotations

import math

import numpy as np


def quantiles(spec, n: int) -> np.ndarray:
    """The n-point multiset of a distribution: its (i + 0.5) / n
    quantiles, ascending. ``spec`` is an int (constant) or
    ``{"dist": "uniform" | "log_uniform", "lo": a, "hi": b}``."""
    if isinstance(spec, (int, float)):
        return np.full(n, int(spec), np.int64)
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "uniform":
        vals = lo + u * (hi - lo)
    elif spec["dist"] == "log_uniform":
        vals = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.round(vals), lo, hi).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """The n-point multiset of Poisson inter-arrival gaps at ``rate``,
    scaled so that they sum to n / rate."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log(1.0 - u)
    return gaps * (n / rate) / gaps.sum()


def bucket_of(length: int, buckets) -> int:
    return next(b for b in sorted(buckets) if length <= b)


def fixed_order(values: np.ndarray, schedule_seed: int, salt: int):
    """``values`` in the order the traffic file's schedule seed fixes."""
    rng = np.random.RandomState((schedule_seed * 1000003 + salt) % 2 ** 32)
    return values[rng.permutation(len(values))]


def reorder_within_buckets(lengths: np.ndarray, buckets, rng) -> np.ndarray:
    """Permute ``lengths`` among the positions of the same bucket: the
    sequence of buckets stays, the lengths inside it follow ``rng``."""
    out = lengths.copy()
    cls = np.asarray([bucket_of(int(v), buckets) for v in lengths])
    for b in np.unique(cls):
        where = np.flatnonzero(cls == b)
        out[where] = lengths[where][rng.permutation(len(where))]
    return out


def prompts_for(lengths, vocab: int, rng) -> list:
    return [rng.randint(1, vocab, int(n)).astype(np.int64) for n in lengths]
