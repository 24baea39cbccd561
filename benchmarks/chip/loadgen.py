"""The one traffic generator: reads a mix's parameters and draws the
requests of a run from ``--seed``.

Every seed gets the same multiset of sizes and inter-arrival gaps, in
another order: sizes and gaps are the stratified quantiles of their
distributions, in an order drawn from the seed that is balanced: each
run of BLOCK consecutive requests takes one value from each of BLOCK
equal strata. The seed so changes which request comes when, and the
token ids or pixels, but little the amount of work in any stretch of
BLOCK requests. Which values of a stratum fall into the window is the
seed's, so a tail over few requests (the p95 of long prompts) still
differs from seed to seed more than between two runs of one seed.

The arrival process copies ``benchmarks/loadgen.py::build_workload``
(exponential gaps at the offered rate: a Poisson open loop) without its
virtual clock: the run's driver offers each request at its due time on
the host clock.

Distributions (a mix's ``prompt_len`` / ``output_len`` entries):
  {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  {"dist": "uniform", "min": a, "max": b}          integers, both ends in
"""
from __future__ import annotations

import math
import statistics

import numpy as np

# stream ids: one generator per kind of draw, so adding a draw of one
# kind never shifts another
_SIZES, _GAPS, _TOKENS, _PIXELS, _SAMPLE = range(5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, stream])


BLOCK = 16


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def balanced(sorted_vals: np.ndarray, rng: np.random.Generator,
             block: int = BLOCK) -> np.ndarray:
    """``sorted_vals`` reordered so that every run of ``block``
    consecutive entries holds one value of each of ``block`` strata (runs
    of consecutive sorted values); which one, and the order inside a run,
    are drawn from ``rng``."""
    n = len(sorted_vals)
    nb = -(-n // block)
    grid = np.full(block * nb, -1)
    grid[:n] = np.arange(n)
    grid = np.stack([rng.permutation(r) for r in grid.reshape(block, nb)])
    order = np.concatenate([rng.permutation(grid[:, b]) for b in range(nb)])
    return sorted_vals[order[order >= 0]]


def stratified(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer sizes: the distribution's quantiles at (i + 1/2)/n,
    clipped to [min, max], in a balanced order drawn from ``rng``."""
    q = _quantiles(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(v) for v in q])
        vals = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        vals = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    vals = np.clip(vals, dist["min"], dist["max"]).astype(np.int64)
    return balanced(vals, rng)


def arrival_offsets(rate: float, n: int, rng: np.random.Generator
                    ) -> np.ndarray:
    """Seconds from the window's start at which each of ``n`` requests
    is due: exponential gaps at ``rate`` per second (stratified)."""
    gaps = -np.log1p(-_quantiles(n)) / rate
    return np.cumsum(balanced(gaps, rng))


def n_requests(traffic: dict, seconds: float) -> int:
    """Requests drawn for a run: enough for the window, the drain after
    it, and a few seconds more."""
    span = seconds + traffic.get("drain_s", 0) + 5.0
    return int(math.ceil(traffic["rate_per_s"] * span))


def lm_requests(traffic: dict, vocab: int, seconds: float, seed: int):
    """(offsets, prompts, max_new) for an LM mix."""
    n = n_requests(traffic, seconds)
    rs = rng_for(seed, _SIZES)
    plens = stratified(traffic["prompt_len"], n, rs)
    outs = stratified(traffic["output_len"], n, rs)
    offsets = arrival_offsets(traffic["rate_per_s"], n,
                              rng_for(seed, _GAPS))
    rt = rng_for(seed, _TOKENS)
    prompts = [rt.integers(0, vocab, size=int(p), dtype=np.int32)
               for p in plens]
    return offsets, prompts, [int(o) for o in outs]


def images(traffic: dict, shape: tuple, seconds: float, seed: int):
    """(offsets, images) for an image mix: pixels uniform in [0, 1) on a
    grid of 1/``grid`` (every grid value is exact in float32)."""
    n = n_requests(traffic, seconds)
    offsets = arrival_offsets(traffic["rate_per_s"], n,
                              rng_for(seed, _GAPS))
    grid = traffic["pixel_grid"]
    codes = rng_for(seed, _PIXELS).integers(0, grid, size=(n, *shape),
                                            dtype=np.int32)
    return offsets, codes.astype(np.float32) / grid


def sample(indices: list, k: int, must: list, seed: int) -> list:
    """``must`` plus up to ``k - len(must)`` others of ``indices``, drawn
    from the seed; sorted."""
    rest = sorted(set(indices) - set(must))
    rng = rng_for(seed, _SAMPLE)
    take = max(0, min(k - len(must), len(rest)))
    pick = list(rng.choice(rest, size=take, replace=False)) if take else []
    return sorted(set(must) | {int(i) for i in pick})
