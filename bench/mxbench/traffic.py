"""The one traffic generator: a mix file of parameters in, requests out.

A mix (`bench/traffic/<name>.json`) gives open-loop arrivals at a fixed
rate, the untimed stretch `warm_s` that fills the batcher before the
window opens, and the length distributions.  Every seed gets the same
requests (prompt length paired with answer length) and the same arrival
times, from the stratified quantiles of the stated distributions:

* a fixed base order spreads the strata evenly over the run: request i
  takes the strata that rank the i-th point of a three-dimensional
  Kronecker sequence (Roberts' R3), one coordinate each for prompt length,
  answer length and the gap before it, so every stretch of a few arrivals
  holds short and long requests alike;
* the seed permutes the requests among the arrival times only within
  consecutive blocks of `order_block`, and draws the token ids.

So the work of a run, and where in the run each part of it falls to within
a block, is fixed; the seed moves only the order inside each block."""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due_s: float = 0.0  # arrival, seconds after the warm-up starts


# Roberts' R3: powers of 1/phi_3, phi_3 the real root of x^4 = x + 1
_PHI3 = 1.2207440846057596
_R3 = np.array([_PHI3 ** -1, _PHI3 ** -2, _PHI3 ** -3])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def spread_order(n: int) -> np.ndarray:
    """(n, 3) stratum ranks: column k ranks coordinate k of the first n
    points of R3, so consecutive rows fall in strata far apart."""
    pts = (0.5 + np.outer(np.arange(n), _R3)) % 1.0
    return np.argsort(np.argsort(pts, axis=0, kind="stable"), axis=0,
                      kind="stable")


def block_shuffle(n: int, block: int, rng) -> np.ndarray:
    """A permutation of range(n) that moves each index only within its
    block of ``block`` consecutive ones."""
    out = np.arange(n)
    for lo in range(0, n, block):
        out[lo:lo + block] = lo + rng.permutation(min(block, n - lo))
    return out


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the stratified quantiles of a lognormal (median, sigma)
    clipped to [min, max].  With ``page_multiple`` set, each length L has
    L - 1 a whole number of such pages (rounded to the nearest that lies in
    the range), which bounds the prefill tail-chunk shapes."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    raw = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                  spec["min"], spec["max"])
    page = spec.get("page_multiple")
    if not page:
        return np.round(raw).astype(np.int64)
    lo = -(-(spec["min"] - 1) // page)
    hi = (spec["max"] - 1) // page
    if lo > hi:
        raise ValueError(f"no length in [{spec['min']}, {spec['max']}] has "
                         f"L - 1 a multiple of {page}")
    k = np.clip(np.round((raw - 1) / page), lo, hi).astype(np.int64)
    return 1 + page * k


def count(mix: dict, seconds: float) -> int:
    """Requests in a plan: rate x (warm-up + window)."""
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return max(1, int(round(mix["rate_per_s"]
                            * (mix.get("warm_s", 0.0) + seconds))))


def plan(mix: dict, seconds: float, seed: int, vocab: int) -> List[Planned]:
    """The run's requests, sorted by due time."""
    n = count(mix, seconds)
    block = int(mix["order_block"])
    rng = np.random.default_rng(seed)
    rank = spread_order(n)
    p_len = lengths(mix["prompt"], n)[rank[:, 0]]
    o_len = lengths(mix["output"], n)[rank[:, 1]]
    gaps = (-np.log1p(-_quantiles(n)) / mix["rate_per_s"])[rank[:, 2]]
    order = block_shuffle(n, block, rng)
    out = [Planned(rid=i,
                   prompt=rng.integers(0, vocab, int(p_len[j]),
                                       dtype=np.int32),
                   max_new=int(o_len[j]))
           for i, j in enumerate(order)]
    for r, t in zip(out, np.cumsum(gaps)):
        r.due_s = float(t)
    return out


def prompt_lengths(mix: dict, seconds: float) -> np.ndarray:
    """Every prompt length a plan of this mix and window can hold (the
    same for every seed)."""
    return np.unique(lengths(mix["prompt"], count(mix, seconds)))


def max_tokens(mix: dict) -> int:
    """The longest prompt plus the longest answer the mix can draw."""
    return int(lengths(mix["prompt"], 1_000).max()
               + lengths(mix["output"], 1_000).max())
