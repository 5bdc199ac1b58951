"""The one load generator: requests and arrivals from a mix file and a seed.

A mix file holds parameters only::

    {"loop": "closed", "clients": 8, "requests": 512, "block": 32,
     "prompt": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                "min": 32, "max": 192},
     "output": {"dist": "uniform", "min": 16, "max": 64},
     "arrivals": {"dist": "gamma", "shape": 0.25, "rate": 4.0},
     "schedule_seed": 1}

Lengths (and open-loop gaps) are drawn per block of ``block`` requests at
the block's evenly spaced quantiles, then shuffled: every run sends the
same sizes and gaps per block, so a seed never changes the amount of work.
The shuffle follows the run's seed, or, where the mix states a
``schedule_seed``, that seed: then every run replays one schedule of
arrivals and lengths, and the run's seed draws only the token ids (a tail
over some tens of bursty requests swings with the order alone). Gaps are
rescaled so that every block's mean rate is exactly ``rate``. Token ids are
uniform over the vocabulary.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np
from scipy.special import gammaincinv


@dataclasses.dataclass
class RequestSpec:
    uid: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    due: float | None       # seconds after the window opens (open loop)


def rng_for(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, q: np.ndarray) -> np.ndarray:
    """Integer lengths at quantiles ``q`` of a length distribution."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    elif spec["dist"] == "uniform":
        x = np.floor(lo + q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def gaps(spec: dict, q: np.ndarray) -> np.ndarray:
    """Inter-arrival gaps (s) at quantiles ``q``, mean exactly 1/rate."""
    if spec["dist"] != "gamma":
        raise ValueError(f"unknown arrival distribution {spec['dist']!r}")
    g = gammaincinv(spec["shape"], q)
    return g * (len(q) / spec["rate"]) / g.sum()


def request_count(mix: dict, seconds: float) -> int:
    """Requests to draw: the closed-loop pool, or enough arrivals to cover
    the window twice over."""
    block = mix["block"]
    if mix["loop"] == "closed":
        n = mix["requests"]
    else:
        n = 2 * mix["arrivals"]["rate"] * seconds + block
    return int(math.ceil(n / block) * block)


def make_requests(mix: dict, vocab: int, seed: int,
                  seconds: float) -> list:
    rng = rng_for(seed, 0xB0)
    order = (rng_for(mix["schedule_seed"], 0xA1) if "schedule_seed" in mix
             else rng)
    block = mix["block"]
    q = quantiles(block)
    base = {k: lengths(mix[k], q) for k in ("prompt", "output")}
    base_gaps = gaps(mix["arrivals"], q) if mix["loop"] == "open" else None
    out, t = [], 0.0
    for b in range(request_count(mix, seconds) // block):
        plen = order.permutation(base["prompt"])
        olen = order.permutation(base["output"])
        gap = None if base_gaps is None else order.permutation(base_gaps)
        for i in range(block):
            due = None
            if gap is not None:
                due = t
                t += float(gap[i])
            out.append(RequestSpec(
                uid=b * block + i,
                prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                max_new=int(olen[i]), due=due))
    return out
