"""The one traffic generator: every mix is a data file under `traffic/`.

An open-loop mix (`"loop": "open"`) describes Poisson arrivals at a fixed
rate, and prompt and output lengths that follow clipped lognormal laws.
The set of prompt lengths, of output lengths and of gaps between arrivals
is the same for every seed: each is the law's quantile at evenly spaced
probabilities.  The seed orders each set on its own (a uniform
permutation), so it decides which prompt goes with which output and when
each request arrives, and it draws token ids and temperatures.  So every
seed offers the same work, in another order.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

#: large seeds (more than 32 bits) are folded into numpy's seed sequence
SEED_SALT = 0x5EED


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray          # (P,) int32 token ids
    max_new_tokens: int
    temperature: float
    arrival_s: float


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any whole-number seed."""
    key = [SEED_SALT, seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF]
    key += [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(key))


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> List[int]:
    """`n` whole lengths at the quantiles (i + 0.5) / n of a lognormal
    with the given median and shape, clipped to [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def exponential_quantiles(n: int, mean: float) -> List[float]:
    return [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def request_count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def open_loop(mix: dict, seconds: float, seed: int, vocab_size: int
              ) -> List[Req]:
    """The requests of one window of `seconds` of an open-loop mix."""
    n = request_count(mix, seconds)
    p, o = mix["prompt_len"], mix["output_len"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    gaps = exponential_quantiles(n, 1.0 / mix["rate_per_s"])
    temps = [mix["temperatures"][i % len(mix["temperatures"])]
             for i in range(n)]
    rng = rng_for(seed, "traffic")
    prompts, outputs, gaps, temps = (
        [v[k] for k in rng.permutation(n)]
        for v in (prompts, outputs, gaps, temps))
    t = 0.0
    out: List[Req] = []
    for rid in range(n):
        t += gaps[rid]
        out.append(Req(
            rid=rid,
            prompt=rng.integers(1, vocab_size, prompts[rid]).astype(np.int32),
            max_new_tokens=outputs[rid],
            temperature=float(temps[rid]),
            arrival_s=t))
    return out
