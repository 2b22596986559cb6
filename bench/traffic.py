"""The one traffic generator: a mix file's parameters and a seed -> requests.

A mix (``bench/traffic/<mix>.json``) gives lengths as clipped lognormals
and the loop as open (Poisson arrivals at a rate the cell fixes) or closed
(a fixed number of requests outstanding).  Requests come in blocks of
``block``: within a block the lengths sit at the midpoints of ``block``
equal-probability strata of the distribution, and the inter-arrival gaps
at the midpoints of ``block`` strata of the exponential, in an order drawn
once from the mix's ``order_seed``.  So every run seed offers the same
sizes at the same times, and the seed draws the token ids (and, through
``bench/weights.py``, the weights): runs with different seeds do the same
work, as two runs of one seed do.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

# token ids 0 and 1 are the engine's pad and end-of-sequence ids: prompts
# never hold them
FIRST_TOKEN_ID = 2


@dataclass
class Job:
    """One request as the generator makes it."""
    index: int
    prompt: np.ndarray          # (L,) int32
    max_new_tokens: int
    greedy: bool
    due_s: float = 0.0          # open loop: offset from the first arrival


def seed_words(seed: int, salt: str) -> np.random.Generator:
    """A numpy generator for (seed, salt); any non-negative integer seed."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), int(seed) >> 64,
                                 *map(ord, salt)])
    return np.random.default_rng(ss)


def seed32(seed: int, salt: str) -> int:
    """A 32-bit integer from (seed, salt), for PRNG keys and engine seeds."""
    return int(seed_words(seed, salt).integers(0, 2**31 - 1))


def stratified(dist: dict, n: int) -> np.ndarray:
    """The midpoints of n equal-probability strata of a clipped lognormal
    (``median``, ``sigma``, ``min``, ``max``), as integers."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(n: int) -> np.ndarray:
    """Midpoints of n equal-probability strata of Exp(1), scaled so that
    they sum to exactly n (the block keeps the rate exactly)."""
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (n / g.sum())


def make_jobs(mix: dict, seed: int, n_jobs: int, vocab: int,
              rate_rps: float = 0.0) -> list:
    """-> the first n_jobs requests of the mix, with this seed's tokens.

    Open loops (``rate_rps`` > 0) give each job a due time; the first job is
    due at 0.  ``greedy_every`` k marks one job in every k as greedy (the
    jobs whose served tokens the correctness check can compare)."""
    block = int(mix.get("block", 32))
    greedy_every = int(mix.get("greedy_every", 1))
    order = seed_words(int(mix.get("order_seed", 0)), "order")
    rng = seed_words(seed, "tokens")
    p_base = stratified(mix["prompt"], block)
    o_base = stratified(mix["output"], block)
    n_blocks = -(-n_jobs // block)
    jobs, t = [], 0.0
    for blk in range(n_blocks):
        p_len = order.permutation(p_base)
        o_len = order.permutation(o_base)
        gaps = order.permutation(exp_gaps(block))
        greedy = order.permutation(block) % greedy_every == 0
        for i in range(block):
            idx = blk * block + i
            if idx >= n_jobs:
                break
            prompt = rng.integers(FIRST_TOKEN_ID, vocab, int(p_len[i]),
                                  dtype=np.int64).astype(np.int32)
            jobs.append(Job(index=idx, prompt=prompt,
                            max_new_tokens=int(o_len[i]),
                            greedy=bool(greedy[i]), due_s=t))
            if rate_rps > 0:
                t += gaps[i] / rate_rps
    return jobs

