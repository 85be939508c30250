"""The one generator of the benchmark's traffic, read from a mix's data
file (``bench/mixes/<name>.json``).

Every seed gets the same requests in the same order: each length
distribution is sampled at evenly spaced quantiles, prompt and output
lengths are paired by one fixed permutation, and the backlog is queued
longest first.  A seed draws only which users send them and the tokens in
them, so it changes the inputs and not the work: the time a closed backlog
takes on a fixed number of slots depends on the order it is queued in.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

# the permutation that pairs output lengths with prompt lengths; a
# constant, so every seed gets the same pairs
PAIRING_SEED = 0


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (k + 1/2) / n of ``spec``:
    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    {"dist": "uniform", "min", "max"} (both ends included)."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_users(rng: np.random.Generator, users: int, a: float, n: int):
    """``n`` bank rows drawn with P(row k) proportional to 1 / (k + 1)^a."""
    p = 1.0 / np.arange(1, users + 1) ** a
    return rng.choice(users, size=n, p=p / p.sum())


def shapes(mix: dict) -> list:
    """The backlog's (prompt length, output length) pairs, longest first;
    the same for every seed."""
    n = mix["requests"]
    prompt = lengths(mix["prompt"], n)
    output = lengths(mix["output"], n)[
        np.random.default_rng(PAIRING_SEED).permutation(n)]
    order = sorted(range(n), key=lambda i: (-(prompt[i] + output[i]), i))
    return [(int(prompt[i]), int(output[i])) for i in order]


def requests(mix: dict, seed: int, vocab: int) -> list:
    """The requests of one run: (user row, prompt tokens, output length),
    ``mix["requests"]`` of them in the order of ``shapes``."""
    rng = np.random.default_rng(seed)
    pairs = shapes(mix)
    users = zipf_users(rng, mix["users"], mix["zipf_a"], len(pairs))
    return [(int(u), rng.integers(0, vocab, p).astype(np.int32), g)
            for u, (p, g) in zip(users, pairs)]


def check_sample(reqs: list, seed: int, tokens: int) -> list:
    """Indices of the requests the correctness check reads: the one with
    the most output tokens, then others in a seeded order until the sample
    holds ``tokens`` output tokens."""
    order = np.random.default_rng(seed + 1).permutation(len(reqs)).tolist()
    longest = max(range(len(reqs)), key=lambda i: (reqs[i][2], -i))
    order.remove(longest)
    pick, total = [longest], reqs[longest][2]
    for i in order:
        if total >= tokens:
            break
        pick.append(i)
        total += reqs[i][2]
    return pick
