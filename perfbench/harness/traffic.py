"""One general traffic generator. A traffic mix is a data file of
parameters (``perfbench/traffic/<name>.json``); this module turns it and
``--seed`` into requests, arrival times or training batches.

Steadiness rule: every seed gets the SAME multiset of lengths and of
inter-arrival gaps (stratified quantiles of the distributions the file
names), in another order, and other token ids. A seed then changes which
request meets which, not how much work the window holds. It is the rule
the benchmark is written to ("give every seed the same set of sizes and
arrivals, in another order"): the gaps are the exponential's own
quantiles, so their distribution, the mean rate and the clustering a
permutation of them makes are a Poisson stream's; what is taken away is
the run-to-run variance of the COUNT of arrivals and of the work they
bring, which a check with six runs a side would read as noise.
"""

from __future__ import annotations

import json
import math
from statistics import NormalDist

import numpy as np

KINDS = ("closed", "open", "train")


def load(path):
    with open(path) as f:
        t = json.load(f)
    if t.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    return t


def _rng(seed, stream):
    # any whole number is a valid seed (the driver's pass 2**31)
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), int(stream)])


def lengths(spec, n):
    """n lengths: the (i + 0.5)/n quantiles of the named distribution,
    rounded and clipped to [min, max]. A multiset — the caller orders it."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    mu, sg = math.log(float(spec["median"])), float(spec["sigma"])
    vals = [math.exp(mu + sg * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def request_stream(traffic, vocab, seed):
    """Endless (prompt_ids int32[n], max_new_tokens) pairs. Each pass over
    the pool is a fresh permutation of the same prompt and output lengths,
    paired independently; ids are uniform over the vocabulary, so no two
    prompts share a prefix (the work counters rely on it: a mix with shared
    prefixes needs counters that know what the prefix cache served, and
    comes with them)."""
    pool = int(traffic["pool"])
    p_len = lengths(traffic["prompt"], pool)
    o_len = lengths(traffic["output"], pool)
    epoch = 0
    while True:
        rng = _rng(seed, 1000 + epoch)
        pp, oo = rng.permutation(p_len), rng.permutation(o_len)
        for n, k in zip(pp, oo):
            yield rng.integers(0, vocab, int(n), dtype=np.int64).astype(
                np.int32), int(k)
        epoch += 1


def arrival_times(traffic, seconds, seed):
    """Open-loop due times in [0, seconds): a Poisson stream (the same
    stratified exponential gaps for every seed, permuted) plus
    ``burst_size`` simultaneous arrivals every ``burst_every_s``.
    ``rate_rps`` is the TOTAL offered rate, bursts included."""
    rate = float(traffic["rate_rps"])
    every = float(traffic.get("burst_every_s", 0.0))
    size = int(traffic.get("burst_size", 0)) if every > 0 else 0
    base = rate - (size / every if every > 0 else 0.0)
    if base <= 0:
        raise ValueError("rate_rps must exceed the bursts' own rate")
    n = max(1, int(round(base * seconds)))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / base
                     for i in range(n)])
    times = np.cumsum(_rng(seed, 3).permutation(gaps))
    times = times[times < seconds].tolist()
    if every > 0:
        k = 1
        while k * every < seconds:
            times += [k * every] * size
            k += 1
    return sorted(times)


def train_batches(traffic, seed):
    """Endless int64 [batch, seq] id arrays drawn from the first
    ``corpus_ids`` ids of the vocabulary (so the loss can fall); every row
    differs."""
    b, s = int(traffic["batch"]), int(traffic["seq"])
    used = int(traffic["corpus_ids"])
    rng = _rng(seed, 11)
    while True:
        yield rng.integers(0, used, (b, s), dtype=np.int64)
