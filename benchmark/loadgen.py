"""Seeded, replayable traffic from a data file of parameters.

The arrival processes are ``tools/loadgen.py``'s (``poisson``, ``bursty``,
``diurnal``, ``heavy_tail``; the modulated ones by thinning), kept here so that the program's
generator may change without moving the yardstick. Lengths come from the
traffic file's own distributions, not from tenant presets.

Every run of a mix does the same work: the set of (prompt, output) lengths
and the set of arrival gaps are fixed by the FILE's ``shape_seed``, in blocks of
requests that each span the whole range of lengths; a run's ``--seed`` only
shuffles the blocks, and the requests inside each, and draws the prompts'
tokens. So runs with different seeds differ little more than two runs of
one seed (PERF.md, PR 23: a plain shuffle spread tokens/s by 7%).
"""

from __future__ import annotations

import math

import numpy as np


def _rate_fn(process: str, rate: float, *, burst_factor=8.0, period=1.0,
             duty=0.2, amplitude=0.8, floor_frac=0.05):
    if process == "poisson":
        return lambda t: rate
    if process == "bursty":
        hi = rate * burst_factor
        lo = max(rate * floor_frac,
                 rate * (1 - duty * burst_factor) / max(1e-9, 1 - duty))
        return lambda t: hi if (t % period) < duty * period else lo
    if process == "diurnal":
        return lambda t: max(
            rate * floor_frac,
            rate * (1 + amplitude * math.sin(2 * math.pi * t / period)))
    raise ValueError(f"unknown arrival process {process!r}")


def arrival_gaps(process: str, rate: float, n: int, seed: int, alpha=1.5,
                 **kw) -> np.ndarray:
    """``n`` inter-arrival gaps of ``process`` at mean ``rate`` a second."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0; got {rate}")
    rng = np.random.default_rng(seed)
    if process == "heavy_tail":
        if alpha <= 1.0:
            raise ValueError(f"heavy_tail needs alpha > 1; got {alpha}")
        xm = (alpha - 1.0) / (alpha * rate)
        return xm * (1.0 + rng.pareto(alpha, n))
    r = _rate_fn(process, rate, **kw)
    # thinning (Lewis and Shedler): candidates at the highest rate, each kept
    # with probability r(t) / highest. (tools/loadgen.py draws each gap at
    # the rate of the instant before it, which skips whole bursts.)
    horizon = np.linspace(0.0, 4.0 * kw.get("period", 1.0), 512)
    highest = max(r(float(t)) for t in horizon)
    gaps, t, last = [], 0.0, 0.0
    while len(gaps) < n:
        t += rng.exponential(1.0 / highest)
        if rng.random() * highest <= r(t):
            gaps.append(t - last)
            last = t
    return np.asarray(gaps)


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a clipped lognormal
    (``median``, ``sigma``, ``min``, ``max``): the distribution itself, with
    no sampling noise, so that every run holds the same set."""
    from statistics import NormalDist

    qs = (np.arange(n) + 0.5) / n
    z = np.asarray([NormalDist().inv_cdf(float(q)) for q in qs])
    lens = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(lens), spec["min"], spec["max"]).astype(np.int64)


def request_pool(traffic: dict) -> list[list[tuple[int, int]]]:
    """The mix's fixed pool of (prompt length, output length) pairs, in
    blocks of ``block`` requests. Prompt lengths and output lengths are each
    cut into ``block`` strata by size, and every block holds one prompt of
    each stratum and one output of each stratum, paired by the file's
    ``shape_seed``: any ``block`` requests in a row then carry nearly the
    same work, whatever the order. A pair that would overrun ``max_total``
    positions gives up output."""
    n, k = int(traffic["pool"]), int(traffic["block"])
    if n % k:
        raise ValueError(f"pool {n} is not a multiple of block {k}")
    rng = np.random.default_rng(int(traffic["shape_seed"]))
    total = int(traffic["max_total"])

    def strata(spec):
        # sorted lengths cut into k strata, each shuffled: column b of the
        # result is what block b takes from every stratum
        rows = np.sort(lognormal_lengths(spec, n)).reshape(k, n // k)
        return np.stack([rng.permutation(row) for row in rows])

    prompts, outputs = strata(traffic["prompt_len"]), strata(traffic["output_len"])
    blocks = []
    for b in range(n // k):
        pairing = rng.permutation(k)  # which output stratum meets which prompt
        blocks.append([
            (int(prompts[s, b]), int(min(outputs[pairing[s], b], total - prompts[s, b])))
            for s in range(k)])
    return blocks


def make_requests(traffic: dict, seed: int, vocab: int, n: int) -> list[dict]:
    """``n`` requests: the pool's blocks in an order drawn from ``seed``,
    each block's requests in an order drawn from ``seed`` (all again in new
    orders when ``n`` is larger than the pool), each with random prompt
    tokens. Open loop mixes get a ``due`` time each, seconds from the start
    of load."""
    rng = np.random.default_rng(seed)
    blocks = request_pool(traffic)
    order = []
    while len(order) < n:
        for b in rng.permutation(len(blocks)):
            order.extend(blocks[b][i] for i in rng.permutation(len(blocks[b])))
    requests = []
    for prompt_len, out_len in order[:n]:
        requests.append({
            "prompt": rng.integers(0, vocab, prompt_len).astype(np.int32),
            "max_new_tokens": out_len,
        })
    if traffic["loop"] == "open":
        arrivals = traffic["arrivals"]
        gaps = arrival_gaps(arrivals["process"], float(arrivals["rate"]), n,
                            int(traffic["shape_seed"]),
                            **arrivals.get("params", {}))
        due = np.cumsum(rng.permutation(gaps))
        for req, t in zip(requests, due):
            req["due"] = float(t)
    return requests


def lateness(due: list[float], sent: list[float]) -> list[float]:
    """How late the generator ran: sent minus due, never below zero."""
    return [max(0.0, s - d) for d, s in zip(due, sent)]


def training_batch(traffic: dict, rng: np.random.Generator, rows: int,
                   seq: int, vocab: int) -> np.ndarray:
    """``rows`` packed sequences of the seeded successor language: an
    alphabet of ``alphabet`` token ids scattered over the whole vocabulary,
    each row walking it from its own start with its own odd stride, so that
    rows differ and the loss can fall."""
    a = int(traffic["alphabet"])
    ids = np.random.default_rng(int(traffic["shape_seed"])).choice(
        vocab, a, replace=False)
    starts = rng.integers(0, a, rows)
    strides = 2 * rng.integers(0, a // 2, rows) + 1
    walk = (starts[:, None] + strides[:, None] * np.arange(seq)[None, :]) % a
    return ids[walk].astype(np.int32)
