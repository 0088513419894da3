"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into requests. It knows nothing of the program.

Two kinds of load:

* ``closed``: ``clients`` callers, each sending its next request the moment
  the previous one finishes. The batch opens as a closed loop runs in
  steady state: with ``first_output`` ``residual``, the clients' first
  requests have, after their first token, the remaining outputs of
  requests caught mid-flight (:func:`residuals`), so they finish spread
  out and not in one wave.
* ``poisson``: independent users arriving at ``rate`` requests per second
  with exponential gaps, sent when due whether or not earlier requests
  have finished.

Lengths are drawn as ``uniform`` (``min``..``max``) or ``lognormal``
(``median``, ``sigma``, clipped to ``min``..``max``). Every draw is
stratified: the n values are the distribution's quantiles at
(i + 1/2) / n, put in an order drawn from the seed. So every seed sends the
same work (the same set of lengths and of gaps; in a closed loop, the same
set in each round of requests, one request per client), in another order,
with other tokens; the Poisson arithmetic is that of
``benchmarks/bench_serving.py`` (``rng.exponential(1 / rate)``) in
stratified form.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the generator makes it."""
    rid: int
    prompt: np.ndarray          # (P,) int32 token ids
    max_new_tokens: int
    client: int = -1            # closed loop: the client that sends it
    due: float = 0.0            # open loop: seconds after the window opens


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent random streams of one seed (traffic, order, sample)."""
    return np.random.default_rng([int(seed), int(stream)])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified draws of a length distribution, in ascending
    order, as whole numbers inside the clip."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(quantiles(spec, n))


def _prompts(lengths, vocab: int, rng) -> list[np.ndarray]:
    return [rng.integers(0, vocab, (int(t),), dtype=np.int32)
            for t in lengths]


def residuals(spec: dict, n: int) -> np.ndarray:
    """The n stratified remaining outputs of requests caught mid-flight in
    a closed loop's steady state, in ascending order. A request in flight
    is length-biased, and its remaining part uniform over its length: the
    residual-life distribution, P(R <= r) = sum_{x <= r} P(L >= x) / E[L],
    of the output lengths L."""
    lengths = quantiles(spec, max(n, 256))
    grid = np.arange(1, int(lengths.max()) + 1)
    surv = (lengths[None, :] >= grid[:, None]).mean(axis=1)
    cdf = np.cumsum(surv) / surv.sum()
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u) + 1, grid[-1])


def closed(mix: dict, seed: int, vocab: int) -> list[list[Spec]]:
    """Per client, the requests it sends in order: round j holds the j-th
    request of every client, drawn as one stratified set."""
    c, k = int(mix["clients"]), int(mix["requests_per_client"])
    rng = rng_for(seed, 0)
    first = mix.get("first_output")
    if first not in (None, "residual"):
        raise ValueError(f"unknown first_output {first!r}")
    streams: list[list[Spec]] = [[] for _ in range(c)]
    for j in range(k):
        p = draw(mix["prompt"], c, rng)
        o = draw(mix["output"], c, rng)
        if j == 0 and first == "residual":
            # the first token, then the remaining output
            o = 1 + rng.permutation(residuals(mix["output"], c))
        toks = _prompts(p, vocab, rng)
        for cl in range(c):
            streams[cl].append(Spec(rid=j * c + cl, prompt=toks[cl],
                                    max_new_tokens=int(o[cl]), client=cl))
    return streams


def poisson(mix: dict, seed: int, vocab: int, seconds: float,
            rate: float | None = None) -> list[Spec]:
    """Requests due in ``[0, seconds)`` at ``rate`` (the mix's unless
    given), in due order. The floor(rate * seconds) stratified gaps sum to
    less than ``seconds`` in any order, so every seed sends them all."""
    rate = float(mix["rate"] if rate is None else rate)
    n = max(1, math.floor(rate * seconds))
    rng = rng_for(seed, 0)
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
    due = np.cumsum(gaps)
    p = draw(mix["prompt"], n, rng)
    o = draw(mix["output"], n, rng)
    toks = _prompts(p, vocab, rng)
    return [Spec(rid=i, prompt=toks[i], max_new_tokens=int(o[i]),
                 due=float(due[i]))
            for i in range(n) if due[i] < seconds]


def length_ranges(mix: dict) -> tuple[tuple[int, int], tuple[int, int]]:
    """(prompt min, max), (output min, max) that the mix can send."""
    out = mix["output"]
    lo = 2 if mix.get("first_output") == "residual" else int(out["min"])
    return ((int(mix["prompt"]["min"]), int(mix["prompt"]["max"])),
            (lo, int(out["max"]) + (mix.get("first_output") == "residual")))
