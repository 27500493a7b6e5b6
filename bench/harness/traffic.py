"""Open-loop traffic from a mix's data file (``bench/traffic/<mix>.json``).

A mix states the sentence-count distribution, ``m``, ``lam`` and the arrival
process; each cell fixes its own offered rate (``bench/cells/<cell>.json``).  One schedule of ``K = round(rate * seconds)``
requests is drawn per phase:

* sizes are the distribution's quantiles at ``(k + 0.5) / K``, one per
  request, rounded and clipped to the mix's range, and the gaps the
  exponential quantiles at ``(k + 0.5) / K``; both shuffled by the seed.
  Every seed therefore offers the same set of sizes and arrivals in another
  order, so the seed changes which document comes when, not how much work
  a run holds;
* each request's text is a fresh document drawn from the seed.

Arrivals are open loop: a request is due at its scheduled time whether or
not earlier ones have finished, and latency counts from that due time.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Sequence

import numpy as np

from harness import corpus

SEG_BUCKET = 8
LEN_BUCKET = 64


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due: float  # seconds after the phase starts
    n: int  # sentences
    doc_seed: int
    sentences: tuple

    @property
    def text(self) -> str:
        return " ".join(self.sentences)


def _stratified(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def sentence_counts(mix: dict, k: int) -> np.ndarray:
    """The ``k`` sentence counts of one phase, in quantile order."""
    s = mix["sentences"]
    if s["dist"] != "lognormal":
        raise ValueError(f"unknown sentence distribution {s['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf(u) for u in _stratified(k)])
    n = np.exp(math.log(s["median"]) + s["sigma"] * z)
    return np.clip(np.rint(n), s["min"], s["max"]).astype(int)


def gaps(mix: dict, k: int, seconds: float) -> np.ndarray:
    """Inter-arrival gaps in quantile order, scaled so the ``k`` arrivals
    fill ``seconds`` less half a mean gap."""
    a = mix["arrivals"]
    if a["process"] != "poisson":
        raise ValueError(f"unknown arrival process {a['process']!r}")
    g = -np.log1p(-_stratified(k))
    return g * (seconds * (k - 0.5) / k) / g.sum()


def requests_in(mix: dict, seconds: float, rate: float) -> int:
    return max(1, int(round(rate * seconds)))


def schedule(mix: dict, seed: int, seconds: float, *, rate: float,
             phase: int = 0) -> List[Request]:
    """The requests of one phase (0: window, 1: warm-up traffic, ...)."""
    k = requests_in(mix, seconds, rate)
    rng = np.random.default_rng([seed, phase])
    sizes = rng.permutation(sentence_counts(mix, k))
    due = np.cumsum(rng.permutation(gaps(mix, k, seconds)))
    doc_seeds = rng.integers(0, 2**62, size=k)
    return [
        Request(i, float(due[i]) - float(due[0]), int(sizes[i]),
                int(doc_seeds[i]),
                tuple(corpus.document(int(doc_seeds[i]), int(sizes[i]))))
        for i in range(k)
    ]


def bucket(n: int, base: int) -> int:
    b = base
    while b < n:
        b *= 2
    return b


def length_buckets(n: int, max_len: int) -> List[int]:
    """Every encoder length bucket a document of ``n`` generated sentences
    can land in (BOS + each sentence's bytes and separator, capped)."""
    lo_b, hi_b = corpus.sentence_bytes_range()
    lo = min(bucket(1 + n * (lo_b + 1), LEN_BUCKET), max_len)
    hi = min(bucket(1 + n * (hi_b + 1), LEN_BUCKET), max_len)
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def encoder_lattice(sizes: Sequence[int], max_len: int) -> dict:
    """(length bucket -> segment buckets) reachable by these sentence counts:
    a launch groups jobs of one length bucket and pads segments to the
    largest member's bucket, which is itself some member's bucket."""
    pairs: dict = {}
    for n in sorted(set(sizes)):
        for length in length_buckets(n, max_len):
            pairs.setdefault(length, set()).add(bucket(n, SEG_BUCKET))
    return {length: sorted(g) for length, g in sorted(pairs.items())}


def slice_lattice(sizes: Sequence[int], max_len: int) -> dict:
    """(segment bucket -> sentence counts) whose rows can be read out of a
    launch padded to that segment bucket."""
    lattice = encoder_lattice(sizes, max_len)
    out: dict = {}
    for n in sorted(set(sizes)):
        for length in length_buckets(n, max_len):
            for g in lattice[length]:
                if g >= bucket(n, SEG_BUCKET):
                    out.setdefault(g, set()).add(n)
    return {g: sorted(ns) for g, ns in sorted(out.items())}
