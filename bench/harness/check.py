"""The comparison that decides ``correct``, and the quality metric.

Each number is a gap against a plain reference, computed after the window
has closed from what the timed path returned:

* ``embed_gap``: largest ``1 - cos`` between a served sentence embedding and
  the configuration's plain float32 reference at ``"highest"`` matmul
  precision, over a sample of the window's requests drawn from the seed
  (the longest always in it).  A sentence the reference sees no token of
  must come back as a zero row.
* ``bad_selections``: window requests that failed, never finished, or did
  not return exactly ``m`` distinct sentences.
* ``objective_gap``: largest difference between a request's returned
  objective and the float64 objective of its returned selection, rebuilt
  here from the served embeddings (Eqs. 1-3), over the magnitude of the
  terms it sums.
* ``energy_gap``: largest difference between a solve job's returned best
  energy and ``h.s + s^T J s`` recomputed in float64 from its returned spins
  and its own instance, over every job of the window's requests (infinite
  when no job was tapped).
* ``anneal_rank``: mean, over the window's solve jobs of at most
  ``ANNEAL_MAX_N`` spins (up to ``ANNEAL_SAMPLE`` of them, drawn from the
  seed), of the share of the instance's spin states whose energy lies below
  the returned best energy, found by enumerating every state in float64.
  An anneal that ran reads near 0; the best of ``r`` states never annealed
  reads about ``1 / (r + 1)``; no job to rank reads infinite.

``quality_norm_obj``, the mean normalized objective (Eq. 13) of the window's
selections against exact bounds from enumeration, is an end-to-end metric,
not a check.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Sequence

import numpy as np

ZERO_ROW = 1e-6


def scores(e: np.ndarray):
    """mu, beta of one document from its sentence embeddings (float64)."""
    e = np.asarray(e, np.float64)
    e = e / np.maximum(np.linalg.norm(e, axis=-1, keepdims=True), 1e-9)
    doc = e.mean(axis=0)
    doc = doc / max(np.linalg.norm(doc), 1e-9)
    beta = e @ e.T
    np.fill_diagonal(beta, 0.0)
    return e @ doc, beta


def objective(mu, beta, lam: float, idx: Sequence[int]) -> float:
    idx = list(idx)
    return float(mu[idx].sum() - lam * beta[np.ix_(idx, idx)].sum())


def _magnitude(mu, beta, lam: float, idx: Sequence[int]) -> float:
    idx = list(idx)
    return float(np.abs(mu[idx]).sum() + lam * np.abs(beta[np.ix_(idx, idx)]).sum())


class Enumerator:
    """Exact max and min of Eq. 3 over all ``m``-subsets (combinations cached
    per size)."""

    def __init__(self):
        self._combos: Dict[tuple, np.ndarray] = {}

    def combos(self, n: int, m: int) -> np.ndarray:
        key = (n, m)
        if key not in self._combos:
            c = np.fromiter(
                itertools.chain.from_iterable(itertools.combinations(range(n), m)),
                dtype=np.int32).reshape(-1, m)
            self._combos[key] = c
        return self._combos[key]

    def bounds(self, mu, beta, lam: float, m: int):
        c = self.combos(len(mu), m)
        obj = mu[c].sum(axis=1)
        for a, b in itertools.combinations(range(m), 2):
            obj = obj - 2.0 * lam * beta[c[:, a], c[:, b]]
        return float(obj.max()), float(obj.min())


def normalized(obj: float, hi: float, lo: float) -> float:
    return (obj - lo) / max(hi - lo, 1e-12)


def selection_ok(resp, n: int, m: int) -> bool:
    if resp is None:
        return False
    sel = np.asarray(resp.selection)
    return (sel.shape == (n,) and int(sel.sum()) == m
            and set(np.unique(sel)) <= {0, 1} and len(resp.selected) == m)


def embed_gap(served: List[np.ndarray], reference: List[np.ndarray]) -> float:
    gap = 0.0
    for s, r in zip(served, reference):
        s = np.asarray(s, np.float64)
        r = np.asarray(r, np.float64)
        for u, v in zip(s, r):
            nv, nu = np.linalg.norm(v), np.linalg.norm(u)
            if nv < ZERO_ROW:
                gap = max(gap, 0.0 if nu < ZERO_ROW else 1.0)
            elif nu < ZERO_ROW:
                gap = 1.0
            else:
                gap = max(gap, 1.0 - float(u @ v) / (nu * nv))
    return gap


def energy_gap(jobs) -> float:
    if not jobs:
        return float("inf")  # nothing was tapped: no answer to check
    gap = 0.0
    for job in jobs:
        res = job.result
        if res is None:
            return float("inf")
        h = np.asarray(job.ising.h, np.float64)
        j = np.asarray(job.ising.j, np.float64)
        spins = np.asarray(res.spins, np.float64).reshape(-1, h.shape[0])
        energies = np.asarray(res.energies, np.float64).reshape(-1)
        ref = spins @ h + np.einsum("ri,ij,rj->r", spins, j, spins)
        gap = max(gap, float(np.max(np.abs(ref - energies))))
    return gap


ANNEAL_MAX_N = 16
ANNEAL_SAMPLE = 256


def _all_states(n: int, cache: dict) -> np.ndarray:
    if n not in cache:
        bits = (np.arange(2 ** n)[:, None] >> np.arange(n)) & 1
        cache[n] = (2.0 * bits - 1.0).astype(np.float64)
    return cache[n]


def anneal_rank(jobs, rng) -> float:
    small = [j for j in jobs if int(j.ising.n) <= ANNEAL_MAX_N]
    if not small:
        return float("inf")  # no job to rank: the check saw nothing
    if len(small) > ANNEAL_SAMPLE:
        small = [small[i] for i in sorted(rng.choice(len(small), ANNEAL_SAMPLE,
                                                     replace=False))]
    cache: dict = {}
    ranks = []
    for job in small:
        if job.result is None:
            return float("inf")
        h = np.asarray(job.ising.h, np.float64)
        j = np.asarray(job.ising.j, np.float64)
        states = _all_states(h.shape[0], cache)
        e = states @ h + np.einsum("ri,ij,rj->r", states, j, states)
        best = float(np.asarray(job.result.energies, np.float64).reshape(-1)[0])
        ranks.append(float(np.mean(e < best - 1e-9 * (1.0 + abs(best)))))
    return float(np.mean(ranks))


def selection_numbers(served, embeddings: Dict[int, np.ndarray], m: int,
                      lam: float) -> dict:
    """bad_selections, objective_gap and the mean normalized objective
    (``quality_norm_obj``) over the window's requests."""
    enum = Enumerator()
    bad, obj_gap, norms = 0, 0.0, []
    for s in served:
        resp = s.response
        if s.done is None or not selection_ok(resp, s.n, m):
            bad += 1
            continue
        mu, beta = scores(embeddings[s.rid])
        idx = np.nonzero(np.asarray(resp.selection))[0]
        ref = objective(mu, beta, lam, idx)
        obj_gap = max(obj_gap, abs(float(resp.objective) - ref)
                      / max(_magnitude(mu, beta, lam, idx), 1e-12))
        hi, lo = enum.bounds(mu, beta, lam, m)
        norms.append(normalized(ref, hi, lo))
    mean = float(np.mean(norms)) if norms else 0.0
    return {"bad_selections": bad, "objective_gap": obj_gap,
            "quality_norm_obj": mean}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k]["limit"] for k in limits)
