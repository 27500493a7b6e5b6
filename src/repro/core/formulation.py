"""McDonald-style extractive summarization as ILP -> QUBO -> Ising.

Implements the paper's Eqs. (3)-(12):

  * :func:`es_objective`       -- Eq. (3) maximization objective (FP reference).
  * :func:`qubo_original`      -- Eq. (8)  penalty-form QUBO.
  * :func:`qubo_improved`      -- Eq. (10) QUBO with the linear bias term mu_b.
  * :func:`qubo_to_ising`      -- Eq. (6)  change of variables x = (1+s)/2.
  * :func:`original_ising`     -- Eq. (9).
  * :func:`improved_ising`     -- Eq. (11)+(12), the paper's core contribution C2.

Conventions (used consistently across the whole package):

  * QUBO energy (minimized):   H(x) = sum_i Q_ii x_i + sum_{i != j} Q_ij x_i x_j
    with Q symmetric and the off-diagonal sum running over *ordered* pairs
    (both (i,j) and (j,i)), exactly as written in the paper.  In matrix form
    H(x) = x^T Q x  (since x_i^2 = x_i).
  * Ising energy (minimized):  H(s) = h . s + sum_{i != j} J_ij s_i s_j
    = h . s + s^T J s  with J symmetric, zero diagonal.
  * The ES objective Eq. (3) is a MAXIMIZATION; QUBO/Ising are MINIMIZATIONS of
    its negation plus the cardinality penalty.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Problem containers
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EsProblem:
    """An extractive-summarization instance (Eq. 3).

    Attributes:
      mu:    (N,) relevance score of each sentence (cosine to doc centroid).
      beta:  (N, N) symmetric pairwise redundancy, zero diagonal.
      m:     summary length budget (number of sentences to select).
      lam:   redundancy weight ``lambda`` in Eq. (3).
    """

    mu: Array
    beta: Array
    m: int
    lam: float = 1.0

    @property
    def n(self) -> int:
        return int(self.mu.shape[-1])

    def subproblem(self, idx: np.ndarray) -> "EsProblem":
        """Restriction to a subset of sentences (used by decomposition)."""
        idx = np.asarray(idx)
        return EsProblem(
            mu=np.asarray(self.mu)[idx],
            beta=np.asarray(self.beta)[np.ix_(idx, idx)],
            m=self.m,
            lam=self.lam,
        )

    def with_m(self, m: int) -> "EsProblem":
        return dataclasses.replace(self, m=m)


@dataclasses.dataclass(frozen=True)
class QuboProblem:
    """H(x) = x^T Q x over x in {0,1}^N (Q symmetric; diag = linear terms)."""

    q: Array  # (N, N)

    @property
    def n(self) -> int:
        return int(self.q.shape[-1])


@dataclasses.dataclass(frozen=True)
class IsingProblem:
    """H(s) = h.s + s^T J s over s in {-1,+1}^N (J symmetric, zero diag)."""

    h: Array  # (N,)
    j: Array  # (N, N)

    @property
    def n(self) -> int:
        return int(self.h.shape[-1])


# ---------------------------------------------------------------------------
# Objectives / energies
# ---------------------------------------------------------------------------


def es_objective(problem: EsProblem, x: Array) -> Array:
    """Eq. (3) objective (maximized); batched over leading dims of ``x``.

    ``x`` is a {0,1} float/int array with shape (..., N).  The cardinality
    constraint is NOT included -- callers enforce/repair it separately.
    """
    x = x.astype(jnp.float32)
    mu = jnp.asarray(problem.mu, jnp.float32)
    beta = jnp.asarray(problem.beta, jnp.float32)
    lin = x @ mu
    quad = jnp.einsum("...i,ij,...j->...", x, beta, x)  # ordered pairs, zero diag
    return lin - problem.lam * quad


def qubo_energy(q: Array, x: Array) -> Array:
    """H(x) = x^T Q x, batched over leading dims of x."""
    x = x.astype(jnp.float32)
    return jnp.einsum("...i,ij,...j->...", x, q.astype(jnp.float32), x)


def ising_energy(h: Array, j: Array, s: Array) -> Array:
    """H(s) = h.s + s^T J s, batched over leading dims of s."""
    s = s.astype(jnp.float32)
    return s @ h.astype(jnp.float32) + jnp.einsum(
        "...i,ij,...j->...", s, j.astype(jnp.float32), s
    )


# ---------------------------------------------------------------------------
# Penalty coefficient
# ---------------------------------------------------------------------------


def gamma_auto(problem: EsProblem, safety: float = 1.1) -> float:
    """A penalty weight making the unconstrained optimum feasible.

    Exchange argument: with k > M selected, removing the weakest sentence
    improves the penalized objective whenever ``Gamma > mu_i - 2 lam sum beta``
    (so ``Gamma > max mu`` suffices when beta >= 0); with k < M, adding any
    sentence i costs at most ``2 lam * (top-(M-1) sum of beta_i.)`` redundancy
    (only selected partners count), repaid by at least ``Gamma``.  Hence

        Gamma > max( max_i mu_i, 2 lam max_i top_{M-1}(beta_i.) )

    makes every infeasible configuration dominated by a neighbour one step
    closer to the feasible set.  Using the top-(M-1) partial row sums instead
    of full row sums keeps Gamma ~3x smaller on dense beta, preserving
    coupling resolution under integer quantization (Sec. III-A's concern).
    """
    mu = np.asarray(problem.mu)
    beta = np.asarray(problem.beta)
    kpart = max(min(problem.m - 1, problem.n - 1), 0)
    if kpart > 0:
        top = np.sort(np.maximum(beta, 0.0), axis=-1)[:, -kpart:].sum(axis=-1).max()
        # Slack for negative couplings in the removal direction.
        neg = np.maximum(-beta, 0.0).sum(axis=-1).max()
    else:
        top, neg = 0.0, 0.0
    bound = max(
        mu.max(initial=0.0) + 2.0 * problem.lam * neg,
        2.0 * problem.lam * (top + neg),
        1e-6,
    )
    return float(safety * bound)


# ---------------------------------------------------------------------------
# QUBO constructions (Eq. 8 and Eq. 10)
# ---------------------------------------------------------------------------


def qubo_original(problem: EsProblem, gamma: Optional[float] = None) -> QuboProblem:
    """Eq. (8): min_x sum_i (-mu_i - 2*Gamma*M + Gamma) x_i
    + sum_{i!=j} (lam*beta_ij + Gamma) x_i x_j."""
    return qubo_improved(problem, gamma=gamma, mu_b=0.0)


def qubo_improved(
    problem: EsProblem,
    gamma: Optional[float] = None,
    mu_b: Optional[float] = None,
) -> QuboProblem:
    """Eq. (10): the improved QUBO with linear bias term ``mu_b``.

    ``mu_b=None`` selects the paper's Eq. (12) median-matching rule;
    ``mu_b=0`` recovers the original formulation Eq. (8).  Built in host
    float32 numpy: a request's problem is at most a few thousand entries,
    and a device program per sentence count cost more than the math.
    """
    if gamma is None:
        gamma = gamma_auto(problem)
    mu = np.asarray(problem.mu, np.float32)
    beta = np.asarray(problem.beta, np.float32)
    lam, gamma = np.float32(problem.lam), np.float32(gamma)
    if mu_b is None:
        h, j = _ising_coeffs(mu, beta, problem.m, lam, gamma, np.float32(0.0))
        off = j[~np.eye(len(h), dtype=bool)]
        med_j = np.median(off) if off.size else np.float32(0.0)
        mu_b = 2.0 * (np.median(h) - med_j)
    lin = -(mu + np.float32(mu_b)) - 2.0 * gamma * problem.m + gamma
    q = lam * beta + gamma
    np.fill_diagonal(q, lin)
    return QuboProblem(q=q)


# ---------------------------------------------------------------------------
# QUBO -> Ising (Eq. 6 with the ordered-pair convention, derived exactly)
# ---------------------------------------------------------------------------


def qubo_to_ising(qubo: QuboProblem) -> IsingProblem:
    """Exact change of variables x = (1+s)/2 on H(x) = x^T Q x.

    With Q symmetric:  H = const + h.s + s^T J s  where
        h_i  = Q_ii / 2 + (1/2) sum_{j != i} Q_ij
        J_ij = Q_ij / 4                       (i != j)

    (The paper's Eq. (6) lists a 1/4 weight on the row sum; the exact constant
    under the ordered-pair convention written in its Eqs. (5) and (4) is 1/2.
    We keep the exact transformation so QUBO and Ising energies agree up to a
    constant, which the tests verify; the improved-formulation phenomenon is
    unchanged.)
    """
    q = np.asarray(qubo.q, np.float32)
    off = q.copy()
    np.fill_diagonal(off, 0.0)
    h = np.diag(q) / 2.0 + off.sum(axis=-1) / 2.0
    return IsingProblem(h=h, j=off / 4.0)


def ising_offset(qubo: QuboProblem) -> float:
    """Constant c with H_qubo(x) = H_ising(s) + c under x = (1+s)/2."""
    q = np.asarray(qubo.q, np.float64)
    n = qubo.n
    off = q * (1.0 - np.eye(n))
    return float(np.diag(q).sum() / 2.0 + off.sum() / 4.0)


def _ising_coeffs(mu, beta, m, lam, gamma, mu_b):
    """Closed-form h, J for the (improved) ES Ising model -- used for Eq. 12."""
    quad = lam * beta + gamma
    np.fill_diagonal(quad, 0.0)
    lin = -(mu + mu_b) - 2.0 * gamma * m + gamma
    h = lin / 2.0 + quad.sum(axis=-1) / 2.0
    return h, quad / 4.0


def original_ising(problem: EsProblem, gamma: Optional[float] = None) -> IsingProblem:
    """Eq. (9): Ising form of the original QUBO."""
    return qubo_to_ising(qubo_original(problem, gamma=gamma))


def improved_ising(
    problem: EsProblem,
    gamma: Optional[float] = None,
    mu_b: Optional[float] = None,
) -> IsingProblem:
    """Eq. (11) with mu_b from Eq. (12) by default: the paper's contribution C2."""
    return qubo_to_ising(qubo_improved(problem, gamma=gamma, mu_b=mu_b))


def spins_to_selection(s: Array) -> Array:
    """s in {-1,+1} -> x in {0,1}."""
    return ((s + 1) // 2).astype(jnp.int32) if s.dtype in (jnp.int32, jnp.int8) else (
        (s + 1.0) / 2.0
    ).astype(jnp.int32)


def selection_to_spins(x: Array) -> Array:
    return (2 * x - 1).astype(jnp.float32)
