"""The request's problem is built on the host: scores (Eqs. 1-2), the
Eq. 8-12 formulation, stochastic rounding and the iteration keys.

Each host function is checked against a test-local ``jax.numpy`` copy of
the device formulas it replaced: the float formulation to rtol 1e-6, the
rounding and the keys bit for bit on identical inputs.  A guard test then
serves new sentence counts through the solve pipeline and asserts that no
program is built between the embeddings and the submitted instances.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline
from repro.core.formulation import (
    EsProblem,
    IsingProblem,
    gamma_auto,
    improved_ising,
    original_ising,
)
from repro.core.rounding import SCHEMES, quantize_ising, quantize_ising_many
from repro.data.synthetic import scores_from_embeddings
from repro.serving.api import KofnSpec, problem_from_embeddings

# ---------------------------------------------------------------------------
# The replaced device formulas, copied as they were.
# ---------------------------------------------------------------------------


def _old_scores(e):
    e = e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-9)
    doc = jnp.mean(e, axis=0)
    doc = doc / jnp.maximum(jnp.linalg.norm(doc), 1e-9)
    mu = e @ doc
    beta = e @ e.T
    beta = beta * (1.0 - jnp.eye(e.shape[0]))
    return mu, beta


def _old_offdiag_values(j):
    n = j.shape[-1]
    return jnp.reshape(jnp.ravel(j)[:-1], (n - 1, n + 1))[:, 1:].ravel()


def _old_ising_coeffs(mu, beta, m, lam, gamma, mu_b):
    n = mu.shape[-1]
    eye = jnp.eye(n, dtype=jnp.float32)
    quad = (lam * beta + gamma) * (1.0 - eye)
    lin = -(mu + mu_b) - 2.0 * gamma * m + gamma
    h = lin / 2.0 + quad.sum(axis=-1) / 2.0
    return h, quad / 4.0


@functools.partial(jax.jit, static_argnames=("m", "use_eq12"))
def _old_qubo_improved_q(mu, beta, lam, gamma, mu_b, *, m, use_eq12):
    n = mu.shape[-1]
    if use_eq12:
        h, j = _old_ising_coeffs(mu, beta, m, lam, gamma, 0.0)
        mu_b = 2.0 * (jnp.median(h) - jnp.median(_old_offdiag_values(j)))
    lin = -(mu + mu_b) - 2.0 * gamma * m + gamma
    quad = lam * beta + gamma
    return quad * (1.0 - jnp.eye(n, dtype=jnp.float32)) + jnp.diag(lin)


@jax.jit
def _old_qubo_to_ising_arrays(q):
    n = q.shape[-1]
    off = q * (1.0 - jnp.eye(n, dtype=jnp.float32))
    return jnp.diag(q) / 2.0 + off.sum(axis=-1) / 2.0, off / 4.0


def _old_ising(problem, improved):
    q = _old_qubo_improved_q(
        jnp.asarray(problem.mu, jnp.float32), jnp.asarray(problem.beta, jnp.float32),
        jnp.float32(problem.lam), jnp.float32(gamma_auto(problem)), jnp.float32(0.0),
        m=problem.m, use_eq12=improved,
    )
    return _old_qubo_to_ising_arrays(q)


def _old_round(v, scheme, key):
    if scheme == "deterministic":
        return jnp.round(v)
    lo = jnp.floor(v)
    frac = v - lo
    p_up = jnp.where(frac > 0.0, 0.5, 0.0) if scheme == "stochastic_5050" else frac
    up = jax.random.uniform(key, v.shape) < p_up
    return lo + up.astype(v.dtype)


@functools.partial(jax.jit, static_argnames=("scheme", "int_range"))
def _old_quantize_arrays(h, j, key, *, scheme, int_range):
    n = h.shape[-1]
    m = jnp.maximum(jnp.max(jnp.abs(h)), jnp.max(jnp.abs(j)))
    scale = int_range / jnp.maximum(m, 1e-12)
    kh, kj = jax.random.split(key)
    if scheme == "deterministic":
        kh = kj = None
    h_q = jnp.clip(_old_round(h * scale, scheme, kh), -int_range, int_range)
    upper = jnp.triu(jnp.ones((n, n), bool), k=1)
    j_q = jnp.where(upper, _old_round(j * scale, scheme, kj), 0.0)
    j_q = jnp.clip(j_q + j_q.T, -int_range, int_range)
    return h_q, j_q, scale


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _embeddings(n, seed=0, dim=64):
    rng = np.random.default_rng([seed, n])
    topics = rng.normal(size=(4, dim))
    e = topics[rng.integers(0, 4, n)] * 2.0 + rng.normal(size=(n, dim))
    return e.astype(np.float32)


def _problem(n, m, lam=0.5, seed=0):
    mu, beta = scores_from_embeddings(_embeddings(n, seed))
    return EsProblem(mu=mu, beta=beta, m=m, lam=lam)


# ---------------------------------------------------------------------------
# Scores and formulation: host float32 against the device formulas
# ---------------------------------------------------------------------------

SIZES = (2, 5, 20, 59, 60, 150)


@pytest.mark.parametrize("n", SIZES)
def test_host_scores_match_device_formulas(n):
    e = _embeddings(n, seed=1)
    e[-1] = 0.0  # a sentence the encoder saw no token of
    mu, beta = scores_from_embeddings(e)
    mu_old, beta_old = _old_scores(jnp.asarray(e))
    assert isinstance(mu, np.ndarray) and isinstance(beta, np.ndarray)
    assert mu.dtype == beta.dtype == np.float32
    np.testing.assert_allclose(mu, np.asarray(mu_old), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(beta, np.asarray(beta_old), rtol=1e-6, atol=1e-6)
    assert np.all(np.diag(beta) == 0.0)


@pytest.mark.parametrize("improved", [True, False], ids=["improved", "original"])
@pytest.mark.parametrize("m", [1, 3])
@pytest.mark.parametrize("n", SIZES)
def test_host_formulation_matches_device_formulas(n, m, improved):
    problem = _problem(n, m)
    ising = improved_ising(problem) if improved else original_ising(problem)
    h_old, j_old = _old_ising(problem, improved)
    assert isinstance(ising.h, np.ndarray) and ising.h.dtype == np.float32
    assert isinstance(ising.j, np.ndarray) and ising.j.dtype == np.float32
    # h_i sums row i of Q, terms of both signs: an entry near 0 is judged
    # against the magnitude of the terms it sums, the rest at rtol 1e-6.
    q = np.asarray(_old_qubo_improved_q(
        jnp.asarray(problem.mu), jnp.asarray(problem.beta), jnp.float32(problem.lam),
        jnp.float32(gamma_auto(problem)), jnp.float32(0.0), m=m, use_eq12=improved))
    terms = float(np.abs(q).sum(axis=-1).max())
    np.testing.assert_allclose(ising.h, np.asarray(h_old), rtol=1e-6, atol=1e-6 * terms)
    np.testing.assert_allclose(ising.j, np.asarray(j_old), rtol=1e-6)


def test_general_relevance_paths_score_on_the_host():
    e = _embeddings(12, seed=2)
    items = [f"s{i}" for i in range(12)]
    query = _embeddings(1, seed=3)
    for spec, emb in ((KofnSpec(m=3, relevance="uniform"), e),
                      (KofnSpec(m=3, relevance="query", query="q"),
                       np.concatenate([e, query]))):
        problem = problem_from_embeddings(spec, items, emb)
        assert isinstance(problem.mu, np.ndarray)
        _, beta_old = _old_scores(jnp.asarray(e))
        np.testing.assert_allclose(problem.beta, np.asarray(beta_old),
                                   rtol=1e-6, atol=1e-6)
    spec = KofnSpec(m=3, relevance="query", query="q")
    problem = problem_from_embeddings(spec, items, np.concatenate([e, query]))
    eu = e / np.linalg.norm(e, axis=-1, keepdims=True)
    qu = query[0] / np.linalg.norm(query[0])
    np.testing.assert_allclose(problem.mu, eu @ qu, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("as_device", [False, True], ids=["numpy", "jax"])
def test_subproblem_gathers_match(as_device):
    problem = _problem(60, 3)
    if as_device:
        problem = EsProblem(mu=jnp.asarray(problem.mu), beta=jnp.asarray(problem.beta),
                            m=3, lam=0.5)
    idx = np.array([0, 4, 5, 17, 33, 59])
    sub = problem.subproblem(idx)
    assert isinstance(sub.mu, np.ndarray) and isinstance(sub.beta, np.ndarray)
    np.testing.assert_array_equal(sub.mu, np.asarray(jnp.asarray(problem.mu)[idx]))
    np.testing.assert_array_equal(
        sub.beta, np.asarray(jnp.asarray(problem.beta)[np.ix_(idx, idx)]))
    assert (sub.m, sub.lam) == (3, 0.5)


# ---------------------------------------------------------------------------
# Rounding and keys: bit-identical to the device implementation
# ---------------------------------------------------------------------------

# Both sides of the draw-bucket edges: 8/9, 64/65 (h) and 64/65 (n*n past
# 4096, J), plus sizes inside the buckets.
ROUND_SIZES = (5, 8, 9, 20, 59, 64, 65)


@pytest.mark.parametrize("n", ROUND_SIZES)
@pytest.mark.parametrize("scheme", SCHEMES)
def test_rounding_bit_identical_to_device(scheme, n):
    ising = improved_ising(_problem(n, 3, seed=4))
    keys = list(jax.random.split(jax.random.key(n), 3))
    h, j = jnp.asarray(ising.h), jnp.asarray(ising.j)
    old = [_old_quantize_arrays(h, j, k, scheme=scheme, int_range=14) for k in keys]
    for k, (h_old, j_old, s_old) in zip(keys, old):
        new = quantize_ising(ising, scheme, int_range=14, key=k)
        np.testing.assert_array_equal(new.ising.h, np.asarray(h_old))
        np.testing.assert_array_equal(new.ising.j, np.asarray(j_old))
        assert new.scale == float(s_old)
    for batch in (keys, jnp.stack(keys)):
        many = quantize_ising_many(ising, batch, scheme, int_range=14)
        assert len(many) == len(keys)
        for q, (h_old, j_old, s_old) in zip(many, old):
            np.testing.assert_array_equal(q.ising.h, np.asarray(h_old))
            np.testing.assert_array_equal(q.ising.j, np.asarray(j_old))
            assert q.scale == float(s_old)


@pytest.mark.parametrize("iterations", [1, 3, 10])
def test_iteration_keys_match_sequential_split_chain(iterations):
    key = jax.random.fold_in(jax.random.key(7), 123)
    expected, k = [], key
    for _ in range(iterations):
        k, k_quant, k_solve = jax.random.split(k, 3)
        expected.append((k_quant, k_solve))
    got = pipeline._iteration_keys(key, iterations)
    assert len(got) == iterations
    for (kq, ks), (eq, es) in zip(got, expected):
        np.testing.assert_array_equal(jax.random.key_data(kq), jax.random.key_data(eq))
        np.testing.assert_array_equal(jax.random.key_data(ks), jax.random.key_data(es))


# ---------------------------------------------------------------------------
# No program per new sentence count
# ---------------------------------------------------------------------------

_COMPILE = "/jax/core/compile/backend_compile_duration"


class _RecordingBackend:
    """A host backend that records what is submitted and solves nothing."""

    def __init__(self):
        self.instances = []

    def submit(self, ising, key, **kw):
        self.instances.append(ising)
        return None


def test_new_sentence_counts_build_no_program():
    cfg = pipeline.SolveConfig()
    backend = _RecordingBackend()
    base = jax.random.key(0)
    # Served embeddings arrive as device arrays; make them before counting.
    emb = {n: jnp.asarray(_embeddings(n, seed=5)) for n in (30, 7, 13, 22, 41, 59)}

    def serve(n, rid):
        items = [f"s{i}" for i in range(n)]
        problem = problem_from_embeddings(KofnSpec(m=3), items, emb[n])
        pipeline._submit_iterations(problem, jax.random.fold_in(base, rid), cfg,
                                    backend, 0)

    serve(30, 0)  # warm: the key and draw programs exist from here on
    built = []

    def on_build(event, duration, **kw):
        if event == _COMPILE:
            built.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_build)
    try:
        for rid, n in enumerate((7, 13, 22, 41, 59), start=1):
            serve(n, rid)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_build)
    assert built == []
    assert len(backend.instances) == 6 * cfg.iterations
    last = backend.instances[-1]
    assert isinstance(last, IsingProblem) and last.n == 59
    assert np.all(np.abs(last.j) <= 14) and np.array_equal(last.j, last.j.T)
