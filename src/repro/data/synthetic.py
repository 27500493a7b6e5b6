"""Deterministic synthetic news-style corpus (DESIGN.md deviation 2).

CNN/DailyMail and XSum are not downloadable offline, so benchmarks draw from
a topic-mixture generator whose induced Ising statistics match the paper's
regime: every sentence pair has nonzero redundancy (dense beta), relevance
mu_i in roughly (0.3, 0.95), redundancy beta_ij moderate with high values for
same-topic sentence pairs.

Two layers:
  * :func:`synthetic_embeddings`  -- unit-norm sentence embeddings directly
    (fast path for solver/benchmark work);
  * :func:`synthetic_document`    -- actual text (template sentences tagged
    with topic words), exercised by the tokenizer/embedder path.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TOPICS = [
    "the city council budget vote",
    "the championship final result",
    "the new vaccine trial data",
    "the coastal storm damage",
    "the quarterly earnings report",
    "the wildfire evacuation order",
    "the transit strike negotiations",
    "the satellite launch schedule",
]

_TEMPLATES = [
    "Officials said {t} would be reviewed on {d}.",
    "Residents reacted to {t} with a mixture of relief and concern.",
    "Analysts noted that {t} had shifted expectations for {d}.",
    "A spokesperson declined to comment on {t}.",
    "Early reports about {t} were revised later on {d}.",
    "Witnesses described {t} in detail to reporters.",
    "The committee linked {t} to broader regional trends.",
    "Experts cautioned that {t} remained uncertain pending {d}.",
]
_DATES = ["Monday", "Tuesday", "Wednesday", "Thursday", "Friday"]


def synthetic_embeddings(
    key: jax.Array,
    n_sentences: int,
    *,
    dim: int = 64,
    n_topics: int = 4,
    topic_strength: float = 2.2,
) -> jnp.ndarray:
    """(N, dim) unit-norm embeddings from a topic mixture.

    Each sentence = strong topic component + isotropic noise, normalized.
    Same-topic pairs end up with high cosine (redundant); cross-topic pairs
    stay moderately correlated through a shared document component, so beta
    is dense -- as the paper observes for real SBERT embeddings.
    """
    k_doc, k_topic, k_assign, k_noise, k_w = jax.random.split(key, 5)
    doc = jax.random.normal(k_doc, (dim,))
    topics = jax.random.normal(k_topic, (n_topics, dim))
    assign = jax.random.randint(k_assign, (n_sentences,), 0, n_topics)
    noise = jax.random.normal(k_noise, (n_sentences, dim))
    weight = jax.random.uniform(k_w, (n_sentences, 1), minval=0.6, maxval=1.4)
    e = doc[None] + topic_strength * weight * topics[assign] + noise
    return e / jnp.linalg.norm(e, axis=-1, keepdims=True)


def scores_from_embeddings(e) -> Tuple[np.ndarray, np.ndarray]:
    """Paper Eqs. (1)-(2): mu_i = cos(e_i, mean_doc); beta_ij = cos(e_i, e_j).

    Host float32 numpy on any array-like ``e``: sentence counts vary per
    request, and device ops -- eager or jitted -- build a program per
    distinct count."""
    # The eps guard only bites on an exactly-zero row (a sentence fully
    # truncated by the backbone's max_len) -- that row scores mu=0, beta=0
    # instead of NaN-poisoning the whole objective; nonzero rows divide by
    # their exact norm, unchanged.
    e = np.asarray(e, np.float32)
    e = e / np.maximum(np.linalg.norm(e, axis=-1, keepdims=True), np.float32(1e-9))
    doc = e.mean(axis=0)
    doc = doc / np.maximum(np.linalg.norm(doc), np.float32(1e-9))
    beta = e @ e.T
    np.fill_diagonal(beta, 0.0)
    return e @ doc, beta


def synthetic_benchmark(
    seed: int, n_sentences: int, m: int, *, lam: float = 1.0, dim: int = 64
):
    """One benchmark instance: EsProblem built from synthetic embeddings."""
    from repro.core.formulation import EsProblem

    e = synthetic_embeddings(jax.random.key(seed), n_sentences, dim=dim)
    mu, beta = scores_from_embeddings(e)
    return EsProblem(mu=mu, beta=beta, m=m, lam=lam)


def benchmark_suite(
    n_benchmarks: int, n_sentences: int, m: int = 6, *, lam: float = 1.0, seed0: int = 0
):
    """The paper's '20 benchmarks of N-sentence paragraphs' analogue."""
    return [
        synthetic_benchmark(seed0 + i, n_sentences, m, lam=lam)
        for i in range(n_benchmarks)
    ]


def synthetic_document(seed: int, n_sentences: int) -> List[str]:
    """Readable synthetic article text (for the tokenizer/embedder path)."""
    rng = np.random.default_rng(seed)
    doc_topics = rng.choice(
        len(TOPICS), size=min(len(TOPICS), max(2, n_sentences // 6)), replace=False
    )
    sents = []
    for i in range(n_sentences):
        t = TOPICS[int(rng.choice(doc_topics))]
        tpl = _TEMPLATES[int(rng.integers(len(_TEMPLATES)))]
        d = _DATES[int(rng.integers(len(_DATES)))]
        sents.append(tpl.format(t=t, d=d))
    return sents
