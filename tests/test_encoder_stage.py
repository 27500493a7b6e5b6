"""Encoder serving stage: bucketing determinism, flash parity, pipelining.

The stage's contract: a job's embeddings are a pure function of its own
texts (padded-length bucketing + causal backbone + per-segment pooling
make batch-mates inert), the Pallas flash-attention path matches the
naive SDPA reference at serving shapes, and encode drains genuinely
overlap Ising drains when the stage fronts the farm.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolveConfig
from repro.data.synthetic import scores_from_embeddings, synthetic_document
from repro.embeddings import EncoderStage
from repro.embeddings import serving
from repro.farm import CobiFarm
from repro.serving import SummarizationEngine

CFG = SolveConfig(solver="cobi", iterations=2, reads=6, int_range=14,
                  steps=100, p=20, q=10)


def _overlap_seconds(a, b):
    """Total length of the intersection of two interval lists."""
    total = 0.0
    for a0, a1 in a:
        for b0, b1 in b:
            total += max(0.0, min(a1, b1) - max(a0, b0))
    return total


# ------------------------------------------------- bucketing determinism


def test_batch_composition_invariance():
    """Same sentences -> bit-identical embeddings (and identical mu/beta)
    no matter what else shares the encode drain."""
    target = synthetic_document(1, 3)
    mate_a = synthetic_document(2, 3)
    mate_b = synthetic_document(3, 2)
    batched_stage = EncoderStage.tiny(linger=0.1)
    futs = [batched_stage.submit(target), batched_stage.submit(mate_a),
            batched_stage.submit(mate_b)]
    batched = futs[0].result(timeout=120)
    receipt = futs[0].receipt()
    [f.result(timeout=120) for f in futs]
    batched_stage.close()
    # the drain really batched: the target shared its launch
    assert receipt.batch_jobs >= 2
    solo_stage = EncoderStage.tiny()
    solo = solo_stage.submit(target).result(timeout=120)
    solo_stage.close()
    np.testing.assert_array_equal(np.asarray(batched), np.asarray(solo))
    mu_b, beta_b = scores_from_embeddings(batched)
    mu_s, beta_s = scores_from_embeddings(solo)
    np.testing.assert_array_equal(np.asarray(mu_b), np.asarray(mu_s))
    np.testing.assert_array_equal(np.asarray(beta_b), np.asarray(beta_s))


def test_receipts_and_stats_meter_the_stage():
    stage = EncoderStage.tiny()
    fut = stage.submit(synthetic_document(4, 4), tag=77)
    emb = fut.result(timeout=120)
    r = fut.receipt()
    assert emb.shape[0] == 4
    assert r.tag == 77
    assert r.encoder_seconds > 0.0
    assert r.bytes_h2d > 0 and r.bytes_d2h > 0
    assert r.padded_len in (64, 128)
    s = stage.stats()
    assert s.jobs == 1 and s.launches == 1 and s.busy_seconds > 0.0
    assert stage.estimate_seconds(100) > 0.0
    assert len(stage.busy_intervals()) == 1
    # sync face + empty-job edge
    e2 = stage.encode(["one sentence."])
    assert e2.shape[0] == 1
    e0 = stage.submit([]).result(timeout=10)
    assert e0.shape == (0, stage.cfg.d_model)
    stage.close()
    with pytest.raises(RuntimeError, match="closed"):
        stage.submit(["x."])


# ------------------------------------------------- prewarm compiles only

_COMPILE = "/jax/core/compile/backend_compile_duration"


class _LowerOnly:
    """Stands in for the jitted ``_embed_batch``: lowering goes through,
    a launch fails the test."""

    def __init__(self, fn):
        self.fn = fn

    def lower(self, *args, **kwargs):
        return self.fn.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        raise AssertionError("prewarm launched _embed_batch")


def _routed_stage():
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config("moonlight-16b-a3b").reduced()
    params = jax.jit(init_params, static_argnums=0)(cfg, jax.random.key(2))
    return EncoderStage(cfg, params, max_len=128)


@pytest.mark.parametrize("make_stage", [EncoderStage.tiny, _routed_stage],
                         ids=["sbert-tiny", "routed-small"])
def test_prewarm_compiles_without_launching(make_stage, monkeypatch):
    """``prewarm`` builds each shape's program and launches none; a
    following submit of a warmed shape builds no ``_embed_batch``."""
    stage = make_stage()
    jax.clear_caches()  # shapes other tests compiled would hide the build
    lengths = [64, 128]
    built = []

    def on_build(event, duration, **kw):
        if event == _COMPILE:
            built.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_build)
    try:
        with monkeypatch.context() as m:
            m.setattr(serving, "_embed_batch", _LowerOnly(serving._embed_batch))
            assert stage.prewarm(lengths=lengths, batches=[4],
                                 segments=[8]) == 2
        assert stage.stats().launches == 0
        assert stage.stats().prewarmed == 2
        assert built.count("jit(_embed_batch)") == 2
        del built[:]
        fut = stage.submit(synthetic_document(5, 2))
        fut.result(timeout=120)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_build)
        stage.close()
    assert fut.receipt().padded_len in lengths
    assert stage.stats().launches == 1
    assert "jit(_embed_batch)" not in built


# ------------------------------------------------- flash-attention parity


def test_flash_kernel_matches_sdpa_at_serving_shapes():
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import _sdpa

    key = jax.random.key(0)
    for (b, s, h, d) in [(4, 64, 2, 16), (2, 128, 4, 16)]:
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, s), 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
        v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
        out_flash = flash_attention(q, k, v, causal=True, interpret=True)
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        mask = pos[:, None, :] <= pos[:, :, None]
        out_ref = _sdpa(q, k, v, mask, d**-0.5)
        np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref),
                                   atol=2e-5, rtol=2e-5)


def test_model_flash_impl_matches_sdpa_impl():
    """attn_impl='flash' routes the backbone through the Pallas kernel and
    reproduces the forced-naive path at the stage's serving shapes."""
    from repro.configs.base import get_config
    from repro.data.tokenizer import ByteTokenizer
    from repro.models import embed_sentences, init_params

    cfg = get_config("sbert-paper").reduced()
    params = init_params(cfg, jax.random.key(1))
    tok = ByteTokenizer()
    sents = synthetic_document(9, 4)
    tokens, segs = tok.encode_sentences(sents, 128)
    args = (jnp.asarray(tokens)[None], jnp.asarray(segs)[None])
    e_sdpa = embed_sentences(cfg.replace(attn_impl="sdpa"), params, *args,
                             n_segments=len(sents))
    e_flash = embed_sentences(cfg.replace(attn_impl="flash"), params, *args,
                              n_segments=len(sents))
    np.testing.assert_allclose(np.asarray(e_flash), np.asarray(e_sdpa),
                               atol=2e-4, rtol=2e-4)


# ------------------------------------------------- two-stage pipelining


def test_encode_overlaps_ising_drains():
    """Encode of later requests overlaps Ising drains of earlier ones when
    an EncoderStage fronts a self-draining farm (the tentpole's pipeline
    claim, asserted on the two stages' busy-interval intersection)."""
    docs = [" ".join(synthetic_document(30 + i, 8)) for i in range(6)]
    cfg = SolveConfig(solver="cobi", iterations=4, reads=16, int_range=14,
                      steps=400, p=20, q=10)
    overlap = 0.0
    for attempt in range(3):
        stage = EncoderStage.tiny(max_len=512)
        stage.prewarm(lengths=[512])
        farm = CobiFarm(2, policy="bin-full")
        eng = SummarizationEngine(cfg, encoder=stage, farm=farm)
        # Staggered open-loop arrivals: by the time later requests encode,
        # earlier requests' solve jobs are draining on the farm's
        # background thread -- that concurrency is what's under test.
        futs = []
        for doc in docs:
            futs.append(eng.submit(doc, m=4))
            time.sleep(0.08)
        responses = [f.result(timeout=300) for f in futs]
        eng.close()
        for r in responses:
            assert r.encoder_seconds > 0.0
            assert r.encoder_bytes > 0
            assert r.encoder_joules > 0.0
        overlap = _overlap_seconds(stage.busy_intervals(),
                                   farm.busy_intervals())
        if overlap > 0.0:
            break
    assert overlap > 0.0


def test_engine_stats_expose_stage():
    stage = EncoderStage.tiny()
    with SummarizationEngine(CFG, n_chips=2, encoder=stage) as eng:
        eng.submit(" ".join(synthetic_document(8, 10)), m=4).result(
            timeout=300)
        stats = eng.stats()
    assert stats["encoder_stage"]["jobs"] >= 1
    assert stats["encoder_stage"]["busy_seconds"] > 0.0
    assert stats["admission"]["admitted"] == 1


# ------------------------------------------------- query-embedding cache


def test_query_cache_hit_is_bit_identical_and_invalidated_on_params_swap():
    """submit_query: a hit returns the SAME embedding at zero metered cost,
    concurrent same-query submissions coalesce onto one encode, and a
    params swap invalidates everything cached."""
    stage = EncoderStage.tiny()
    q = "which sentence answers the question?"
    e1 = stage.submit_query(q).result(timeout=120)
    f2 = stage.submit_query(q, tag=9)
    e2 = f2.result(timeout=120)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    r2 = f2.receipt()
    assert r2.encoder_seconds == 0.0 and r2.batch_jobs == 0 and r2.tag == 9
    assert stage.cache_stats() == {
        "hits": 1, "misses": 1, "size": 1, "capacity": 256, "hit_rate": 0.5,
    }
    # In-flight coalescing: the second submission lands before the first
    # resolves, still counts as a hit, still bit-identical.
    fa = stage.submit_query("a brand new query")
    fb = stage.submit_query("a brand new query")
    np.testing.assert_array_equal(np.asarray(fa.result(timeout=120)),
                                  np.asarray(fb.result(timeout=120)))
    st = stage.cache_stats()
    assert st["hits"] == 2 and st["misses"] == 2 and st["size"] == 2
    # A params swap (same values, new object) drops the cache: the rows
    # were computed under the old weights object.
    stage.params = jax.tree_util.tree_map(lambda x: x, stage.params)
    e3 = stage.submit_query(q).result(timeout=120)
    st = stage.cache_stats()
    assert st["misses"] == 3 and st["hits"] == 2 and st["size"] == 1
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e3))
    stage.close()


def test_engine_query_relevance_uses_cache_and_reports_hit_rate():
    """Two rerank requests against the same query but different candidate
    sets share ONE query encode; the hit rate surfaces in engine stats."""
    from repro.serving.api import KofnSpec

    stage = EncoderStage.tiny()
    with SummarizationEngine(CFG, n_chips=2, encoder=stage) as eng:
        q = "what changed in the budget vote?"
        futs = [
            eng.submit(items=synthetic_document(21 + i, 6),
                       kofn=KofnSpec(m=2, relevance="query", query=q))
            for i in range(2)
        ]
        for f in futs:
            assert len(f.result(timeout=300.0).selected) == 2
        stats = eng.stats()
    cache = stats["encoder_cache"]
    assert cache["hits"] == 1 and cache["misses"] == 1
    assert cache["hit_rate"] == pytest.approx(0.5)
