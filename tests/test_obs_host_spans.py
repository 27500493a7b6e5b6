"""Host-work spans: every serving thread names what it does.

On a traced engine run (encoder stage in front of a manual-policy COBI
farm) the submit path, the driver, the encoder stage and the farm mark
their host work with the spans of ``repro.obs.HOST_WORK_SPANS``, the queue
with ``request.queued`` and the driver's waits with ``engine.idle``.  The
invariants:

* every such span occurs, and on each track the host-work spans nest or
  follow one another (one thread per track), never partly overlap;
* nothing stays open at quiescence, and no span is emitted per encode
  poll or per empty driver round;
* span intervals are tracer readings taken at the event: each
  ``encode.job`` lies inside its launch's ``encoder.launch``;
* tracing still never changes a selection.
"""

import time

import numpy as np
import pytest

from repro.core import SolveConfig
from repro.data.synthetic import synthetic_document
from repro.embeddings import EncoderStage
from repro.obs import HOST_WORK_SPANS
from repro.serving import SummarizationEngine, SummarizeRequest

CFG = SolveConfig(solver="cobi", iterations=2, reads=6, int_range=14,
                  steps=100, p=20, q=10)
DOCS = [" ".join(synthetic_document(700 + i, n)) for i, n in
        enumerate([14, 64, 9, 18])]
NEW_SPANS = ("engine.submit", "request.queued", "engine.idle",
             "engine.barrier", "request.problem", "solve.formulate",
             "solve.reduce", "engine.resolve", "encoder.tokenize",
             "encoder.batch", "encoder.pack", "encoder.launch",
             "encoder.readout")


def _serve(tracing: bool):
    """Two requests one by one (the driver idles between them), then a
    batch of the other two, one decomposed."""
    eng = SummarizationEngine(CFG, n_chips=2, seed=0, tracing=tracing,
                              encoder=EncoderStage.tiny(max_len=512))
    out = []
    for doc in DOCS[:2]:
        out.append(eng.submit(doc, m=4).result(timeout=300))
        time.sleep(0.08)  # longer than one idle span
    out += eng.run_batch([SummarizeRequest(text=d, m=4) for d in DOCS[2:]])
    time.sleep(0.08)
    recs = eng.obs.tracer.records()
    unclosed = eng.obs.tracer.unclosed_spans()
    drains = eng.farm.stats().drains
    eng.close()
    return out, recs, unclosed, drains


@pytest.fixture(scope="module")
def traced():
    return _serve(True)


def _spans(recs, names):
    return [r for r in recs if r["kind"] == "span" and r["name"] in names]


def test_every_new_span_occurs(traced):
    _, recs, _, _ = traced
    seen = {r["name"] for r in recs if r["kind"] == "span"}
    assert set(NEW_SPANS) <= seen, set(NEW_SPANS) - seen
    assert set(HOST_WORK_SPANS) - {"pool.job"} <= seen
    assert not {"request.queued", "engine.idle"} & set(HOST_WORK_SPANS)


def test_host_work_nests_on_each_track(traced):
    _, recs, _, _ = traced
    by_track = {}
    for r in _spans(recs, HOST_WORK_SPANS):
        by_track.setdefault(r["track"], []).append(r)
    assert {"submit", "driver", "encoder", "farm"} <= set(by_track)
    for track, spans in by_track.items():
        spans.sort(key=lambda r: (r["t0"], -r["t1"]))
        stack = []
        for r in spans:
            while stack and stack[-1]["t1"] <= r["t0"]:
                stack.pop()
            if stack:  # an enclosing span is still open: r must fit in it
                assert r["t1"] <= stack[-1]["t1"], (
                    f"{r['name']} overlaps {stack[-1]['name']} on {track}")
            stack.append(r)


def test_request_spans_hang_off_the_root(traced):
    _, recs, _, _ = traced
    roots = {r["trace"]: r for r in _spans(recs, ("request",))}
    assert len(roots) == len(DOCS)
    for r in _spans(recs, ("request.queued", "request.problem",
                           "solve.formulate", "solve.reduce",
                           "engine.resolve", "encoder.tokenize")):
        assert r["parent"] == roots[r["trace"]]["id"], r["name"]
    for r in _spans(recs, ("request.queued",)):
        root = roots[r["trace"]]
        assert r["t0"] <= r["t1"] == root["t0"]


def test_nothing_open_and_no_span_per_poll(traced):
    _, recs, unclosed, drains = traced
    assert unclosed == 0
    # a barrier span only for a round that drained work
    assert 0 < len(_spans(recs, ("engine.barrier",))) <= drains
    idle = _spans(recs, ("engine.idle",))
    assert idle
    # one bounded wait each (IDLE_SPAN_SECONDS, plus scheduling slack)
    assert max(r["t1"] - r["t0"] for r in idle) < 1.0


def test_encode_jobs_lie_inside_their_launch(traced):
    _, recs, _, _ = traced
    launches = [(r["t0"], r["t1"]) for r in _spans(recs, ("encoder.launch",))]
    jobs = _spans(recs, ("encode.job",))
    assert jobs and launches
    for r in jobs:
        assert any(a <= r["t0"] <= r["t1"] <= b for a, b in launches)


def test_untraced_selections_bit_identical(traced):
    responses, _, _, _ = traced
    untraced, recs, unclosed, _ = _serve(False)
    assert recs == [] and unclosed == 0
    for a, b in zip(responses, untraced):
        np.testing.assert_array_equal(a.selection, b.selection)
        assert a.objective == b.objective
