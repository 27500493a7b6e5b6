"""Observability: tracer ring semantics, metrics registry, trace export,
and the engine-level conservation invariants.

The load-bearing invariants:

* **Zero cost when disabled**: a disabled tracer returns the ``NULL_SPAN``
  singleton, records nothing, and a tracing-disabled engine run is
  bit-identical to a traced one (tracing never touches PRNG keys, instance
  data, or scheduling order).
* **Span trees complete**: every adopted request has exactly one CLOSED
  root ``request`` span; every other span in the request's trace is
  parented; ``unclosed_spans == 0`` after any run (phase spans are emitted
  atomically, so generator error paths cannot leak).
* **Meter conservation**: farm.job span meters are copied verbatim from
  receipts, so their sums equal the registry's receipt-fed histogram sums
  bit-for-bit, and span byte sums equal ``FarmStats`` byte totals exactly.
* **Flight recorder**: a ``RequestFailed`` terminal carries the request's
  last-N trace records including the closed root span.
"""

import json

import numpy as np
import pytest

from repro.core import SolveConfig
from repro.data.synthetic import synthetic_document
from repro.farm import FaultPlan
from repro.obs import (
    NULL_SPAN,
    FlightRecorder,
    MetricsRegistry,
    Observability,
    Tracer,
    chrome_trace,
    prometheus_text,
    validate_chrome_trace,
)
from repro.serving import (
    RequestFailed,
    RetryPolicy,
    SummarizationEngine,
    SummarizeRequest,
)

CFG = SolveConfig(solver="cobi", iterations=2, reads=6, int_range=14,
                  steps=100, p=20, q=10)
DOCS = [" ".join(synthetic_document(500 + i, n)) for i, n in
        enumerate([14, 70, 18, 12])]


def _reqs():
    return [SummarizeRequest(text=d, m=5, request_id=i + 1)
            for i, d in enumerate(DOCS)]


# --------------------------------------------------------------- tracer


def test_disabled_tracer_is_null_and_free():
    tr = Tracer(enabled=False)
    s = tr.span("x", trace_id=1)
    assert s is NULL_SPAN
    assert s.child("y") is NULL_SPAN
    assert not s  # falsy: `if span:` guards cost nothing
    s.set(a=1)
    s.event("e")
    s.end()
    tr.emit_span("z", trace_id=1)
    tr.event("e2", trace_id=1)
    tr.register_root(1, s)
    assert tr.root_id(1) is None
    assert tr.records() == []
    assert tr.unclosed_spans() == 0 and tr.dropped == 0


def test_span_lifecycle_and_parenting():
    tr = Tracer()
    with tr.span("root", trace_id=9, track="t") as root:
        tr.register_root(9, root)
        with root.child("kid", sim_t0=1.0) as kid:
            kid.set(meter=2.5)
            kid.event("tick", sim_t=1.5)
            kid.end(sim_t1=2.0)
    recs = tr.records(9)
    by_name = {r["name"]: r for r in recs}
    assert by_name["kid"]["parent"] == by_name["root"]["id"]
    assert by_name["kid"]["sim0"] == 1.0 and by_name["kid"]["sim1"] == 2.0
    assert by_name["kid"]["attrs"]["meter"] == 2.5
    assert by_name["tick"]["kind"] == "event"
    assert by_name["tick"]["parent"] == by_name["kid"]["id"]
    assert tr.unclosed_spans() == 0
    # end() is idempotent: a second end must not double-close
    closed = tr.closed
    by_name_span = [r for r in recs if r["kind"] == "span"]
    assert len(by_name_span) == 2
    assert tr.closed == closed


def test_ring_bounds_and_drop_count():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.emit_span("s", trace_id=1, t0=float(i), t1=float(i))
    assert len(tr.records()) == 8
    assert tr.dropped == 12
    assert tr.records()[-1]["t0"] == 19.0  # newest survive


def test_emit_span_is_atomic():
    tr = Tracer()
    tr.emit_span("a", trace_id=1, t0=0.0, t1=1.0, v=3)
    assert tr.unclosed_spans() == 0
    (r,) = tr.records()
    assert r["t0"] == 0.0 and r["t1"] == 1.0 and r["attrs"]["v"] == 3


def test_root_registration_resolves_until_commit():
    tr = Tracer()
    root = tr.span("request", trace_id=5)
    tr.register_root(5, root)
    assert tr.root_id(5) == root.span_id
    assert tr.root_id(None) is None
    assert tr.root_id(404) is None
    root.end()
    assert tr.root_id(5) is None  # entry removed once the root commits


# -------------------------------------------------------------- metrics


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "jobs", labels=("backend",))
    c.labels(backend="farm").inc()
    c.labels(backend="farm").inc(2)
    c.labels(backend="pool").inc()
    assert c.labels(backend="farm").value == 3.0
    assert c.total() == 4.0
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5.0
    h = reg.histogram("lat_seconds", "latency")
    for v in (0.001, 0.01, 0.1):
        h.observe(v)
    hc = h.labels()  # label-less family: the solo child holds the stats
    assert hc.count == 3 and hc.sum == 0.001 + 0.01 + 0.1
    assert hc.vmin == 0.001 and hc.vmax == 0.1
    assert 0.0 < hc.ewma < 0.1


def test_registry_reregistration_and_kind_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", labels=("k",))
    b = reg.counter("x_total", "x", labels=("k",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", labels=("other",))
    with pytest.raises(ValueError):
        a.labels(wrong="v")


def test_snapshot_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("a_total", "help a").inc(2)
    reg.histogram("b_seconds", "help b", labels=("w",)).labels(
        w="x").observe(0.5)
    snap = reg.snapshot()
    assert snap["a_total"]["series"][0]["value"] == 2.0
    assert snap["b_seconds"]["series"][0]["labels"] == {"w": "x"}
    text = prometheus_text(reg)
    assert "# TYPE a_total counter" in text
    assert "# TYPE b_seconds histogram" in text
    assert 'w="x"' in text


# ------------------------------------------------------ export/recorder


def test_chrome_trace_roundtrip_and_validation():
    tr = Tracer()
    root = tr.span("request", trace_id=1, track="engine")
    tr.register_root(1, root)
    tr.emit_span("farm.job", trace_id=1, parent=root.span_id,
                 track="chip0", t0=0.0, t1=0.5, sim_t0=0.0, sim_t1=0.0002)
    tr.event("mark", trace_id=1, track="engine")
    root.end()
    doc = chrome_trace(tr)
    n = validate_chrome_trace(doc)
    assert n == len(doc["traceEvents"])
    json.dumps(doc)  # exported document must be JSON-serializable
    # a sim-stamped span appears on BOTH clock tracks (pid 1 wall, pid 2 sim)
    pids = {e["pid"] for e in doc["traceEvents"]
            if e.get("name") == "farm.job"}
    assert pids == {1, 2}
    assert doc["otherData"]["unclosed_spans"] == 0
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"no_ph": 1}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({})


def test_flight_recorder_dumps_last_n_for_one_trace():
    tr = Tracer()
    rec = FlightRecorder(tr, last_n=3)
    for i in range(6):
        tr.emit_span(f"s{i}", trace_id=7, t0=float(i), t1=float(i))
    tr.emit_span("other", trace_id=8)
    dump = rec.dump(7)
    assert [r["name"] for r in dump] == ["s3", "s4", "s5"]  # oldest first
    assert rec.dump(404) == []
    off = FlightRecorder(Tracer(enabled=False))
    assert off.dump(7) == []


def test_observability_bundle_disabled_keeps_registry_live():
    obs = Observability.disabled()
    assert not obs.tracer.enabled
    obs.registry.counter("still_counts_total", "x").inc()
    assert obs.registry.snapshot()["still_counts_total"]["series"][0][
        "value"] == 1.0


# ------------------------------------------------- engine conservation


@pytest.fixture(scope="module")
def traced_run():
    eng = SummarizationEngine(CFG, n_chips=2, seed=0)
    responses = eng.run_batch(_reqs(), seed=0)
    recs = eng.obs.tracer.records()
    snap = eng.obs.registry.snapshot()
    obs_stats = eng.stats()["obs"]
    farm_stats = eng.farm.stats()
    eng.close()
    return responses, recs, snap, obs_stats, farm_stats


def test_engine_run_closes_every_span(traced_run):
    _, _, _, obs_stats, _ = traced_run
    assert obs_stats["unclosed_spans"] == 0
    assert obs_stats["dropped_events"] == 0


def test_engine_span_trees_complete(traced_run):
    _, recs, _, _, _ = traced_run
    roots = {r["trace"]: r["id"] for r in recs
             if r["kind"] == "span" and r["name"] == "request"}
    assert sorted(roots) == [1, 2, 3, 4]  # one closed root per request
    for r in recs:
        if r["kind"] != "span" or r["trace"] not in roots:
            continue
        if r["id"] != roots[r["trace"]]:
            assert r["parent"] is not None, f"orphan span {r['name']}"


def test_engine_meter_conservation_bitwise(traced_run):
    _, recs, snap, _, farm_stats = traced_run
    jobs = [r for r in recs if r["kind"] == "span" and r["name"] == "farm.job"]
    assert jobs
    span_chip_s = sum(r["attrs"]["chip_seconds"] for r in jobs)
    span_joules = sum(r["attrs"]["energy_joules"] for r in jobs)
    hist_chip_s = sum(s["sum"]
                      for s in snap["farm_job_chip_seconds"]["series"])
    hist_joules = sum(s["sum"]
                      for s in snap["farm_job_energy_joules"]["series"])
    # bit-for-bit: spans and histograms fold the SAME receipt values in the
    # SAME order, so even float association cannot diverge
    assert span_chip_s == hist_chip_s
    assert span_joules == hist_joules
    # bytes are integers: span sums equal the drain-level FarmStats exactly
    assert sum(r["attrs"]["bytes_h2d"] for r in jobs) == farm_stats.bytes_h2d
    assert sum(r["attrs"]["bytes_d2h"] for r in jobs) == farm_stats.bytes_d2h
    assert len(jobs) == farm_stats.jobs_completed


def test_tracing_disabled_is_bit_identical(traced_run):
    responses, _, _, _, _ = traced_run
    eng = SummarizationEngine(CFG, n_chips=2, seed=0, tracing=False)
    untraced = eng.run_batch(_reqs(), seed=0)
    assert eng.obs.tracer.records() == []
    assert eng.stats()["obs"]["tracing"] is False
    eng.close()
    for a, b in zip(responses, untraced):
        np.testing.assert_array_equal(a.selection, b.selection)
        assert a.objective == b.objective


def test_stats_views_read_from_registry(traced_run):
    _, _, snap, _, _ = traced_run
    adm = snap["admission_admitted_total"]["series"][0]["value"]
    assert adm == len(DOCS)
    farm_jobs = sum(s["value"] for s in snap["farm_jobs_total"]["series"])
    assert farm_jobs > 0


def test_request_failed_carries_flight_log():
    eng = SummarizationEngine(CFG, n_chips=2,
                              faults=FaultPlan(seed=5, corrupt_rate=1.0),
                              retry=RetryPolicy(max_retries=1,
                                                failover=False))
    fut = eng.submit(DOCS[0], m=5)
    with pytest.raises(RequestFailed) as ei:
        fut.result(timeout=120.0)
    log = ei.value.flight_log
    assert log, "flight recorder dump missing from RequestFailed"
    terminal = [r for r in log if r.get("name") == "request"
                and not r.get("open")]
    assert terminal, "terminal root span record missing from flight log"
    assert terminal[-1]["attrs"]["outcome"] == "RequestFailed"
    assert eng.stats()["obs"]["unclosed_spans"] == 0
    eng.close()


def test_flight_log_empty_when_tracing_disabled():
    eng = SummarizationEngine(CFG, n_chips=2, tracing=False,
                              faults=FaultPlan(seed=5, corrupt_rate=1.0),
                              retry=RetryPolicy(max_retries=1,
                                                failover=False))
    fut = eng.submit(DOCS[0], m=5)
    with pytest.raises(RequestFailed) as ei:
        fut.result(timeout=120.0)
    assert ei.value.flight_log == ()
    eng.close()
