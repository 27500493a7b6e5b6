"""The host-work readers on a made-up run whose spans, device busy time and
gaps are known, and on the spans of a program that marks no host work."""

import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import pytest  # noqa: E402

from harness import host_spans, spec  # noqa: E402
from harness.context import Context  # noqa: E402
from harness.trace import DeviceTrace, Event  # noqa: E402

NAMES = ("queue_wait_ms", "driver_busy_pct", "formulate_ms",
         "encoder_host_ms", "idle_host_pct", "idle_empty_pct")


def readers():
    return {n: spec.load_module(BENCH / "metrics" / f"{n}.py").read
            for n in NAMES}


def span(name, t0, t1, *, trace=None, track="driver", id=None, parent=None):
    return {"kind": "span", "name": name, "trace": trace, "id": id,
            "parent": parent, "track": track, "t0": t0, "t1": t1,
            "attrs": {}}


# Device busy [1, 2] and [5, 6] of the window [0, 10]: idle [0, 1], [2, 5],
# [6, 10], 8 s in all.
TRACE = DeviceTrace([Event("k", 1.0, 2.0), Event("k", 5.0, 6.0)], [],
                    start=0.0, stop=10.0)
SPANS = [
    # request 1: submitted at 0.4, queued 0.5-1.0, in the engine 1.0-3.0
    span("engine.submit", 0.4, 0.5, trace=1, track="submit"),
    span("request.queued", 0.5, 1.0, trace=1, track="engine"),
    span("request", 1.0, 3.0, trace=1, track="engine", id=1),
    span("solve.formulate", 2.0, 2.5, trace=1),
    span("solve.reduce", 2.5, 2.8, trace=1),
    span("engine.idle", 3.0, 6.0),
    # request 2: queued 6.0-7.0, in the engine 7.0-8.0, two solve windows,
    # resolved past the window's close (9.2)
    span("request.queued", 6.0, 7.0, trace=2, track="engine"),
    span("request", 7.0, 8.0, trace=2, track="engine", id=2),
    span("solve.formulate", 7.0, 7.2, trace=2),
    span("solve.formulate", 7.5, 7.6, trace=2),
    span("engine.resolve", 9.0, 9.4, trace=2),
    # one encoder launch in the window (5.5-6.5), one after its close
    span("encoder.batch", 5.5, 6.5, track="encoder", id=50),
    span("encoder.pack", 5.5, 5.6, track="encoder", parent=50),
    span("encoder.launch", 5.6, 6.2, track="encoder", parent=50),
    span("encoder.readout", 6.2, 6.5, track="encoder", parent=50),
    span("encoder.batch", 9.5, 9.9, track="encoder", id=60),
    span("encoder.pack", 9.5, 9.6, track="encoder", parent=60),
    # a request of an earlier phase, and farm host work with no request in
    span("request", -2.0, 0.2, trace=9, track="engine", id=9),
    span("farm.pack", 8.5, 8.7, track="farm"),
]


def context(spans, trace=TRACE):
    served = [SimpleNamespace(rid=1), SimpleNamespace(rid=2)]
    return Context(None, served, [], spans, trace, {}, {}, (0.2, 9.2))


def test_readers_on_a_made_up_run():
    got = {n: r(context(SPANS)) for n, r in readers().items()}
    assert got["queue_wait_ms"] == pytest.approx(1e3 * (0.5 + 1.0) / 2)
    # driver work 2.0-2.8, 7.0-7.2, 7.5-7.6, 9.0-9.2 (clipped) of 9 s
    assert got["driver_busy_pct"] == pytest.approx(100 * 1.3 / 9.0)
    # request 1: 0.5 s, request 2: 0.2 + 0.1 s
    assert got["formulate_ms"] == pytest.approx(1e3 * (0.5 + 0.3) / 2)
    # the launch starting in the window: pack 0.1 + readout 0.3
    assert got["encoder_host_ms"] == pytest.approx(1e3 * 0.4)
    # host work over idle time: 0.4-0.5, 2.0-2.8, 6.0-6.5, 7.0-7.2,
    # 7.5-7.6, 8.5-8.7, 9.0-9.4, 9.5-9.9
    host = 0.1 + 0.8 + 0.5 + 0.2 + 0.1 + 0.2 + 0.4 + 0.4
    assert got["idle_host_pct"] == pytest.approx(100 * host / 8.0)
    # a request in the system over idle time: 0-0.2 (the earlier one),
    # 0.5-1.0, 2.0-3.0, 6.0-8.0
    inflight = 0.2 + 0.5 + 1.0 + 2.0
    assert got["idle_empty_pct"] == pytest.approx(100 * (8.0 - inflight) / 8.0)
    assert got["idle_host_pct"] + got["idle_empty_pct"] <= 100.0


def test_without_host_work_spans_the_readers_find_nothing(monkeypatch):
    # a program from before host-work spans: no HOST_WORK_SPANS to import,
    # and only the request and backend spans in its ring
    monkeypatch.setattr(host_spans, "host_work_names", lambda: None)
    old = [r for r in SPANS if r["name"] in ("request", "farm.pack")]
    for name, read in readers().items():
        assert read(context(old)) is None, name
    assert readers()["idle_empty_pct"](context(SPANS, trace=None)) is None


def test_interval_helpers():
    assert host_spans.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [(0, 2), (3, 4)]
    assert host_spans.intersect([(0, 2), (3, 4)], [(1, 3.5)]) == [(1, 2), (3, 3.5)]
    assert host_spans.clip([(0, 2), (3, 4)], 1, 1) == []
    assert host_spans.device_idle(TRACE) == [(0.0, 1.0), (2.0, 5.0), (6.0, 10.0)]
    assert host_spans.host_work_names()[0] == "engine.submit"
