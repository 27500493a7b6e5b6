"""The trace reduction, on a trace recorded on the CPU and on a made-up
one whose busy time and gaps are known."""

import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from harness.trace import DeviceTrace, Event, Profile  # noqa: E402


def test_made_up_trace():
    ops = [Event("k1", 1.0, 2.0), Event("k2", 1.5, 2.5), Event("k1", 4.0, 4.5)]
    mods = [Event("jit__embed_batch(3)", 1.0, 2.5), Event("jit_other", 4.0, 4.5)]
    tr = DeviceTrace(ops, mods, start=0.5, stop=5.0)
    assert tr.window_s == pytest.approx(4.5)
    assert tr.busy_s == pytest.approx(2.0)
    assert tr.op_time("k1") == pytest.approx(1.5)
    assert tr.module_time("_embed_batch") == pytest.approx(1.5)
    assert tr.top_ops(1) == [["k1", pytest.approx(1.5)]]
    spans = [("request", 0.0, 5.0), ("farm.pack", 2.6, 3.9)]
    gaps = tr.idle_gaps(spans, 3)
    assert gaps[0] == ["farm.pack", pytest.approx(1.5)]
    assert [g[0] for g in gaps[1:]] == ["request", "request"]
    assert sum(g[1] for g in gaps) == pytest.approx(tr.window_s - tr.busy_s)


def test_cpu_trace_reduction():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with Profile() as prof:
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.02)
    tr = prof.reduce()
    assert tr.window_s >= 0.06
    assert 0.0 < tr.busy_s < tr.window_s
    assert any("dot" in name for name, _ in tr.top_ops(10))
    gaps = tr.idle_gaps([("sleeping", prof.start, prof.stop)], 2)
    assert gaps[0][0] == "sleeping" and gaps[0][1] >= 0.015
    # device events sit inside the host window they were launched in
    assert min(e.t0 for e in tr.ops) >= prof.align_t - 1e-3
    assert max(e.t1 for e in tr.ops) <= prof.stop + 1e-3
