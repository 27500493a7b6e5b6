"""The host-work metrics in a CPU rehearsal of a traced run, and the clock
the program's spans share with the device trace."""

import glob
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(Path(__file__).parent)]

import pytest  # noqa: E402

import bench_tiny_cell  # noqa: E402
from harness import host_spans, runner, spec  # noqa: E402
from harness.trace import ALIGN, Profile  # noqa: E402

NEW = ("queue_wait_ms", "driver_busy_pct", "formulate_ms", "encoder_host_ms",
       "idle_host_pct", "idle_empty_pct")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = bench_tiny_cell.make_root(tmp_path_factory.mktemp("bench"))
    cell = spec.cell(bench_tiny_cell.cell_name(), root)
    return runner.run_cell(cell, seed=2**31 + 29, seconds=3.0, trace=True,
                           t_start=time.perf_counter(),
                           peaks=spec.peaks("TPU v5 lite", spec.ROOT),
                           ref_sample=4)


def test_new_metrics_in_a_traced_run(traced):
    assert traced["correct"], traced["checks"]
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["queue_wait_ms"] > 0.0 and m["formulate_ms"] > 0.0
    assert m["encoder_host_ms"] > 0.0
    assert 0.0 < m["driver_busy_pct"] <= 100.0
    assert 0.0 <= m["idle_host_pct"] and 0.0 <= m["idle_empty_pct"]
    assert m["idle_host_pct"] + m["idle_empty_pct"] <= 100.0
    # the program's queue span reads the wait the harness times from its
    # own submit: they differ by the call into the engine
    assert abs(m["queue_wait_ms"] - m["admit_wait_ms"]) <= 2.0


def _module_events(path, align_t, module):
    """(start, end) on the host clock of the operations the CPU backend ran
    for the jitted program ``module`` (the ``hlo_module`` stat)."""
    from jax.profiler import ProfileData

    (f,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(f)
    lines = [ln for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines]
    align_ns = next(ev.start_ns for ln in lines for ev in ln.events
                    if ev.name == ALIGN)
    off = align_t - align_ns * 1e-9
    out = []
    for ln in lines:
        if not ln.name.startswith("tf_XLA"):
            continue
        for ev in ln.events:
            if ev.duration_ns > 0 and dict(ev.stats).get("hlo_module") == module:
                t0 = ev.start_ns * 1e-9 + off
                out.append((t0, t0 + ev.duration_ns * 1e-9))
    return out


def test_encoder_launch_spans_hold_the_embed_program():
    from repro.embeddings import EncoderStage
    from repro.obs import Observability

    stage = EncoderStage.tiny(max_len=512, obs=Observability())
    docs = [[f"Sentence {i} of document {d} is here." for i in range(4 + d)]
            for d in range(5)]
    for d in docs:  # compile every shape first
        stage.submit(d).result(timeout=300)
    stage.obs.tracer.clear()
    prof = Profile()
    with prof:
        futs = [stage.submit(d) for d in docs[:3]]
        [f.result(timeout=300) for f in futs]
        time.sleep(0.02)
        futs = [stage.submit(d) for d in docs[3:]]
        [f.result(timeout=300) for f in futs]
    tracer = stage.obs.tracer
    offset = time.perf_counter() - tracer.now()
    stage.close()
    launches = host_spans.union(
        (r["t0"] + offset, r["t1"] + offset) for r in tracer.records()
        if r["name"] == "encoder.launch")
    try:
        ops = host_spans.union(_module_events(prof.dir, prof.align_t,
                                              "jit__embed_batch"))
    finally:
        import shutil
        shutil.rmtree(prof.dir, ignore_errors=True)
    assert launches and ops
    inside = host_spans.length(host_spans.intersect(ops, launches))
    assert inside >= 0.95 * host_spans.length(ops)
