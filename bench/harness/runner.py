"""One run of one cell: set-up, warm-up, the measured window, the checks.

Set-up makes the weights, builds the system, warms every shape the cell's
traffic can meet (the encoder's (batch, length, segments) lattice, the
readout slices of each launch, every per-size program of the request path
by serving one document of each size the traffic draws, and the farm's
launch lattice), then runs a short warm-up of the cell's own traffic.  The
window follows at once; nothing in it may compile, and the count is
printed.  The comparison with the plain reference runs after the window
has closed and the program's state is freed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import resource
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import build, check, serve, traffic
from harness.compiles import CompileCounter
from harness.context import Context
from harness.trace import Profile

COLLECT_GRACE = 60.0  # seconds past the window close to wait for answers
# Every cell's rule besides its own limits: nothing compiles in the window.
HARNESS_LIMITS = {"window_compiles": {"limit": 0}}
REF_SAMPLE = 16  # requests whose embeddings are checked against the reference


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def rss_gib() -> float:
    """Peak resident memory of this process so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def sizes_for(mix: dict, seconds: float, rates) -> List[int]:
    """Every sentence count the window and warm-up phases can draw."""
    out = set()
    for rate in rates:
        for secs in (seconds, mix["warmup_seconds"]):
            k = traffic.requests_in(mix, secs, rate)
            out.update(int(n) for n in traffic.sentence_counts(mix, k))
    return sorted(out)


def _serve_sizes(system, sizes: List[int], seed: int, step: int, m: int):
    """Serve one document of each size through ``system``, ``step`` at a
    time; raises if one fails."""
    rng = np.random.default_rng([seed, 7, sizes[0] if sizes else 0])
    for i in range(0, len(sizes), step):
        reqs = [traffic.Request(j, 0.0, n, 0, tuple(
            traffic.corpus.document(int(rng.integers(2**62)), n)))
            for j, n in enumerate(sizes[i:i + step])]
        served = serve.submit_schedule(system.engine, reqs, time.perf_counter(), m)
        serve.collect(served, time.perf_counter() + 900.0)
        for s in served:
            if s.response is None:
                raise RuntimeError(f"warm-up request of {s.n} sentences failed: "
                                   f"{s.error!r}")


def _in_parallel(fn, items, workers: int) -> None:
    with ThreadPoolExecutor(max_workers=max(1, workers)) as pool:
        for f in [pool.submit(fn, x) for x in items]:
            f.result()


def warm(system, cell, sizes: List[int], seed: int, weights) -> dict:
    """Compile everything the cell's traffic can reach; returns seconds per
    part.

    Compiles run on ``warm.workers`` threads at once (the compiler releases
    the interpreter).  The per-size programs of the request path are
    reached by serving one document of each size: the sizes are shared out
    over the system under test and ``workers - 1`` helper systems built
    alike (same weights, configuration and backend kind), whose programs
    land in the same process-wide caches; the helpers are closed after."""
    mix = cell.mix
    max_len = system.stage.max_len
    batches = mix["warm"]["encoder_batches"]
    workers = int(mix["warm"].get("workers", 1))
    took = {}
    t = time.perf_counter()
    lattice = traffic.encoder_lattice(sizes, max_len)
    _in_parallel(lambda item: system.stage.prewarm(
        lengths=[item[0]], batches=[item[1]], segments=lattice[item[0]]),
        [(length, b) for length in lattice for b in batches], workers)
    took["encoder_lattice"] = time.perf_counter() - t
    t = time.perf_counter()
    d = system.stage.cfg.d_model

    def slices(item):
        b, g, ns = item
        x = jnp.zeros((b, g, d), jnp.float32)
        for n in ns:
            x[0, :n].block_until_ready()
    _in_parallel(slices, [(b, g, ns) for b in batches
                          for g, ns in traffic.slice_lattice(sizes, max_len).items()],
                 workers)
    took["readout_slices"] = time.perf_counter() - t
    t = time.perf_counter()
    if system.farm is not None:
        solve = cell.config["solve"]
        system.farm.prewarm(reads=(solve["reads"],), steps=solve["steps"],
                            max_bins=mix["warm"]["farm_max_bins"],
                            max_slots=system.farm.lanes_per_chip // min(sizes))
    took["farm_lattice"] = time.perf_counter() - t
    t = time.perf_counter()
    helpers = [build.build(cell.config, weights, seed=seed, lam=mix["lam"],
                           tracing=False)
               for _ in range(workers - 1)]
    systems = [system] + helpers
    # longest first, dealt round-robin, so each thread gets a like share
    order = sorted(sizes, reverse=True)
    try:
        _in_parallel(lambda k: _serve_sizes(systems[k], order[k::len(systems)],
                                            seed, max(batches), mix["m"]),
                     range(len(systems)), len(systems))
    finally:
        for h in helpers:
            h.engine.close()
    took["per_size_programs"] = time.perf_counter() - t
    return took


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def max_batch(taps) -> int:
    """Most encode jobs that shared one launch among the window's requests
    (from the receipts the program returns; read after the window)."""
    most = 0
    for fut in taps.embeddings.values():
        if fut.done() and fut.exception(0.0) is None:
            most = max(most, fut.receipt(0.0).batch_jobs)
    return most


def _spans_on_host_clock(system, offset: float) -> List[dict]:
    out = []
    for r in system.obs.tracer.records():
        if r.get("kind") != "span":
            continue
        r = dict(r)
        r["t0"] += offset
        r["t1"] += offset
        out.append(r)
    return out


def setup(cell, *, seed: int, sizes: List[int], tracing: bool):
    """Weights, system, taps and the compile counter, all shapes warm."""
    config = cell.config
    counter = CompileCounter()
    enc = config["encoder"]
    weights = build.make_weights(config, cell.reference, seed)
    jax.block_until_ready(weights)
    system = build.build(config, weights, seed=seed, lam=cell.mix["lam"],
                         tracing=tracing)
    taps = serve.Taps(system.engine)
    took = warm(system, cell, sizes, seed, weights)
    log("warm-up seconds: " + ", ".join(f"{k} {v:.6f}" for k, v in took.items())
        + f" ({len(sizes)} sentence counts); peak host memory "
        f"{rss_gib():.3f} GiB")
    return system, weights, taps, counter, enc


def sweep(cell, *, seed: int, seconds: float, rates: List[float]) -> List[dict]:
    """Step the offered rate behind one set-up; one line per rate."""
    mix = cell.mix
    system, _, taps, counter, _ = setup(
        cell, seed=seed, sizes=sizes_for(mix, seconds, rates), tracing=False)
    rows = []
    for i, rate in enumerate(rates):
        sched = traffic.schedule(mix, seed + i, seconds, phase=2, rate=rate)
        win = window(system, taps, counter, sched, seconds, mix["m"],
                     trace=False)
        served = win.served
        lat = [s.latency if s.response is not None else win.t_all - s.due
               for s in served]
        done = sum(1 for s in served if s.done is not None and s.done <= win.close)
        row = {"rate": rate, "requests": len(served),
               "latency_p50_ms": 1e3 * percentile(lat, 50),
               "latency_p95_ms": 1e3 * percentile(lat, 95),
               "completed_in_window_share": done / len(served),
               "drain_s_after_close": max(win.t_all - win.close, 0.0),
               "compiles": win.compiles, "compile_names": win.compile_names,
               "reloads": win.reloads,
               "max_encoder_batch": max_batch(taps)}
        log("sweep " + " ".join(f"{k}={v}" for k, v in row.items()))
        rows.append(row)
    system.engine.close()
    return rows


@dataclasses.dataclass
class Window:
    served: list
    t0: float
    close: float
    t_all: float
    compiles: int
    compile_names: dict
    reloads: int
    reload_names: dict
    profile: Optional[Profile]


def window(system, taps, counter, schedule, seconds: float, m: int, *,
           trace: bool, earlier=()) -> Window:
    """Serve ``schedule`` open loop over ``seconds``; wait for every answer
    (and those of ``earlier`` requests) up to the grace period."""
    taps.jobs, taps.embeddings = [], {}
    with contextlib.ExitStack() as stack:
        prof = stack.enter_context(Profile()) if trace else None
        taps.active = True
        counter.reset()
        counter.active = True
        t0 = time.perf_counter() + 0.005
        served = serve.submit_schedule(system.engine, schedule, t0, m)
        close = t0 + seconds
        if time.perf_counter() < close:
            time.sleep(close - time.perf_counter())
    serve.collect(served, close + COLLECT_GRACE)
    counter.active = False
    taps.active = False
    serve.collect(list(earlier), close + COLLECT_GRACE)
    return Window(served, t0, close, time.perf_counter(), counter.count,
                  dict(counter.names), counter.reloads,
                  dict(counter.reload_names), prof)


def numbers(cell, enc: dict, weights, win: Window, taps, seed: int,
            ref_sample: int = REF_SAMPLE) -> dict:
    """Every number ``correct`` compares, plus ``quality_norm_obj``."""
    served = win.served
    m, lam = cell.mix["m"], cell.mix["lam"]
    rids = {s.rid for s in served}
    emb = {}
    for s in served:
        fut = taps.embeddings.get(s.rid)
        if fut is not None and fut.done() and fut.exception(0.0) is None:
            emb[s.rid] = np.asarray(fut.result(0.0), np.float32)
    out = check.selection_numbers(
        [s if s.rid in emb else dataclasses.replace(s, done=None)
         for s in served], emb, m, lam)
    jobs = [j for j in taps.jobs if j.tag in rids]
    out["window_compiles"] = win.compiles
    out["energy_gap"] = check.energy_gap(jobs)
    out["anneal_rank"] = check.anneal_rank(jobs, np.random.default_rng([seed, 13]))
    ok = [s for s in served if s.rid in emb]
    sample = []
    if ok:
        rng = np.random.default_rng([seed, 11])
        longest = max(ok, key=lambda s: (s.n, s.index))
        rest = [s for s in ok if s is not longest]
        k = min(ref_sample - 1, len(rest))
        sample = [longest] + [rest[i] for i in rng.choice(len(rest), k,
                                                          replace=False)]
    ref = cell.reference.embed_documents(enc, weights,
                                         [list(s.sentences) for s in sample])
    out["embed_gap"] = (check.embed_gap([emb[s.rid] for s in sample], ref)
                        if sample else float("inf"))
    return {k: float(v) for k, v in out.items()}


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
             peaks: Optional[dict], ref_sample: int = REF_SAMPLE) -> dict:
    mix = cell.mix
    m, rate = mix["m"], cell.rate
    schedule = traffic.schedule(mix, seed, seconds, rate=rate, phase=0)
    warm_phase = traffic.schedule(mix, seed, mix["warmup_seconds"], rate=rate,
                                  phase=1)
    system, weights, taps, counter, enc = setup(
        cell, seed=seed, sizes=sizes_for(mix, seconds, [rate]),
        tracing=trace)
    pre = serve.submit_schedule(system.engine, warm_phase, time.perf_counter(), m)
    win = window(system, taps, counter, schedule, seconds, m, trace=trace,
                 earlier=pre)
    setup_s = win.t0 - t_start
    served, t0, close = win.served, win.t0, win.close

    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    offset = time.perf_counter() - system.obs.tracer.now()
    spans = _spans_on_host_clock(system, offset) if trace else []
    est = system.stage.stats()
    system.engine.close()

    lateness = [s.submitted - s.due for s in served]
    log(f"generator lateness: median {percentile(lateness, 50) * 1e3:.3f} ms, "
        f"p99 {percentile(lateness, 99) * 1e3:.3f} ms, "
        f"max {max(lateness) * 1e3:.3f} ms over {len(served)} requests")
    max_len = enc["max_seq_len"]
    tokens = {s.rid: cell.reference.n_tokens(s.sentences, max_len)
              for s in served}
    n_cut = sum(1 + sum(len(x.encode()) + 1 for x in s.sentences) > max_len
                for s in served)
    log(f"traffic: {len(served)} requests at {rate} req/s, mean "
        f"{np.mean([s.n for s in served]):.3f} sentences, decomposed share "
        f"{sum(s.n > 59 for s in served) / len(served):.6f}, documents cut by "
        f"the encoder's {max_len} positions {n_cut / len(served):.6f}")
    log(f"encoder: {est.launches} launches, mean batch {est.mean_batch:.3f}, "
        f"most jobs in one window launch {max_batch(taps)}; peak host memory "
        f"{rss_gib():.3f} GiB")

    lat = [s.latency if s.response is not None else win.t_all - s.due
           for s in served]
    failed = sum(s.response is None for s in served)
    in_window = sum(1 for s in served + pre
                    if s.done is not None and t0 <= s.done <= close)
    nums = numbers(cell, enc, weights, win, taps, seed, ref_sample)
    limits = {**cell.limits, **HARNESS_LIMITS}
    checks = {k: {"value": nums[k], "limit": v["limit"]}
              for k, v in limits.items()}
    correct = check.verdict(nums, limits) and failed == 0

    units = {x["name"]: x["unit"] for x in cell.end_to_end + cell.per_layer}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    metrics: dict = {}
    out: dict = {}
    if not trace:
        e2e = {
            "setup_s": setup_s,
            "latency_p50_ms": 1e3 * percentile(lat, 50),
            "latency_p95_ms": 1e3 * percentile(lat, 95),
            "summaries_per_s": in_window / seconds,
            "quality_norm_obj": nums["quality_norm_obj"],
        }
        for x in cell.end_to_end:
            metrics[x["name"]] = {"value": e2e[x["name"]], "unit": x["unit"]}
        log(f"latency: p50 {e2e['latency_p50_ms']!r} ms, p95 "
            f"{e2e['latency_p95_ms']!r} ms")
    else:
        dtrace = win.profile.reduce()
        log(f"trace: {len(dtrace.ops)} device ops, {len(dtrace.modules)} "
            f"programs; peak host memory {rss_gib():.3f} GiB")
        jobs = [j for j in taps.jobs if j.tag in {s.rid for s in served}]
        ctx = Context(cell, served, jobs, spans, dtrace, peaks or {}, tokens,
                      (t0, close))
        for name, reader in cell.readers.items():
            v = reader(ctx)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": units[name]}
        device["busy_s"] = dtrace.busy_s
        device["window_s"] = dtrace.window_s
        host_spans = [(r["name"], r["t0"], r["t1"]) for r in spans]
        out["breakdown"] = {"device_ops": dtrace.top_ops(10),
                            "idle_gaps": dtrace.idle_gaps(host_spans, 10)}
    log(f"peak host memory at the end {rss_gib():.3f} GiB")
    log(f"programs reloaded from the compile cache in window: {win.reloads} "
        f"{win.reload_names}")
    log(f"compiles in window: {win.compiles} {win.compile_names}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return {"correct": bool(correct), "attempted": len(served), "failed": failed,
            "metrics": metrics, "device": device, **out, "checks": checks}


