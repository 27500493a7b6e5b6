"""End-to-end serving driver (the paper's deployment scenario), in two modes.

**Batch mode** (default): a batch of summarization requests served through
``SummarizationEngine.run_batch`` -- all requests' subproblems share the
farm's packed anneals round by round -- with per-request latency and
projected COBI energy.

  PYTHONPATH=src python examples/summarize_service.py [--requests 6]

**Open-loop mode** (``--arrival-rate R``): requests arrive continuously at R
requests/second through the enqueueing ``submit()`` API, each returning an
awaitable ``ResponseFuture``; responses are collected in completion order
and admission control (``--max-queue-depth``, ``--deadline``) sheds or
degrades load under overload instead of letting the queue grow unboundedly:

  PYTHONPATH=src python examples/summarize_service.py \\
      --arrival-rate 200 --requests 32 --max-queue-depth 8 --policy deadline

``--policy bin-full|deadline|timer`` makes the farm self-draining: the
engine never supplies a round barrier, futures resolve from the background
drive loop, and results stay bit-identical to the manual default.

``--route`` adds the cost-model backend router above admission (needs the
default COBI farm, ``--chips > 0``): instead of shedding, farm overload
spills onto the host worker pool, picked per request from per-backend
latency/energy/quality predictions.  ``--profile`` points at a fitted
``CalibrationProfile`` JSON (``benchmarks/CALIBRATION_cobi_pool.json``);
without it routing uses the paper's hardware constants.  Responses report
which backend served them; results stay bit-identical either way.
"""

import argparse
import time

from repro.core import SolveConfig
from repro.data.synthetic import synthetic_document
from repro.farm import DRAIN_POLICIES
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (
    AdmissionConfig,
    EngineOverloadedError,
    SummarizationEngine,
    SummarizeRequest,
)

SIZES = [14, 20, 26, 70, 18, 24]  # mixed: some need decomposition (>59 spins)


def _print_response(resp):
    score = f"{resp.normalized:.3f}" if resp.normalized is not None else "n/a"
    extras = ""
    if resp.deadline_met is not None:
        extras += f" | deadline {'MET' if resp.deadline_met else 'MISSED'}"
    if resp.degraded:
        extras += f" | degraded to reads={resp.reads_used}"
    if resp.backend_used is not None:
        extras += f" | via {resp.backend_used}"
    print(
        f"  req {resp.request_id}: {len(resp.summary)} sentences | "
        f"norm_obj={score} | wall={resp.wall_seconds * 1e3:.0f} ms | "
        f"projected solver={resp.projected_solver_seconds * 1e3:.2f} ms, "
        f"{resp.projected_energy_joules * 1e3:.3f} mJ | "
        f"xfer={(resp.bytes_h2d + resp.bytes_d2h) / 1024:.0f} KiB | "
        f"solves={resp.solver_invocations}{extras}"
    )


def _print_farm(engine):
    if engine.farm is not None:
        s = engine.farm.stats()
        print(
            f"Farm: {s.jobs_completed} jobs packed into {s.super_instances} "
            f"super-instances on {len(s.chips)} chips | mean lane occupancy "
            f"{s.mean_occupancy:.0%} | simulated makespan {s.sim_seconds * 1e3:.2f} ms"
        )


def run_batch_mode(engine, args):
    sizes = SIZES[: args.requests] or SIZES
    reqs = [
        SummarizeRequest(
            text=" ".join(synthetic_document(100 + i, n)), m=6, request_id=i + 1
        )
        for i, n in enumerate(sizes)
    ]
    print(f"Serving {len(reqs)} requests on solver={args.solver!r} ...")
    responses = engine.run_batch(reqs)

    total_e = 0.0
    for resp in responses:
        _print_response(resp)
        total_e += resp.projected_energy_joules
    print(f"\nBatch projected solver energy: {total_e * 1e3:.3f} mJ "
          f"(paper: ~3 orders below CPU Tabu search)")
    _print_farm(engine)
    print("First summary:")
    for s in responses[0].summary:
        print(f"  - {s}")


def run_open_loop(engine, args):
    """Continuous arrival at --arrival-rate rps: submit() enqueues, futures
    resolve as the driver + drain policy serve; admission sheds overload."""
    n = args.requests
    gap = 1.0 / args.arrival_rate
    print(f"Open loop: {n} requests at {args.arrival_rate:.0f} rps, "
          f"policy={args.policy!r}, max_queue_depth="
          f"{args.max_queue_depth or 'unbounded'} ...")
    futures, rejected = [], 0
    t0 = time.perf_counter()
    for i in range(n):
        doc = " ".join(synthetic_document(300 + i, SIZES[i % len(SIZES)] % 40))
        sim_now = engine.backend.sim_now() if engine.backend is not None else 0.0
        deadline = sim_now + args.deadline if args.deadline > 0 else None
        try:
            futures.append(engine.submit(doc, m=6, deadline=deadline))
        except EngineOverloadedError:
            rejected += 1
        time.sleep(gap)
    responses = [f.result(timeout=600.0) for f in futures]
    wall = time.perf_counter() - t0

    for resp in responses:
        _print_response(resp)
    met = [r.deadline_met for r in responses if r.deadline_met is not None]

    # The open-loop report reads the unified metrics registry -- the same
    # counters Prometheus would scrape -- rather than per-component stats
    # dicts (which are themselves views over this registry).
    snap = engine.metrics_snapshot()

    def _value(name, **labels):
        fam = snap.get(name)
        if fam is None:
            return 0.0
        total = 0.0
        for s in fam["series"]:
            if all(s["labels"].get(k) == v for k, v in labels.items()):
                total += s.get("value", s.get("count", 0.0))
        return total

    degraded = int(_value("admission_degraded_total"))
    spilled = int(_value("admission_spilled_total"))
    peak_depth = int(_value("admission_peak_depth"))
    print(
        f"\nGoodput {len(responses) / wall:.1f} rps | offered "
        f"{n / wall:.1f} rps | shed {rejected}/{n} "
        f"({100 * rejected / max(n, 1):.0f}%) | degraded {degraded} | "
        f"peak queue depth {peak_depth}"
        + (f" | deadlines met {sum(met)}/{len(met)}" if met else "")
        + (f" | spilled {spilled}" if spilled else "")
    )
    obs = engine.stats()["obs"]
    lat = snap.get("farm_job_sim_latency_seconds")
    lat_line = ""
    if lat is not None and lat["series"]:
        cnt = sum(s["count"] for s in lat["series"])
        if cnt:
            tot = sum(s["sum"] for s in lat["series"])
            lat_line = (f" | farm job sim latency mean "
                        f"{tot / cnt * 1e3:.3f} ms over {cnt} jobs")
    print(f"Registry: tracing={obs['tracing']} "
          f"unclosed_spans={obs['unclosed_spans']} "
          f"dropped_events={obs['dropped_events']}" + lat_line)
    if engine.router is not None:
        print(f"Router: {engine.router.stats()} | "
              f"admission errors: {engine.admission.estimate_errors()}")
    _print_farm(engine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--solver", default="cobi", choices=["cobi", "tabu", "sa"])
    ap.add_argument("--chips", type=int, default=4,
                    help="simulated COBI chips in the farm (0 = legacy loop)")
    ap.add_argument("--policy", default="manual", choices=list(DRAIN_POLICIES),
                    help="farm drain policy (non-manual = self-draining farm)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop arrivals per second (0 = batch mode)")
    ap.add_argument("--max-queue-depth", type=int, default=0,
                    help="admission cap on in-flight requests (0 = unbounded)")
    ap.add_argument("--overload", default="reject", choices=["reject", "degrade"],
                    help="admission response past the cap / infeasible deadline")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request sim-clock deadline in seconds (0 = none)")
    ap.add_argument("--route", action="store_true",
                    help="cost-model backend routing above admission "
                         "(spill farm overload to the host pool)")
    ap.add_argument("--profile", default=None,
                    help="CalibrationProfile JSON for --route (default: "
                         "built-in hardware-constant profile)")
    args = ap.parse_args()
    enable_compile_cache()

    admission = None
    if args.max_queue_depth > 0 or args.deadline > 0:
        admission = AdmissionConfig(
            max_queue_depth=args.max_queue_depth or None,
            overload=args.overload,
        )
    engine = SummarizationEngine(
        SolveConfig(solver=args.solver, iterations=4, reads=8, int_range=14,
                    steps=300, p=20, q=10),
        score_against_exact=True,
        n_chips=args.chips,
        policy=args.policy,
        admission=admission,
        routing=args.route,
        profile=args.profile,
    )
    if args.arrival_rate > 0:
        run_open_loop(engine, args)
    else:
        run_batch_mode(engine, args)
    engine.close()


if __name__ == "__main__":
    main()
