"""Readings that set the limits of ``correct``: the program's numbers over
many seeds, and the numbers of the controls and faults planted in its place.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 --plants none,fp8_encoder,state_unchanged

One process and one set-up serve every (plant, seed) pair: each gets a
window of the cell's own traffic at its own rate, then the comparison of
``bench/harness/check.py``.  One JSON line per pair goes to standard
output.  Plants are listed in ``bench/harness/plants.py``; ``none`` is the
program as configured.  The benchmark's runs (``bench/run.py``) never call
this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plants", default="none")
    args = ap.parse_args(argv)
    from harness import cache, plants, runner, serve, spec, traffic
    from run import device_guard

    cache.enable(ROOT)
    cell = spec.cell(args.workload, ROOT)
    device_guard(cell.chips)
    control(cell, [int(s) for s in args.seeds.split(",")],
            args.plants.split(","), args.seconds, plants, runner, serve,
            traffic)


def control(cell, seeds, plant_names, seconds, plants, runner, serve, traffic,
            ref_sample=None):
    mix = cell.mix
    rate = cell.rate
    system, weights, taps, counter, enc = runner.setup(
        cell, seed=seeds[0], sizes=runner.sizes_for(mix, seconds, [rate]),
        tracing=False)
    pre = serve.submit_schedule(
        system.engine, traffic.schedule(mix, seeds[0], mix["warmup_seconds"],
                                        rate=rate, phase=1),
        time.perf_counter(), mix["m"])
    serve.collect(pre, time.perf_counter() + 120.0)
    rows = []
    for name in plant_names:
        for seed in seeds:
            sched = traffic.schedule(mix, seed, seconds, rate=rate, phase=0)
            with plants.plant(name, reference=cell.reference, enc=enc,
                              weights=weights):
                win = runner.window(system, taps, counter, sched, seconds,
                                    mix["m"], trace=False)
            kw = {} if ref_sample is None else {"ref_sample": ref_sample}
            nums = runner.numbers(cell, enc, weights, win, taps, seed, **kw)
            failed = sum(s.response is None for s in win.served)
            row = {"plant": name, "seed": seed, "requests": len(win.served),
                   "failed": failed, **nums}
            print(json.dumps(row), flush=True)
            rows.append(row)
    system.engine.close()
    return rows


if __name__ == "__main__":
    main()
