"""On-chip benchmark of the served summarization path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --sweep 10,20,30

Runs one cell of ``BENCHMARK.json`` in this process on the chip it starts
on: set-up (weights from the seed, every shape the traffic can meet warmed),
a short warm-up of the cell's open-loop traffic, then ``--seconds`` of
measured window.  With ``--trace 0`` the result holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from the program's spans
and a profiler trace of the window.  The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``[, ``breakdown``], ``checks``); the numbers compared for
``correct`` close standard error, each beside its limit.

``--sweep`` steps the offered rate behind one set-up and prints one line
per rate (latency percentiles and the share completed inside the window),
to find a cell's knee.  It makes no checks.

Exits non-zero, printing no result, unless ``jax.devices()`` holds a TPU
and as many devices as the cell asks for.
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


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (req/s)")
    return ap.parse_args(argv)


def device_guard(chips: int):
    """The run's first device; exits non-zero off a TPU or short of chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, found {len(devs)}")
    return devs[0]


def main(argv=None) -> None:
    args = parse(argv)
    from harness import cache, runner, spec

    cache_dir = cache.enable(ROOT)
    cell = spec.cell(args.workload, ROOT)
    dev = device_guard(cell.chips)
    peaks = spec.peaks(dev.device_kind, ROOT)
    runner.log(f"device: {dev.platform} {dev.device_kind} x{cell.chips}; "
               f"compile cache {cache_dir}")
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        rows = runner.sweep(cell, seed=args.seed, seconds=args.seconds,
                            rates=rates)
        print(json.dumps({"sweep": rows, "device": {
            "platform": dev.platform, "kind": dev.device_kind}}))
        return
    result = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=T_START,
                             peaks=peaks)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
