"""Benchmark driver (deliverable d): one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus a header comment)."""

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single module")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        chaos_soak,
        encoder_serving,
        farm_throughput,
        fig1_formulation,
        fig23_iterations,
        fig5_decomposition,
        fig6_solvers,
        fused_readout,
        kernel_bench,
        repair_bench,
        roofline,
        supplementary,
        tts_ets,
    )

    modules = {
        "fig1": fig1_formulation.run,
        "fig23": fig23_iterations.run,
        "fig5": fig5_decomposition.run,
        "fig6": fig6_solvers.run,
        "tts_ets": tts_ets.run,
        "supplementary": supplementary.run,
        "kernels": kernel_bench.run,
        "roofline": roofline.run,
        "farm": farm_throughput.run,
        "fused_readout": fused_readout.run,
        "repair": repair_bench.run,
        "chaos": chaos_soak.run,
        "encoder": encoder_serving.run,
    }
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for name, fn in modules.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---", flush=True)
        fn()
    print(f"# total_seconds={time.perf_counter() - t0:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
