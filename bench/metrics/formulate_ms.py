"""Solve pipeline: mean time per window request in ``solve.formulate``
spans (the Ising build, quantization and submission of each solve round;
a decomposed request sums its windows' rounds)."""


def read(ctx):
    per = {}
    for r in ctx.spans_named("solve.formulate"):
        per[r["trace"]] = per.get(r["trace"], 0.0) + r["t1"] - r["t0"]
    return ctx.mean_ms(per.values())
