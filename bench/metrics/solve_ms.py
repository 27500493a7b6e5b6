"""Solve pipeline: mean ``request.solve`` span (formulation, quantization,
solver rounds and the host reduce)."""


def read(ctx):
    return ctx.mean_ms(r["t1"] - r["t0"] for r in ctx.spans_named("request.solve"))
