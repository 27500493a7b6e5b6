"""Encoder stage: mean ``request.encode`` span (submit to embeddings)."""


def read(ctx):
    return ctx.mean_ms(r["t1"] - r["t0"] for r in ctx.spans_named("request.encode"))
