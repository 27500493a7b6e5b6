"""MCMC bank: mean ``pool.job`` span (one worker's unbatched kernel launch
and host reduce)."""


def read(ctx):
    return ctx.mean_ms(r["t1"] - r["t0"] for r in ctx.spans_named("pool.job"))
