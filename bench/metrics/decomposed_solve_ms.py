"""Decomposition: mean ``request.solve`` span of requests solved in more
than one window (more solver invocations than one window's iterations)."""


def read(ctx):
    per_window = int(ctx.solve["iterations"])
    return ctx.mean_ms(
        r["t1"] - r["t0"] for r in ctx.spans_named("request.solve")
        if r["attrs"].get("solver_invocations", 0) > per_window)
