"""Engine and admission: mean wait from a request's submission to the start
of its root ``request`` span (the engine's round loop adopting it)."""


def read(ctx):
    submitted = {s.rid: s.submitted for s in ctx.served}
    return ctx.mean_ms(r["t0"] - submitted[r["trace"]]
                       for r in ctx.spans_named("request"))
