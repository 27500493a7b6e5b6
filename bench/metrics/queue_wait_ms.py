"""Engine and admission: mean ``request.queued`` span of the window's
requests, from the start of the engine's submit path (admission included)
to the driver adopting the request: the program's own reading of the wait
``admit_wait_ms`` takes from the harness's submit time."""


def read(ctx):
    return ctx.mean_ms(r["t1"] - r["t0"] for r in ctx.spans_named("request.queued"))
