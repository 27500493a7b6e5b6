"""Engine and admission: share of the window in which the engine's driver
thread runs named work (the union of its ``driver``-track spans other than
``engine.idle``, clipped to the window)."""

from harness import host_spans


def read(ctx):
    t0, t1 = ctx.window
    busy = [(r["t0"], r["t1"]) for r in ctx.spans
            if r["track"] == "driver" and r["name"] != "engine.idle"]
    if not busy or t1 <= t0:
        return None
    inside = host_spans.clip(host_spans.union(busy), t0, t1)
    return 100.0 * host_spans.length(inside) / (t1 - t0)
