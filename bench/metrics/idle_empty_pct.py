"""Device: share of the traced window's device idle time during which no
request is in the system: no ``request.queued`` (submitted, not yet
adopted) and no ``request`` span (adopted, not yet resolved) is open."""

from harness import host_spans


def read(ctx):
    queued = [r for r in ctx.spans if r["name"] == "request.queued"]
    if ctx.trace is None or not queued:  # a program without the queue span
        return None
    idle = host_spans.device_idle(ctx.trace)
    total = host_spans.length(idle)
    if total <= 0.0:
        return None
    inflight = host_spans.spans_of(ctx.spans, ("request.queued", "request"))
    busy = host_spans.length(host_spans.intersect(idle, inflight))
    return 100.0 * (total - busy) / total
