"""Device: share of the traced window's device idle time during which at
least one host-work span (``repro.obs.HOST_WORK_SPANS``) is open on some
thread: idle time the host is busy in named work."""

from harness import host_spans


def read(ctx):
    names = host_spans.host_work_names()
    if names is None or ctx.trace is None:
        return None
    idle = host_spans.device_idle(ctx.trace)
    total = host_spans.length(idle)
    if total <= 0.0:
        return None
    work = host_spans.spans_of(ctx.spans, names)
    return 100.0 * host_spans.length(host_spans.intersect(idle, work)) / total
