"""Kernel ``cobi_dynamics``: share of its roofline (see Context.roofline);
its device time is the trace's operations named after the fused COBI
kernel."""

OP = r"cobi"


def read(ctx):
    return ctx.roofline(OP)
