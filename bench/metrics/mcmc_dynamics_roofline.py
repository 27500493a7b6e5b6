"""Kernel ``mcmc_dynamics``: share of its roofline (see Context.roofline);
its device time is the trace's operations named after the MCMC kernel."""

OP = r"mcmc"


def read(ctx):
    return ctx.roofline(OP)
