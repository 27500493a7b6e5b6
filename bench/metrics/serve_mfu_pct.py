"""Whole served step: model FLOPs of the window's requests (the encoder on
real tokens, the solver kernels on real spins, reads and steps) over the
sum of their latencies times the bf16 peak.  Overlapping requests only add
to the denominator, so it cannot pass 100%."""


def read(ctx):
    done = [s for s in ctx.served if s.latency is not None]
    if not done:
        return None
    from harness import counts
    flops = sum(counts.encoder_flops(ctx.enc, ctx.tokens[s.rid]) for s in done)
    rids = {s.rid for s in done}
    flops += sum(ctx.job_work(j)[0] for j in ctx.jobs if j.tag in rids)
    lat = sum(s.latency for s in done)
    return 100.0 * flops / (lat * ctx.peaks["bf16_flops_per_s"])
