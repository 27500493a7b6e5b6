"""Encoder stage: FLOPs of the real tokens it encoded in the traced window
over the device time of its launches (the ``_embed_batch`` program) times
the bf16 peak."""

from harness import counts

MODULE = r"_embed_batch"


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.module_time(MODULE)
    done = [r["trace"] for r in ctx.spans_named("encode.job")
            if ctx.in_trace(r["t1"])]
    if t <= 0.0 or not done:
        return None
    flops = sum(counts.encoder_flops(ctx.enc, ctx.tokens[rid]) for rid in done)
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
