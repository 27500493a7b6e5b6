"""Routed encoder (``mla_moe`` configurations): FLOPs of the real work of
every encoder launch that ended in the traced window over the device time
of the ``_embed_batch`` program times the bf16 peak.  A launch's work is
its jobs' rows at their real lengths (``encode.job`` spans, whose wall
window is the launch's) and the held experts' choices its ``encoder.route``
span counted (``bench/harness/mla_moe_counts.py``).  Nothing to read
without ``encoder.route`` spans."""

from harness import mla_moe_counts

MODULE = r"_embed_batch"


def read(ctx):
    if ctx.trace is None or "arch_keys" not in ctx.enc:
        return None
    t = ctx.trace.module_time(MODULE)
    launch_of = {r["parent"]: (r["t0"], r["t1"]) for r in ctx.spans
                 if r["name"] == "encoder.launch" and ctx.in_trace(r["t1"])}
    routes = {launch_of[r["parent"]]: r["attrs"]["held_choices"]
              for r in ctx.spans
              if r["name"] == "encoder.route" and r["parent"] in launch_of}
    if t <= 0.0 or not routes:
        return None
    flops = sum(mla_moe_counts.held_expert_flops(ctx.enc, n)
                for n in routes.values())
    for r in ctx.spans:
        if r["name"] == "encode.job" and (r["t0"], r["t1"]) in routes:
            flops += mla_moe_counts.row_flops(ctx.enc, r["attrs"]["n_tokens"])
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
