"""Routed encoder: how unevenly the held experts are loaded.  Mean, over
the ``encoder.route`` spans that start in the window (one per launch), of
the most choices any held expert took in any MoE layer over the mean
choices per held expert and layer.  1 is an even load.

A diagnostic of the traffic and the seeded router and bias, not a lever:
the dropless layer computes a buffer sized for the worst case whatever
the load, so its device time does not follow this number, and a change
that moved it would change the embeddings.  Its pair is a load-dependent
grouped-matmul kernel's roofline, once one exists."""


def read(ctx):
    t0, t1 = ctx.window
    ratios = [r["attrs"]["expert_max"] / r["attrs"]["expert_mean"]
              for r in ctx.spans
              if r["name"] == "encoder.route" and t0 <= r["t0"] <= t1
              and r["attrs"].get("expert_mean", 0) > 0]
    return sum(ratios) / len(ratios) if ratios else None
