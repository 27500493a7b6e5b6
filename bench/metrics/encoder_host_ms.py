"""Encoder stage: mean host time per launch in ``encoder.pack`` and
``encoder.readout`` (the spans around ``encoder.launch``), over the
``encoder.batch`` spans that start in the window."""

PHASES = ("encoder.pack", "encoder.readout")


def read(ctx):
    t0, t1 = ctx.window
    per = {r["id"]: 0.0 for r in ctx.spans
           if r["name"] == "encoder.batch" and t0 <= r["t0"] <= t1}
    for r in ctx.spans:
        if r["name"] in PHASES and r["parent"] in per:
            per[r["parent"]] += r["t1"] - r["t0"]
    return ctx.mean_ms(per.values())
