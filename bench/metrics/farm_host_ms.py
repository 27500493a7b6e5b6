"""COBI farm: mean host time per drain group in ``farm.pack``,
``farm.place`` and ``farm.readout`` (the spans around the kernel launch)."""

PHASES = ("farm.pack", "farm.place", "farm.readout")


def read(ctx):
    t0, t1 = ctx.window
    groups = {r["id"] for r in ctx.spans
              if r["name"] == "farm.group" and t0 <= r["t0"] <= t1}
    per = {}
    for r in ctx.spans:
        if r["name"] in PHASES and r["parent"] in groups:
            per[r["parent"]] = per.get(r["parent"], 0.0) + r["t1"] - r["t0"]
    return ctx.mean_ms(per.values())
