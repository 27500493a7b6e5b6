"""The window's p50 latency from due time, read in the traced run: the
end-to-end latency the untraced runs print, kept per layer because it
swings with the order in which a seed deals out the long documents."""

import numpy as np


def read(ctx):
    lat = [s.latency for s in ctx.served if s.latency is not None]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
