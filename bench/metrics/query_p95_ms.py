"""95th percentile of the latency of every read scheduled in the window,
each from its scheduled arrival to its answer. A read that failed counts
with the time until it was given up, a minute past the window's close."""

import numpy as np


def read(ctx):
    lat = [(r.done - r.scheduled) * 1e3 for r in ctx.window.requests
           if r.scheduled is not None]
    return float(np.percentile(lat, 95)) if lat else None
