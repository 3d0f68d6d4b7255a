"""How late the open-loop client sent: the 99th percentile, over the
window's reads, of the send time less the scheduled arrival. A high
value means the client, not the server, set the latency."""

import numpy as np


def read(ctx):
    lag = [(r.sent - r.scheduled) * 1e3 for r in ctx.window.requests
           if r.scheduled is not None]
    return float(np.percentile(lag, 99)) if lag else None
