"""Median wait of a read before its batch was dispatched, over every read
of the window: from the client's send to the dispatch of the batch that
answered it, as the scheduler reports the batch (its completion less its
wall time) to the harness's ``on_batch`` hook."""

import numpy as np


def read(ctx):
    waits = [(r.dispatched - r.sent) * 1e3 for r in ctx.window.requests
             if r.dispatched is not None]
    return float(np.percentile(waits, 50)) if waits else None
