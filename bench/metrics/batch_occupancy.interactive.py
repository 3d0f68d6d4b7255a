"""Share of the served batch rows that held a read: the service's
counters ``serving.requests`` over ``serving.batch_rows`` in the
window (the rest is padding)."""


def read(ctx):
    rows = ctx.counter("serving.batch_rows")
    if rows <= 0:
        return None
    return 100.0 * ctx.counter("serving.requests") / rows
