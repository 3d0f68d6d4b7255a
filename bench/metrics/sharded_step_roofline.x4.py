"""Share of its roofline that the whole device step reaches on a cell
split by files over its chips: the least time the problem's bytes need at
the chips' aggregate memory bandwidth (each probe's row read and written
once, counted from the reads by the probe kernel's work function, so it
stays the same work whatever implements the gather) over the chips' mean
busy time in the traced span.

It is the whole step's share, not one kernel's: inside the ``sharded``
backend's ``shard_map`` every chip runs a plain XLA gather, whose ops
carry no name the program pins, so the busy time also holds the hash and
the coverage postlude."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    work = ctx.kernel("probe_rows").work(ctx)
    bandwidth = ctx.cell.chips * ctx.peak["hbm_bytes_per_s"]
    return 100.0 * work["bytes"] / bandwidth / ctx.trace.busy_s
