"""``probe_rows``: the planned row gather of the ``idl_probe`` query
backend (``repro.kernels.idl_probe``).

The problem's work: each probe reads one row of the bit-sliced index,
``n_files / 32`` words, and the gathered row is written out once. It is
a gather, with no arithmetic to speak of, so its bound is the memory.
"""

NAME = "probe_rows"


def matches(op_name: str) -> bool:
    """Whether a device op of the trace is this kernel."""
    return NAME in op_name


def work(ctx) -> dict:
    """Operations and bytes of the probes of the reads answered in the
    traced span, whose batches are the ones whose kernels ran in it (to
    within the one batch in flight at each end of the span)."""
    cfg = ctx.cell.config
    t0, t1 = ctx.trace.span
    kmers = sum(len(r.read) - cfg["k"] + 1 for r in ctx.window.requests
                if r.error is None and r.done is not None
                and t0 < r.done <= t1)
    row_bytes = cfg["n_files"] // 32 * 4
    return {"bytes": 2 * kmers * cfg["eta"] * row_bytes, "flops": 0}
