"""Share of its roofline that the probe kernel reaches: the least time
the chip needs for the problem's work (the larger of its operations over
the peak and its bytes over the memory bandwidth) over the kernel's time
in the trace. The work is counted from the reads, not from the kernel,
so it stays the same work whatever kernel a later change puts there."""


def read(ctx):
    if ctx.trace is None:
        return None
    kernel = ctx.kernel("probe_rows")
    seconds = ctx.trace.kernel_seconds(kernel.matches)
    if seconds <= 0:
        return None
    work = kernel.work(ctx)
    least = max(work["bytes"] / ctx.peak["hbm_bytes_per_s"],
                work["flops"] / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least / seconds
