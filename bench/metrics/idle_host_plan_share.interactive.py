"""Share of the traced window in which the device runs no op while the
host is inside the program's ``plan`` stage: the idle time the host run
planner explains. It is at most ``device_idle_share`` of the same run;
the difference is idle time the planner does not explain."""

from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    return spans.idle_share_inside(spans.run_trace(), ctx.trace.ops, "plan")
