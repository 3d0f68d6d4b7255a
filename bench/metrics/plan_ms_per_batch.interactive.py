"""Host run planner time a batch: the mean of the program's ``plan`` stage
over the window (the histogram ``serving.stage_ms{stage=plan}``), from
the hash locations' pull through the probe kernel's launch."""

from bench import spans


def read(ctx):
    return spans.stage_ms_per_call(ctx.window.counters, "plan")
