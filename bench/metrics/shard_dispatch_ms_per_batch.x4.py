"""Host time a batch of the ``sharded`` backend's dispatch: the mean of
the program's ``shard.gather`` stage over the window (the histogram
``serving.stage_ms{stage=shard.gather}``), the call of the one
``shard_map`` program that hashes the reads and gathers every chip's
slice of the probed rows."""

from bench import spans


def read(ctx):
    return spans.stage_ms_per_call(ctx.window.counters, "shard.gather")
