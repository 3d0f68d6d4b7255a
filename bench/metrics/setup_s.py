"""Set-up: from the start of the process to the window's open.

It holds loading, the index build on the device, compiling or loading
every program from the cache, warm-up, and the first batches that fill
the pipeline.
"""


def read(ctx):
    return ctx.setup_s
