"""Reads answered per second by a closed loop: the reads of every batch
completed inside the window, over the window's length. The window opens
and closes at batch completions, so it holds whole batches."""


def read(ctx):
    if ctx.cell.traffic["loop"] != "closed":
        return None
    w = ctx.window
    reads = sum(n for t, n in w.batches if w.t_open < t <= w.t_close)
    return reads / (w.t_close - w.t_open) if reads else None
