"""Index bytes the query planner has the kernel move per probe: the
planner's counters ``locality.planned_tile_bytes`` over
``locality.probes`` for the queries of the window. IDL's locality shows
as fewer bytes per probe than random hashing."""


def read(ctx):
    probes = ctx.counter("locality.probes", op="query")
    if probes <= 0:
        return None
    return ctx.counter("locality.planned_tile_bytes", op="query") / probes
