"""Stream runtime: host time spent copying ring rows into contiguous
arrays for a query (``stream/gather`` spans: the sharded stream's
gathers and the compiled plans' ring exports), per tick."""


def read(ctx):
    ticks = ctx["work"].get("ticks", 0)
    spans = [s.duration for s in ctx["spans"] if s.name == "stream/gather"]
    return 1e3 * sum(spans) / ticks if ticks and spans else None
