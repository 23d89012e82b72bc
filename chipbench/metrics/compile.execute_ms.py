"""Compiled stream plans: time in ``compile/execute`` spans per tick
(each ends in the host copy of the jitted output, so it covers the
device work)."""


def read(ctx):
    ticks = ctx["work"].get("ticks", 0)
    spans = [s.duration for s in ctx["spans"] if s.name == "compile/execute"]
    return 1e3 * sum(spans) / ticks if ticks and spans else None
