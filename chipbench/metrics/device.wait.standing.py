"""Device: host time blocked on the device's results (``device/wait``
spans: the compiled plans' fetches and the ends of executor nodes),
per tick."""


def read(ctx):
    ticks = ctx["work"].get("ticks", 0)
    spans = [s.duration for s in ctx["spans"] if s.name == "device/wait"]
    return 1e3 * sum(spans) / ticks if ticks and spans else None
