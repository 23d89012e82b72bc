"""Device: host time blocked on the device's results (``device/wait``
spans at the end of every executor node), per query the analyst sent."""


def read(ctx):
    n = ctx["work"].get("queries", 0)
    spans = [s.duration for s in ctx["spans"] if s.name == "device/wait"]
    return 1e3 * sum(spans) / n if n and spans else None
