"""Migrator casts: time in ``executor/cast`` spans per query the
analyst sent in the traced window."""


def read(ctx):
    n = ctx["work"].get("queries", 0)
    spans = [s.duration for s in ctx["spans"] if s.name == "executor/cast"]
    return 1e3 * sum(spans) / n if n and spans else None
