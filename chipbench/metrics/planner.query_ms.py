"""Planner / executor: time in ``planner/query`` spans per query the
analyst sent in the traced window."""


def read(ctx):
    n = ctx["work"].get("queries", 0)
    spans = [s.duration for s in ctx["spans"] if s.name == "planner/query"]
    return 1e3 * sum(spans) / n if n and spans else None
