"""Compiled stream plans: share of streaming sub-queries the compiled
path handed to the interpreter (``compile.stats()``: interpreted over
executions plus interpreted, in the traced window)."""


def counters():
    from repro.stream import compile as qc
    s = qc.stats()
    return {"executions": s["executions"], "interpreted": s["interpreted"]}


def read(ctx):
    c = ctx["own"]["compile.interpreted_share"]
    n = c["executions"] + c["interpreted"]
    return 100.0 * c["interpreted"] / n if n else None
