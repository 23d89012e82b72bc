"""Device: idle share of the traced window, 1 - (union of the intervals
in which an operation ran on the chip) / window, from the profiler
trace."""


def read(ctx):
    d = ctx["device"]
    if d is None or d["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
