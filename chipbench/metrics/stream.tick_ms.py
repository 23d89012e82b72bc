"""Stream runtime: mean wall time of one ``StreamRuntime.tick``
(``repro_stream_tick_seconds`` sum over count in the traced window)."""


def read(ctx):
    count, total = ctx["registry"].get("repro_stream_tick_seconds", (0, 0))
    return 1e3 * total / count if count else None
