"""Stream runtime: megabytes the stream path copied from host arrays to
the device (``repro_stream_h2d_bytes_total``), per tick."""


def read(ctx):
    ticks = ctx["work"].get("ticks", 0)
    moved = ctx["registry"].get("repro_stream_h2d_bytes_total")
    return moved / 1e6 / ticks if ticks and moved else None
