"""Front door: mean time to fan one tick's results out to the tenants'
subscriptions (``repro_serve_fanout_seconds`` sum over count in the
traced window)."""


def read(ctx):
    count, total = ctx["registry"].get("repro_serve_fanout_seconds", (0, 0))
    return 1e3 * total / count if count else None
