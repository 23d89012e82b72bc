"""Array island: share of the array aggregates in the traced window
that reduced over a mask already built
(``repro_array_masked_aggregates_total``) rather than in the fused
filter-and-aggregate program (``repro_array_fused_aggregates_total``)."""


def read(ctx):
    reg = ctx["registry"]
    masked = reg.get("repro_array_masked_aggregates_total", 0)
    n = masked + reg.get("repro_array_fused_aggregates_total", 0)
    return 100.0 * masked / n if n else None
