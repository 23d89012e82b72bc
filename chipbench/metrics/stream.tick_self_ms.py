"""Stream runtime: the tick's own bookkeeping, the self time of
``stream/tick`` spans (``repro.obs.trace.self_times``: the tick minus
its queries, the front door's fan-out and every other child span), per
tick."""


def read(ctx):
    try:
        from repro.obs.trace import self_times
    except ImportError:            # a program without self times
        return None
    ticks = ctx["work"].get("ticks", 0)
    own = self_times(ctx["spans"])
    spans = [own[s.span_id] for s in ctx["spans"] if s.name == "stream/tick"]
    return 1e3 * sum(spans) / ticks if ticks and spans else None
