"""ml island and model step: time in ``ml/score`` spans per scored
window (each ends in the device wait for its score, so it covers the
window's forward on the chip)."""


def read(ctx):
    spans = [s.duration for s in ctx["spans"] if s.name == "ml/score"]
    return 1e3 * sum(spans) / len(spans) if spans else None
