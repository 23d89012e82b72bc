"""ml island and model step: model FLOPs of the traced window over what
the chip's bf16 peak gives in it, in %.  The FLOPs are the tokens the
island scored for the configuration's architecture
(``repro_ml_tokens_scored_total{arch=...}``) times that family's FLOPs
per token at the window's length (``FLOPS_PER_TOKEN``, by the
configuration's ``model_type``).  Silent where the island scored no
tokens, or scored tokens of another architecture, whose FLOPs this
count does not know."""
import re

from chipbench import harness
from chipbench.reference import jamba

NAME = "repro_ml_tokens_scored_total"
FLOPS_PER_TOKEN = {"jamba": jamba.flops_per_token}


def read(ctx):
    cfg = ctx["config"]
    tokens = {}
    for k, v in ctx["registry"].items():
        m = re.fullmatch(re.escape(NAME) + r"\{(.*)\}", k)
        if m and v:
            arch = re.search(r"(?:^|,)arch=([^,]+)", m.group(1))
            tokens[arch.group(1) if arch else None] = v
    per_token = FLOPS_PER_TOKEN.get(cfg.get("model_type"))
    if (set(tokens) != {cfg.get("arch")} or per_token is None
            or ctx["window_s"] <= 0):
        return None
    flops = tokens[cfg["arch"]] * per_token(cfg, cfg["window"])
    peak = harness.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * flops / (ctx["window_s"] * peak)
