"""ml island and model step: share of the Mamba layers of the windows
scored in the traced window whose selective scan ran as the Pallas
kernel (``repro_ml_scan_kernel_total``) rather than the reference
``lax.scan`` (``repro_ml_scan_reference_total``), in %.  Silent where
neither counter moved: a program without them, or a model without
Mamba layers."""


def read(ctx):
    reg = ctx["registry"]
    kernel = reg.get("repro_ml_scan_kernel_total", 0)
    n = kernel + reg.get("repro_ml_scan_reference_total", 0)
    return 100.0 * kernel / n if n else None
