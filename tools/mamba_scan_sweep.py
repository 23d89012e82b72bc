"""Time the Mamba selective-scan kernel over channel blocks (BD) and
chunks at one layer of the icu-jamba2 cell's scorer (S 2048, Di 5120,
N 16; u, dt, B, C in bfloat16 as the model hands them over), beside the
reference ``lax.scan``, on one TPU.

  PYTHONPATH=src python tools/mamba_scan_sweep.py [--out FILE]

Each row: ms a call, from 20 calls run back to back (host clock around
them and ``block_until_ready``, after a warm-up call; median of 5), the
compile seconds, and the largest |y - y_ref| and |h - h_ref| against the
reference on the same inputs.  A (BD, chunk) the compiler refuses (VMEM) is reported
with its error.  Any platform but a TPU: exit 2.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mamba_scan import mamba_scan as k
from repro.kernels.mamba_scan import ref

S, DI, N = 2048, 5120, 16
GRID = [(bd, chunk) for bd in (128, 256, 512, 1024, 1280, 2560, 5120)
        for chunk in (128, 256, 512)]


def _time(fn, *args, calls: int = 20, repeats: int = 5) -> float:
    """Median over ``repeats`` of the ms a call takes when ``calls`` run
    back to back and the last is waited for: the device runs them one
    after another, so the host's dispatch of each hides under the
    device time of the one before."""
    jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(calls)])
        ms.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(ms)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    u = jnp.asarray(rng.standard_normal((1, S, DI)), bf)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (1, S, DI)), bf)
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (DI, N))
    b = jnp.asarray(rng.standard_normal((1, S, N)), bf)
    c = jnp.asarray(rng.standard_normal((1, S, N)), bf)
    h0 = jnp.asarray(rng.standard_normal((1, DI, N)) * 0.2, jnp.float32)
    reference = jax.jit(ref.selective_scan)
    y_ref, h_ref = reference(u, dt, a, b, c, h0)
    rows = [{"path": "reference", "ms": _time(reference, u, dt, a, b, c,
                                              h0)}]
    print(json.dumps(rows[-1]), flush=True)
    for bd, chunk in GRID:
        fn = jax.jit(lambda *x, bd=bd, chunk=chunk: k.selective_scan_chunked(
            *x, bd=bd, chunk=chunk, interpret=False))
        row = {"path": "kernel", "bd": bd, "chunk": chunk}
        try:
            t0 = time.perf_counter()
            compiled = fn.lower(u, dt, a, b, c, h0).compile()
            row["compile_s"] = time.perf_counter() - t0
        except Exception as e:                       # noqa: BLE001
            row["error"] = str(e).splitlines()[0][:200]
        else:
            y, h = compiled(u, dt, a, b, c, h0)
            row["y_err"] = float(jnp.max(jnp.abs(y - y_ref)))
            row["h_err"] = float(jnp.max(jnp.abs(h - h_ref)))
            row["ms"] = _time(compiled, u, dt, a, b, c, h0)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "shape": [S, DI, N], "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
