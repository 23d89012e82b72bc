"""Pallas TPU kernel: Mamba-1 selective scan, lane-dense.

  h_t = exp(dt_t ⊙ A) h_{t-1} + (dt_t u_t) ⊗ B_t;  y_t = h_t · C_t

Layout.  The state of a block of BD channels is held as (N, BD): d_state
(16) on the sublanes, channels on the 128 lanes.  Every step is then
elementwise VPU/EUP work on whole (N, BD) tiles with no padded lane:
``dt_t`` and ``dt_t u_t`` are lane rows broadcast down the sublanes, B_t
and C_t are sublane columns broadcast along the lanes, and ``y_t`` is a
16-row sublane sum stored as one row of the (chunk, BD) output block.
Held the other way, (BD, N), d_state fills 16 of 128 lanes (7/8 of each
vreg padding) and every ``y_t`` needs a cross-lane reduction.

B and C arrive transposed, (B, N, S), so the steps of a 128-step group
read their columns from one (N, 128) tile at static lane offsets (the
group is unrolled): no lane-dynamic slice.  u and dt are read in the
dtype the model hands over and upcast here; the recurrence, h and the y
sum are float32.

Grid: (B, Di/BD, S/chunk), the chunk axis sequential.  h lives in VMEM
scratch across the chunks of a channel block (in registers within a
group), seeded from ``h0`` at the first chunk and emitted at the last.
A v5e TensorCore runs the grid serially, so BD and chunk trade per-step
overhead against block size; ``BD`` and ``CHUNK`` are the sweep's pick
at the jamba2-3b cell's shape (S 2048, Di 5120, N 16; PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BD = 1280
CHUNK = 256


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref,
                 hout_ref, h_ref, *, chunk: int, group: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _seed():
        h_ref[...] = h0_ref[0]

    a = a_ref[...]                                     # (N, BD)

    def steps(g, h):
        base = pl.multiple_of(g * group, group)
        u = u_ref[0, pl.ds(base, group), :].astype(jnp.float32)
        dt = dt_ref[0, pl.ds(base, group), :].astype(jnp.float32)
        b = b_ref[0, :, pl.ds(base, group)].astype(jnp.float32)  # (N, G)
        c = c_ref[0, :, pl.ds(base, group)].astype(jnp.float32)
        du = dt * u                                    # (G, BD)
        for j in range(group):
            h = (jnp.exp(dt[j:j + 1] * a) * h
                 + du[j:j + 1] * b[:, j:j + 1])
            y_ref[0, pl.ds(base + j, 1), :] = jnp.sum(
                h * c[:, j:j + 1], axis=0, keepdims=True)
        return h

    h = jax.lax.fori_loop(0, chunk // group, steps, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == pl.num_programs(2) - 1)
    def _emit():
        hout_ref[0] = h


@functools.partial(jax.jit, static_argnames=("bd", "chunk", "interpret"))
def selective_scan_chunked(u, dt, a, b, c, h0, *, bd: int = BD,
                           chunk: int = CHUNK, interpret: bool = True):
    """u,dt: (B,S,Di) any float; a: (Di,N); b,c: (B,S,N); h0: (B,Di,N)
    -> (y (B,S,Di) f32, h (B,Di,N) f32).  Needs S % chunk == 0 and
    Di % bd == 0 (ops.py pads and picks the block)."""
    bsz, s, di = u.shape
    n = a.shape[1]
    assert di % bd == 0 and s % chunk == 0, (di, bd, s, chunk)
    group = min(chunk, LANES)
    assert chunk % group == 0, (chunk, group)

    kernel = functools.partial(_scan_kernel, chunk=chunk, group=group)
    rows = pl.BlockSpec((1, chunk, bd), lambda bi, d, ci: (bi, ci, d))
    cols = pl.BlockSpec((1, n, chunk), lambda bi, d, ci: (bi, 0, ci))
    state = pl.BlockSpec((1, n, bd), lambda bi, d, ci: (bi, 0, d))
    y, h = pl.pallas_call(
        kernel,
        grid=(bsz, di // bd, s // chunk),
        in_specs=[rows, rows,
                  pl.BlockSpec((n, bd), lambda bi, d, ci: (0, d)),
                  cols, cols, state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, di), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n, di), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="mamba_selective_scan",
    )(u, dt, a.astype(jnp.float32).T, b.swapaxes(1, 2), c.swapaxes(1, 2),
      h0.astype(jnp.float32).swapaxes(1, 2))
    return y, h.swapaxes(1, 2)
