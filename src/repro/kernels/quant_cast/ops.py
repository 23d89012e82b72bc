"""Jit'd public wrappers for the quant_cast kernel: arbitrary-shape tensors
are flattened, padded to (ROWS x BLOCK) tiles, and routed through the Pallas
kernel (interpret=True on CPU; compiled on TPU).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.quant_cast import quant_cast as k
from repro.kernels.quant_cast import ref


def _pad_to_tiles(flat: jax.Array) -> Tuple[jax.Array, int]:
    tile = k.ROWS * k.BLOCK
    n = flat.shape[0]
    pad = (-n) % tile
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, k.BLOCK), n


def quantize(x: jax.Array, block: int = k.BLOCK, *, use_kernel: bool = True
             ) -> Tuple[jax.Array, jax.Array]:
    """Any-shape f32 -> (q int8 (nb, BLOCK), scale f32 (nb, 1)).

    ``block`` is fixed to the kernel lane width (128); the argument is kept
    for API compatibility with MigrationParams.quant_block.
    """
    flat = x.astype(jnp.float32).reshape(-1)
    x2d, _ = _pad_to_tiles(flat)
    if use_kernel:
        q, scale = k.quantize_2d(x2d, interpret=interpret_mode(x2d))
    else:
        q, scale = ref.quantize_blocks(x2d)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array, shape, *,
               use_kernel: bool = True) -> jax.Array:
    if use_kernel:
        x2d = k.dequantize_2d(q, scale,
                              interpret=interpret_mode(q))
    else:
        x2d = ref.dequantize_blocks(q, scale)
    n = int(np.prod(shape))
    return x2d.reshape(-1)[:n].reshape(shape)
