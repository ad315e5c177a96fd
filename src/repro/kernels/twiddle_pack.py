"""Pallas TPU kernel: DCT/DST post-twiddle (the r2r "shuffle" hot loop).

After the length-2M complex FFT, every real transform applies a per-mode
twiddle and packs the real result (section II / transforms.py):

    y[r, k] = cos[k] * re[r, k] + sin[k] * im[r, k]

Fusing the two multiplies, the add and the pack keeps the pass at one HBM
read per operand and one write -- flups' pack() + shuffle() in a single
VMEM-resident kernel.  cos/sin are broadcast along rows (one VMEM copy per
lane tile).

On power-of-two lengths this pass no longer runs standalone in the solve:
``fft_stockham_twiddle`` executes the same epilogue in the FFT's final-
stage registers (DESIGN.md #9).  This kernel remains the non-pow2 path
and the unit the fused variant is validated against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

DEFAULT_BLOCK = (256, 256)


def _kernel(re_ref, im_ref, cos_ref, sin_ref, out_ref):
    out_ref[...] = (cos_ref[...] * re_ref[...] +
                    sin_ref[...] * im_ref[...])


def twiddle_pack(re, im, cos, sin, block=DEFAULT_BLOCK):
    """re/im: (rows, k); cos/sin: (k,) -> y (rows, k)."""
    rows, k = re.shape
    br = min(block[0], rows)
    bk = min(block[1], k)
    grid = (pl.cdiv(rows, br), pl.cdiv(k, bk))
    mat = pl.BlockSpec((br, bk), lambda i, j: (i, j))
    vec = pl.BlockSpec((1, bk), lambda i, j: (0, j))
    fn = pallas_call(
        _kernel,
        grid=grid,
        in_specs=[mat, mat, vec, vec],
        out_specs=mat,
        out_shape=jax.ShapeDtypeStruct(re.shape, re.dtype),
    )
    return fn(re, im, cos.reshape(1, -1), sin.reshape(1, -1))
