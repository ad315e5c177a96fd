"""Pallas TPU kernel: fused Green-function multiply + normalization.

The spectral convolution u_hat = f_hat * G_hat * norm is the only O(N^3)
pointwise pass of the solve; fusing the complex scale with the
normalization halves its HBM traffic vs two separate elementwise ops.

Complex data is carried as separate (re, im) f32 planes (TPU-native: the
MXU/VPU have no complex type).  Blocks are (rows_tile, lane_tile) VMEM
tiles over a (rows, lanes) view, 8x128-aligned.

Batched multi-RHS solves add a leading grid dimension: ``re``/``im`` of
shape (B, rows, lanes) against ONE shared (rows, lanes) Green plane -- the
kernel grids over (B, row tiles, lane tiles) and the Green BlockSpec simply
ignores the batch index, so the kernel streams the Green tile from VMEM B
times instead of materializing a broadcast copy in HBM.

When the last forward direction is a power-of-two DFT this pass no longer
runs standalone: ``fft_stockham_scale`` executes the same multiply in that
FFT's final-stage registers (DESIGN.md #9).  This kernel remains the path
for every other plan shape and the backward-normalization-free contract's
reference.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

DEFAULT_BLOCK = (256, 256)


def _kernel(re_ref, im_ref, g_ref, out_re_ref, out_im_ref, *, scale):
    g = g_ref[...] * scale
    out_re_ref[...] = re_ref[...] * g
    out_im_ref[...] = im_ref[...] * g


def _kernel_batched(re_ref, im_ref, g_ref, out_re_ref, out_im_ref, *, scale):
    g = g_ref[...] * scale
    out_re_ref[0] = re_ref[0] * g
    out_im_ref[0] = im_ref[0] * g


def spectral_scale(re, im, green, scale: float,
                   block=DEFAULT_BLOCK):
    """re/im: (rows, lanes) or (B, rows, lanes); green: (rows, lanes).

    Returns the scaled (re, im) pair with the input shape; the batched form
    shares one Green plane across the leading axis.
    """
    batched = re.ndim == 3
    rows, lanes = re.shape[-2:]
    br = min(block[0], rows)
    bl = min(block[1], lanes)
    gspec2d = pl.BlockSpec((br, bl), lambda *ij: ij[-2:])
    if batched:
        grid = (re.shape[0], pl.cdiv(rows, br), pl.cdiv(lanes, bl))
        spec = pl.BlockSpec((1, br, bl), lambda b, i, j: (b, i, j))
        body = _kernel_batched
    else:
        grid = (pl.cdiv(rows, br), pl.cdiv(lanes, bl))
        spec = gspec2d
        body = _kernel
    fn = pallas_call(
        partial(body, scale=scale),
        grid=grid,
        in_specs=[spec, spec, gspec2d],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(re.shape, re.dtype),
                   jax.ShapeDtypeStruct(im.shape, im.dtype)],
    )
    return fn(re, im, green)
