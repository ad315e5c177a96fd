"""Pallas TPU kernel: batched radix-4/2 Stockham complex FFT (last axis).

The 1-D FFT is the compute hot spot the paper delegates to fftw; on TPU we
keep a (batch_tile, N) block resident in VMEM and run all the Stockham
stages in-register -- the autosort variant needs no bit-reversal pass, so
every stage is a pure vectorized butterfly + twiddle multiply (VPU-shaped:
the N axis stays the 128-lane minor dimension).

Stages are RADIX-4 whenever the remaining sub-transform length divides by 4
(two radix-2 passes algebraically fused: half the stage count, half the
twiddle loads and pack shuffles on power-of-two lengths) with a single
radix-2 step absorbing the odd log2 factor.  ``max_radix=2`` forces the
pure radix-2 pipeline (the A/B baseline ``BENCH_kernels.json`` records).

Fusable epilogues run in the FINAL stage's registers, saving one full HBM
round trip each (flups' shuffle/pack folded into the transform itself):

* ``fft_stockham_twiddle`` -- the r2r post-twiddle
  ``y = a * re[start:start+k] + b * im[start:start+k]`` (the standalone
  ``twiddle_pack`` kernel's job) emitting only the k retained real bins;
* ``fft_stockham_scale``  -- the spectral Green multiply (the standalone
  ``spectral_scale`` kernel's job) scaling the ``[start, start+k)`` bins by
  a per-(row, bin) real plane, shared across any leading batch.

Complex data is (re, im) f32 pairs.  Twiddles are computed in-kernel from
an integer iota (Mosaic's ``tpu.iota`` takes integer types only) cast to
the data dtype; N is static.

Mosaic compiles the kernel on the TPU for the lengths in ``TPU_LENGTHS``
(``tests/test_tpu_compile.py`` compiles them for a described v5e): below
512 the radix-4 stages' minor-dim reshapes are refused ("unsupported
shape cast"), above 1024 the kernel runs out of VMEM.  ``fits(n,
platform)`` is the plan-time rule the transform layer routes by; every
other length runs XLA's FFT.  In interpret mode (any other platform)
every power of two runs.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

# power-of-two FFT lengths the kernel compiles for on the TPU (inclusive)
TPU_LENGTHS = (512, 1024)


def fits(n: int, platform: str) -> bool:
    """Whether a length-``n`` FFT runs in this kernel on ``platform``: a
    power of two, within ``TPU_LENGTHS`` on the TPU."""
    if n < 2 or n & (n - 1):
        return False
    if platform == "tpu":
        return TPU_LENGTHS[0] <= n <= TPU_LENGTHS[1]
    return True


def _iota(n, dtype):
    """``0, 1, ..., n-1`` in ``dtype`` via an integer iota."""
    return jnp.arange(n, dtype=jnp.int32).astype(dtype)


def _stages(n):
    k = int(np.log2(n))
    assert 2 ** k == n, f"stockham kernel needs power-of-two N, got {n}"
    return k


def stage_count(n: int, max_radix: int = 4, n_in=None) -> int:
    """Butterfly passes the kernel will run for a length-``n`` transform
    (the BENCH_kernels.json bookkeeping; radix-4 halves it on pow2 N)."""
    k = _stages(n)
    if n_in is not None and n_in < n:
        k -= 1                      # the degenerate pruned first stage
    if max_radix < 4:
        return k + (1 if n_in is not None and n_in < n else 0)
    return k // 2 + k % 2 + (1 if n_in is not None and n_in < n else 0)


def _fft_body(xr, xi, *, n, inverse, n_in=None, max_radix=4):
    """All Stockham stages on a (batch_tile, n) register block.

    ``n_in`` < n activates the PRUNED first stage (Hockney zero tail): the
    inputs hold only the n_in = n//2 nonzero samples, and the first DIF
    stage -- whose upper-half operand is identically zero -- degenerates to
    a copy + twiddle modulation (no adds, half the stage-1 VMEM reads).
    """
    br = xr.shape[0]
    sign = 2.0 * np.pi / n if inverse else -2.0 * np.pi / n
    m, l = n, 1
    if n_in is not None and n_in < n:
        assert n == 2 * n_in and not inverse
        half = n // 2
        ang = _iota(half, xr.dtype) * xr.dtype.type(sign)
        wr = jnp.cos(ang)
        wi = jnp.sin(ang)
        # x1 == 0: e = x0, d = x0 * w  (the skipped butterflies)
        orr = xr * wr - xi * wi
        oii = xr * wi + xi * wr
        xr = jnp.concatenate([xr[..., None], orr[..., None]],
                             axis=2).reshape(br, half, 2).reshape(br, n)
        xi = jnp.concatenate([xi[..., None], oii[..., None]],
                             axis=2).reshape(br, half, 2).reshape(br, n)
        m, l = half, 2
    while m > 1:
        if m % 4 == 0 and max_radix >= 4:
            # radix-4 DIF stage == two fused radix-2 stages: quarters
            # (A, B, C, D) of each length-m sub-transform combine as
            #   y0 = (A+C) + (B+D)
            #   y1 = ((A-C) -+ i(B-D)) W^j      y2 = ((A+C) - (B+D)) W^2j
            #   y3 = ((A-C) +- i(B-D)) W^3j
            # packed [y0 y1 y2 y3] into the l-axis (the Stockham autosort
            # order two radix-2 passes would have produced).
            q = m // 4
            xr4 = xr.reshape(br, m, l)
            xi4 = xi.reshape(br, m, l)
            ar, brr, cr, dr = (xr4[:, i * q:(i + 1) * q, :] for i in range(4))
            ai, bii, ci, di = (xi4[:, i * q:(i + 1) * q, :] for i in range(4))
            t0r, t0i = ar + cr, ai + ci
            t1r, t1i = ar - cr, ai - ci
            t2r, t2i = brr + dr, bii + di
            t3r, t3i = brr - dr, bii - di
            if inverse:     # +i * t3
                u3r, u3i = -t3i, t3r
            else:           # -i * t3
                u3r, u3i = t3i, -t3r
            ang = _iota(q, xr.dtype) * xr.dtype.type(sign * (n // m))
            w1r = jnp.cos(ang)[None, :, None]
            w1i = jnp.sin(ang)[None, :, None]
            w2r = jnp.cos(2.0 * ang)[None, :, None]
            w2i = jnp.sin(2.0 * ang)[None, :, None]
            w3r = jnp.cos(3.0 * ang)[None, :, None]
            w3i = jnp.sin(3.0 * ang)[None, :, None]
            y0r, y0i = t0r + t2r, t0i + t2i
            e1r, e1i = t1r + u3r, t1i + u3i
            y1r = e1r * w1r - e1i * w1i
            y1i = e1r * w1i + e1i * w1r
            e2r, e2i = t0r - t2r, t0i - t2i
            y2r = e2r * w2r - e2i * w2i
            y2i = e2r * w2i + e2i * w2r
            e3r, e3i = t1r - u3r, t1i - u3i
            y3r = e3r * w3r - e3i * w3i
            y3i = e3r * w3i + e3i * w3r
            xr = jnp.concatenate(
                [y0r[..., None, :], y1r[..., None, :],
                 y2r[..., None, :], y3r[..., None, :]],
                axis=2).reshape(br, q, 4 * l).reshape(br, n)
            xi = jnp.concatenate(
                [y0i[..., None, :], y1i[..., None, :],
                 y2i[..., None, :], y3i[..., None, :]],
                axis=2).reshape(br, q, 4 * l).reshape(br, n)
            m, l = q, 4 * l
            continue
        half = m // 2
        # radix-2 step (the odd log2 factor, or the whole pipeline under
        # max_radix=2); view as (batch, m, l)
        xr3 = xr.reshape(br, m, l)
        xi3 = xi.reshape(br, m, l)
        x0r, x1r = xr3[:, :half, :], xr3[:, half:, :]
        x0i, x1i = xi3[:, :half, :], xi3[:, half:, :]
        # twiddles computed in-kernel (iota -> cos/sin on the VPU); n, m
        # are static so sign*(n//m) folds to an immediate
        ang = _iota(half, xr.dtype) * xr.dtype.type(sign * (n // m))
        wr = jnp.cos(ang)[None, :, None]
        wi = jnp.sin(ang)[None, :, None]
        er, ei = x0r + x1r, x0i + x1i
        dr, di = x0r - x1r, x0i - x1i
        orr = dr * wr - di * wi
        oii = dr * wi + di * wr
        xr = jnp.concatenate([er[..., None, :], orr[..., None, :]],
                             axis=2).reshape(br, half, 2 * l).reshape(br, n)
        xi = jnp.concatenate([ei[..., None, :], oii[..., None, :]],
                             axis=2).reshape(br, half, 2 * l).reshape(br, n)
        m, l = half, 2 * l
    if inverse:
        xr = xr / n
        xi = xi / n
    return xr, xi


def _kernel(re_ref, im_ref, out_re_ref, out_im_ref, *, n, inverse,
            n_in=None, max_radix=4):
    """One (batch_tile, n) FFT block, full complex spectrum out."""
    xr, xi = _fft_body(re_ref[...], im_ref[...], n=n, inverse=inverse,
                       n_in=n_in, max_radix=max_radix)
    out_re_ref[...] = xr
    out_im_ref[...] = xi


def _kernel_twiddle(re_ref, im_ref, a_ref, b_ref, out_ref, *, n, n_in,
                    start, k, max_radix):
    """FFT + r2r post-twiddle epilogue: the final stage's registers feed
    ``y = a * re + b * im`` over bins [start, start+k) directly -- no full
    spectrum ever reaches HBM."""
    xr, xi = _fft_body(re_ref[...], im_ref[...], n=n, inverse=False,
                       n_in=n_in, max_radix=max_radix)
    out_ref[...] = (a_ref[...] * xr[:, start:start + k] +
                    b_ref[...] * xi[:, start:start + k])


def _kernel_scale(re_ref, im_ref, g_ref, out_re_ref, out_im_ref, *, n,
                  n_in, start, k, max_radix):
    """FFT + spectral-scale epilogue (3-D refs, leading batch of size 1 per
    grid step): the Green multiply runs on the final stage's registers and
    only the scaled [start, start+k) bins are written."""
    xr, xi = _fft_body(re_ref[0], im_ref[0], n=n, inverse=False,
                       n_in=n_in, max_radix=max_radix)
    g = g_ref[...]
    out_re_ref[0] = xr[:, start:start + k] * g
    out_im_ref[0] = xi[:, start:start + k] * g


def _pruned(n, pad_to, inverse):
    """(n_fft, n_in) of the optionally zero-tail-pruned forward shape."""
    if pad_to is None:
        _stages(n)
        return n, None
    assert pad_to == 2 * n, (pad_to, n)
    assert not inverse, "pruned zero-tail input is a forward-only shape"
    _stages(pad_to)
    return pad_to, n


def fft_stockham(re, im, batch_block=8, inverse=False, pad_to=None,
                 max_radix=4):
    """re/im: (batch, N) f32 -> (re, im) of the complex FFT along axis -1.

    ``pad_to = 2 * N`` computes the length-``pad_to`` FFT of the signal
    zero-extended to double length (the Hockney doubling shape) WITHOUT
    materializing the zeros: the kernel reads the (batch, N) block and
    runs a degenerate first stage (see ``_fft_body``), emitting (batch,
    pad_to) spectra.  Forward only.
    """
    b, n = re.shape
    n_out, n_in = _pruned(n, pad_to, inverse)
    bb = min(batch_block, b)
    grid = (pl.cdiv(b, bb),)
    spec_in = pl.BlockSpec((bb, n), lambda i: (i, 0))
    spec_out = pl.BlockSpec((bb, n_out), lambda i: (i, 0))
    fn = pallas_call(
        partial(_kernel, n=n_out, inverse=inverse, n_in=n_in,
                max_radix=max_radix),
        grid=grid,
        in_specs=[spec_in, spec_in],
        out_specs=[spec_out, spec_out],
        out_shape=[jax.ShapeDtypeStruct((b, n_out), re.dtype),
                   jax.ShapeDtypeStruct((b, n_out), im.dtype)],
    )
    return fn(re, im)


def fft_stockham_twiddle(re, im, a, b, start=0, batch_block=8,
                         pad_to=None, max_radix=4):
    """Forward FFT fused with the r2r post-twiddle epilogue.

    re/im: (batch, N); a/b: (k,) twiddle tables.  Returns the real
    (batch, k) array ``a * Re(F)[start:start+k] + b * Im(F)[start:start+k]``
    in ONE kernel -- the ``twiddle_pack`` pass runs in the FFT's final-stage
    registers instead of as its own HBM round trip.
    """
    bsz, n = re.shape
    n_out, n_in = _pruned(n, pad_to, False)
    k = a.shape[-1]
    assert b.shape[-1] == k and start + k <= n_out, (a.shape, start, n_out)
    bb = min(batch_block, bsz)
    grid = (pl.cdiv(bsz, bb),)
    spec_in = pl.BlockSpec((bb, n), lambda i: (i, 0))
    vec = pl.BlockSpec((1, k), lambda i: (0, 0))
    fn = pallas_call(
        partial(_kernel_twiddle, n=n_out, n_in=n_in, start=start, k=k,
                max_radix=max_radix),
        grid=grid,
        in_specs=[spec_in, spec_in, vec, vec],
        out_specs=pl.BlockSpec((bb, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, k), re.dtype),
    )
    return fn(re, im, a.reshape(1, k), b.reshape(1, k))


def fft_stockham_scale(re, im, g, start=0, batch_block=8, pad_to=None,
                       max_radix=4):
    """Forward FFT fused with the spectral Green-multiply epilogue.

    re/im: (rows, N); g: (grows, k) with rows % grows == 0 (leading
    multi-RHS batch shares one Green plane).  Returns the complex pair
    ``(Re(F) * g, Im(F) * g)`` over bins [start, start+k), shape (rows, k),
    in ONE kernel -- the ``spectral_scale`` pass runs in the FFT's
    final-stage registers.
    """
    rows, n = re.shape
    n_out, n_in = _pruned(n, pad_to, False)
    grows, k = g.shape
    assert rows % grows == 0, (rows, grows)
    assert start + k <= n_out, (start, k, n_out)
    nb = rows // grows
    re3 = re.reshape(nb, grows, n)
    im3 = im.reshape(nb, grows, n)
    bb = min(batch_block, grows)
    grid = (nb, pl.cdiv(grows, bb))
    spec_in = pl.BlockSpec((1, bb, n), lambda b_, i: (b_, i, 0))
    spec_out = pl.BlockSpec((1, bb, k), lambda b_, i: (b_, i, 0))
    gspec = pl.BlockSpec((bb, k), lambda b_, i: (i, 0))
    fn = pallas_call(
        partial(_kernel_scale, n=n_out, n_in=n_in, start=start, k=k,
                max_radix=max_radix),
        grid=grid,
        in_specs=[spec_in, spec_in, gspec],
        out_specs=[spec_out, spec_out],
        out_shape=[jax.ShapeDtypeStruct((nb, grows, k), re.dtype),
                   jax.ShapeDtypeStruct((nb, grows, k), im.dtype)],
    )
    orr, oi = fn(re3, im3, g)
    return orr.reshape(rows, k), oi.reshape(rows, k)
