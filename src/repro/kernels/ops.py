"""Jitted public wrappers for the Pallas kernels (+ dtype plumbing).

The kernels are Mosaic-compiled on the TPU and interpreted on every other
platform (``kernels.platform``), decided for the platform each program is
lowered for.

All wrappers preserve the input dtype (f64 runs in interpret mode, where
the CPU test suite checks the kernels; on the TPU the solver feeds f32),
so ``engine="pallas"`` matches ``engine="xla"`` to roundoff instead of
truncating to f32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .fft_stockham import (fft_stockham, fft_stockham_scale,
                           fft_stockham_twiddle)
from .spectral_scale import spectral_scale
from .twiddle_pack import twiddle_pack


def _rows(shape):
    r = 1
    for s in shape[:-1]:
        r *= s
    return r


def _cdt(real_dtype):
    return jnp.complex128 if real_dtype == jnp.float64 else jnp.complex64


@jax.jit
def green_checksum(fhat, green):
    """Reference side of the ABFT Green-multiply invariant (DESIGN.md #13).

    The spectral pointwise pass is linear in ``fhat``, so its output must
    reduce to ``sum(fhat * green)``; this computes that reference as ONE
    fused multiply-reduce (never materializing the product block), which
    is what keeps the ``verify="abft"`` overhead of checking the solve's
    only O(N^3) pointwise pass negligible.
    """
    g = green if jnp.iscomplexobj(fhat) else green.astype(fhat.dtype)
    return jnp.sum(fhat * g)


@partial(jax.jit, static_argnames=("scale",))
def green_multiply(fhat, green, scale: float = 1.0):
    """Complex (or real) spectral field times real Green + norm factor.

    The only O(N^3) pointwise pass of the solve: one fused kernel instead
    of separate Green / normalization multiplies.  ``fhat`` may carry
    leading batch axes over a shared ``green`` (multi-RHS solves): the
    kernel then grids over the flattened batch instead of broadcasting the
    Green plane into a batched HBM copy.
    """
    shp = fhat.shape
    bnd = fhat.ndim - green.ndim
    grows, lanes = _rows(green.shape), green.shape[-1]
    batch = 1
    for s in shp[:bnd]:
        batch *= s
    kshape = (batch, grows, lanes) if bnd else (grows, lanes)
    if jnp.iscomplexobj(fhat):
        rdt = jnp.float64 if fhat.dtype == jnp.complex128 else jnp.float32
        g2 = green.reshape(grows, lanes).astype(rdt)
        re = fhat.real.reshape(kshape).astype(rdt)
        im = fhat.imag.reshape(kshape).astype(rdt)
        orr, oi = spectral_scale(re, im, g2, scale)
        return (orr + 1j * oi).reshape(shp).astype(fhat.dtype)
    g2 = green.reshape(grows, lanes).astype(fhat.dtype)
    re = fhat.reshape(kshape)
    orr, _ = spectral_scale(re, re, g2, scale)
    return orr.reshape(shp).astype(fhat.dtype)


@jax.jit
def post_twiddle(re, im, a, b):
    """Generic r2r post-twiddle ``y = a * re + b * im`` over the last axis.

    ``re``/``im``: (..., k) real planes of the rfft half spectrum;
    ``a``/``b``: (k,) twiddle tables (any float dtype; cast to ``re``).
    """
    shp = re.shape
    rows, k = _rows(shp), shp[-1]
    av = jnp.asarray(a, dtype=re.dtype)
    bv = jnp.asarray(b, dtype=re.dtype)
    y = twiddle_pack(re.reshape(rows, k), im.reshape(rows, k).astype(re.dtype),
                     av, bv)
    return y.reshape(shp)


@jax.jit
def dct2_post_twiddle(fhat_half):
    """DCT-II from the rfft of the symmetric extension (transforms.dct2
    inner step): y_k = cos_k * re_k + sin_k * im_k over the first M modes."""
    import numpy as np
    m = fhat_half.shape[-1]
    k = np.arange(m)
    return post_twiddle(fhat_half.real, fhat_half.imag,
                        np.cos(np.pi * k / (2.0 * m)),
                        np.sin(np.pi * k / (2.0 * m)))


@partial(jax.jit, static_argnames=("start", "pad_to", "max_radix"))
def rfft_twiddle(x, a, b, start: int = 0, pad_to: int | None = None,
                 max_radix: int = 4):
    """Fused rfft + r2r post-twiddle: ``a * Re(F)[start:start+k] +
    b * Im(F)[start:start+k]`` of the real (..., N) array ``x`` in ONE
    Pallas kernel (the ``twiddle_pack`` pass runs in the FFT's final-stage
    registers -- one HBM round trip instead of three).  ``pad_to = 2N``
    composes with the pruned Hockney zero tail."""
    shp = x.shape
    n = shp[-1]
    rows = _rows(shp)
    re = x.reshape(rows, n)
    im = jnp.zeros_like(re)
    av = jnp.asarray(a, dtype=x.dtype)
    bv = jnp.asarray(b, dtype=x.dtype)
    y = fft_stockham_twiddle(re, im, av, bv, start=start, pad_to=pad_to,
                             max_radix=max_radix)
    return y.reshape(shp[:-1] + (av.shape[-1],))


def _fft_green(x, green2d, half: bool, pad_to, max_radix: int = 4):
    """Shared body of the fused forward-FFT x Green epilogues."""
    shp = x.shape
    n = shp[-1]
    rows = _rows(shp)
    if jnp.iscomplexobj(x):
        rdt = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
        re = x.real.reshape(rows, n).astype(rdt)
        im = x.imag.reshape(rows, n).astype(rdt)
    else:
        rdt = x.dtype
        re = x.reshape(rows, n)
        im = jnp.zeros_like(re)
    n_fft = pad_to if pad_to is not None else n
    k = n_fft // 2 + 1 if half else n_fft
    g2 = green2d.reshape(-1, k).astype(rdt)
    orr, oi = fft_stockham_scale(re, im, g2, start=0, pad_to=pad_to,
                                 max_radix=max_radix)
    return (orr + 1j * oi).reshape(shp[:-1] + (k,)).astype(_cdt(rdt))


@partial(jax.jit, static_argnames=("pad_to", "max_radix"))
def fft1d_green(x, green, pad_to: int | None = None, max_radix: int = 4):
    """Fused forward complex FFT x Green multiply: ``FFT(x) * green`` with
    ``green`` real of shape (..., n_fft) broadcast over any leading batch
    of ``x`` -- the last forward direction's ``spectral_scale`` pass runs
    in the FFT's final-stage registers."""
    return _fft_green(x, green, half=False, pad_to=pad_to,
                      max_radix=max_radix)


@partial(jax.jit, static_argnames=("pad_to", "max_radix"))
def rfft_green(x, green, pad_to: int | None = None, max_radix: int = 4):
    """Fused rfft x Green multiply on the half spectrum: ``rfft(x) * green``
    with ``green`` real of shape (..., n_fft//2+1); ``pad_to = 2N`` prunes
    the Hockney zero tail inside the same kernel."""
    return _fft_green(x, green, half=True, pad_to=pad_to,
                      max_radix=max_radix)


@partial(jax.jit, static_argnames=("inverse", "pad_to", "max_radix"))
def fft1d(x, inverse: bool = False, pad_to: int | None = None,
          max_radix: int = 4):
    """Batched complex FFT via the Stockham kernel. x: (..., N) complex.

    ``pad_to = 2N`` is the PRUNED Hockney-doubling entry point: the
    length-2N spectrum of the zero-tail-extended signal, computed without
    materializing the zeros (the kernel's degenerate first stage)."""
    shp = x.shape
    rows = _rows(shp)
    rdt = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    re = x.real.reshape(rows, shp[-1]).astype(rdt)
    im = x.imag.reshape(rows, shp[-1]).astype(rdt)
    orr, oi = fft_stockham(re, im, inverse=inverse, pad_to=pad_to,
                           max_radix=max_radix)
    n_out = pad_to if pad_to is not None else shp[-1]
    return (orr + 1j * oi).reshape(shp[:-1] + (n_out,)).astype(_cdt(rdt))


@partial(jax.jit, static_argnames=("pad_to", "max_radix"))
def rfft_pallas(x, pad_to: int | None = None, max_radix: int = 4):
    """rfft of a real (..., N) array via the Stockham kernel: complex FFT
    with a zero imaginary plane, cropped to the half spectrum.  ``pad_to =
    2N`` prunes the Hockney zero tail (length-2N spectrum, N+1 bins kept,
    no materialized padding)."""
    shp = x.shape
    n = shp[-1]
    rows = _rows(shp)
    re = x.reshape(rows, n)
    im = jnp.zeros_like(re)
    orr, oi = fft_stockham(re, im, pad_to=pad_to, max_radix=max_radix)
    half = (pad_to if pad_to is not None else n) // 2 + 1
    out = (orr[:, :half] + 1j * oi[:, :half]).astype(_cdt(x.dtype))
    return out.reshape(shp[:-1] + (half,))


@partial(jax.jit, static_argnames=("keep", "max_radix"))
def ifft_pruned(y, keep: int, max_radix: int = 4):
    """First ``keep`` samples of the length-2n inverse FFT of ``y`` via the
    parity split: x_j = (ifft_n(Y_even)_j + e^{i pi j / n} ifft_n(Y_odd)_j)
    / 2 for j < n -- two half-length Stockham inverses instead of one
    double-length inverse plus a crop (``keep <= n`` required)."""
    shp = y.shape
    n2 = shp[-1]
    n = n2 // 2
    assert keep <= n, (keep, n2)
    rows = _rows(shp)
    rdt = jnp.float64 if y.dtype == jnp.complex128 else jnp.float32
    y2 = y.reshape(rows, n2)
    halves = []
    for part in (y2[:, 0::2], y2[:, 1::2]):
        orr, oi = fft_stockham(part.real.astype(rdt), part.imag.astype(rdt),
                               inverse=True, max_radix=max_radix)
        halves.append(orr + 1j * oi)
    j = jnp.arange(n, dtype=rdt)
    mod = jnp.exp(1j * jnp.pi * j / n).astype(_cdt(rdt))
    out = 0.5 * (halves[0] + mod[None, :] * halves[1])
    return out[:, :keep].reshape(shp[:-1] + (keep,)).astype(_cdt(rdt))


@partial(jax.jit, static_argnames=("n", "keep", "max_radix"))
def irfft_pruned(y, n: int, keep: int, max_radix: int = 4):
    """First ``keep`` samples of the length-``n`` irfft of a hermitian half
    spectrum (..., n//2+1): hermitian extension + parity-split pruned
    inverse, real part."""
    shp = y.shape
    rows = _rows(shp)
    y2 = y.reshape(rows, shp[-1])
    tail = jnp.conj(y2[:, n - n // 2 - 1:0:-1])
    full = jnp.concatenate([y2, tail], axis=-1)
    out = ifft_pruned(full, keep, max_radix=max_radix)
    rdt = jnp.float64 if y.dtype == jnp.complex128 else jnp.float32
    return out.real.reshape(shp[:-1] + (keep,)).astype(rdt)


@partial(jax.jit, static_argnames=("n", "max_radix"))
def irfft_pallas(y, n: int, max_radix: int = 4):
    """irfft of a hermitian half spectrum (..., N//2+1) -> real (..., N)."""
    shp = y.shape
    rows = _rows(shp)
    y2 = y.reshape(rows, shp[-1])
    # hermitian extension to the full length-n spectrum
    tail = jnp.conj(y2[:, n - n // 2 - 1:0:-1])
    full = jnp.concatenate([y2, tail], axis=-1)
    rdt = jnp.float64 if y.dtype == jnp.complex128 else jnp.float32
    orr, _ = fft_stockham(full.real.astype(rdt), full.imag.astype(rdt),
                          inverse=True, max_radix=max_radix)
    return orr.reshape(shp[:-1] + (n,)).astype(rdt)
