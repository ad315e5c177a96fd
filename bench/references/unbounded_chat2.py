"""Plain reference for the fully unbounded Poisson problem with the CHAT2
kernel, written from the paper (arXiv 2211.07777, sections II and IV) and
independent of the code under test.

The problem: lap(u) = f on the node-centred grid x_i = i h, i = 0..n, of
the cube [0, L]^3, with u decaying at infinity.  The solution is the
convolution u = G * f with the free-space Green's function
G(r) = -1 / (4 pi r), sampled on the grid and integrated by the midpoint
rule (weight h^3), with the singular self cell replaced by its cell
average -<1/r>_cell / (4 pi) = -CUBE_MEAN_INV_R / (4 pi h).  Hockney's
doubling makes the discrete convolution exact: f is zero-padded to 2n
points per axis, the kernel is sampled at the circular distances
min(j, 2n - j) h, and the product of their DFTs is transformed back.

Everything is float64 numpy on the host.  ``solve(f, precision="bf16")``
is the control: the same algorithm with the input, the kernel spectrum
and every intermediate rounded to bfloat16, as a pipeline that stored its
fields in bfloat16 would compute it.
"""
from __future__ import annotations

import numpy as np
import scipy.fft as sfft

# mean of 1/|r| over the unit cube [-1/2, 1/2]^3 (the potential at the
# centre of a uniform unit cube)
CUBE_MEAN_INV_R = 2.3800774834429582


def kernel_spectrum(n: int, h: float) -> np.ndarray:
    """DFT of the doubled, quadrature-weighted kernel, at wavenumber
    indices k = 0..n per axis ((n+1)^3, real).  The doubled kernel is even
    along every axis, so its DFT over 2n points is real and equals the
    type-I DCT of the octant j = 0..n; indices k > n mirror to 2n - k."""
    j = np.arange(n + 1, dtype=np.float64) * h
    r2 = (j[:, None, None] ** 2 + j[None, :, None] ** 2) + j[None, None, :] ** 2
    r2[0, 0, 0] = 1.0
    g = -1.0 / (4.0 * np.pi * np.sqrt(r2))
    g[0, 0, 0] = -CUBE_MEAN_INV_R / (4.0 * np.pi * h)
    g *= h ** 3
    return sfft.dctn(g, type=1, workers=-1)


def _fold(n: int) -> np.ndarray:
    """Index map k -> min(k, 2n - k) over k = 0..2n-1."""
    k = np.arange(2 * n)
    return np.minimum(k, 2 * n - k)


def _round_bf16(x):
    """Round a float32 or complex64 array to bfloat16 in place (nearest,
    ties to even), keeping it in its 32-bit type."""
    u = x.view(np.uint32)
    u += np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
    u &= np.uint32(0xFFFF0000)
    return x


def solve(f: np.ndarray, L: float = 1.0, precision: str = "f64",
          block: int = 64) -> np.ndarray:
    """u with lap(u) = f for an (n+1)^3 node-centred field ``f``.

    The zero-padded transform runs one axis at a time, the last first
    (real to complex).  The first axis's forward transform, the product
    with the kernel spectrum and that axis's inverse transform run over
    ``block`` rows of the second axis at a time, so that the doubled
    spectrum is never held whole."""
    n = f.shape[0] - 1
    assert f.shape == (n + 1,) * 3, f.shape
    if precision not in ("f64", "bf16"):
        raise ValueError(precision)
    m, h = 2 * n, L / n
    fold = _fold(n)
    spec = kernel_spectrum(n, h)[fold]                    # (m, n+1, n+1)
    if precision == "f64":
        real, r = np.float64, (lambda x: x)
    else:
        real, r = np.float32, _round_bf16
        spec = r(spec.astype(np.float32))
    x = r(np.array(f, real))
    x = r(sfft.rfft(x, n=m, axis=2, workers=-1))          # (n+1, n+1, n+1)
    x = r(sfft.fft(x, n=m, axis=1, workers=-1))           # (n+1, m, n+1)
    for j in range(0, m, block):
        y = r(sfft.fft(x[:, j:j + block], n=m, axis=0, workers=-1))
        y *= spec[:, fold[j:j + block]]
        r(y)
        x[:, j:j + block] = r(sfft.ifft(y, axis=0, workers=-1)[:n + 1])
    x = r(np.ascontiguousarray(sfft.ifft(x, axis=1, workers=-1)[:, :n + 1]))
    return r(np.ascontiguousarray(
        sfft.irfft(x, n=m, axis=2, workers=-1)[..., :n + 1]))
