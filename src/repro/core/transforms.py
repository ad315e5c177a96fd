"""1-D transforms used by the solver, all on the LAST axis.

Every real-to-real transform (DCT/DST types I-IV) runs a HALF-SPECTRUM real
FFT (``jnp.fft.rfft`` / ``irfft``) on the real (anti)symmetric extension --
half the FLOPs and bytes of the full-complex algorithm (kept in
``transforms_ref`` as the old-path baseline).  No complex intermediates exist
before the twiddle: forward transforms post-twiddle the rfft half spectrum
(``y = a * re + b * im``, the ``twiddle_pack`` kernel shape), inverse-family
transforms pre-twiddle the real input into the half spectrum consumed by
``irfft``.  All conventions match ``scipy.fft`` unnormalized ("backward") --
scipy is the oracle in the tests.

Twiddle tables are precomputed per ``(kind, m)`` (``twiddle_tables``, cached)
so a plan's ``TransformSchedule`` can hand them to the Pallas post-twiddle
kernel; constant factors (the 2M of the type-III inverses) are folded into
the tables, so no transform performs a standalone scaling multiply.

The pencil engine always shuffles the active direction to the last axis
(flups' ``shuffle()``), so all transforms here are axis=-1.

Engine selection: every public transform takes ``engine=None`` (pure XLA) or
a ``repro.core.engine.TransformEngine``; ``engine="pallas"`` routes the
post-twiddle through the ``twiddle_pack`` Pallas kernel and the FFT lengths
the ``fft_stockham`` kernel takes on the engine's platform
(``TransformEngine.kernel_fft``) through that kernel (see
``repro.kernels.ops``); every other length runs ``jnp.fft``.  On kernel
lengths the forward post-twiddle kinds (dct1/dct2/dst2) run the FUSED
``rfft_twiddle`` kernel instead -- the twiddle executes in the FFT's
final-stage registers, one HBM round trip instead of three (DESIGN.md #9).

The DFT directions' MXU route (``mxu_dft_forward``/``mxu_dft_backward``):
on the TPU, a float32 DFT of a length in ``MXU_DFT_LENGTHS`` runs as
products against plan-time DFT matrices (``dft_matrices``, held by the
plan's ``TransformSchedule``) at ``Precision.HIGHEST`` instead of
``jnp.fft``.  The matrices are cut to the live rows and columns, so the
pruned forward reads only the live inputs and the pruned inverse writes
only the kept outputs; the routing rule is ``TransformEngine.mxu_dft``.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp

from .bc import TransformKind

__all__ = [
    "dct1", "dct2", "dct3", "dct4",
    "dst1", "dst2", "dst3", "dst4",
    "r2r_forward", "r2r_backward", "r2r_normfact", "twiddle_tables",
]


def _rdtype(x):
    return x.dtype


def _use_pallas(engine) -> bool:
    return engine is not None and getattr(engine, "use_pallas", False)


def _kernel(engine, n: int) -> bool:
    """Whether the engine runs a length-``n`` FFT in the Stockham kernel."""
    return engine is not None and engine.kernel_fft(n)


def _scan_dtype(dtype):
    """Widest float for the O(M) prefix sums of dst1 / odd-M dct4: their
    roundoff accumulates linearly along the axis, so run them in f64 when
    x64 is enabled (and stay put otherwise -- requesting f64 under
    disabled x64 would only emit a truncation warning)."""
    return jnp.float64 if jax.config.jax_enable_x64 else dtype


# ---------------------------------------------------------------------------
# engine-aware FFT backends (jnp by default, Stockham kernel for pallas)
# ---------------------------------------------------------------------------

def _rfft(z, engine):
    if _kernel(engine, z.shape[-1]):
        from repro.kernels import ops
        return ops.rfft_pallas(z, max_radix=engine.max_radix)
    return jnp.fft.rfft(z, axis=-1)


def _irfft(c, n, engine):
    if _kernel(engine, n):
        from repro.kernels import ops
        return ops.irfft_pallas(c, n, max_radix=engine.max_radix)
    return jnp.fft.irfft(c, n=n, axis=-1)


def _cfft(z, engine, inverse=False):
    """Engine-aware complex FFT over the last axis (the solver's c2c dirs)."""
    if not jnp.iscomplexobj(z):
        z = z.astype(jnp.complex128 if z.dtype == jnp.float64
                     else jnp.complex64)
    if _kernel(engine, z.shape[-1]):
        from repro.kernels import ops
        return ops.fft1d(z, inverse=inverse, max_radix=engine.max_radix)
    return (jnp.fft.ifft if inverse else jnp.fft.fft)(z, axis=-1)


# ---------------------------------------------------------------------------
# pruned DFT variants (Hockney doubling: length-n_fft spectra of signals
# whose tail is identically zero / inverses of which only a head is kept)
# ---------------------------------------------------------------------------

def _zpad(x, n_fft):
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n_fft - x.shape[-1])]
    return jnp.pad(x, pad)


def _rfft_padded(x, n_fft, engine):
    """Length-``n_fft`` half spectrum of ``[x, 0, ..., 0]`` from only the
    ``x.shape[-1]`` nonzero inputs.  The Pallas engine skips the zero tail
    inside the Stockham kernel (first stage reads half the VMEM and does no
    dead adds); the XLA engine pads -- jnp.fft has no pruned entry point,
    and the explicit pad keeps the result BIT-IDENTICAL to a dense plan's
    (the pruned-vs-dense equality tests rely on this)."""
    n_in = x.shape[-1]
    if n_in == n_fft:
        return _rfft(x, engine)
    if _kernel(engine, n_fft) and n_fft == 2 * n_in:
        from repro.kernels import ops
        return ops.rfft_pallas(x, pad_to=n_fft, max_radix=engine.max_radix)
    return _rfft(_zpad(x, n_fft), engine)


def _cfft_padded(z, n_fft, engine):
    """Length-``n_fft`` complex spectrum of the zero-tail-extended ``z``."""
    n_in = z.shape[-1]
    if n_in == n_fft:
        return _cfft(z, engine)
    if (_kernel(engine, n_fft) and n_fft == 2 * n_in
            and jnp.iscomplexobj(z)):
        from repro.kernels import ops
        return ops.fft1d(z, pad_to=n_fft, max_radix=engine.max_radix)
    return _cfft(_zpad(z, n_fft), engine)


def _irfft_crop(y, n_fft, keep, engine):
    """First ``keep`` samples of the length-``n_fft`` irfft.  The Pallas
    engine reconstructs only the retained half via the parity split (two
    half-length inverse FFTs, so the kernel runs at ``n_fft // 2``); XLA
    reconstructs fully and crops."""
    if keep >= n_fft:
        return _irfft(y, n_fft, engine)
    if _kernel(engine, n_fft // 2) and keep <= n_fft // 2:
        from repro.kernels import ops
        return ops.irfft_pruned(y, n_fft, keep, max_radix=engine.max_radix)
    return _irfft(y, n_fft, engine)[..., :keep]


def _icfft_crop(z, keep, engine):
    """First ``keep`` samples of the inverse complex FFT of ``z``."""
    n_fft = z.shape[-1]
    if keep >= n_fft:
        return _cfft(z, engine, inverse=True)
    if _kernel(engine, n_fft // 2) and keep <= n_fft // 2:
        from repro.kernels import ops
        return ops.ifft_pruned(z, keep, max_radix=engine.max_radix)
    return _cfft(z, engine, inverse=True)[..., :keep]


# ---------------------------------------------------------------------------
# DFT-matrix products (the MXU route): no pad before the pruned forward, no
# crop after the pruned inverse -- the matrices hold only the live rows and
# columns
# ---------------------------------------------------------------------------

# DFT lengths the MXU route takes on the TPU, set from a chip
# micro-benchmark of jnp.fft against the products (benchmarks/bench_mxu_dft.py)
MXU_DFT_LENGTHS = (256, 512, 1024)


def _roots(n: int):
    """``e^{-2 pi i r / n}`` for ``r = 0..n-1`` in float64, exact at the
    quarter turns (so the imaginary parts a c2r inverse drops are 0)."""
    r = np.arange(n)
    w = np.exp(-2j * np.pi * r / n)
    quarter = (4 * r) % n == 0
    w[quarter] = np.array([1, -1j, -1, 1j])[4 * r[quarter] // n]
    return w


@lru_cache(maxsize=None)
def dft_matrices(dft: str, n_fft: int, n_in: int) -> dict:
    """Plan-time matrices of a length-``n_fft`` DFT direction whose forward
    reads ``n_in`` live inputs and whose inverse keeps the first ``n_in``
    outputs; built in float64 with the exact reduction ``(j k) mod n_fft``,
    then cast to complex64.

      fwd  ``F[:n_in, :n_out]``: ``n_out`` = ``n_fft // 2 + 1`` bins for
           ``dft="r2c"``, ``n_fft`` for ``"c2c"``
      bwd  c2c: ``conj(F)[:n_fft, :n_in] / n_fft``; r2c: ``B[k, j] =
           w_k e^{+2 pi i j k / n_fft} / n_fft`` over the half spectrum,
           with the Hermitian weights ``w_k`` (1 at DC and Nyquist, 2
           elsewhere) folded in, so that the c2r inverse of ``y`` is
           ``Re(y) @ Re(B) - Im(y) @ Im(B)``
    """
    w = _roots(n_fft)
    n_out = n_fft // 2 + 1 if dft == "r2c" else n_fft
    fwd = w[np.outer(np.arange(n_in), np.arange(n_out)) % n_fft]
    bwd = np.conj(w[np.outer(np.arange(n_out), np.arange(n_in)) % n_fft])
    if dft == "r2c":
        weight = np.full(n_out, 2.0)
        weight[0] = 1.0
        if n_fft % 2 == 0:
            weight[-1] = 1.0
        bwd = bwd * weight[:, None]
    return {"dft": dft, "fwd": fwd.astype(np.complex64),
            "bwd": (bwd / n_fft).astype(np.complex64)}


def _dot(x, m):
    """``x @ m`` over the last axis of ``x`` at float32 precision (on the
    TPU, ``HIGHEST`` is 6 bf16 passes of the MXU)."""
    m = jnp.asarray(m, dtype=x.dtype)
    return jax.lax.dot_general(x, m, (((x.ndim - 1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def mxu_dft_forward(x, mats):
    """The spectrum of the live inputs ``x`` (``[..., n_in]``, real or
    complex) as ``x @ F``: the r2c half spectrum or the c2c full one, equal
    to ``jnp.fft.rfft``/``fft`` of ``x`` zero-extended to ``n_fft``."""
    f = mats["fwd"]
    if jnp.iscomplexobj(x):
        return _dot(x, f)
    return jax.lax.complex(_dot(x, f.real), _dot(x, f.imag))


def mxu_dft_backward(y, mats):
    """The first ``n_in`` samples of the inverse of the spectrum ``y``:
    ``jnp.fft.ifft`` (c2c) or ``jnp.fft.irfft`` (r2c, a real result; the
    imaginary parts of the DC and Nyquist bins are ignored, as there)."""
    b = mats["bwd"]
    if mats["dft"] == "c2c":
        return _dot(y, b)
    return _dot(y.real, b.real) - _dot(y.imag, b.imag)


def _post(re, im, a, b, engine, out_dtype):
    """y = a * re + b * im along the last axis (the r2r post-twiddle)."""
    if _use_pallas(engine):
        from repro.kernels import ops
        return ops.post_twiddle(re, im, a, b).astype(out_dtype)
    av = jnp.asarray(a, dtype=out_dtype)
    bv = jnp.asarray(b, dtype=out_dtype)
    return (av * re + bv * im).astype(out_dtype)


# ---------------------------------------------------------------------------
# twiddle tables (plan-time constants, float64; cast at use)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def twiddle_tables(kind: TransformKind, m: int):
    """Precomputed twiddle constants for a size-``m`` transform of ``kind``.

    Keys (all values ``np.float64``):
      post_a/post_b  forward post-twiddle  ``y = a*re + b*im``
      pre_re/pre_im  inverse-family pre-twiddle (2M factor folded in)
      split_c/split_s  type-IV cos/sin input split
    """
    if kind == TransformKind.DCT1:
        return {}
    if kind == TransformKind.DST1:
        # NR-style auxiliary sequence for the length-(m+1) rfft formulation
        j = np.arange(m + 1)
        return {"aux_sin": np.sin(np.pi * j / (m + 1.0))}
    if kind == TransformKind.DCT2:
        k = np.arange(m)
        th = np.pi * k / (2.0 * m)
        return {"post_a": np.cos(th), "post_b": np.sin(th)}
    if kind == TransformKind.DST2:
        k = np.arange(1, m + 1)
        th = np.pi * k / (2.0 * m)
        return {"post_a": np.sin(th), "post_b": -np.cos(th)}
    if kind == TransformKind.DCT3:
        k = np.arange(m)
        th = np.pi * k / (2.0 * m)
        return {"pre_re": 2.0 * m * np.cos(th),
                "pre_im": 2.0 * m * np.sin(th)}
    if kind == TransformKind.DST3:
        k = np.arange(1, m + 1)
        th = np.pi * k / (2.0 * m)
        return {"pre_re": 2.0 * m * np.sin(th),
                "pre_im": -2.0 * m * np.cos(th)}
    if kind in (TransformKind.DCT4, TransformKind.DST4):
        n = np.arange(m)
        b = np.pi * (2 * n + 1) / (4.0 * m)
        t = {"split_c": np.cos(b), "split_s": np.sin(b),
             "alt_sign": (-1.0) ** n}
        if m % 2 == 0:
            # half-length complex-FFT formulation (see dct4): pre-twiddle
            # e^{-i pi (4p+1)/(4M)} on z_p = x_{2p} + i x_{M-1-2p}, post
            # e^{-i pi q/M} on the length-M/2 spectrum
            p = np.arange(m // 2)
            pre = np.pi * (4 * p + 1) / (4.0 * m)
            post = np.pi * p / m
            t.update(q4_pre_re=np.cos(pre), q4_pre_im=-np.sin(pre),
                     q4_post_re=np.cos(post), q4_post_im=-np.sin(post))
        return t
    raise ValueError(kind)


def _tables(kind, m, tables):
    return twiddle_tables(kind, m) if tables is None else tables


# ---------------------------------------------------------------------------
# DCT types
# ---------------------------------------------------------------------------

def _rfft_twiddle_fused(z, a, b, start, count, engine, out_dtype):
    """Fused rfft + post-twiddle (``a*re + b*im`` over ``count`` bins from
    ``start``) when the Pallas engine can run it as ONE kernel; None when
    the caller must take the unfused rfft + ``_post`` path."""
    if not _kernel(engine, z.shape[-1]):
        return None
    from repro.kernels import ops
    return ops.rfft_twiddle(z, a[:count], b[:count], start=start,
                            max_radix=engine.max_radix).astype(out_dtype)


def dct1(x, engine=None, tables=None):
    """DCT-I: y_k = x_0 + (-1)^k x_{M-1} + 2 sum_{n=1}^{M-2} x_n cos(pi k n/(M-1)).

    Even extension of length 2(M-1); the rfft of a real even signal is real,
    and its M half-spectrum bins are exactly the DCT-I coefficients.
    """
    m = x.shape[-1]
    z = jnp.concatenate([x, x[..., -2:0:-1]], axis=-1)  # even ext, len 2(M-1)
    fused = _rfft_twiddle_fused(z, np.ones(m), np.zeros(m), 0, m, engine,
                                _rdtype(x))
    if fused is not None:
        return fused
    return _rfft(z, engine).real.astype(_rdtype(x))


def dct2(x, engine=None, tables=None):
    """DCT-II: y_k = 2 sum_n x_n cos(pi k (2n+1) / (2M))."""
    m = x.shape[-1]
    t = _tables(TransformKind.DCT2, m, tables)
    z = jnp.concatenate([x, x[..., ::-1]], axis=-1)     # even ext, len 2M
    fused = _rfft_twiddle_fused(z, t["post_a"], t["post_b"], 0, m, engine,
                                _rdtype(x))
    if fused is not None:
        return fused
    f = _rfft(z, engine)[..., :m]
    return _post(f.real, f.imag, t["post_a"], t["post_b"], engine, _rdtype(x))


def dct3(x, engine=None, tables=None):
    """DCT-III: y_k = x_0 + 2 sum_{n=1}^{M-1} x_n cos(pi n (2k+1) / (2M)).

    Pre-twiddle the real input into the hermitian half spectrum whose
    length-2M irfft carries the DCT-III in its first M samples (the 2M
    normalization of irfft is folded into the twiddle table).
    """
    m = x.shape[-1]
    t = _tables(TransformKind.DCT3, m, tables)
    dt = jnp.complex128 if x.dtype == jnp.float64 else jnp.complex64
    c = (x * jnp.asarray(t["pre_re"], x.dtype) +
         1j * (x * jnp.asarray(t["pre_im"], x.dtype))).astype(dt)
    c = jnp.concatenate(
        [c, jnp.zeros(x.shape[:-1] + (1,), dtype=dt)], axis=-1)
    return _irfft(c, 2 * m, engine)[..., :m].astype(_rdtype(x))


def dct4(x, engine=None, tables=None):
    """DCT-IV: y_k = 2 sum_n x_n cos(pi (2k+1)(2n+1) / (4M)).

    Standard half-length formulation (even M, the MDCT/FFTW-style
    algorithm): fold the input into the length-M/2 complex sequence
    z_p = (x_{2p} + i x_{M-1-2p}) e^{-i pi (4p+1)/(4M)}; with
    t_q = FFT_{M/2}(z)_q e^{-i pi q/M} the outputs are
    y_{2q} = 2 Re t_q and y_{M-1-2q} = -2 Im t_q -- ONE complex FFT of
    length M/2 where the old path ran two length-2M real extensions (a
    DCT2 + a DST2), the BENCH_kernels laggard.

    Odd M falls back to the product-to-sum identity: with
    c_n = x_n cos(pi(2n+1)/(4M)),  y_k + y_{k-1} = 2 DCT2(c)_k (and
    y_0 = DCT2(c)_0), i.e. one DCT-II plus an O(M) alternating prefix sum
    y_k = (-1)^k [Y_0 + 2 sum_{j=1..k} (-1)^j Y_j].
    """
    m = x.shape[-1]
    t = _tables(TransformKind.DCT4, m, tables)
    dtype = _rdtype(x)
    if m % 2 == 0:
        dt = jnp.complex128 if dtype == jnp.float64 else jnp.complex64
        a = x[..., 0::2]                      # x_{2p}
        b = x[..., ::-1][..., 0::2]           # x_{M-1-2p}
        pre = (jnp.asarray(t["q4_pre_re"], dtype)
               + 1j * jnp.asarray(t["q4_pre_im"], dtype)).astype(dt)
        post = (jnp.asarray(t["q4_post_re"], dtype)
                + 1j * jnp.asarray(t["q4_post_im"], dtype)).astype(dt)
        z = (a.astype(dt) + 1j * b.astype(dt)) * pre
        tq = _cfft(z, engine) * post
        even = (2.0 * tq.real).astype(dtype)          # y_{2q}
        odd = (-2.0 * tq.imag[..., ::-1]).astype(dtype)   # y_{1+2r}
        return jnp.stack([even, odd], axis=-1).reshape(x.shape)
    c = (x * jnp.asarray(t["split_c"], dtype=dtype)).astype(dtype)
    y2 = dct2(c, engine).astype(_scan_dtype(dtype))
    sgn = jnp.asarray(t["alt_sign"], y2.dtype)
    cs = jnp.cumsum(sgn * y2, axis=-1)
    return (sgn * (2.0 * cs - y2[..., :1])).astype(dtype)


# ---------------------------------------------------------------------------
# DST types
# ---------------------------------------------------------------------------

def dst1(x, engine=None, tables=None):
    """DST-I: y_k = 2 sum_n x_n sin(pi (k+1)(n+1) / (M+1)).

    Standard length-N formulation (N = M+1, the Numerical-Recipes
    auxiliary sequence): with u = [0, x] and its reversal ur = [0, rev(x)],
    the rfft Y of  v_j = sin(pi j/N)(u_j + ur_j) + (u_j - ur_j)/2  carries
    the even coefficients directly (y_{2k} = -2 Im Y_k) and the odd ones as
    a prefix sum (y_{2k+1} = Re Y_0 + 2 sum_{j=1..k} Re Y_j) -- ONE rfft of
    length M+1 instead of the old odd extension's rfft of length 2(M+1).
    """
    m = x.shape[-1]
    t = _tables(TransformKind.DST1, m, tables)
    dtype = _rdtype(x)
    s = jnp.asarray(t["aux_sin"], dtype=dtype)                 # sin(pi j/N)
    zeros = jnp.zeros(x.shape[:-1] + (1,), dtype=x.dtype)
    u = jnp.concatenate([zeros, x], axis=-1)                   # u_j
    ur = jnp.concatenate([zeros, x[..., ::-1]], axis=-1)       # u_{N-j}
    v = s * (u + ur) + 0.5 * (u - ur)
    f = _rfft(v, engine)                                       # bins 0..N//2
    n_odd = (m + 1) // 2                                       # y_1, y_3, ...
    n_even = m // 2                                            # y_2, y_4, ...
    re = f.real[..., :n_odd].astype(_scan_dtype(dtype))
    odd = (2.0 * jnp.cumsum(re, axis=-1) - re[..., :1]).astype(dtype)
    even = (-2.0 * f.imag[..., 1:n_even + 1]).astype(dtype)
    if n_even < n_odd:                                         # odd M
        even = jnp.concatenate(
            [even, jnp.zeros(x.shape[:-1] + (1,), dtype=dtype)], axis=-1)
    out = jnp.stack([odd, even], axis=-1).reshape(x.shape[:-1] + (2 * n_odd,))
    return out[..., :m]


def dst2(x, engine=None, tables=None):
    """DST-II: y_k = 2 sum_n x_n sin(pi (k+1)(2n+1) / (2M))."""
    m = x.shape[-1]
    t = _tables(TransformKind.DST2, m, tables)
    z = jnp.concatenate([x, -x[..., ::-1]], axis=-1)    # odd ext, len 2M
    fused = _rfft_twiddle_fused(z, t["post_a"], t["post_b"], 1, m, engine,
                                _rdtype(x))
    if fused is not None:
        return fused
    f = _rfft(z, engine)[..., 1:m + 1]
    return _post(f.real, f.imag, t["post_a"], t["post_b"], engine, _rdtype(x))


def dst3(x, engine=None, tables=None):
    """DST-III: y_k = (-1)^k x_{M-1} + 2 sum_{n=0}^{M-2} x_n sin(pi (n+1)(2k+1)/(2M)).

    Mirror of dct3: pre-twiddle into bins 1..M of the half spectrum (bin 0
    stays zero), irfft, keep the first M samples.
    """
    m = x.shape[-1]
    t = _tables(TransformKind.DST3, m, tables)
    dt = jnp.complex128 if x.dtype == jnp.float64 else jnp.complex64
    c = (x * jnp.asarray(t["pre_re"], x.dtype) +
         1j * (x * jnp.asarray(t["pre_im"], x.dtype))).astype(dt)
    c = jnp.concatenate(
        [jnp.zeros(x.shape[:-1] + (1,), dtype=dt), c], axis=-1)
    return _irfft(c, 2 * m, engine)[..., :m].astype(_rdtype(x))


def dst4(x, engine=None, tables=None):
    """DST-IV: y_k = 2 sum_n x_n sin(pi (2k+1)(2n+1) / (4M)).

    Reversal identity: DST4(x)_k = (-1)^k DCT4(rev(x))_k, so the type-IV
    sine transform rides the half-length complex-FFT dct4 for free (the
    twiddle-table layout is shared by the two kinds).
    """
    m = x.shape[-1]
    t = _tables(TransformKind.DST4, m, tables)
    sgn = jnp.asarray(t["alt_sign"], dtype=_rdtype(x))
    return sgn * dct4(x[..., ::-1], engine=engine, tables=t)


# ---------------------------------------------------------------------------
# dispatch + normalization
# ---------------------------------------------------------------------------

_FWD = {
    TransformKind.DCT1: dct1, TransformKind.DCT2: dct2,
    TransformKind.DCT3: dct3, TransformKind.DCT4: dct4,
    TransformKind.DST1: dst1, TransformKind.DST2: dst2,
    TransformKind.DST3: dst3, TransformKind.DST4: dst4,
}

_INV = {
    TransformKind.DCT1: dct1, TransformKind.DCT2: dct3,
    TransformKind.DCT3: dct2, TransformKind.DCT4: dct4,
    TransformKind.DST1: dst1, TransformKind.DST2: dst3,
    TransformKind.DST3: dst2, TransformKind.DST4: dst4,
}


def r2r_normfact(kind: TransformKind, m: int) -> float:
    """1 / (forward o backward) amplification for size-m transforms."""
    if kind in (TransformKind.DCT1,):
        return 1.0 / (2.0 * (m - 1))
    if kind in (TransformKind.DST1,):
        return 1.0 / (2.0 * (m + 1))
    return 1.0 / (2.0 * m)


def r2r_forward(x, kind: TransformKind, engine=None, tables=None):
    return _FWD[kind](x, engine=engine, tables=tables)


def r2r_backward(y, kind: TransformKind, engine=None, tables=None):
    """Unnormalized inverse; the solver folds ``r2r_normfact`` into the
    Green's function (standalone callers multiply by it themselves)."""
    return _INV[kind](y, engine=engine, tables=tables)
