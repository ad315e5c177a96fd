"""The paper's analytical validation cases (Section IV / Appendix B).

  A. symmetric + periodic BCs (even-even x, odd-even y, periodic z)
  B. fully unbounded (a compact Gaussian-like bump)
  C. two semi-unbounded + one fully unbounded

Each ``case_*(n, layout)`` returns ``(rhs, sol)`` on the n^3-cell unit
cube with ``lap(sol) == rhs``: the right-hand side a solve takes and the
exact solution its output is checked against.  The fields are built from
one-dimensional coordinate axes that broadcast, so a case costs two
full-size arrays however large ``n`` is.
"""
from __future__ import annotations

import numpy as np

from .bc import BCType, DataLayout

__all__ = ["grids", "case_a", "case_b", "case_c", "CASES"]

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB
L = 1.0


def grids(n, layout):
    """Physical coordinates per direction for an n^3-cell cubic domain,
    as broadcastable (n', 1, 1), (1, n', 1), (1, 1, n') axes."""
    h = L / n
    if layout == DataLayout.NODE:
        x = np.arange(n + 1) * h
    else:
        x = (np.arange(n) + 0.5) * h
    return np.meshgrid(x, x, x, indexing="ij", sparse=True)


# --- case A: even-even x, odd-even y, periodic z (Appendix B-A) -----------

def case_a(n, layout):
    x, y, z = grids(n, layout)
    kx, ky, kz = np.pi / L, 2.5 * np.pi / L, 8 * np.pi / L
    sol = np.cos(kx * x) * np.sin(ky * y) * np.sin(kz * z)
    rhs = -(kx**2 + ky**2 + kz**2) * sol
    return rhs, sol


# --- case B: fully unbounded (Appendix B-B) --------------------------------

def _bump(s):
    """exp(10(1 - 1/(1-s^2))) with compact support |s|<1."""
    inside = np.abs(s) < 0.99999
    ss = np.where(inside, s, 0.0)
    val = np.exp(10.0 * (1.0 - 1.0 / (1.0 - ss * ss)))
    return np.where(inside, val, 0.0)


def _bump_d2(s):
    """second derivative of _bump wrt s (analytical)."""
    inside = np.abs(s) < 0.99999
    ss = np.where(inside, s, 0.0)
    one = 1.0 - ss * ss
    f = np.exp(10.0 * (1.0 - 1.0 / one))
    # f' = f * (-20 s / one^2)
    # f'' = f * [ (20 s / one^2)^2 - 20 (1 + 3 s^2) / one^3 ]
    d2 = f * ((20.0 * ss / one**2) ** 2 - 20.0 * (1.0 + 3.0 * ss * ss) / one**3)
    return np.where(inside, d2, 0.0)


def case_b(n, layout):
    x, y, z = grids(n, layout)
    sx, sy, sz = 2 * x / L - 1, 2 * y / L - 1, 2 * z / L - 1
    fx, fy, fz = _bump(sx), _bump(sy), _bump(sz)
    d2x, d2y, d2z = (_bump_d2(sx) * (2 / L) ** 2,
                     _bump_d2(sy) * (2 / L) ** 2,
                     _bump_d2(sz) * (2 / L) ** 2)
    sol = fx * fy * fz
    rhs = d2x * fy * fz + fx * d2y * fz + fx * fy * d2z
    return rhs, sol


# --- case C: semi-unbounded x (even right), semi z (odd left), unbounded y -

def case_c(n, layout):
    x, y, z = grids(n, layout)

    def g(s):
        return _bump(s)

    def g2(s, scale):
        return _bump_d2(s) * scale**2

    # X: even image around x = L -> bumps at 0.7L and 1.3L (width 0.5L)
    ax1, ax2 = (2 * x - 1.4 * L) / L, (2 * x - 2.6 * L) / L
    X = g(ax1) + g(ax2)
    X2 = g2(ax1, 2 / L) + g2(ax2, 2 / L)
    # Y: unbounded bump centered 0.5L
    ay = 2 * y / L - 1
    Y = g(ay)
    Y2 = g2(ay, 2 / L)
    # Z: odd image around z = 0 -> + at 0.3L, - at -0.3L
    az1, az2 = (2 * z - 0.6 * L) / L, (2 * z + 0.6 * L) / L
    Z = g(az1) - g(az2)
    Z2 = g2(az1, 2 / L) - g2(az2, 2 / L)

    sol = X * Y * Z
    rhs = X2 * Y * Z + X * Y2 * Z + X * Y * Z2
    return rhs, sol


# case name -> (field function, boundary conditions per direction)
CASES = {
    "A": (case_a, ((E, E), (O, E), (P, P))),
    "B": (case_b, ((U, U), (U, U), (U, U))),
    "C": (case_c, ((U, E), (U, U), (O, U))),
}
