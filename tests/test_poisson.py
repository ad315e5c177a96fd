"""Validation of the Poisson solver against the paper's analytical cases.

Section IV / Appendix B of the paper:
  A. symmetric + periodic BCs (even-even x, odd-even y, periodic z)
  B. fully unbounded
  C. two semi-unbounded + one fully unbounded

Convergence orders are asserted per Green's function kind (Figs 6-8).
Both layouts (cell/node) are exercised; the paper's validation uses the
node-centered layout.
"""
import numpy as np
import pytest

from repro.core.analytic import CASES, L
from repro.core.bc import DataLayout
from repro.core.green import GreenKind
from repro.distributed.pencil import DistributedPoissonSolver


def linf_error(case, bcs, n, layout, green, eps_factor=2.0):
    fn, _ = CASES[case] if isinstance(case, str) else (case, None)
    rhs, sol = fn(n, layout)
    s = DistributedPoissonSolver((n, n, n), L, bcs, layout=layout,
                                 green_kind=green, eps_factor=eps_factor)
    u = np.asarray(s.solve(rhs.astype(np.float64)))
    return np.max(np.abs(u - sol))


def observed_order(case, bcs, layout, green, ns=(32, 64), **kw):
    errs = [linf_error(case, bcs, n, layout, green, **kw) for n in ns]
    return np.log(errs[0] / errs[-1]) / np.log(ns[-1] / ns[0]), errs


# ---------------------------------------------------------------------------
# case A: spectral BCs -> CHAT2 is exact, LGF2/HEJ2 are 2nd order
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", [DataLayout.NODE, DataLayout.CELL])
def test_case_a_chat2_exact(layout):
    fn, bcs = CASES["A"]
    err = linf_error("A", bcs, 48, layout, GreenKind.CHAT2)
    assert err < 1e-10, err


@pytest.mark.parametrize("green,order", [
    (GreenKind.LGF2, 2.0), (GreenKind.HEJ2, 2.0), (GreenKind.HEJ4, 4.0),
    (GreenKind.HEJ6, 6.0),
])
def test_case_a_orders(green, order):
    # the 8 pi / L mode of the paper's case A needs n >= 64 to reach the
    # asymptotic regime of the regularized kernels (eps = 2h)
    fn, bcs = CASES["A"]
    ns = (32, 64) if green == GreenKind.LGF2 else (64, 128)
    p, errs = observed_order("A", bcs, DataLayout.NODE, green, ns=ns)
    assert p > order - 0.45, (p, errs)


# ---------------------------------------------------------------------------
# case B: fully unbounded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", [DataLayout.NODE, DataLayout.CELL])
def test_case_b_chat2_second_order(layout):
    fn, bcs = CASES["B"]
    p, errs = observed_order("B", bcs, layout, GreenKind.CHAT2)
    assert p > 1.55, (p, errs)


@pytest.mark.parametrize("green,order", [
    (GreenKind.LGF2, 2.0), (GreenKind.HEJ2, 2.0),
    (GreenKind.HEJ4, 4.0), (GreenKind.HEJ6, 6.0),
])
def test_case_b_orders(green, order):
    fn, bcs = CASES["B"]
    ns = (32, 64) if order <= 2 else (48, 96)  # HEJ4+ preasymptotic below 48
    p, errs = observed_order("B", bcs, DataLayout.NODE, green, ns=ns)
    assert p > order - 0.5, (p, errs)


def test_case_b_hej0_spectral_like():
    """HEJ0 (truncated spectral kernel) converges faster than order 6."""
    fn, bcs = CASES["B"]
    p, errs = observed_order("B", bcs, DataLayout.NODE, GreenKind.HEJ0)
    assert p > 6.0 or errs[-1] < 1e-10, (p, errs)


# ---------------------------------------------------------------------------
# case C: semi-unbounded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", [DataLayout.NODE, DataLayout.CELL])
def test_case_c_chat2_second_order(layout):
    fn, bcs = CASES["C"]
    p, errs = observed_order("C", bcs, layout, GreenKind.CHAT2)
    assert p > 1.55, (p, errs)


@pytest.mark.parametrize("green,order", [
    (GreenKind.HEJ2, 2.0), (GreenKind.HEJ4, 4.0),
])
def test_case_c_orders(green, order):
    fn, bcs = CASES["C"]
    ns = (32, 64) if order <= 2 else (48, 96)
    p, errs = observed_order("C", bcs, DataLayout.NODE, green, ns=ns)
    assert p > order - 0.5, (p, errs)
