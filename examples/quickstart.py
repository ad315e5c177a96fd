"""Quickstart: solve an unbounded Poisson problem in ~10 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax

jax.config.update("jax_enable_x64", True)

from repro.core.bc import BCType, DataLayout
from repro.core.green import GreenKind
from repro.core.solver import get_solver

N, L = 64, 1.0
U = (BCType.UNB, BCType.UNB)

solver = get_solver((N, N, N), L, (U, U, U), layout=DataLayout.NODE,
                    green_kind=GreenKind.HEJ4)

# a Gaussian bump as the right-hand side: the potential is analytic
from scipy.special import erf

a = 50.0
h = L / N
x, y, z = np.meshgrid(*([np.arange(N + 1) * h] * 3), indexing="ij")
r = np.sqrt((x - 0.5) ** 2 + (y - 0.5) ** 2 + (z - 0.5) ** 2)
rhs = np.exp(-a * r * r)

u = np.asarray(solver.solve(rhs))
print(f"solved {u.shape} grid: u in [{u.min():.5f}, {u.max():.5f}]")

# exact: u = -Q erf(sqrt(a) r) / (4 pi r),  Q = (pi/a)^{3/2}
Q = (np.pi / a) ** 1.5
rs = np.where(r > 1e-12, r, 1.0)
u_ref = -Q * erf(np.sqrt(a) * rs) / (4 * np.pi * rs)
u_ref = np.where(r > 1e-12, u_ref, -Q * np.sqrt(a) / (2 * np.pi ** 1.5))
err = np.max(np.abs(u - u_ref)) / np.abs(u_ref).max()
print(f"relative E_inf vs analytic Gaussian potential = {err:.2e}")
assert err < 2e-2
print("OK")
