"""Resilient solve runtime (DESIGN.md #10).

``faults``      deterministic fault injection (the chaos-test substrate)
``resilience``  graceful-degradation ladder, retry policy, SolveError
``health``      numerical health guards (NaN/Inf, spectral/FD residual)
``abft``        algorithm-based fault tolerance: per-stage checksum
                invariants, wire sidecars, localize-and-recompute
                (DESIGN.md #13)
``spans``       host spans at the solver's layer boundaries, off by default
"""
from . import abft, faults, health, resilience, spans  # noqa: F401

from .abft import IntegrityError  # noqa: F401
from .resilience import SolveError  # noqa: F401
