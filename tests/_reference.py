"""A plain flups solve to hold the solver against.

The natural-layout walk of the paper's algorithm, eager and on one device:
forward transform each direction in plan order, multiply by the Green's
function (every normalization folded in), inverse transform in reverse
order, crop the up-front doubling.  It shares the plan, the Green's
function and the 1-D transforms with the solver, and none of the solver's
pipeline: no pencils, topology switches, layout schedule, mesh padding,
placement or crop program.
"""
import numpy as np
import jax.numpy as jnp

from repro.core.bc import DataLayout
from repro.core.engine import (bwd_1d, crop_doubling, fwd_1d,
                               materialize_doubling)
from repro.core.green import GreenKind
from repro.core.solver import build_green, make_plan


def reference_solve(f, shape, L, bcs, layout=DataLayout.CELL,
                    green_kind=GreenKind.CHAT2, **plan_kw):
    """u with lap(u) = f on one field ``f`` of the plan's input shape, in
    ``f``'s dtype (float64 for a host array)."""
    plan = make_plan(tuple(shape), L, bcs, layout, green_kind, **plan_kw)
    f = jnp.asarray(f)
    assert f.shape == plan.input_shape, (f.shape, plan.input_shape)
    y = materialize_doubling(f, plan.dirs)
    for d in plan.order:
        y = fwd_1d(y, plan.dirs[d])
    y = y * jnp.asarray(build_green(plan)).astype(
        y.real.dtype if jnp.iscomplexobj(y) else y.dtype)
    for d in reversed(plan.order):
        y = bwd_1d(y, plan.dirs[d])
    if jnp.iscomplexobj(y):
        y = y.real
    return np.asarray(crop_doubling(y, plan.dirs).astype(f.dtype))
