"""TransformEngine: xla/pallas equivalence + plan-time normalization folding.

The acceptance bar for the engine layer:
  * ``engine="pallas"`` (interpret mode) matches ``engine="xla"`` within
    1e-5 on full mixed-BC solves (both solvers);
  * the solve emits ZERO standalone normalization multiplies -- the only
    float-array multiply in the jaxpr is the fused Green multiply.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.analytic import CASES
from repro.core.bc import BCType, DataLayout
from repro.core.engine import (TransformEngine, as_engine, build_schedule)
from repro.core.green import GreenKind
from repro.core.solver import PoissonSolver, make_plan

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB


def test_engine_resolution():
    assert as_engine(None).name == "xla"
    assert as_engine("pallas").use_pallas
    assert as_engine(TransformEngine("xla")) == TransformEngine("xla")
    with pytest.raises(ValueError):
        TransformEngine("cuda")


def test_schedule_folds_all_normfacts():
    plan = make_plan((16, 16, 16), 1.0, ((E, E), (O, E), (P, P)),
                     DataLayout.CELL)
    sched = build_schedule(plan, "xla")
    want = 1.0
    for p in plan.dirs:
        want *= p.normfact
    assert sched.norm == pytest.approx(want, rel=1e-15)
    # r2r dirs carry twiddle tables, the DFT dir carries none
    assert sched.fwd_tables[2] is None
    assert sched.fwd_tables[0] is not None


@pytest.mark.parametrize("case,layout", [
    ("A", DataLayout.CELL), ("A", DataLayout.NODE)])
def test_engines_match_on_mixed_bc_solve(case, layout):
    """pallas (interpret) == xla within 1e-5 on the paper's case A BCs."""
    fn, bcs = CASES[case]
    n = 32
    rhs, _ = fn(n, layout)
    kw = dict(layout=layout, green_kind=GreenKind.CHAT2)
    sx = PoissonSolver((n, n, n), 1.0, bcs, engine="xla", **kw)
    sp = PoissonSolver((n, n, n), 1.0, bcs, engine="pallas", **kw)
    ux = np.asarray(sx.solve(rhs.astype(np.float64)))
    up = np.asarray(sp.solve(rhs.astype(np.float64)))
    np.testing.assert_allclose(up, ux, rtol=1e-5, atol=1e-5)
    # the Pallas path itself ran: the ladder did not swap in XLA
    assert sp.stats["degradations"] == [] and sp.stats["retries"] == 0


@pytest.mark.slow
def test_engines_match_on_unbounded_solve():
    """Semi/unbounded dirs (Hockney-doubled power-of-two FFTs) also match."""
    fn, bcs = CASES["C"]
    n = 16
    rhs, _ = fn(n, DataLayout.CELL)
    kw = dict(layout=DataLayout.CELL, green_kind=GreenKind.CHAT2)
    sx = PoissonSolver((n, n, n), 1.0, bcs, engine="xla", **kw)
    sp = PoissonSolver((n, n, n), 1.0, bcs, engine="pallas", **kw)
    ux = np.asarray(sx.solve(rhs.astype(np.float64)))
    up = np.asarray(sp.solve(rhs.astype(np.float64)))
    np.testing.assert_allclose(up, ux, rtol=1e-5, atol=1e-5)


def test_pallas_engine_actually_uses_kernels():
    """The pallas engine must put pallas_call ops in the traced solve."""
    n = 16
    s = PoissonSolver((n, n, n), 1.0, ((E, E), (O, E), (P, P)),
                      layout=DataLayout.CELL, engine="pallas")
    f = jnp.zeros(s.input_shape)
    trace = str(jax.make_jaxpr(s._solve_impl)(f))
    assert "pallas_call" in trace
    sx = PoissonSolver((n, n, n), 1.0, ((E, E), (O, E), (P, P)),
                       layout=DataLayout.CELL, engine="xla")
    assert "pallas_call" not in str(jax.make_jaxpr(sx._solve_impl)(f))


def test_zero_standalone_normalization_multiplies():
    """All-even node solve (DCT-I, twiddle-free): the ONLY float-array mul
    in the jaxpr is the fused Green multiply -- every per-direction
    normfact pass of the seed implementation is gone."""
    n = 16
    s = PoissonSolver((n, n, n), 1.0, ((E, E), (E, E), (E, E)),
                      layout=DataLayout.NODE, engine="xla")
    f = jnp.zeros(s.input_shape)
    jaxpr = jax.make_jaxpr(s._solve_impl)(f)
    float_muls = [
        eq for eq in jaxpr.jaxpr.eqns
        if eq.primitive.name == "mul"
        and any(jnp.issubdtype(v.aval.dtype, jnp.inexact)
                for v in eq.invars if hasattr(v, "aval"))
    ]
    assert len(float_muls) == 1, (
        f"expected exactly the Green multiply, got {len(float_muls)} "
        "float-array multiplies")


def test_green_folds_normalization():
    """build_green output includes prod(normfact): solving with an
    unnormalized manual pipeline reproduces the solver result."""
    from repro.core.solver import build_green
    from repro.core import transforms as tr
    n = 8
    plan = make_plan((n, n, n), 1.0, ((E, E), (E, E), (E, E)),
                     DataLayout.CELL)
    g = build_green(plan)
    norm = np.prod([p.normfact for p in plan.dirs])
    plain = g / norm
    # spectral symbol of the pure-Neumann problem is norm-free in `plain`
    w2 = sum(np.meshgrid(*[np.square(p.modes) for p in plan.dirs],
                         indexing="ij"))
    mask = w2 > 1e-12
    np.testing.assert_allclose(plain[mask], -1.0 / w2[mask], rtol=1e-10)


def test_distributed_engines_match():
    """DistributedPoissonSolver(engine="pallas") == engine="xla"."""
    from repro.distributed.pencil import DistributedPoissonSolver
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn, bcs = CASES["A"]
    n = 16
    layout = DataLayout.CELL
    rhs, _ = fn(n, layout)
    kw = dict(layout=layout, green_kind=GreenKind.CHAT2, mesh=mesh,
              dtype=jnp.float64)
    sx = DistributedPoissonSolver((n, n, n), 1.0, bcs, engine="xla", **kw)
    sp = DistributedPoissonSolver((n, n, n), 1.0, bcs, engine="pallas", **kw)
    ux = np.asarray(sx.solve(rhs))
    up = np.asarray(sp.solve(rhs))
    np.testing.assert_allclose(up, ux, rtol=1e-5, atol=1e-5)
    for s in (sx, sp):
        assert s.stats["degradations"] == [] and s.stats["retries"] == 0
    assert set(sp.stage_map().values()) == {"pallas"}
    assert set(sx.stage_map().values()) == {"xla"}


def test_distributed_matches_reference_with_pallas_engine():
    """Pallas-engine distributed solve still matches the single-process
    reference solver (mixed-BC validation of tests/test_poisson.py)."""
    from repro.distributed.pencil import DistributedPoissonSolver
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn, bcs = CASES["A"]
    n = 16
    layout = DataLayout.CELL
    rhs, _ = fn(n, layout)
    ref = PoissonSolver((n, n, n), 1.0, bcs, layout=layout,
                        green_kind=GreenKind.CHAT2, engine="xla")
    ds = DistributedPoissonSolver(
        (n, n, n), 1.0, bcs, layout=layout, green_kind=GreenKind.CHAT2,
        mesh=mesh, dtype=jnp.float64, engine="pallas")
    want = np.asarray(ref.solve(rhs.astype(np.float64)))
    got = np.asarray(ds.solve(rhs))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert ds.stats["degradations"] == [] and ds.stats["retries"] == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.complex64])
def test_pin_row_major_keeps_values(dtype):
    """``pin_row_major`` changes the layout XLA may pick, never a value;
    a complex array is pinned plane by plane."""
    from repro.core.engine import pin_row_major
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 5, 7)),
                    dtype=jnp.float32)
    if dtype == jnp.complex64:
        x = x + 1j * x[::-1]
    fn = jax.jit(lambda v: pin_row_major(v.transpose(2, 0, 1)))
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.asarray(x).transpose(2, 0, 1))
    want = 2 if dtype == jnp.complex64 else 1
    assert str(jax.make_jaxpr(fn)(x)).count("layout_constraint") == want


def test_stage_outputs_pinned_row_major():
    """Every stage of the scheduled distributed solve hands its output on
    through a row-major layout constraint: XLA's own layout choice for the
    backward stages miscompiled the 256^3 unbounded solve on a TPU v5e."""
    from jax.sharding import NamedSharding
    from repro.distributed.pencil import DistributedPoissonSolver
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    s = DistributedPoissonSolver((8, 8, 8), 1.0, ((U, U),) * 3,
                                 layout=DataLayout.NODE, mesh=mesh,
                                 dtype=jnp.float32)
    f = jax.ShapeDtypeStruct(s.padded_input_shape(), jnp.float32,
                             sharding=NamedSharding(mesh, s.input_spec()))
    jaxpr = str(jax.make_jaxpr(s.jit_for())(f, s._green_shape()))
    assert jaxpr.count("layout_constraint") >= len(s.stage_map()) == 7
