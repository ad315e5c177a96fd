"""Pruned (deferred-doubling) execution == dense (up-front) execution.

The valid-extent execution model's correctness net:

* property-based pruned-vs-dense solve equality over per-direction BC
  mixes (unb / semi / per / sym), CELL + NODE layouts, both engines,
  batched and unbatched -- BIT-EXACT on the xla engine (the pruned path
  feeds the very same FFT lengths the dense plan does; only the geometry
  around them moves), allclose on pallas (whose pruned kernels use the
  skip-zero first stage / parity-split algorithms);
* the pruned Pallas kernel entry points against numpy oracles;
* plan bookkeeping: ``valid_in`` extents, pre_padded placement, and the
  periodic no-op guarantee;
* the distributed solver under both modes + the lowered-HLO byte counts:
  a pruned plan's first forward topology switch must ship FEWER bytes
  than the dense plan's (asserted via ``hlo_stats.comm_bytes_stats``).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.bc import BCType, DataLayout
from repro.core.solver import make_plan
from repro.distributed.pencil import DistributedPoissonSolver

U, P, E, O = BCType.UNB, BCType.PER, BCType.EVEN, BCType.ODD

# per-direction BC category -> a representative (left, right) pair
CATS = {
    "unb": (U, U),
    "semi": (U, E),
    "per": (P, P),
    "sym": (E, O),
}


def _solvers(cats, layout, engine, n=4):
    bcs = tuple(CATS[c] for c in cats)
    a = DistributedPoissonSolver((n,) * 3, 1.0, bcs, layout=layout,
                                 engine=engine, doubling="deferred")
    b = DistributedPoissonSolver((n,) * 3, 1.0, bcs, layout=layout,
                                 engine=engine, doubling="upfront")
    return a, b


@settings(max_examples=12, deadline=None)
@given(c0=st.sampled_from(["unb", "semi", "per"]),
       c1=st.sampled_from(["unb", "semi", "per"]),
       c2=st.sampled_from(["unb", "semi", "per"]),
       layout=st.sampled_from(["CELL", "NODE"]),
       batched=st.booleans(), seed=st.integers(min_value=0, max_value=2**31))
def test_pruned_equals_dense_xla_bitexact(c0, c1, c2, layout, batched, seed):
    """Any unb/semi/per mix, any layout, batched or not: deferred ==
    upfront solve, bit for bit, on the xla engine -- the pruned path feeds
    the SAME FFT lengths the same values, only the geometry around them
    moves."""
    a, b = _solvers((c0, c1, c2), DataLayout[layout], "xla")
    rng = np.random.default_rng(seed)
    shape = ((2,) + a.input_shape) if batched else a.input_shape
    f = jnp.asarray(rng.standard_normal(shape))
    ua = np.asarray(a.solve(f))
    ub = np.asarray(b.solve(f))
    assert np.array_equal(ua, ub), np.max(np.abs(ua - ub))


@settings(max_examples=6, deadline=None)
@given(c0=st.sampled_from(list(CATS)), c1=st.sampled_from(list(CATS)),
       layout=st.sampled_from(["CELL", "NODE"]),
       seed=st.integers(min_value=0, max_value=2**31))
def test_pruned_equals_dense_xla_with_sym_dirs(c0, c1, layout, seed):
    """Mixes including symmetric (r2r) directions: equality to a few ulp.
    Sym dims are untouched by doubling, but their type-IV kinds run complex
    multiply chains whose FMA contraction XLA may fuse differently for the
    two batch shapes -- bit-exactness is only guaranteed for the
    unb/semi/per mixes above."""
    a, b = _solvers((c0, c1, "sym"), DataLayout[layout], "xla")
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.standard_normal(a.input_shape))
    ua = np.asarray(a.solve(f))
    ub = np.asarray(b.solve(f))
    np.testing.assert_allclose(ua, ub, rtol=1e-13, atol=1e-15)


@settings(max_examples=4, deadline=None)
@given(c0=st.sampled_from(["unb", "per"]), c1=st.sampled_from(["unb", "semi"]),
       layout=st.sampled_from(["CELL", "NODE"]),
       seed=st.integers(min_value=0, max_value=2**31))
def test_pruned_equals_dense_pallas(c0, c1, layout, seed):
    """The pallas engine's pruned kernels (skip-zero first stage, parity
    split) agree with the dense path to roundoff."""
    a, b = _solvers((c0, c1, "unb"), DataLayout[layout], "pallas")
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.standard_normal(a.input_shape))
    ua = np.asarray(a.solve(f))
    ub = np.asarray(b.solve(f))
    np.testing.assert_allclose(ua, ub, rtol=1e-10, atol=1e-12)


def test_pruned_engines_agree():
    """xla and pallas engines agree on a pruned all-unbounded solve (the
    pruned Stockham entry points against jnp.fft)."""
    bcs = (CATS["unb"],) * 3
    sx = DistributedPoissonSolver((8,) * 3, 1.0, bcs, engine="xla")
    sp = DistributedPoissonSolver((8,) * 3, 1.0, bcs, engine="pallas")
    f = jnp.asarray(np.random.default_rng(3).standard_normal(sx.input_shape))
    np.testing.assert_allclose(np.asarray(sx.solve(f)),
                               np.asarray(sp.solve(f)),
                               rtol=1e-9, atol=1e-11)


# -- pruned kernel entry points (numpy oracles) -----------------------------

def test_rfft_pallas_pruned_matches_padded():
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 16))
    got = np.asarray(ops.rfft_pallas(jnp.asarray(x), pad_to=32))
    want = np.fft.rfft(np.concatenate([x, 0 * x], axis=-1), axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_fft1d_pruned_matches_padded():
    from repro.kernels import ops
    rng = np.random.default_rng(1)
    z = rng.standard_normal((4, 16)) + 1j * rng.standard_normal((4, 16))
    got = np.asarray(ops.fft1d(jnp.asarray(z), pad_to=32))
    want = np.fft.fft(np.concatenate([z, 0 * z], axis=-1), axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_ifft_pruned_matches_cropped_inverse():
    from repro.kernels import ops
    rng = np.random.default_rng(2)
    Y = rng.standard_normal((3, 32)) + 1j * rng.standard_normal((3, 32))
    got = np.asarray(ops.ifft_pruned(jnp.asarray(Y), 12))
    want = np.fft.ifft(Y, axis=-1)[:, :12]
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_irfft_pruned_matches_cropped_irfft():
    from repro.kernels import ops
    rng = np.random.default_rng(3)
    Yh = np.fft.rfft(rng.standard_normal((3, 32)), axis=-1)
    got = np.asarray(ops.irfft_pruned(jnp.asarray(Yh), 32, 16))
    want = np.fft.irfft(Yh, n=32, axis=-1)[:, :16]
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_stockham_zero_tail_stage():
    from repro.kernels.fft_stockham import fft_stockham
    rng = np.random.default_rng(4)
    re = rng.standard_normal((3, 16)).astype(np.float64)
    im = rng.standard_normal((3, 16)).astype(np.float64)
    gr, gi = fft_stockham(jnp.asarray(re), jnp.asarray(im), pad_to=32)
    z = np.concatenate([re + 1j * im, np.zeros((3, 16))], axis=-1)
    want = np.fft.fft(z, axis=-1)
    np.testing.assert_allclose(np.asarray(gr) + 1j * np.asarray(gi), want,
                               rtol=1e-10, atol=1e-10)


# -- plan bookkeeping -------------------------------------------------------

def test_plan_valid_extents():
    bcs = (CATS["unb"], CATS["per"], CATS["semi"])
    dp = make_plan((8, 8, 8), 1.0, bcs)
    du = make_plan((8, 8, 8), 1.0, bcs, doubling="upfront")
    # deferred: every axis lives at its user extent outside its transform
    assert [p.valid_in for p in dp.dirs] == [8, 8, 8]
    assert not any(p.pre_padded for p in dp.dirs)
    # upfront: only the fully-unbounded dir doubles (semi keeps its r2r
    # slicing, per never pads)
    assert [p.pre_padded for p in du.dirs] == [True, False, False]
    assert [p.valid_in for p in du.dirs] == [16, 8, 8]
    # spectral storage identical across modes (Green's function reuse)
    assert [p.n_out for p in dp.dirs] == [p.n_out for p in du.dirs]


def test_periodic_plan_doubling_is_noop():
    bcs = (CATS["per"],) * 3
    dp = make_plan((8, 8, 8), 1.0, bcs)
    du = make_plan((8, 8, 8), 1.0, bcs, doubling="upfront")
    assert dp.dirs == du.dirs


def test_make_plan_rejects_unknown_doubling():
    with pytest.raises(AssertionError):
        make_plan((8, 8, 8), 1.0, (CATS["unb"],) * 3, doubling="sideways")


# -- distributed equality + the comm-bytes acceptance probe -----------------

_DIST_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core.bc import BCType
from repro.core.comm import CommConfig
from repro.distributed.pencil import DistributedPoissonSolver
from repro.launch.hlo_stats import comm_bytes_stats
sys.path.insert(0, "tests")
from _reference import reference_solve

U, P = (BCType.UNB, BCType.UNB), (BCType.PER, BCType.PER)
mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)

stats = {}
for case, bcs in (("unb", (U, U, U)), ("per", (P, P, P))):
    f = rng.standard_normal((16,) * 3)
    want = reference_solve(f, (16,) * 3, 1.0, bcs)
    got = {}
    for doubling in ("deferred", "upfront"):
        ds = DistributedPoissonSolver(
            (16,) * 3, 1.0, bcs, mesh=mesh, dtype=jnp.float64,
            comm=CommConfig("overlap", 2), doubling=doubling)
        u = np.asarray(ds.solve(f))
        assert np.max(np.abs(u - want)) < 1e-10, (case, doubling)
        got[doubling] = u
        ds2 = DistributedPoissonSolver(
            (16,) * 3, 1.0, bcs, mesh=mesh, lazy_green=True,
            doubling=doubling)
        stats[(case, doubling)] = comm_bytes_stats(ds2.lower().as_text())
    # pruned == dense bit-exact through the distributed pipeline too
    assert np.array_equal(got["deferred"], got["upfront"]), case

unb_p, unb_d = stats[("unb", "deferred")], stats[("unb", "upfront")]
per_p, per_d = stats[("per", "deferred")], stats[("per", "upfront")]
# 4 switches per solve in every lowering
assert len(unb_p["per_collective"]) == 4, unb_p
# the acceptance criterion: the pruned plan's FIRST forward switch moves
# less data than the dense plan's (it ships n-point axes, never 2n)
assert unb_p["first_bytes"] < unb_d["first_bytes"], (unb_p, unb_d)
assert unb_p["first_bytes"] * 2 <= unb_d["first_bytes"], (unb_p, unb_d)
assert unb_p["total_bytes"] < unb_d["total_bytes"]
# periodic: doubling is a plan no-op, wire bytes identical
assert per_p["per_collective"] == per_d["per_collective"], (per_p, per_d)
print("OK " + json.dumps({"pruned_first": unb_p["first_bytes"],
                          "dense_first": unb_d["first_bytes"]}))
"""


def test_distributed_pruned_vs_dense_and_comm_bytes():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_COMM_CACHE", None)
    out = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
