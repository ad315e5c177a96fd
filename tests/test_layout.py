"""Layout-scheduled pipeline == baseline (moveaxis) pipeline, plus the
fused Pallas epilogues and radix-4 Stockham stages (DESIGN.md #9).

The layout-scheduling correctness net:

* property-based scheduled-vs-baseline solve equality over per-direction
  BC category mixes, CELL + NODE layouts, batched and unbatched, both
  doubling modes -- BIT-EXACT on the xla engine (relayouts only reorder
  rows; the per-row transform and pointwise math is identical);
* the same equality through the distributed pencil solver for all four
  comm strategies x both relayout folds (subprocess, 8 host devices);
* ``hlo_stats.transpose_stats`` on the lowered distributed solve: the
  scheduled pipeline emits ZERO standalone transposes between stages (the
  one relayout per direction change is fused into the topology switch),
  the baseline pipeline does not;
* the Pallas fused epilogues (post-twiddle and Green multiply running in
  the FFT's final-stage registers) against numpy oracles and against
  their unfused two-kernel paths;
* radix-4 Stockham stages == radix-2 == numpy, including the pruned
  zero-tail first stage and the inverse.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.bc import BCType, DataLayout
from repro.core.engine import (LayoutSchedule, build_schedule, relayout,
                               schedule_layouts, switch_layout, to_last)
from repro.core.solver import make_plan
from repro.distributed.pencil import DistributedPoissonSolver

U, P, E, O = BCType.UNB, BCType.PER, BCType.EVEN, BCType.ODD

CATS = {
    "unb": (U, U),
    "semi": (U, E),
    "per": (P, P),
    "sym": (E, O),
}


# -- layout schedule bookkeeping --------------------------------------------

def test_schedule_layouts_invariants():
    """Every stage keeps its active dim minor-most; every consecutive pair
    of layouts is exactly one switch_layout step; bwd[0] reuses the
    spectral layout."""
    for order in ((0, 1, 2), (2, 0, 1), (1, 2, 0), (0, 2, 1)):
        lay = schedule_layouts(order, 3)
        assert isinstance(lay, LayoutSchedule)
        for i, d in enumerate(order):
            assert lay.fwd[i][-1] == d, (order, i)
        rev = tuple(reversed(order))
        for i, d in enumerate(rev):
            assert lay.bwd[i][-1] == d, (order, i)
        assert lay.bwd[0] == lay.spectral == lay.fwd[-1]
        for prev, (a, b) in zip(lay.fwd, zip(order, order[1:])):
            nxt = switch_layout(prev, a, b)
            assert nxt[0] == a and nxt[-1] == b


def test_order_policy_minimizes_edge_relayouts():
    """Single-category plans pick the order whose pipeline starts AND ends
    in the user's natural layout; mixed plans keep the historical order
    (ties break lexicographically)."""
    nat = (0, 1, 2)
    for bcs in (((P, P),) * 3, ((U, U),) * 3):
        plan = make_plan((8,) * 3, 1.0, bcs)
        lay = schedule_layouts(plan.order, 3)
        assert lay.fwd[0] == nat and lay.bwd[-1] == nat, plan.order
        assert make_plan((8,) * 3, 1.0, bcs,
                         order_policy="natural").order == nat
    # mixed sym+dft: historical order survives (it is already minimal)
    plan = make_plan((8,) * 3, 1.0, ((E, E), (O, E), (P, P)))
    assert plan.order == (0, 1, 2)


def test_relayout_roundtrip_and_batch_axes():
    x = jnp.arange(2 * 3 * 4 * 5).reshape(2, 3, 4, 5)
    src, dst = (0, 1, 2), (2, 0, 1)
    y = relayout(x, src, dst)
    assert y.shape == (2, 5, 3, 4)          # leading batch axis untouched
    assert np.array_equal(np.asarray(relayout(y, dst, src)), np.asarray(x))
    assert relayout(x, src, src) is x
    assert to_last((0, 1, 2), 1) == (0, 2, 1)


def test_r2c_follows_the_scheduled_order():
    """The r2c direction is the first EXECUTED DFT dim, not the lowest
    index -- the spectral storage follows the scheduled order."""
    plan = make_plan((8,) * 3, 1.0, ((P, P),) * 3)
    d0 = plan.order[0]
    assert plan.dirs[d0].dft == "r2c"
    assert all(plan.dirs[d].dft == "c2c" for d in plan.order[1:])


# -- scheduled == baseline, one device --------------------------------------

def _solvers(cats, layout, engine, doubling="deferred", n=4):
    bcs = tuple(CATS[c] for c in cats)
    kw = dict(layout=layout, engine=engine, doubling=doubling)
    a = DistributedPoissonSolver((n,) * 3, 1.0, bcs, relayout="scheduled",
                                 **kw)
    b = DistributedPoissonSolver((n,) * 3, 1.0, bcs, relayout="baseline", **kw)
    return a, b


@settings(max_examples=14, deadline=None)
@given(c0=st.sampled_from(list(CATS)), c1=st.sampled_from(list(CATS)),
       c2=st.sampled_from(list(CATS)),
       layout=st.sampled_from(["CELL", "NODE"]),
       doubling=st.sampled_from(["deferred", "upfront"]),
       batched=st.booleans(), seed=st.integers(min_value=0, max_value=2**31))
def test_scheduled_equals_baseline_xla_bitexact(c0, c1, c2, layout, doubling,
                                                batched, seed):
    """Any BC mix, any layout, batched or not, both doubling modes:
    layout-scheduled == baseline, bit for bit, on the xla engine -- the
    relayouts only reorder rows, every transform sees the same values."""
    a, b = _solvers((c0, c1, c2), DataLayout[layout], "xla", doubling)
    rng = np.random.default_rng(seed)
    shape = ((2,) + a.input_shape) if batched else a.input_shape
    f = jnp.asarray(rng.standard_normal(shape))
    ua = np.asarray(a.solve(f))
    ub = np.asarray(b.solve(f))
    assert np.array_equal(ua, ub), np.max(np.abs(ua - ub))


@settings(max_examples=4, deadline=None)
@given(c0=st.sampled_from(["unb", "per", "sym"]),
       layout=st.sampled_from(["CELL", "NODE"]),
       seed=st.integers(min_value=0, max_value=2**31))
def test_scheduled_equals_baseline_pallas(c0, layout, seed):
    """On the pallas engine the scheduled pipeline swaps in the FUSED
    epilogue kernels, so the comparison is to roundoff, not bits."""
    a, b = _solvers((c0, "per", "unb"), DataLayout[layout], "pallas", n=8)
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.standard_normal(a.input_shape))
    np.testing.assert_allclose(np.asarray(a.solve(f)),
                               np.asarray(b.solve(f)),
                               rtol=1e-9, atol=1e-11)


def test_order_policies_agree_to_roundoff():
    """order_policy="layout" (reordered execution) solves the same problem
    as the historical natural order to fp accuracy."""
    bcs = (CATS["unb"],) * 3
    a = DistributedPoissonSolver((8,) * 3, 1.0, bcs)
    b = DistributedPoissonSolver((8,) * 3, 1.0, bcs, order_policy="natural")
    assert a.plan.order != b.plan.order
    f = jnp.asarray(np.random.default_rng(0).standard_normal(a.input_shape))
    np.testing.assert_allclose(np.asarray(a.solve(f)),
                               np.asarray(b.solve(f)),
                               rtol=1e-12, atol=1e-13)


# -- distributed equality + lowered-HLO transpose census --------------------

_DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings
warnings.simplefilter("ignore")
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core.bc import BCType, DataLayout
from repro.core.comm import CommConfig
from repro.distributed.pencil import DistributedPoissonSolver
from repro.launch.hlo_stats import transpose_stats

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB
mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
CASES = [
    (((E, E), (O, E), (P, P)), DataLayout.NODE, "deferred"),
    (((U, U), (U, U), (U, U)), DataLayout.CELL, "deferred"),
    (((U, E), (U, U), (O, U)), DataLayout.CELL, "upfront"),
    (((P, P), (P, P), (P, P)), DataLayout.CELL, "deferred"),
]
n = 16
for bcs, layout, doubling in CASES:
    for strat in ("a2a", "pipelined", "fused", "overlap"):
        for fold in ("pack", "unpack"):
            kw = dict(layout=layout, mesh=mesh, dtype=jnp.float64,
                      doubling=doubling, comm=CommConfig(strat, 2, fold))
            sb = DistributedPoissonSolver((n, n, n), 1.0, bcs,
                                          relayout="baseline", **kw)
            ss = DistributedPoissonSolver((n, n, n), 1.0, bcs,
                                          relayout="scheduled", **kw)
            f = rng.standard_normal(sb.input_shape)
            err = np.max(np.abs(np.asarray(sb.solve(f))
                                - np.asarray(ss.solve(f))))
            assert err == 0.0, (strat, fold, layout.name, doubling, err)
            fb = np.stack([f, -0.5 * f, 2.0 * f, 0.25 * f])
            errb = np.max(np.abs(np.asarray(sb.solve(fb))
                                 - np.asarray(ss.solve(fb))))
            assert errb == 0.0, (strat, fold, "batch", errb)

# lowered-HLO transpose census: the acceptance probe of DESIGN.md #9
P2 = (P, P)
for fold in ("pack", "unpack"):
    ss = DistributedPoissonSolver((16,) * 3, 1.0, (P2, P2, P2), mesh=mesh,
                                  comm=CommConfig("a2a", 1, fold),
                                  relayout="scheduled", lazy_green=True)
    ts = transpose_stats(ss.lower().as_text())
    assert ts["standalone"] == 0, (fold, ts)
    assert ts["collectives"] == 4 and ts["switch_fused"] <= 4, (fold, ts)
    # single-category order (2, 0, 1): both edge adapters are identity
    assert ts["edge"] == 0, (fold, ts)
sb = DistributedPoissonSolver((16,) * 3, 1.0, (P2, P2, P2), mesh=mesh,
                              comm=CommConfig("a2a"), relayout="baseline",
                              order_policy="natural", lazy_green=True)
tb = transpose_stats(sb.lower().as_text())
assert tb["standalone"] > 0, tb   # the census must discriminate

# chunked overlap keeps its interleave AND the zero-standalone property
so = DistributedPoissonSolver((16,) * 3, 1.0, (P2, P2, P2), mesh=mesh,
                              comm=CommConfig("overlap", 4),
                              relayout="scheduled", lazy_green=True)
ts = transpose_stats(so.lower().as_text())
assert ts["standalone"] == 0, ts
assert ts["collectives"] == 16, ts

# the autotune key carries the layout choice: same plan, different
# relayout/order must never replay each other's cached winner
ka = sb.autotune_key()
kb = ss.autotune_key()
assert ka != kb
assert ("relayout", "scheduled") in kb and ("relayout", "baseline") in ka
print("OK")
"""


def test_distributed_scheduled_equals_baseline_and_hlo_census():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_COMM_CACHE", None)
    out = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# -- Pallas fused epilogues vs numpy oracles --------------------------------

def _kernel_launches(jaxpr_text: str) -> int:
    """Pallas kernel launches in a printed jaxpr: each launch carries a
    Mosaic and an interpreted lowering (``repro.kernels.platform``), and
    only the interpreted one is ``interpret=True``."""
    return jaxpr_text.count("interpret=True")


@pytest.mark.parametrize("n,start", [(16, 0), (64, 1), (128, 5)])
def test_rfft_twiddle_matches_numpy(n, start):
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    x = rng.standard_normal((7, n)).astype(np.float32)
    k = n // 2 - start
    a = rng.standard_normal(k)
    b = rng.standard_normal(k)
    got = np.asarray(ops.rfft_twiddle(jnp.asarray(x), a, b, start=start))
    F = np.fft.fft(x, axis=-1)
    want = a * F.real[:, start:start + k] + b * F.imag[:, start:start + k]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_rfft_twiddle_pruned_zero_tail():
    """pad_to composes the Hockney skip-zero first stage with the fused
    post-twiddle epilogue."""
    from repro.kernels import ops
    rng = np.random.default_rng(1)
    n = 32
    x = rng.standard_normal((5, n)).astype(np.float32)
    a = rng.standard_normal(n + 1)
    b = rng.standard_normal(n + 1)
    got = np.asarray(ops.rfft_twiddle(jnp.asarray(x), a, b, pad_to=2 * n))
    F = np.fft.fft(np.concatenate([x, np.zeros_like(x)], axis=-1), axis=-1)
    want = a * F.real[:, :n + 1] + b * F.imag[:, :n + 1]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("batched", [False, True])
def test_fft_green_epilogues_match_numpy(batched):
    from repro.kernels import ops
    rng = np.random.default_rng(2)
    n, rows = 32, 6
    B = 3 if batched else 1
    z = (rng.standard_normal((B * rows, n))
         + 1j * rng.standard_normal((B * rows, n))).astype(np.complex64)
    g_full = rng.standard_normal((rows, n)).astype(np.float32)
    g_half = rng.standard_normal((rows, n // 2 + 1)).astype(np.float32)
    got = np.asarray(ops.fft1d_green(jnp.asarray(z), jnp.asarray(g_full)))
    want = np.fft.fft(z, axis=-1) * np.tile(g_full, (B, 1))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    xr = rng.standard_normal((B * rows, n)).astype(np.float32)
    got = np.asarray(ops.rfft_green(jnp.asarray(xr), jnp.asarray(g_half)))
    want = np.fft.rfft(xr, axis=-1) * np.tile(g_half, (B, 1))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_fused_r2r_matches_unfused_and_scipy():
    """dct2/dst2/dct1 through the pallas engine now run the fused
    rfft+twiddle kernel; they must still match scipy and the xla path."""
    import scipy.fft as sfft
    from repro.core import transforms as tr
    from repro.core.engine import TransformEngine
    rng = np.random.default_rng(3)
    eng = TransformEngine("pallas")
    # widths chosen so the fused kernel actually engages: dct2/dst2 extend
    # to 2M (M=32 -> 64), dct1 to 2(M-1) (M=33 -> 64)
    for name, fn, m, sref in (("dct2", tr.dct2, 32, lambda v: sfft.dct(v, 2)),
                              ("dst2", tr.dst2, 32, lambda v: sfft.dst(v, 2)),
                              ("dct1", tr.dct1, 33, lambda v: sfft.dct(v, 1))):
        x = rng.standard_normal((5, m))
        fused = np.asarray(fn(jnp.asarray(x), engine=eng))
        unfused = np.asarray(fn(jnp.asarray(x), engine=None))
        np.testing.assert_allclose(fused, sref(x), rtol=1e-8, atol=1e-8)
        np.testing.assert_allclose(fused, unfused, rtol=1e-8, atol=1e-8)
        # the pallas path must actually be the fused single kernel
        trace = str(jax.make_jaxpr(
            lambda v: fn(v, engine=eng))(jnp.asarray(x)))
        assert _kernel_launches(trace) == 1, name


def test_fwd_last_green_fuses_and_matches_unfused():
    """The schedule-level green fusion hook: fused == transform + multiply,
    and the fused trace contains ONE pallas_call where the unfused path
    has two (FFT then spectral_scale)."""
    plan = make_plan((8,) * 3, 1.0, ((P, P),) * 3)
    sched = build_schedule(plan, "pallas")
    d = plan.order[-1]
    assert sched.can_fuse_green(d)
    rng = np.random.default_rng(4)
    x = jnp.asarray((rng.standard_normal((8, 8, 8))
                     + 1j * rng.standard_normal((8, 8, 8))),
                    dtype=jnp.complex64)
    green = jnp.asarray(rng.standard_normal((8, 8, plan.dirs[d].n_out)),
                        dtype=jnp.float32)
    fused = np.asarray(sched.fwd_last_green(x, d, green))
    unfused = np.asarray(sched.green_multiply(sched.fwd_last(x, d), green))
    np.testing.assert_allclose(fused, unfused, rtol=1e-4, atol=1e-4)
    trace = str(jax.make_jaxpr(
        lambda v: sched.fwd_last_green(v, d, green))(x))
    assert _kernel_launches(trace) == 1


# -- radix-4 Stockham stages ------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8, 32, 128, 512])
def test_radix4_matches_radix2_and_numpy(n):
    from repro.kernels.fft_stockham import fft_stockham, stage_count
    rng = np.random.default_rng(5)
    re = rng.standard_normal((5, n)).astype(np.float32)
    im = rng.standard_normal((5, n)).astype(np.float32)
    want = np.fft.fft(re + 1j * im, axis=-1)
    tol = 1e-3 * np.sqrt(n)
    for mr in (2, 4):
        gr, gi = fft_stockham(jnp.asarray(re), jnp.asarray(im), max_radix=mr)
        np.testing.assert_allclose(np.asarray(gr), want.real, atol=tol)
        np.testing.assert_allclose(np.asarray(gi), want.imag, atol=tol)
        br, bi = fft_stockham(jnp.asarray(want.real.astype(np.float32)),
                              jnp.asarray(want.imag.astype(np.float32)),
                              inverse=True, max_radix=mr)
        np.testing.assert_allclose(np.asarray(br), re, atol=1e-3)
    k = int(np.log2(n))
    assert stage_count(n, 2) == k
    assert stage_count(n, 4) == k // 2 + k % 2


def test_radix4_pruned_zero_tail():
    from repro.kernels.fft_stockham import fft_stockham
    rng = np.random.default_rng(6)
    n = 64
    re = rng.standard_normal((4, n)).astype(np.float32)
    im = rng.standard_normal((4, n)).astype(np.float32)
    zre = np.concatenate([re, np.zeros_like(re)], axis=-1)
    zim = np.concatenate([im, np.zeros_like(im)], axis=-1)
    want = np.fft.fft(zre + 1j * zim, axis=-1)
    gr, gi = fft_stockham(jnp.asarray(re), jnp.asarray(im), pad_to=2 * n)
    np.testing.assert_allclose(np.asarray(gr), want.real, atol=1e-3 * n**0.5)
    np.testing.assert_allclose(np.asarray(gi), want.imag, atol=1e-3 * n**0.5)
