"""The MXU route of the DFT directions: pruned DFT-matrix products.

On the TPU a DFT direction of a routed length runs as float32 products
against plan-time DFT matrices cut to the live rows and columns
(``TransformEngine.mxu_dft``).  The products themselves are
platform-agnostic, so they are checked here on the CPU against
``numpy.fft`` to float32 rounding; the routing rule is checked for every
input it reads (platform, dtype, Stockham priority, length); and a solve
with a TPU engine traced on the CPU must run every transform stage on the
route, at ``Precision.HIGHEST``, with no FFT left.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import transforms as tr
from repro.core.bc import BCType, DataLayout
from repro.core.engine import TransformEngine, build_schedule, stage_map
from repro.core.solver import PoissonSolver, make_plan

U, E, O = BCType.UNB, BCType.EVEN, BCType.ODD
REL = 2e-6          # float32 rounding of a length <= 1024 product


def _rel(got, want):
    got = np.asarray(got)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def _complex(rng, shape):
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _case(kind, n, n_in, lead, rng):
    """(routed function, its input, the numpy.fft answer in float64)."""
    if kind == "c2c_fwd":
        x = _complex(rng, lead + (n_in,))
        return (lambda v: tr.mxu_dft_forward(v, tr.dft_matrices(
            "c2c", n, n_in)), x, np.fft.fft(x.astype(np.complex128), n=n))
    if kind == "r2c_fwd":
        x = rng.standard_normal(lead + (n_in,)).astype(np.float32)
        return (lambda v: tr.mxu_dft_forward(v, tr.dft_matrices(
            "r2c", n, n_in)), x, np.fft.rfft(x.astype(np.float64), n=n))
    if kind == "c2c_inv":
        y = _complex(rng, lead + (n,))
        return (lambda v: tr.mxu_dft_backward(v, tr.dft_matrices(
            "c2c", n, n_in)), y,
            np.fft.ifft(y.astype(np.complex128))[..., :n_in])
    y = _complex(rng, lead + (n // 2 + 1,))
    return (lambda v: tr.mxu_dft_backward(v, tr.dft_matrices(
        "r2c", n, n_in)), y,
        np.fft.irfft(y.astype(np.complex128), n=n)[..., :n_in])


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("kind", ["c2c_fwd", "r2c_fwd", "c2c_inv",
                                  "c2r_inv"])
@pytest.mark.parametrize("live", ["half", "half+1", "full"])
@pytest.mark.parametrize("n", [128, 256, 512, 1024])
def test_products_match_numpy_fft(n, live, kind, batched):
    """Forward from ``n_in`` live inputs of a length-``n`` transform, or
    inverse keeping the first ``n_in`` outputs, equals ``numpy.fft`` on the
    zero-extended input (or the cropped output) to float32 rounding."""
    n_in = {"half": n // 2, "half+1": n // 2 + 1, "full": n}[live]
    rng = np.random.default_rng(n * 7 + n_in)
    lead = (2, 3, 5) if batched else (7,)
    fn, x, want = _case(kind, n, n_in, lead, rng)
    got = jax.jit(fn)(jnp.asarray(x))
    assert got.shape == want.shape
    assert got.dtype == (jnp.float32 if kind == "c2r_inv"
                         else jnp.complex64)
    assert _rel(got, want) <= REL


@pytest.mark.parametrize("n", [128, 512])
def test_c2r_ignores_dc_and_nyquist_imaginary_parts(n):
    """Like ``irfft``, the c2r product drops the imaginary parts of the DC
    and Nyquist bins: the matrices are exact at the quarter turns."""
    m = tr.dft_matrices("r2c", n, n // 2 + 1)
    assert np.all(m["bwd"][[0, n // 2]].imag == 0)
    y = np.zeros((1, n // 2 + 1), np.complex64)
    y[0, 0] = 1j
    y[0, -1] = 1j
    assert np.all(np.asarray(tr.mxu_dft_backward(jnp.asarray(y), m)) == 0)


def test_matrices_use_the_exact_index_reduction():
    """``F[j, k] = e^{-2 pi i ((j k) mod N) / N}``: every entry is one of
    the N roots, to float32 rounding of the float64 root."""
    n, n_in = 512, 257
    m = tr.dft_matrices("c2c", n, n_in)
    j, k = np.meshgrid(np.arange(n_in), np.arange(n), indexing="ij")
    want = np.exp(-2j * np.pi * ((j * k) % n) / n)
    assert m["fwd"].dtype == np.complex64 and m["fwd"].shape == (n_in, n)
    assert np.max(np.abs(m["fwd"] - want)) < 1e-7
    assert m["bwd"].shape == (n, n_in)
    assert np.max(np.abs(m["bwd"] * n - np.conj(want.T))) < 1e-7


def test_routing_rule():
    """On the TPU for float32/complex64 data and the table's lengths; the
    CPU, float64 and lengths outside the table keep ``jnp.fft``; the
    Stockham kernel's lengths stay with it on the Pallas engine."""
    cpu = TransformEngine("xla", platform="cpu")
    tpu = TransformEngine("xla", platform="tpu")
    pallas = TransformEngine("pallas", platform="tpu")
    for n in tr.MXU_DFT_LENGTHS:
        assert not cpu.mxu_dft(n, jnp.complex64)
        assert tpu.mxu_dft(n, jnp.float32) and tpu.mxu_dft(n, jnp.complex64)
        assert not tpu.mxu_dft(n, jnp.float64)
        assert not tpu.mxu_dft(n, jnp.complex128)
        assert pallas.mxu_dft(n, jnp.complex64) == (not pallas.kernel_fft(n))
    for n in (64, 100, 2048):
        assert n not in tr.MXU_DFT_LENGTHS
        assert not tpu.mxu_dft(n, jnp.complex64)
    assert pallas.kernel_fft(512) and not pallas.mxu_dft(512, jnp.complex64)


def test_schedule_builds_matrices_for_routed_dft_directions():
    """``build_schedule`` hands the routed DFT directions their matrices
    (cut to the live extent) and gives symmetric directions and other
    platforms none."""
    plan = make_plan((256,) * 3, 1.0, ((U, U),) * 3, DataLayout.NODE)
    tpu = build_schedule(plan, TransformEngine("xla", platform="tpu"))
    shapes = {p.dft: m["fwd"].shape
              for p, m in zip(plan.dirs, tpu.dft_mats)}
    assert shapes == {"r2c": (257, 257), "c2c": (257, 512)}
    cpu = build_schedule(plan, TransformEngine("xla", platform="cpu"))
    assert cpu.dft_mats == (None,) * 3
    mixed = make_plan((256,) * 3, 1.0, ((E, O), (U, U), (U, U)),
                      DataLayout.NODE)
    sched = build_schedule(mixed, TransformEngine("xla", platform="tpu"))
    assert sched.dft_mats[0] is None
    assert all(m is not None for m in sched.dft_mats[1:])


def _dots(jaxpr, prefix=""):
    """(name stack, params) of every dot_general, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        stack = prefix + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "dot_general":
            out.append((stack, eqn.params))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out.extend(_dots(sub, stack))
    return out


@pytest.mark.parametrize("relayout,doubling", [
    ("scheduled", "deferred"), ("baseline", "deferred"),
    ("scheduled", "upfront")])
def test_tpu_engine_solve_routes_every_stage(monkeypatch, relayout,
                                             doubling):
    """Case B (unbounded, NODE) at n = 64, FFT length 128, with a TPU
    engine traced on the CPU and 128 added to the routed lengths: the
    solve agrees with the ``jnp.fft`` route to 1e-5 (pruned and dense
    doubling alike), every transform stage reads "mxu", no FFT is left,
    and every routed product runs at ``Precision.HIGHEST``."""
    monkeypatch.setattr(tr, "MXU_DFT_LENGTHS", (128,))
    kw = dict(layout=DataLayout.NODE, relayout=relayout, doubling=doubling)
    mxu = PoissonSolver((64,) * 3, 1.0, ((U, U),) * 3,
                        engine=TransformEngine("xla", platform="tpu"), **kw)
    ref = PoissonSolver((64,) * 3, 1.0, ((U, U),) * 3,
                        engine=TransformEngine("xla", platform="cpu"), **kw)
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.standard_normal(mxu.input_shape), jnp.float32)
    got, want = np.asarray(mxu.solve(f)), np.asarray(ref.solve(f))
    assert _rel(got, want) <= 1e-5

    jaxpr = jax.make_jaxpr(mxu._solve)(f)
    stages = stage_map(jaxpr)
    assert {k: v for k, v in stages.items() if k != "green"} == {
        f"{s}.{d}": "mxu" for s in ("fwd", "bwd") for d in range(3)}
    assert " fft[" not in str(jaxpr) and "fft(" not in str(jaxpr)
    routed = [p for stack, p in _dots(jaxpr.jaxpr)
              if "mxu_dft" in stack.split("/")]
    assert routed
    hi = jax.lax.Precision.HIGHEST
    for p in routed:
        assert tuple(p["precision"]) == (hi, hi), p["precision"]
    assert "xla" in set(stage_map(jax.make_jaxpr(ref._solve)(f)).values())


def test_float64_solve_keeps_fft_on_a_tpu_engine(monkeypatch):
    """float64 data is not routed, even on a TPU engine: the stages read
    "xla" and the jaxpr keeps its FFTs."""
    monkeypatch.setattr(tr, "MXU_DFT_LENGTHS", (32,))
    s = PoissonSolver((16,) * 3, 1.0, ((U, U),) * 3, layout=DataLayout.NODE,
                      engine=TransformEngine("xla", platform="tpu"))
    f = jnp.zeros(s.input_shape, jnp.float64)
    stages = stage_map(jax.make_jaxpr(s._solve)(f))
    assert set(stages.values()) == {"xla"}
