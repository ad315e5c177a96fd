"""The solver without a mesh: ``DistributedPoissonSolver`` on a 1x1 mesh of
the first device.

``get_solver`` without a mesh builds the same class the benchmark runs on
a given mesh.  Its dtype is the input's (one program and one device Green
per dtype), where a solver on a given mesh casts to float32; its Green's
function reaches the jitted program as an argument, never as a constant
folded into the program; it never consumes the caller's input; and its
answer is the one-chip benchmark solver's.
"""
import json
import math
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.bc import BCType, DataLayout
from repro.core.solver import clear_solver_cache, get_solver
from repro.distributed.pencil import DistributedPoissonSolver

U = BCType.UNB
BCS = ((U, U),) * 3
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _solver(**kw):
    return get_solver((16,) * 3, 1.0, BCS, layout=DataLayout.NODE, **kw)


def _rhs(s, dtype):
    rng = np.random.default_rng(0)
    return rng.standard_normal(s.input_shape).astype(dtype)


def test_meshless_get_solver_is_the_one_class_on_one_device():
    clear_solver_cache()
    s = _solver()
    assert type(s) is DistributedPoissonSolver
    assert s.mesh.axis_names == ("data", "model")
    assert dict(s.mesh.shape) == {"data": 1, "model": 1}
    assert list(s.mesh.devices.flat) == [jax.devices()[0]]
    assert s.engine.platform == jax.devices()[0].platform
    assert s.dtype is None
    # every meshless call keys the cache on mesh=None: one instance
    assert _solver() is s


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_meshless_solve_returns_the_input_dtype(dtype):
    s = DistributedPoissonSolver((16,) * 3, 1.0, BCS,
                                 layout=DataLayout.NODE)
    u = s.solve(_rhs(s, dtype))
    assert u.dtype == dtype and u.shape == s.input_shape
    assert np.isfinite(np.asarray(u)).all()
    # one device Green in the solve's dtype
    assert [g.dtype for g in s._green_dev.values()] == [np.dtype(dtype)]


def test_both_dtypes_on_one_meshless_solver():
    s = DistributedPoissonSolver((16,) * 3, 1.0, BCS,
                                 layout=DataLayout.NODE)
    f = _rhs(s, np.float64)
    u64 = np.asarray(s.solve(f))
    u32 = np.asarray(s.solve(f.astype(np.float32)))
    assert u64.dtype == np.float64 and u32.dtype == np.float32
    assert sorted(str(g.dtype) for g in s._green_dev.values()) == [
        "float32", "float64"]
    # the float32 solve is the float64 one to float32 round-off
    assert np.max(np.abs(u32 - u64)) <= 1e-5 * np.max(np.abs(u64))


def test_a_given_mesh_still_casts_to_float32():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    s = DistributedPoissonSolver((16,) * 3, 1.0, BCS,
                                 layout=DataLayout.NODE, mesh=mesh)
    assert s.dtype == jnp.float32
    assert s.solve(_rhs(s, np.float64)).dtype == np.float32


def test_the_caller_keeps_an_uncommitted_input():
    s = DistributedPoissonSolver((16,) * 3, 1.0, BCS,
                                 layout=DataLayout.NODE)
    f = jnp.asarray(_rhs(s, np.float32))
    u1 = np.asarray(s.solve(f))
    assert not f.is_deleted()
    np.testing.assert_array_equal(np.asarray(s.solve(f)), u1)


@pytest.mark.parametrize("verify", [None, "residual", "abft"])
def test_a_meshless_solve_leaves_committed_inputs_whole(verify):
    """A committed input already in the one-device pencil's blocks (an
    array put on device 0, or the solver's own previous output) is the
    caller's: the solve, its checks and a second solve all read it."""
    s = DistributedPoissonSolver((16,) * 3, 1.0, BCS,
                                 layout=DataLayout.NODE, verify=verify)
    f = jax.device_put(_rhs(s, np.float32), jax.devices()[0])
    assert f.committed
    u = s.solve(f)
    assert not f.is_deleted()
    np.testing.assert_array_equal(np.asarray(s.solve(f)), np.asarray(u))
    # the output fed back in: solve(u) keeps u
    assert u.committed
    want = np.asarray(u)
    s.solve(u)
    assert not u.is_deleted()
    np.testing.assert_array_equal(np.asarray(u), want)
    assert s.stats["degradations"] == [] and s.stats["retries"] == 0


@pytest.mark.parametrize("verify,consumed", [(None, True),
                                             ("residual", False)])
def test_a_given_mesh_consumes_its_input_only_unchecked(verify, consumed):
    """On a given mesh a committed input in the pencil's blocks is the
    buffer the solve donates, unless a health check reads it again."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    s = DistributedPoissonSolver((16,) * 3, 1.0, BCS,
                                 layout=DataLayout.NODE, mesh=mesh,
                                 verify=verify)
    f = jax.device_put(_rhs(s, np.float32), jax.devices()[0])
    s.solve(f)
    assert f.is_deleted() == consumed
    assert s.stats["degradations"] == [] and s.stats["retries"] == 0


def test_a_given_mesh_needs_a_dtype():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    with pytest.raises(AssertionError, match="has a dtype"):
        DistributedPoissonSolver((16,) * 3, 1.0, BCS, mesh=mesh,
                                 dtype=None)


def _constant_sizes(text):
    """Element counts of every constant in a lowered program's text."""
    shapes = re.findall(r"stablehlo\.constant dense<[^\n]*?> : "
                        r"tensor<([0-9x]*)[a-z]\w*>", text)
    return [math.prod(int(d) for d in sh.split("x") if d) for sh in shapes]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_green_is_an_argument_not_a_constant(dtype):
    s = DistributedPoissonSolver((16,) * 3, 1.0, BCS,
                                 layout=DataLayout.NODE)
    size = math.prod(s._green_np.shape)
    text = s.lower(dtype=dtype).as_text()
    assert max(_constant_sizes(text), default=0) < size
    main = text[text.index("func.func public @main"):].split("\n")[0]
    gtype = "x".join(map(str, s._green_np.shape)) + "x" + \
        jnp.dtype(dtype).name.replace("float", "f")
    assert f"tensor<{gtype}>" in main, main
    # the check sees a Green closed over by the program
    g = np.asarray(s._green_np, dtype)
    closed = jax.jit(lambda x: x * jnp.asarray(g)).lower(
        jax.ShapeDtypeStruct(g.shape, dtype)).as_text()
    assert max(_constant_sizes(closed)) == size


def test_meshless_solve_matches_the_benchmark_one_chip_solver(
        monkeypatch):
    """The harness's ``solver_kwargs`` on the one-chip cell's config (cut
    to n = 16), on the harness's 1x1 mesh, against ``get_solver`` with no
    mesh and no dtype: the same field to float32 round-off."""
    monkeypatch.syspath_prepend(BENCH)
    from harness import solver_kwargs
    with open(os.path.join(BENCH, "configs",
                           "caseB-unb-node-256.json")) as fh:
        config = dict(json.load(fh), n=16)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                tuple(config["mesh_axes"]))
    kw = solver_kwargs(config, mesh)
    bench = get_solver(**kw)
    for k in ("mesh", "comm", "dtype"):
        kw.pop(k)
    meshless = get_solver(**kw)
    assert meshless is not bench and meshless.dtype is None
    f = _rhs(bench, np.float32)
    want = np.asarray(bench.solve(f))
    got = np.asarray(meshless.solve(f))
    assert got.dtype == want.dtype == np.float32
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
