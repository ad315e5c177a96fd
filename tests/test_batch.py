"""Batched multi-RHS execution + the global plan cache.

Property-based (hypothesis, scipy-free): ``solve((B, *grid))`` must equal
the stack of B single solves to last-ulp tolerance (the batched pipeline
runs the same transform sequence over bigger row batches -- no
reassociation in our code; the tolerance only allows a backend FFT to
dispatch batched rows to a differently-rounded kernel), for random batch
sizes, BC mixes, layouts and Green kinds on both engines.  Plus unit
tests for the ``get_solver`` LRU: hits, eviction order, capacity, and
distinct keys.
"""
import numpy as np
import pytest
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI images without hypothesis: deterministic local shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.bc import BCType, DataLayout
from repro.core.biot_savart import BiotSavartSolver
from repro.core.green import GreenKind
from repro.core.solver import (get_solver, clear_solver_cache,
                               solver_cache_info, set_solver_cache_capacity)
from repro.distributed import pencil
from repro.distributed.pencil import DistributedPoissonSolver

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB

# one direction's BC pair: symmetric, periodic, unbounded and semi mixes
DIR_BCS = [(E, E), (O, E), (O, O), (P, P), (U, U), (U, E), (O, U)]


def _stacked_reference(s, fb):
    return np.stack([np.asarray(s.solve(fb[i])) for i in range(fb.shape[0])])


@settings(max_examples=12, deadline=None)
@given(
    b=st.integers(min_value=1, max_value=4),
    bc0=st.sampled_from(DIR_BCS), bc1=st.sampled_from(DIR_BCS),
    bc2=st.sampled_from(DIR_BCS),
    layout=st.sampled_from([DataLayout.CELL, DataLayout.NODE]),
    green=st.sampled_from([GreenKind.CHAT2, GreenKind.HEJ2]),
    seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
)
def test_batched_solve_equals_stacked_xla(b, bc0, bc1, bc2, layout, green,
                                          seed):
    n = 8
    s = get_solver((n, n, n), 1.0, (bc0, bc1, bc2), layout=layout,
                   green_kind=green)
    rng = np.random.default_rng(seed)
    fb = rng.standard_normal((b,) + s.input_shape)
    want = _stacked_reference(s, fb)
    got = np.asarray(s.solve(fb))
    # identical op sequence over bigger row batches; tolerance only covers
    # backend FFTs that round batched rows differently (bit-exact on CPU)
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-13)


@settings(max_examples=4, deadline=None)
@given(
    b=st.integers(min_value=2, max_value=3),
    bc0=st.sampled_from([(E, E), (U, U), (P, P)]),
    layout=st.sampled_from([DataLayout.CELL, DataLayout.NODE]),
    seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
)
def test_batched_solve_equals_stacked_pallas(b, bc0, layout, seed):
    n = 8
    s = get_solver((n, n, n), 1.0, (bc0, (O, E), (P, P)), layout=layout,
                   engine="pallas")
    rng = np.random.default_rng(seed)
    fb = rng.standard_normal((b,) + s.input_shape)
    want = _stacked_reference(s, fb)
    got = np.asarray(s.solve(fb))
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_batched_rejects_bad_ranks():
    s = DistributedPoissonSolver((8, 8, 8), 1.0, ((E, E), (E, E), (E, E)))
    with pytest.raises(AssertionError):
        s.solve(np.zeros((8, 8)))               # rank too low
    with pytest.raises(AssertionError):
        s.solve(np.zeros((2, 2, 8, 8, 8)))      # two batch axes
    with pytest.raises(AssertionError):
        s.solve(np.zeros((2, 8, 8, 9)))         # wrong grid


def test_batched_biot_savart_uniform_plans():
    """Uniform-BC Biot-Savart runs the single batched 3-component pipeline
    and matches the sequential per-component implementation."""
    import jax
    n = 8
    UU = [(U, U)] * 3
    s = BiotSavartSolver((n, n, n), 1.0, [UU, UU, UU],
                         layout=DataLayout.NODE)
    assert s.batched
    rng = np.random.default_rng(0)
    f = rng.standard_normal(s.input_shape)
    got = np.asarray(s.solve(f))
    want = np.asarray(jax.jit(s._solve_impl)(jnp.asarray(f)))
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-13)


def test_non_uniform_biot_savart_stays_sequential():
    BCS = [[(U, U), (U, U), (O, O)],
           [(U, U), (U, U), (O, O)],
           [(U, U), (U, U), (E, E)]]
    s = BiotSavartSolver((8, 8, 8), 1.0, BCS, layout=DataLayout.NODE)
    assert not s.batched


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_cache():
    clear_solver_cache()
    old = solver_cache_info()["capacity"]
    yield
    set_solver_cache_capacity(old)
    clear_solver_cache()


def test_plan_cache_hit_returns_same_instance(fresh_cache):
    kw = dict(layout=DataLayout.CELL, green_kind=GreenKind.CHAT2)
    s1 = get_solver((8, 8, 8), 1.0, ((E, E), (E, E), (E, E)), **kw)
    s2 = get_solver((8, 8, 8), 1.0, ((E, E), (E, E), (E, E)), **kw)
    assert s1 is s2
    info = solver_cache_info()
    assert info["hits"] == 1 and info["misses"] == 1 and info["size"] == 1


def test_plan_cache_distinct_keys_miss(fresh_cache):
    base = ((8, 8, 8), 1.0, ((E, E), (E, E), (E, E)))
    s0 = get_solver(*base)
    variants = [
        get_solver((8, 8, 9), 1.0, ((E, E), (E, E), (E, E))),
        get_solver((8, 8, 8), 2.0, ((E, E), (E, E), (E, E))),
        get_solver((8, 8, 8), 1.0, ((O, O), (E, E), (E, E))),
        get_solver(*base, layout=DataLayout.NODE),
        get_solver(*base, green_kind=GreenKind.HEJ2),
        get_solver(*base, eps_factor=3.0),
        get_solver(*base, engine="pallas"),
    ]
    assert all(v is not s0 for v in variants)
    assert len({id(v) for v in variants}) == len(variants)
    assert solver_cache_info()["misses"] == 1 + len(variants)
    assert solver_cache_info()["hits"] == 0


def test_plan_cache_lru_eviction(fresh_cache):
    set_solver_cache_capacity(2)
    bcs = ((E, E), (E, E), (E, E))
    s_a = get_solver((8, 8, 8), 1.0, bcs)
    s_b = get_solver((8, 8, 9), 1.0, bcs)
    # touch A so B is the least recently used
    assert get_solver((8, 8, 8), 1.0, bcs) is s_a
    s_c = get_solver((8, 8, 10), 1.0, bcs)         # evicts B
    info = solver_cache_info()
    assert info["size"] == 2 and info["evictions"] == 1
    assert get_solver((8, 8, 8), 1.0, bcs) is s_a  # A survived
    assert get_solver((8, 8, 10), 1.0, bcs) is s_c
    assert get_solver((8, 8, 9), 1.0, bcs) is not s_b   # B was evicted


def test_plan_cache_capacity_shrink_evicts(fresh_cache):
    set_solver_cache_capacity(4)
    bcs = ((E, E), (E, E), (E, E))
    for k in range(4):
        get_solver((8, 8, 8 + k), 1.0, bcs)
    assert solver_cache_info()["size"] == 4
    set_solver_cache_capacity(1)
    info = solver_cache_info()
    assert info["size"] == 1 and info["evictions"] == 3
    # the survivor is the most recently used entry
    assert solver_cache_info()["hits"] == 0
    get_solver((8, 8, 11), 1.0, bcs)
    assert solver_cache_info()["hits"] == 1


def test_plan_cache_solver_still_correct(fresh_cache):
    """Cache round trip must not corrupt the solver: cached instance
    reproduces a freshly constructed solver's output exactly."""
    bcs = ((E, E), (O, E), (P, P))
    s_cached = get_solver((8, 8, 8), 1.0, bcs)
    s_cached2 = get_solver((8, 8, 8), 1.0, bcs)
    fresh = DistributedPoissonSolver((8, 8, 8), 1.0, bcs)
    rng = np.random.default_rng(3)
    f = rng.standard_normal(fresh.input_shape)
    np.testing.assert_allclose(np.asarray(s_cached2.solve(f)),
                               np.asarray(fresh.solve(f)),
                               rtol=1e-13, atol=1e-13)
    assert s_cached is s_cached2


# ---------------------------------------------------------------------------
# single-flight construction (the serve thundering herd)
# ---------------------------------------------------------------------------

def test_single_flight_one_construction_per_key(fresh_cache, monkeypatch):
    """16 threads missing the same key concurrently must construct the
    solver exactly ONCE (the others park on the builder and receive the
    same instance); before the single-flight fix the miss path built
    outside the lock, so every thread paid plan+autotune+jit and the last
    insert silently overwrote its 15 siblings."""
    import threading

    built = []
    build_gate = threading.Barrier(16, timeout=60)
    real = pencil.DistributedPoissonSolver

    class Counting(real):
        def __init__(self, *a, **kw):
            built.append(threading.get_ident())
            super().__init__(*a, **kw)

    monkeypatch.setattr(pencil, "DistributedPoissonSolver", Counting)
    bcs = ((E, E), (O, E), (P, P))
    out, errors = [], []

    def worker():
        try:
            build_gate.wait()               # maximize miss concurrency
            out.append(get_solver((8, 8, 8), 1.0, bcs))
        except Exception as e:  # noqa: BLE001 -- surfaced by the assert
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(built) == 1, f"{len(built)} constructions for one key"
    assert len(out) == 16 and all(s is out[0] for s in out)
    info = solver_cache_info()
    assert info["misses"] == 1
    # a thread that arrives while the build is in flight parks (coalesced);
    # one that arrives after it landed is a plain hit -- either way no
    # second construction happened
    assert info["coalesced"] + info["hits"] == 15


def test_single_flight_failed_build_reraises_everywhere(fresh_cache,
                                                        monkeypatch):
    """A failed construction must re-raise in the builder AND every parked
    waiter, and leave no cache entry (the next call retries cleanly)."""
    import threading

    calls = []
    real = pencil.DistributedPoissonSolver

    class Flaky(real):
        def __init__(self, *a, **kw):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("flaky plan-time failure")
            super().__init__(*a, **kw)

    monkeypatch.setattr(pencil, "DistributedPoissonSolver", Flaky)
    bcs = ((E, E), (E, E), (E, E))
    gate = threading.Barrier(4, timeout=60)
    failures = []

    def worker():
        gate.wait()
        try:
            get_solver((8, 8, 8), 1.0, bcs)
        except RuntimeError:
            failures.append(1)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # exactly one attempt ran (single-flight), and every thread that
    # joined that build saw its failure; late arrivals may have retried
    # and succeeded -- both outcomes are valid, the cache must just not
    # hold a broken entry
    assert failures, "no thread observed the injected build failure"
    assert solver_cache_info()["build_failures"] == 1
    s = get_solver((8, 8, 8), 1.0, bcs)    # clean retry after the failure
    assert s is get_solver((8, 8, 8), 1.0, bcs)
