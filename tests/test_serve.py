"""Serving correctness net (DESIGN.md #11).

The server is a concurrency layer over an already-validated solve, so
every test here reduces to one invariant: serving must change WHEN and
HOW solves run, never WHAT they compute.

* coalesced batched solve bit-exact (xla) vs the same requests solved
  individually, across mixed tenants and padded batch ranks;
* the latency deadline flushes a partial batch (a lone request is never
  held hostage waiting for co-batchable traffic);
* requests with different plan keys never coalesce, and each key's
  responses match its own plan's solve (mixed-key isolation);
* the warm pool evicts LRU plans under memory-budget pressure -- also
  from the module solver LRU -- and an evicted key transparently
  rebuilds;
* a fault-injected request degrades through the PR-6 ladder without
  poisoning co-batched tenants: every co-batched response stays
  bit-exact and the degradation records surface per tenant;
* admission: backpressure rejections, bad-shape rejections, and
  submit-after-stop.
"""
import threading

import numpy as np
import pytest

from repro.core.bc import BCType, DataLayout
from repro.core.solver import clear_solver_cache, get_solver, \
    solver_cache_info
from repro.runtime import faults
from repro.serve import (AdmissionError, PlanSpec, PoissonServer,
                         ServerClosed, default_batch_ranks, percentile)

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB
N = 8
UNB3 = ((U, U),) * 3
PER3 = ((P, P),) * 3


def _spec(bcs=UNB3, **kw):
    return PlanSpec(shape=(N, N, N), bcs=bcs, **kw)


def _rhs(b, seed=0, grid=(N, N, N)):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(grid) for _ in range(b)]


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_solver_cache()
    yield
    clear_solver_cache()


# -- coalescing correctness --------------------------------------------------

def test_coalesced_batch_bitexact_vs_individual():
    spec = _spec()
    fs = _rhs(7, seed=1)                    # 7 -> one full 4-batch + 3->4 pad
    with PoissonServer(max_batch=4, max_delay_ms=2) as srv:
        futs = [srv.submit(f, spec, tenant=f"t{i % 3}")
                for i, f in enumerate(fs)]
        res = [f.result(timeout=120) for f in futs]
    assert any(r.batch_size > 1 for r in res), "nothing coalesced"
    s = get_solver((N, N, N), 1.0, UNB3)
    # the spec's solver and a direct call's are one instance
    assert spec.build() is s
    for f, r in zip(fs, res):
        want = np.asarray(s.solve(f))
        # same plan, same xla pipeline, batch rows are independent: the
        # served (coalesced, possibly zero-padded) answer is BIT-exact
        np.testing.assert_array_equal(want, r.u)


def test_padding_to_nearest_rank():
    spec = _spec(bcs=PER3)
    with PoissonServer(max_batch=8, max_delay_ms=1) as srv:
        futs = [srv.submit(f, spec) for f in _rhs(3, seed=2)]
        res = [f.result(timeout=120) for f in futs]
    ranks = default_batch_ranks(8)
    for r in res:
        assert r.padded_to in ranks
        assert r.padded_to >= r.batch_size
    # 3 live rhs either ran as one deadline batch padded 3->4, or split
    batch = [r for r in res if r.batch_size == 3]
    if batch:
        assert batch[0].padded_to == 4


def test_deadline_flush_releases_partial_batch():
    spec = _spec(bcs=PER3)
    with PoissonServer(max_batch=64, max_delay_ms=5) as srv:
        [f] = _rhs(1, seed=3)
        fut = srv.submit(f, spec)
        r = fut.result(timeout=120)         # far below max_batch: only the
        assert r.batch_size == 1            # deadline can have flushed it
        assert srv.server_stats()["deadline_flushes"] >= 1


def test_mixed_plan_keys_never_coalesce():
    spec_a = _spec(bcs=UNB3)
    spec_b = _spec(bcs=PER3)
    spec_c = _spec(bcs=((E, E), (O, E), (P, P)), layout=DataLayout.NODE)
    grids = {spec_a.key(): (N, N, N), spec_b.key(): (N, N, N),
             spec_c.key(): (N + 1, N + 1, N + 1)}
    with PoissonServer(max_batch=8, max_delay_ms=10) as srv:
        futs = []
        for i, spec in enumerate([spec_a, spec_b, spec_c] * 3):
            [f] = _rhs(1, seed=10 + i, grid=grids[spec.key()])
            futs.append((spec, f, srv.submit(f, spec, tenant=f"t{i % 2}")))
        res = [(spec, f, fut.result(timeout=240)) for spec, f, fut in futs]
    for spec, f, r in res:
        want = np.asarray(spec.build().solve(f))
        np.testing.assert_array_equal(want, r.u)   # no cross-plan bleed
        assert r.batch_size <= 3                   # only same-key coalesce


# -- warm pool ---------------------------------------------------------------

def test_warm_pool_evicts_under_memory_pressure():
    # three plan keys, budget sized to hold roughly one: serving all three
    # must evict (pool LRU + module LRU) yet keep answering correctly
    specs = [_spec(bcs=UNB3), _spec(bcs=PER3),
             _spec(bcs=((E, E), (O, O), (E, E)))]
    one_plan_mb = 0.02                      # 8^3 f64 green ~4KB; tiny budget
    with PoissonServer(max_batch=2, max_delay_ms=1,
                       memory_budget_mb=one_plan_mb) as srv:
        for rep in range(2):
            for i, spec in enumerate(specs):
                [f] = _rhs(1, seed=20 + i)
                r = srv.solve(f, spec, timeout=240)
                want = np.asarray(spec.build().solve(f))
                np.testing.assert_array_equal(want, r.u)
        info = srv.server_stats()["pool"]
    assert info["evictions"] >= 1
    assert info["budget_bytes"] == int(one_plan_mb * 1e6)
    # eviction reached through to the module LRU too
    assert solver_cache_info()["evictions"] >= 1


def test_warm_pool_unbounded_keeps_plans_resident():
    specs = [_spec(bcs=UNB3), _spec(bcs=PER3)]
    with PoissonServer(max_batch=2, max_delay_ms=1) as srv:
        for spec in specs * 2:
            [f] = _rhs(1, seed=31)
            srv.solve(f, spec, timeout=240)
        info = srv.server_stats()["pool"]
    assert info["evictions"] == 0
    assert info["size"] == 2
    assert info["hits"] >= 2                # second round hit warm plans


# -- resilience --------------------------------------------------------------

def test_faulted_request_degrades_without_poisoning_cobatched():
    """One tenant's request arms a hard fault at solve dispatch; the PR-6
    ladder steps relayout scheduled->baseline (bit-exact on xla), the
    whole co-batched solve still returns the right answer for EVERY
    tenant, and only that batch carries degradation records."""
    spec = _spec()
    fs = _rhs(4, seed=4)
    plan = faults.FaultPlan([{"kind": "error", "stage": "dist.dispatch",
                              "count": 1}])
    with PoissonServer(max_batch=4, max_delay_ms=50) as srv:
        futs = [srv.submit(f, spec, tenant=f"t{i}",
                           fault_plan=plan if i == 2 else None)
                for i, f in enumerate(fs)]
        res = [f.result(timeout=240) for f in futs]
        tstats = srv.tenant_stats()
    assert [r.batch_size for r in res] == [4, 4, 4, 4]
    assert plan.log, "armed fault never fired"
    # the ladder downgraded exactly once and every tenant saw the record
    for r in res:
        assert len(r.degradations) == 1
        assert r.degradations[0]["action"] == "relayout:scheduled->baseline"
    for i in range(4):
        assert len(tstats[f"t{i}"]["degradations"]) == 1
    # ...and nobody's answer was poisoned: baseline relayout is bit-exact
    s = get_solver((N, N, N), 1.0, UNB3)
    for f, r in zip(fs, res):
        np.testing.assert_array_equal(np.asarray(s.solve(f)), r.u)


def test_faulted_request_does_not_degrade_clean_warm_plan():
    """The armed batch runs on a fault-token shadow solver: the clean warm
    plan keeps its scheduled relayout for later traffic."""
    spec = _spec(bcs=PER3)
    plan = faults.FaultPlan([{"kind": "error", "stage": "dist.dispatch",
                              "count": 1}])
    with PoissonServer(max_batch=1, max_delay_ms=1) as srv:
        [f0] = _rhs(1, seed=5)
        r_clean0 = srv.solve(f0, spec, timeout=240)
        r_faulted = srv.submit(f0, spec, fault_plan=plan).result(timeout=240)
        r_clean1 = srv.solve(f0, spec, timeout=240)
    assert r_faulted.degradations and not r_clean0.degradations \
        and not r_clean1.degradations
    np.testing.assert_array_equal(r_clean0.u, r_faulted.u)
    np.testing.assert_array_equal(r_clean0.u, r_clean1.u)


# -- admission + lifecycle ---------------------------------------------------

def test_admission_rejects_bad_shape_and_counts_it():
    spec = _spec()
    with PoissonServer(max_batch=2, max_delay_ms=1) as srv:
        with pytest.raises(AdmissionError, match="does not match"):
            srv.submit(np.zeros((N, N)), spec, tenant="short")
        tstats = srv.tenant_stats()
    assert tstats["short"]["rejected"] == 1
    assert srv.server_stats()["rejected"] == 1


def test_submit_after_stop_raises_server_closed():
    spec = _spec(bcs=PER3)
    srv = PoissonServer(max_batch=2, max_delay_ms=1).start()
    [f] = _rhs(1, seed=6)
    srv.solve(f, spec, timeout=240)
    srv.stop()
    with pytest.raises(ServerClosed):
        srv.submit(f, spec)


def test_backpressure_rejects_beyond_max_pending():
    spec = _spec(bcs=PER3)
    srv = PoissonServer(max_batch=4, max_delay_ms=10_000, max_pending=3)
    srv.start()
    try:
        fs = _rhs(5, seed=7)
        futs = [srv.submit(f, spec) for f in fs[:3]]
        with pytest.raises(AdmissionError, match="backpressure"):
            srv.submit(fs[3], spec)
    finally:
        srv.stop()                          # drain flushes the 3 pending
    assert all(f.result(timeout=240).batch_size == 3 for f in futs)


def test_stop_drain_serves_everything():
    spec = _spec(bcs=PER3)
    srv = PoissonServer(max_batch=8, max_delay_ms=10_000).start()
    futs = [srv.submit(f, spec) for f in _rhs(3, seed=8)]
    srv.stop(drain=True)                    # deadline far away: drain flush
    assert all(f.result(timeout=1).u.shape == (N, N, N) for f in futs)
    assert srv.server_stats()["completed"] == 3


def test_drain_deadline_fails_wedged_requests():
    """One wedged solve (a stalled collective, modelled by a ``stall``
    fault sleeping 60s inside dispatch) must not hang ``stop(drain=True)``
    forever: the deadline expires, every unserved request fails with a
    position-stamped ``ServerClosed``, the wedged worker thread is
    abandoned, and shutdown returns in bounded time."""
    import time
    spec = _spec(bcs=PER3)
    plan = faults.FaultPlan([{"kind": "stall", "stage": "dist.dispatch",
                              "seconds": 60.0}])
    srv = PoissonServer(max_batch=1, max_delay_ms=1).start()
    fs = _rhs(3, seed=9)
    wedged = srv.submit(fs[0], spec, fault_plan=plan)
    # let the wedged batch reach the worker so the deadline is the only
    # way out, then pile clean requests behind it (workers=1)
    deadline = time.monotonic() + 10
    while srv.server_stats()["batches"] < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    stuck = [srv.submit(f, spec) for f in fs[1:]]
    t0 = time.monotonic()
    srv.stop(drain=True, timeout=1.0)
    assert time.monotonic() - t0 < 30, "drain deadline did not bound stop"
    positions = []
    for f in [wedged] + stuck:
        with pytest.raises(ServerClosed) as ei:
            f.result(timeout=1)
        assert "drain deadline" in str(ei.value)
        positions.append(ei.value.queue_position)
    # every victim got a distinct 1-based queue position, in-flight first
    assert sorted(positions) == [1, 2, 3], positions
    assert positions[0] == 1, "wedged in-flight request must rank first"
    st = srv.server_stats()
    assert st["drain_timeouts"] == 3
    assert st["failed"] >= 3 and st.get("abandoned_threads", 0) >= 1
    # a stopped server still refuses new work cleanly
    with pytest.raises(ServerClosed):
        srv.submit(fs[0], spec)


# -- stats -------------------------------------------------------------------

def test_tenant_stats_percentiles_and_occupancy():
    spec = _spec(bcs=PER3)
    with PoissonServer(max_batch=2, max_delay_ms=2) as srv:
        futs = [srv.submit(f, spec, tenant="solo") for f in _rhs(6, seed=9)]
        [f.result(timeout=240) for f in futs]
        t = srv.tenant_stats()["solo"]
    assert t["served"] == 6
    assert t["p50_ms"] <= t["p95_ms"] <= t["p99_ms"]
    assert 1 <= t["mean_batch_occupancy"] <= 2


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 95) == 95
    assert percentile(xs, 99) == 99
    assert percentile([7.0], 99) == 7.0


# -- threaded multi-tenant soak (the acceptance harness in miniature) --------

def test_threaded_tenants_mixed_keys_all_bitexact():
    specs = [_spec(bcs=UNB3), _spec(bcs=PER3)]
    n_tenants, per_tenant = 8, 3
    results = {}
    errors = []

    def tenant(i):
        try:
            rng = np.random.default_rng(100 + i)
            spec = specs[i % 2]
            out = []
            for k in range(per_tenant):
                f = rng.standard_normal((N, N, N))
                r = srv.solve(f, spec, tenant=f"t{i}", timeout=240)
                out.append((f, r))
            results[i] = out
        except Exception as e:  # noqa: BLE001 -- collected for the assert
            errors.append((i, e))

    with PoissonServer(max_batch=4, max_delay_ms=5) as srv:
        threads = [threading.Thread(target=tenant, args=(i,))
                   for i in range(n_tenants)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.server_stats()
    assert not errors, errors
    assert stats["completed"] == n_tenants * per_tenant
    refs = {spec.key(): spec.build() for spec in specs}
    for i, out in results.items():
        s = refs[specs[i % 2].key()]
        for f, r in out:
            np.testing.assert_array_equal(np.asarray(s.solve(f)), r.u)
