"""Chaos suite: deterministic fault injection through the resilience layer.

Covers every fault class in ISSUE 6's acceptance criteria: stage NaN/Inf,
Pallas lowering failure, transient errors, hard faults (terminal
``SolveError``), corrupt autotune cache, torn/truncated checkpoints, and
-- in the 8-device subprocess tests -- comm faults walking the distributed
ladder plus device loss resuming the ``--steps`` loop from a checkpoint on
a shrunken mesh.  Recovered solves are compared BIT-EXACTLY against the
fault-free xla baseline.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core.bc import BCType
from repro.core.comm import CommConfig, autotune_comm, clear_autotune_cache
from repro.core.solver import PoissonSolver
from repro.ckpt import checkpoint as ck
from repro.runtime import faults, health, resilience
from repro.runtime.resilience import SolveError

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB
BCS = ((E, E), (O, E), (P, P))


# -- fault-plan semantics ----------------------------------------------------

def test_fault_spec_after_count():
    plan = faults.FaultPlan([
        dict(kind="error", stage="stage.a", after=1, count=2)])
    with plan:
        faults.fail_point("stage.a")                 # hit 1: skipped (after)
        for _ in range(2):                           # hits 2-3: fire
            with pytest.raises(faults.InjectedFault):
                faults.fail_point("stage.a")
        faults.fail_point("stage.a")                 # count exhausted
        faults.fail_point("stage.b")                 # wrong stage
    faults.fail_point("stage.a")                     # plan deactivated
    assert [e["hit"] for e in plan.log] == [2, 3]


def test_fault_plan_from_env(monkeypatch, tmp_path):
    spec = [dict(kind="error", stage="x")]
    monkeypatch.setenv("REPRO_FAULTS", json.dumps(spec))
    with faults.plan_from_env():
        with pytest.raises(faults.InjectedFault):
            faults.fail_point("x")
    pf = tmp_path / "plan.json"
    pf.write_text(json.dumps(spec))
    monkeypatch.setenv("REPRO_FAULTS", str(pf))
    with faults.plan_from_env():
        with pytest.raises(faults.InjectedFault):
            faults.fail_point("x")
    monkeypatch.delenv("REPRO_FAULTS")
    assert faults.plan_from_env() is None


def test_taint_and_step_matching():
    import jax.numpy as jnp
    with faults.FaultPlan([dict(kind="nan", stage="green")]):
        x = faults.taint("green", jnp.ones((2, 3)))
        assert not bool(jnp.isfinite(x).all())
        assert bool(jnp.isfinite(faults.taint("green", jnp.ones(3))).all())
    with faults.FaultPlan([dict(kind="device_loss", step=3)]) as plan:
        assert not faults.should_fire("device_loss", step=2)
        assert faults.should_fire("device_loss", step=3)
        assert plan.log[0]["step"] == 3


# -- ladder unit behaviour ---------------------------------------------------

def test_ladder_rung_order():
    cfg = {"engine": "pallas", "comm": "overlap",
           "relayout": "scheduled", "doubling": "deferred"}
    trail = []
    while True:
        step = resilience.next_rung(cfg)
        if step is None:
            break
        cfg, action = step
        trail.append(action)
    assert trail == ["engine:pallas->xla", "comm:overlap->pipelined",
                     "comm:pipelined->a2a", "relayout:scheduled->baseline",
                     "doubling:deferred->upfront"]
    # single-process configs have no comm knob: it is skipped, not an error
    cfg = {"engine": "xla", "relayout": "baseline", "doubling": "upfront"}
    assert resilience.next_rung(cfg) is None


def test_transient_retry_then_exhaust():
    calls = {"n": 0}
    cfg = {"engine": "xla", "relayout": "baseline", "doubling": "upfront"}

    def attempt():
        calls["n"] += 1
        raise faults.InjectedFault("s", "error", transient=True)

    stats = {"retries": 0, "degradations": []}
    with pytest.raises(SolveError) as ei:
        resilience.run_with_ladder(
            attempt, config=cfg, reconfigure=lambda c: None, stats=stats,
            policy=resilience.RetryPolicy(retries=3, base_delay=0),
            sleep=lambda s: None)
    assert calls["n"] == 4 and stats["retries"] == 3
    assert ei.value.stage == "s" and not ei.value.degradations


@pytest.mark.parametrize("status,transient", [
    ("UNAVAILABLE", True), ("DEADLINE_EXCEEDED", True), ("ABORTED", True),
    # out of device memory: the same program on the same chip hits it again
    ("RESOURCE_EXHAUSTED", False), ("INTERNAL", False)])
def test_runtime_status_transience(status, transient):
    assert resilience.is_transient(RuntimeError(f"{status}: x")) is transient


def _retry_delays(policy, retries=6):
    """Drive run_with_ladder with always-transient failures and capture
    the backoff delays it would have slept."""
    delays = []
    cfg = {"engine": "xla", "relayout": "baseline", "doubling": "upfront"}

    def attempt():
        raise faults.InjectedFault("s", "error", transient=True)

    with pytest.raises(SolveError):
        resilience.run_with_ladder(
            attempt, config=cfg, reconfigure=lambda c: None,
            stats={"degradations": []}, policy=policy,
            sleep=delays.append)
    return delays


def test_decorrelated_jitter_spreads_retry_storms():
    """Co-batched tenants tripping on the same transient must NOT retry
    in lockstep: seeded decorrelated jitter is deterministic per seed,
    spread across seeds, and bounded by [base, max]; ``jitter="none"``
    restores the fixed doubling schedule."""
    mk = lambda **kw: resilience.RetryPolicy(
        retries=6, base_delay=0.05, max_delay=1.0, **kw)
    fixed = _retry_delays(mk(jitter="none"))
    assert fixed == [0.05, 0.1, 0.2, 0.4, 0.8, 1.0]
    a = _retry_delays(mk(seed=1))
    assert a == _retry_delays(mk(seed=1)), "seeded jitter not reproducible"
    assert all(0.05 <= d <= 1.0 for d in a)
    # default schedule actually jitters: not the doubling ramp, and two
    # tenants with different seeds retry at different times
    assert a != fixed
    others = [_retry_delays(mk(seed=s)) for s in range(2, 8)]
    assert all(o != a for o in others)
    # spread, not clustering: pairwise distinct delays at every step >1
    step1 = {round(d[1], 9) for d in [a] + others}
    assert len(step1) >= 5, f"retry storm not decorrelated: {step1}"


def test_retry_seed_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_RETRY_SEED", "1234")
    a = _retry_delays(resilience.RetryPolicy(retries=5, base_delay=0.05))
    b = _retry_delays(resilience.RetryPolicy(retries=5, base_delay=0.05))
    assert a == b, "$REPRO_RETRY_SEED did not pin the jitter RNG"
    monkeypatch.setenv("REPRO_RETRY_SEED", "99")
    assert _retry_delays(
        resilience.RetryPolicy(retries=5, base_delay=0.05)) != a
    # explicit seed wins over the environment
    monkeypatch.setenv("REPRO_RETRY_SEED", "1234")
    c = _retry_delays(resilience.RetryPolicy(retries=5, base_delay=0.05,
                                             seed=7))
    monkeypatch.delenv("REPRO_RETRY_SEED")
    assert c == _retry_delays(resilience.RetryPolicy(retries=5,
                                                     base_delay=0.05,
                                                     seed=7))


# -- solver-level recovery (single process, bit-exact) -----------------------

def _rhs(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(np.float32)


def test_nan_injection_recovers_bit_exact():
    s0 = PoissonSolver((12, 12, 12), 1.0, BCS, engine="xla")
    f = _rhs(s0.input_shape)
    want = np.asarray(s0.solve(f))
    s = PoissonSolver((12, 12, 12), 1.0, BCS, engine="xla", verify="nan")
    with faults.FaultPlan([dict(kind="nan", stage="green")]) as plan:
        got = np.asarray(s.solve(f))
    assert plan.log, "fault never fired"
    assert s.stats["verify_failures"] == 1
    assert len(s.stats["degradations"]) == 1
    assert s.stats["degradations"][0]["stage"].startswith("verify.nan@")
    assert np.array_equal(got, want)


def test_pallas_lowering_failure_degrades_to_xla():
    want = None
    sx = PoissonSolver((12, 12, 12), 1.0, BCS, engine="xla")
    f = _rhs(sx.input_shape)
    want = np.asarray(sx.solve(f))
    sp = PoissonSolver((12, 12, 12), 1.0, BCS, engine="pallas")
    with faults.FaultPlan([dict(kind="pallas_lowering", stage="pallas.*",
                                count=-1)]):
        got = np.asarray(sp.solve(f))
    acts = [d["action"] for d in sp.stats["degradations"]]
    assert acts == ["engine:pallas->xla"]
    assert sp._cfg["engine"] == "xla"
    assert np.array_equal(got, want)


def test_residual_verify_passes_healthy_and_catches_corruption():
    n = 16
    h = 1.0 / n
    pts = (np.arange(n) + 0.5) * h
    x, y, z = np.meshgrid(pts, pts, pts, indexing="ij")
    sol = np.sin(2 * np.pi * x) * np.sin(4 * np.pi * y) * \
        np.cos(2 * np.pi * z)
    rhs = (-(4 + 16 + 4) * np.pi ** 2 * sol).astype(np.float64)
    s = PoissonSolver((n, n, n), 1.0, ((P, P),) * 3, verify="residual")
    s.solve(rhs)
    assert s.stats["last_residual"] < 0.05
    # a corrupted (inf) green multiply must trip the residual/nan guard and
    # recover down the ladder to the same bits as a fault-free solve
    want = np.asarray(PoissonSolver((n, n, n), 1.0, ((P, P),) * 3).solve(rhs))
    with faults.FaultPlan([dict(kind="inf", stage="green")]):
        got = np.asarray(s.solve(rhs))
    assert s.stats["verify_failures"] == 1
    assert np.array_equal(got, want)


def test_hard_fault_raises_structured_solve_error():
    s = PoissonSolver((8, 8, 8), 1.0, BCS)
    f = _rhs(s.input_shape)
    with faults.FaultPlan([dict(kind="error", stage="solve.dispatch",
                                count=-1)]):
        with pytest.raises(SolveError) as ei:
            s.solve(f)
    e = ei.value
    assert e.stage == "solve.dispatch"
    assert [d["action"] for d in e.degradations] == \
        ["relayout:scheduled->baseline", "doubling:deferred->upfront"]
    assert e.config["doubling"] == "upfront"


def test_fault_token_isolates_get_solver_cache():
    from repro.core.solver import get_solver
    s_clean = get_solver((8, 8, 8), 1.0, BCS)
    with faults.FaultPlan([dict(kind="nan", stage="green")]):
        s_armed = get_solver((8, 8, 8), 1.0, BCS)
    assert s_armed is not s_clean
    assert get_solver((8, 8, 8), 1.0, BCS) is s_clean


# -- autotune cache corruption + budget --------------------------------------

def test_corrupt_autotune_cache_falls_through_to_sweep(tmp_path):
    clear_autotune_cache()
    path = str(tmp_path / "comm.json")
    times = {"a2a:1": 3.0, "pipelined:2": 1.0, "pipelined:4": 2.0}

    def timer(cfg):
        return times[f"{cfg.strategy}:{cfg.n_chunks}"]

    cands = [CommConfig("a2a", 1), CommConfig("pipelined", 2),
             CommConfig("pipelined", 4)]
    best = autotune_comm(("kc",), timer, candidates=cands, cache_path=path)
    assert best.strategy == "pipelined" and best.n_chunks == 2
    clear_autotune_cache()
    # rot every entry on load: the loader must ignore the garbage and a
    # live sweep must still find the winner
    with faults.FaultPlan([dict(kind="corrupt_cache", count=-1)]):
        census = {}
        best2 = autotune_comm(("kc",), timer, candidates=cands,
                              cache_path=path, census=census)
    assert best2 == best
    assert len(census["timed"]) == 3


def test_autotune_budget_skips_stallers():
    clear_autotune_cache()

    def timer(cfg):
        if cfg.strategy == "overlap":
            time.sleep(5.0)          # the pathological candidate
        return {"a2a": 2.0, "pipelined": 1.0}[cfg.strategy]

    cands = [CommConfig("a2a", 1), CommConfig("overlap", 2),
             CommConfig("pipelined", 2)]
    census = {}
    t0 = time.perf_counter()
    best = autotune_comm(("kb",), timer, candidates=cands, cache_path="",
                         budget_s=0.2, census=census)
    assert time.perf_counter() - t0 < 4.0, "budget did not bound the sweep"
    assert best.strategy == "pipelined"
    assert census["skipped_budget"] == ["overlap:2"]
    assert set(census["timed"]) == {"a2a:1", "pipelined:2"}


# -- checkpoint integrity ----------------------------------------------------

def _tree(step):
    return {"w": np.full((4, 3), float(step)), "b": np.arange(5.0)}


def test_restore_validates_manifest(tmp_path):
    d = str(tmp_path)
    ck.save(d, 0, _tree(0))
    like = _tree(0)
    out = ck.restore(d, 0, like)
    assert np.array_equal(out["w"], _tree(0)["w"])
    with pytest.raises(ck.CheckpointError, match="leaves"):
        ck.restore(d, 0, {"w": like["w"]})
    with pytest.raises(ck.CheckpointError, match="shape"):
        ck.restore(d, 0, {"w": np.zeros((2, 2)), "b": like["b"]})


def test_truncated_array_skips_step(tmp_path):
    d = str(tmp_path)
    for s in (0, 1, 2):
        ck.save(d, s, _tree(s))
    assert ck.all_steps(d) == [0, 1, 2]
    # torn write past the rename / disk rot: truncate one leaf of step 2
    bad = os.path.join(d, "step_2", "arr_0.npy")
    with open(bad, "r+b") as fh:
        fh.truncate(os.path.getsize(bad) // 2)
    assert ck.all_steps(d) == [0, 1]
    assert ck.latest_step(d) == 1           # restart falls back
    with pytest.raises(ck.CheckpointError, match="damaged"):
        ck.restore(d, 2, _tree(2))
    os.remove(os.path.join(d, "step_1", "arr_1.npy"))
    assert ck.latest_step(d) == 0           # missing leaf also skipped
    out = ck.restore(d, 0, _tree(0))
    assert np.array_equal(out["w"], _tree(0)["w"])


def test_torn_write_mid_leaf_preserves_previous_step(tmp_path):
    d = str(tmp_path)
    ck.save(d, 0, _tree(0))
    with faults.FaultPlan([dict(kind="torn_write", stage="ckpt.leaf.1")]):
        with pytest.raises(faults.InjectedFault):
            ck.save(d, 1, _tree(1))
    # the torn step never committed; the previous one is intact
    assert ck.all_steps(d) == [0]
    out = ck.restore(d, 0, _tree(0))
    assert np.array_equal(out["w"], _tree(0)["w"])
    # a retry of the same save succeeds over the leftover tmp dir
    ck.save(d, 1, _tree(1))
    assert ck.latest_step(d) == 1


# -- distributed chaos (8-device subprocess) ---------------------------------

_DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core.bc import BCType
from repro.core.comm import CommConfig
from repro.distributed.pencil import DistributedPoissonSolver
from repro.runtime import faults, resilience

P = BCType.PER
bcs = ((P, P),) * 3
mesh = jax.make_mesh((2, 4), ("data", "model"))
shape = (16, 16, 16)
rng = np.random.default_rng(0)
f = rng.standard_normal(shape).astype(np.float32)

kw = dict(mesh=mesh, engine="xla")
want = np.asarray(DistributedPoissonSolver(shape, 1.0, bcs, **kw).solve(f))

# hard comm fault in the pipelined strategy: ladder lands on a2a, bit-exact
s = DistributedPoissonSolver(shape, 1.0, bcs,
                             comm=CommConfig("pipelined", 2), **kw)
with faults.FaultPlan([dict(kind="error", stage="comm.pipelined",
                            count=-1)]) as plan:
    got = np.asarray(s.solve(f))
assert plan.log, "comm fault never fired"
assert [d["action"] for d in s.stats["degradations"]] == \
    ["comm:pipelined->a2a"], s.stats["degradations"]
assert np.array_equal(got, want)

# NaN injected into the green stage: verify catches it with stage
# provenance, one rung down recovers bit-exactly
s = DistributedPoissonSolver(shape, 1.0, bcs, verify="nan", **kw)
with faults.FaultPlan([dict(kind="nan", stage="green")]):
    got = np.asarray(s.solve(f))
assert s.stats["verify_failures"] == 1
assert s.stats["degradations"][0]["stage"].startswith("verify.nan@")
assert np.array_equal(got, want)

# transient dispatch errors: backoff retries, no degradation
s = DistributedPoissonSolver(shape, 1.0, bcs, **kw)
with faults.FaultPlan([dict(kind="error", stage="dist.dispatch", count=2,
                            transient=True)]):
    got = np.asarray(s.solve(f))
assert s.stats["retries"] == 2 and not s.stats["degradations"]
assert np.array_equal(got, want)

# ladder exhaustion -> structured SolveError with provenance + trail
s = DistributedPoissonSolver(shape, 1.0, bcs, **kw)
try:
    with faults.FaultPlan([dict(kind="error", stage="dist.dispatch",
                                count=-1)]):
        s.solve(f)
    raise SystemExit("expected SolveError")
except resilience.SolveError as e:
    assert e.stage == "dist.dispatch"
    assert len(e.degradations) == 2, e.degradations
print("OK chaos")
"""


def _run_sub(script, *argv, env_extra=None):
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_COMM_CACHE", None)
    env.pop("REPRO_FAULTS", None)
    env.update(env_extra or {})
    out = subprocess.run([sys.executable, "-c", script, *argv],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    return out


def test_distributed_chaos_ladder():
    out = _run_sub(_DIST_SCRIPT)
    assert "OK chaos" in out.stdout


_LOSS_SCRIPT = r"""
import sys
from repro.launch import solve
err = solve.main(["--n", "16", "--p1", "2", "--p2", "4", "--bcs", "per",
                  "--steps", "6", "--ckpt", sys.argv[1],
                  "--ckpt-every", "2", "--verify", "nan"])
assert err < 1e-5, err
print("OK loss")
"""


@pytest.mark.slow
def test_steps_loop_survives_device_loss(tmp_path):
    # the --steps CFD loop: device loss injected at step 3 shrinks the mesh
    # (2,4)->(1,4), the solver rebuilds elastically and the loop resumes
    # from the last checkpoint; the accumulated field still matches the
    # analytical solution
    out = _run_sub(
        _LOSS_SCRIPT, str(tmp_path / "ck"),
        env_extra={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "REPRO_FAULTS":
                '[{"kind": "device_loss", "stage": "driver", "step": 3}]'})
    assert "OK loss" in out.stdout
    assert "device loss at step 3" in out.stdout
    assert "(1x4) surviving mesh" in out.stdout
