"""SDC chaos net for the ABFT guard (DESIGN.md #13).

``verify="nan"``/``"residual"`` catch non-finite or grossly wrong
solutions; a SILENT flip -- one wrong-but-finite value injected into a
transform stage, a packed collective payload, or a checkpoint leaf --
sails through both.  This net arms ``kind="flip"`` fault specs across
every pipeline stage x relayout schedule x data layout x batching and
requires the ABFT invariants to detect the corruption, attribute it to
the right stage, and repair it via selective recompute:

* detection matrix: >= 95% of fired flips detected, every detection
  attributed to the armed stage, every solve repaired to the fault-free
  baseline (xla engine; bit-exact where repair re-dispatches a
  standalone jit, roundoff-exact where the recompute branch shares the
  faulted jit);
* the two-phase guard (``verify="abft"``): the cheap end-to-end
  sandwich trips, the checked re-dispatch localizes the stage and
  repairs it inline -- no ladder degradation for a transient flip;
* clean soak: both modes, zero integrity records and zero verify
  failures over repeated randomized solves (false-positive guard);
* persistent corruption (``count=-1``) survives recompute and the
  ladder, raising a structured ``SolveError``;
* wire checksums attribute packed-payload corruption to the collective
  (transient -> the re-send path), not the surrounding compute;
* distributed (8-device subprocess): the same invariants through the
  sharded pipeline + checksum-carrying collectives, plus a multi-tenant
  serve soak where one flip-armed tenant is repaired in isolation;
* checkpoint restore: a flipped leaf fails the manifest content digest
  with ``CheckpointError`` instead of silently resuming.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.ckpt import checkpoint as ck
from repro.core.bc import BCType, DataLayout
from repro.distributed.pencil import DistributedPoissonSolver
from repro.runtime import abft, faults
from repro.runtime.resilience import SolveError

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB
BCS = ((E, E), (O, E), (P, P))


def _rhs(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(np.float32)


# -- invariant arithmetic ----------------------------------------------------

def test_lite_probe_axes_bounded_and_deterministic():
    qs = abft.lite_probe_axes((12, 16, 20), np.float32)
    assert [q.shape for q in qs] == [(12,), (16,), (20,)]
    for q in qs:
        assert q.dtype == np.float32
        # bounded away from zero: no site of the rank-1 outer product can
        # attenuate a corruption below 0.125x
        assert np.all((np.abs(q) >= 0.5) & (np.abs(q) <= 1.5))
    qs2 = abft.lite_probe_axes((12, 16, 20), np.float32)
    for a, b in zip(qs, qs2):
        assert np.array_equal(a, b)
    # a different grid draws a different probe
    assert not np.array_equal(
        qs[0], abft.lite_probe_axes((12, 16, 24), np.float32)[0])


def test_lite_mismatch_ab_semantics():
    assert abft.lite_mismatch_ab(1.0, 1.0, 0.0) == 0.0
    assert abft.lite_mismatch_ab(1.0, 1.1, 0.0) == pytest.approx(0.1 / 1.1)
    # the floor keeps near-cancelling dots from amplifying roundoff
    assert abft.lite_mismatch_ab(1e-9, 2e-9, 1.0) == pytest.approx(1e-9)
    # any non-finite value reads as corruption
    assert abft.lite_mismatch_ab(np.nan, 1.0, 0.0) == np.inf
    assert abft.lite_mismatch_ab([1.0, np.inf], [1.0, 1.0], 0.0) == np.inf
    # batched: worst row wins
    assert abft.lite_mismatch_ab([1.0, 2.0], [1.0, 3.0], 0.0) == \
        pytest.approx(1.0 / 3.0)


def test_verify_report_attribution_and_ledger():
    tol = 1e-8
    # repaired stage: pre-mismatch bad, post-recompute clean -> a
    # "recompute" record, no raise
    stats = {}
    recs = abft.verify_report(
        ["fwd.0", "fwd.0.post"], [1.0, 0.0], tol=tol, stats=stats)
    assert [r["action"] for r in recs] == ["recompute"]
    assert stats["integrity"][0]["stage"] == "fwd.0"
    # surviving compute mismatch -> non-transient IntegrityError
    stats = {}
    with pytest.raises(abft.IntegrityError) as ei:
        abft.verify_report(["green", "green.post"], [1.0, 1.0], tol=tol,
                           stats=stats)
    assert ei.value.stage == "verify.abft@green"
    assert not ei.value.transient
    assert stats["verify_failures"] == 1
    assert stats["integrity"][0]["action"] == "escalate"
    # wire-only mismatch -> TRANSIENT (remedy: re-send via retry path)
    with pytest.raises(abft.IntegrityError) as ei:
        abft.verify_report(["wire.comm.a2a"], [1.0], tol=tol)
    assert ei.value.transient
    assert ei.value.stage == "verify.abft@wire.comm.a2a"
    # mixed wire + compute -> NOT transient (re-sending cannot fix compute)
    with pytest.raises(abft.IntegrityError) as ei:
        abft.verify_report(
            ["wire.comm.a2a", "green", "green.post"], [1.0, 1.0, 1.0],
            tol=tol)
    assert not ei.value.transient


def test_wire_checksums_catch_slab_corruption():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    x = rng.standard_normal((8, 6)).astype(np.float32)
    cs = abft.wire_checksums(jnp.asarray(x), 0, 4)
    assert np.allclose(np.asarray(cs),
                       x.reshape(4, 2, 6).sum(axis=(1, 2)), atol=1e-5)
    # clean round trip: no mismatch
    col = abft.Collector()
    abft.wire_verify(jnp.asarray(x), cs, 0, 4, col, "wire.comm.test", 1e-6)
    assert float(np.asarray(col.stacked())[0]) < 1e-6
    # one flipped value in the slab destined to rank 2 -> only that
    # checksum trips, and the report attributes it to the wire
    bad = x.copy()
    bad[5, 3] += 8.0 * np.abs(x).max()
    col = abft.Collector()
    abft.wire_verify(jnp.asarray(bad), cs, 0, 4, col, "wire.comm.test",
                     1e-6)
    with pytest.raises(abft.IntegrityError) as ei:
        abft.verify_report(col.names, np.asarray(col.stacked()), tol=3e-4)
    assert ei.value.transient
    assert ei.value.stage == "verify.abft@wire.comm.test"


# -- detection matrix (one device) -------------------------------------------

STAGES = ["fwd.0", "fwd.1", "fwd.2", "green", "bwd.0", "bwd.1", "bwd.2"]


def _chaos_trial(stage, *, relayout="scheduled", layout=DataLayout.CELL,
                 batched=False, verify="abft-stages", count=1):
    """Arm one flip, solve, and report (fired, detected, attributed,
    repaired) against the fault-free baseline of the same config.

    The repair baseline is the CLEAN solve under the same verify mode:
    the checked pipeline is a different jit than the plain one, so its
    healthy output differs from the plain solve at roundoff -- "repaired"
    means the recompute restored exactly what the unfaulted checked
    pipeline produces."""
    s0 = DistributedPoissonSolver((12, 12, 12), 1.0, BCS, layout=layout,
                                  engine="xla", relayout=relayout)
    shape = ((2,) + s0.input_shape) if batched else s0.input_shape
    f = _rhs(shape, seed=7)
    want = np.asarray(s0.solve(f, verify=verify))
    s = DistributedPoissonSolver((12, 12, 12), 1.0, BCS, layout=layout,
                                 engine="xla", relayout=relayout)
    with faults.FaultPlan([dict(kind="flip", stage=stage,
                                count=count)]) as plan:
        got = np.asarray(s.solve(f, verify=verify))
    recs = s.stats.get("integrity", [])
    detected = [r for r in recs if r["stage"].split("#")[0] == stage]
    # the recompute branch lives in the same jit as the primary apply, so
    # XLA may schedule it with different fusion: the repaired value can
    # sit one roundoff (~1e-7 rel) off the clean checked run even though
    # the injected corruption was ~0.2-0.4 rel.  "repaired" therefore
    # means equal to the clean run at roundoff -- 5+ orders of magnitude
    # below the corruption.  (The distributed test asserts strict
    # bit-exactness, where repair re-dispatches a standalone clean jit.)
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want)))
    return {"fired": bool(plan.log), "detected": bool(detected),
            "attributed": bool(detected),
            "repaired": err <= 1e-5 * scale,
            "degraded": bool(s.stats["degradations"]), "records": recs}


def test_sdc_detection_matrix():
    """Flips across stages x relayout schedules x CELL/NODE x batched:
    >= 95% of fired flips detected, every detection attributed to the
    armed stage, every solve repaired to the clean run WITHOUT walking
    the degradation ladder (inline selective recompute is the remedy)."""
    matrix = [dict(stage=st, relayout=rl)
              for st in STAGES for rl in ("scheduled", "baseline")]
    matrix += [dict(stage=st, layout=DataLayout.NODE)
               for st in ("fwd.0", "green", "bwd.2")]
    matrix += [dict(stage=st, batched=True)
               for st in ("fwd.1", "green", "bwd.0")]
    fired, hits = 0, 0
    for case in matrix:
        r = _chaos_trial(**case)
        assert r["fired"], f"flip never fired: {case}"
        fired += 1
        if r["detected"]:
            hits += 1
            assert r["attributed"], (case, r["records"])
        assert r["repaired"], (case, r["records"])
        assert not r["degraded"], (case, "recompute must not degrade")
    assert hits / fired >= 0.95, f"detected {hits}/{fired}"


def test_two_phase_guard_localizes_then_repairs():
    """``verify="abft"``: the cheap sandwich runs on every solve; a flip
    trips it (hit 1 lands in the sandwich trace), the checked re-dispatch
    localizes the stage (hit 2), and the inline recompute repairs it --
    transient SDC never reaches the degradation ladder."""
    s0 = DistributedPoissonSolver((12, 12, 12), 1.0, BCS, engine="xla")
    f = _rhs(s0.input_shape)
    # after the trip the answer comes from the checked re-dispatch, so
    # the bit-exact baseline is the clean CHECKED pipeline's output
    want = np.asarray(s0.solve(f, verify="abft-stages"))
    s = DistributedPoissonSolver((12, 12, 12), 1.0, BCS, engine="xla",
                                 verify="abft")
    with faults.FaultPlan([dict(kind="flip", stage="fwd.1",
                                count=2)]) as plan:
        got = np.asarray(s.solve(f))
    assert len(plan.log) == 2, plan.log
    recs = s.stats["integrity"]
    assert recs[0]["stage"] == "solve.linearity"
    assert recs[0]["action"] == "localize"
    assert any(r["stage"].split("#")[0] == "fwd.1"
               and r["action"] == "recompute" for r in recs[1:]), recs
    assert s.stats["verify_failures"] == 1
    assert not s.stats["degradations"]
    # equal to the clean checked run at roundoff (see _chaos_trial note)
    assert float(np.max(np.abs(got - want))) <= \
        1e-5 * float(np.max(np.abs(want)))


def test_clean_soak_zero_false_positives():
    """Randomized clean solves under both guard modes: not a single
    integrity record or verify failure may appear (tolerances must sit
    above the roundoff of every healthy config)."""
    for verify in ("abft", "abft-stages"):
        s = DistributedPoissonSolver((16, 16, 16), 1.0, BCS, engine="xla",
                                     verify=verify)
        ref = DistributedPoissonSolver((16, 16, 16), 1.0, BCS, engine="xla")
        for seed in range(8):
            f = _rhs(s.input_shape, seed=seed)
            got = np.asarray(s.solve(f))
            assert np.allclose(got, np.asarray(ref.solve(f)),
                               atol=1e-4, rtol=1e-4)
        assert s.stats["verify_failures"] == 0, verify
        assert not s.stats.get("integrity"), (verify, s.stats["integrity"])
        assert not s.stats["degradations"]


def test_persistent_corruption_escalates_to_solve_error():
    """``count=-1``: the flip re-fires on every recompute and every
    ladder rung's retrace -- the guard must escalate to a structured
    ``SolveError`` with ABFT stage provenance, never return silently
    corrupted output."""
    s = DistributedPoissonSolver((12, 12, 12), 1.0, BCS, engine="xla",
                                 verify="abft-stages")
    f = _rhs(s.input_shape)
    with faults.FaultPlan([dict(kind="flip", stage="green", count=-1)]):
        with pytest.raises(SolveError) as ei:
            s.solve(f)
    assert ei.value.stage == "verify.abft@green"
    ledger = s.stats["integrity"]
    assert any(r["action"] == "escalate" and r["stage"] == "green"
               for r in ledger), ledger
    # the ladder walked its rungs before giving up
    assert [d["action"] for d in ei.value.degradations] == \
        ["relayout:scheduled->baseline", "doubling:deferred->upfront"]


# -- checkpoint content digests ----------------------------------------------

def test_checkpoint_flip_on_restore_raises(tmp_path):
    d = str(tmp_path)
    tree = {"w": np.arange(12.0).reshape(4, 3), "b": np.ones(5)}
    ck.save(d, 0, tree)
    # storage rot between save and restore: one flipped value in leaf 1
    # is shape/dtype/finite-valid -- only the content digest can see it
    with faults.FaultPlan([dict(kind="flip", stage="ckpt.leaf.1")]) as plan:
        with pytest.raises(ck.CheckpointError, match="digest"):
            ck.restore(d, 0, tree)
    assert plan.log, "restore taint never fired"
    # the same checkpoint restores clean without the armed plan
    out = ck.restore(d, 0, tree)
    assert np.array_equal(out["w"], tree["w"])
    assert np.array_equal(out["b"], tree["b"])


def test_checkpoint_digest_recorded_per_leaf(tmp_path):
    import json
    d = str(tmp_path)
    ck.save(d, 0, {"w": np.full((3, 3), 2.0)})
    with open(os.path.join(d, "step_0", "manifest.json")) as fh:
        man = json.load(fh)
    assert all(len(ent["crc32"]) == 8 for ent in man["leaves"])
    # rot the bytes on disk directly: restore must refuse
    path = os.path.join(d, "step_0", "arr_0.npy")
    arr = np.load(path)
    arr[1, 1] += 1.0
    np.save(path, arr)
    with pytest.raises(ck.CheckpointError, match="digest"):
        ck.restore(d, 0, {"w": np.zeros((3, 3))})


# -- distributed chaos (8-device subprocess) ---------------------------------

_DIST_SDC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core.bc import BCType
from repro.core.comm import CommConfig
from repro.distributed.pencil import DistributedPoissonSolver
from repro.runtime import faults, resilience

P, U = BCType.PER, BCType.UNB
mesh = jax.make_mesh((2, 4), ("data", "model"))
n = 16
f = np.random.default_rng(0).standard_normal((n, n, n)).astype(np.float32)

for bcs, comm in ((((P, P),) * 3, CommConfig("a2a")),
                  (((U, U), (P, P), (U, U)), CommConfig("pipelined", 2))):
    s = DistributedPoissonSolver((n, n, n), 1.0, bcs, mesh=mesh, comm=comm,
                                 engine="xla", verify="abft")
    want = np.asarray(s.solve(f))
    # clean guard run: bit-exact vs verify-off (same jit), no records
    s_off = DistributedPoissonSolver((n, n, n), 1.0, bcs, mesh=mesh,
                                     comm=comm, engine="xla")
    assert np.array_equal(want, np.asarray(s_off.solve(f)))
    assert not s.stats.get("integrity"), s.stats
    # transform-stage flip: sandwich trips (hit 1), checked re-dispatch
    # localizes fwd.0 (hit 2), inline recompute repairs -- bit-exact, no
    # ladder degradation
    with faults.FaultPlan([dict(kind="flip", stage="fwd.0",
                                count=2)]) as plan:
        got = np.asarray(s.solve(f))
    assert len(plan.log) == 2, plan.log
    recs = s.stats["integrity"]
    assert recs[0]["stage"] == "solve.linearity", recs
    assert recs[0]["action"] == "localize", recs
    assert any(r["stage"].split("#")[0] == "fwd.0"
               and r["action"] == "recompute" for r in recs[1:]), recs
    assert np.array_equal(got, want), "selective recompute not bit-exact"
    assert not s.stats["degradations"], s.stats["degradations"]
    # wire flip in a packed collective payload: the sandwich detects it,
    # the re-dispatch (a fresh trace = a re-send) comes back clean
    s.stats["integrity"] = []
    with faults.FaultPlan([dict(kind="flip", stage="comm.wire.*",
                                count=1)]) as plan:
        got = np.asarray(s.solve(f))
    assert plan.log, "wire flip never fired"
    assert any(r["stage"] == "solve.linearity"
               for r in s.stats["integrity"])
    assert np.array_equal(got, want)

# wire ATTRIBUTION under the always-checked mode: the receive-side
# checksum row blames the collective (kind="wire"), and recovery goes
# through the transient/ladder path rather than silent acceptance
s = DistributedPoissonSolver((n, n, n), 1.0, ((P, P),) * 3, mesh=mesh,
                             comm=CommConfig("a2a"), engine="xla",
                             verify="abft-stages")
want = np.asarray(s.solve(f))
scale = float(np.max(np.abs(want)))
with faults.FaultPlan([dict(kind="flip", stage="comm.wire.*",
                            count=1)]) as plan:
    got = np.asarray(s.solve(f))
assert plan.log, "wire flip never fired"
wire_recs = [r for r in s.stats["integrity"] if r["kind"] == "wire"]
assert wire_recs and all(r["stage"].startswith("wire.")
                         for r in wire_recs), s.stats["integrity"]
assert float(np.max(np.abs(got - want))) <= 1e-5 * scale

# persistent distributed corruption: every retrace re-fires -> SolveError
s = DistributedPoissonSolver((n, n, n), 1.0, ((P, P),) * 3, mesh=mesh,
                             comm=CommConfig("a2a"), engine="xla",
                             verify="abft-stages")
try:
    with faults.FaultPlan([dict(kind="flip", stage="green", count=-1)]):
        s.solve(f)
    raise SystemExit("expected SolveError")
except resilience.SolveError as e:
    assert e.stage == "verify.abft@green", e.stage
print("OK dist-sdc")
"""


_SERVE_SOAK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core.bc import BCType
from repro.core.comm import CommConfig
from repro.runtime import faults
from repro.serve import PlanSpec, PoissonServer

P = BCType.PER
mesh = jax.make_mesh((2, 4), ("data", "model"))
n = 16
spec = PlanSpec(shape=(n, n, n), bcs=((P, P),) * 3, mesh=mesh,
                solver_kw=(("comm", CommConfig("a2a")),))
rng = np.random.default_rng(0)
fields = [rng.standard_normal((n, n, n)).astype(np.float32)
          for _ in range(4)]

with PoissonServer(max_batch=4, max_delay_ms=1.0, verify="abft") as srv:
    # clean baseline per field through the warm plan
    base = [srv.solve(f, spec, tenant="warm") for f in fields]
    assert all(not r.integrity for r in base)
    # one flip-armed tenant: its request runs on a SHADOW solver (the
    # fault token keys get_solver), gets localized + repaired, and the
    # co-resident clean tenants keep getting pristine bit-exact answers
    plan = faults.FaultPlan([dict(kind="flip", stage="fwd.0", count=2)])
    bad_fut = srv.submit(fields[0], spec, tenant="chaos", fault_plan=plan)
    bad = bad_fut.result(timeout=120)
    stages = [r["stage"] for r in bad.integrity]
    assert "solve.linearity" in stages, bad.integrity
    assert any(s.split("#")[0] == "fwd.0" for s in stages), bad.integrity
    assert np.array_equal(bad.u, base[0].u), "faulted tenant not repaired"
    # soak the clean tenants after the chaos request: zero integrity
    # records, bit-exact vs the pre-chaos baseline
    for t in range(6):
        for i, f in enumerate(fields):
            r = srv.solve(f, spec, tenant=f"t{t}")
            assert not r.integrity, r.integrity
            assert not r.degradations, r.degradations
            assert np.array_equal(r.u, base[i].u), (t, i)
print("OK serve-soak")
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


def test_distributed_sdc_chaos():
    out = _run_sub(_DIST_SDC_SCRIPT)
    assert "OK dist-sdc" in out.stdout


@pytest.mark.slow
def test_serve_soak_flip_armed_tenant_isolated():
    out = _run_sub(_SERVE_SOAK_SCRIPT)
    assert "OK serve-soak" in out.stdout
