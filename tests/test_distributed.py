"""Pencil solve on a 2x4 mesh == the plain natural-layout reference solve
(``tests/_reference.py``), for all comm strategies.

Runs in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test session keeps seeing a single device.  Covers the four
``CommStrategy`` classes plus ``comm="auto"`` (the plan-time autotuner), the
lowered-HLO interleaving signature of the ``overlap`` strategy, and the
pad-instead-of-silent-fallback behavior for prime-length chunk axes.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core.bc import BCType, DataLayout
from repro.core.comm import CommConfig
from repro.core.green import GreenKind
from repro.core.solver import make_plan
from repro.distributed.pencil import DistributedPoissonSolver
sys.path.insert(0, "tests")
from _reference import reference_solve

E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB
cfg = json.loads(sys.argv[1])
bcs = [tuple(getattr(BCType, b) for b in pair) for pair in cfg["bcs"]]
layout = DataLayout[cfg["layout"]]
n = cfg["n"]
mesh = jax.make_mesh((2, 4), ("data", "model"))

shape = make_plan((n, n, n), 1.0, bcs, layout, cfg["green"]).input_shape
rng = np.random.default_rng(0)
f = rng.standard_normal(shape)
want = reference_solve(f, (n, n, n), 1.0, bcs, layout, cfg["green"])

for strategy in ("a2a", "pipelined", "fused", "overlap"):
    ds = DistributedPoissonSolver(
        (n, n, n), 1.0, bcs, layout=layout, green_kind=cfg["green"],
        mesh=mesh, comm=CommConfig(strategy=strategy, n_chunks=2),
        dtype=jnp.float64)
    got = np.asarray(ds.solve(f))
    err = np.max(np.abs(got - want))
    assert err < 1e-10, (strategy, err)
    # in-block multi-RHS batch: solve((B, *grid)) == stacked single solves
    # (B=4 divides n_chunks=2 -> chunked strategies cut along the batch)
    if cfg.get("local_batch"):
        scales = (1.0, -0.5, 2.0, 0.25)
        fb = np.stack([a * f for a in scales])
        gotb = np.asarray(ds.solve(fb))
        for a, g1 in zip(scales, gotb):
            errb = np.max(np.abs(g1 - a * want))
            assert errb < 1e-9, (strategy, "local_batch", errb)
    # batched (multi-pod style): 2 fields over an extra mesh axis
    if cfg.get("batch"):
        mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        ds3 = DistributedPoissonSolver(
            (n, n, n), 1.0, bcs, layout=layout, green_kind=cfg["green"],
            mesh=mesh3, comm=CommConfig(strategy=strategy),
            batch_axis="pod", dtype=jnp.float64)
        fb = np.stack([f, 2.0 * f])
        gotb = np.asarray(ds3.solve(fb))
        assert np.max(np.abs(gotb[0] - want)) < 1e-10
        assert np.max(np.abs(gotb[1] - 2.0 * want)) < 1e-10

if cfg.get("auto"):
    # plan-time autotuner: picks a strategy with no user input, result is
    # still exact, and the winner is cached per (shape, bcs, layout, mesh)
    ds = DistributedPoissonSolver(
        (n, n, n), 1.0, bcs, layout=layout, green_kind=cfg["green"],
        mesh=mesh, comm="auto", dtype=jnp.float64)
    assert isinstance(ds.comm, CommConfig), ds.comm
    # guided search (the default) times only the cost-model shortlist --
    # a strict subset of the candidate space (DESIGN.md #12)
    assert len(ds.autotune_results) >= 1, ds.autotune_results
    cen = ds.autotune_census
    assert cen["space"] >= 4, cen
    assert 1 <= len(cen["shortlist"]) < cen["space"], cen
    assert set(ds.autotune_results) == set(cen["shortlist"])
    got = np.asarray(ds.solve(f))
    assert np.max(np.abs(got - want)) < 1e-10
    ds2 = DistributedPoissonSolver(
        (n, n, n), 1.0, bcs, layout=layout, green_kind=cfg["green"],
        mesh=mesh, comm="auto", dtype=jnp.float64)
    assert ds2.comm == ds.comm
    assert ds2.autotune_results == {}, "second construction must hit cache"
print("OK")
"""


def _run_script(script, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    # a developer's persisted autotune cache must not leak into the
    # comm="auto" assertions (they require a live sweep)
    env.pop("REPRO_COMM_CACHE", None)
    out = subprocess.run(
        [sys.executable, "-c", script, *args],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
    return out


def _run(cfg):
    _run_script(_SCRIPT, json.dumps(cfg))


CASES = [
    # spectral mix (paper case A), node layout: N+1 points -> uneven split
    dict(bcs=[("EVEN", "EVEN"), ("ODD", "EVEN"), ("PER", "PER")],
         layout="NODE", n=16, green="chat2", batch=True),
    dict(bcs=[("EVEN", "EVEN"), ("ODD", "EVEN"), ("PER", "PER")],
         layout="CELL", n=16, green="chat2", auto=True, local_batch=True),
    # fully unbounded (domain doubling through the switches)
    dict(bcs=[("UNB", "UNB"), ("UNB", "UNB"), ("UNB", "UNB")],
         layout="NODE", n=16, green="chat2", local_batch=True),
    # semi-unbounded + unbounded mix (paper case C)
    dict(bcs=[("UNB", "EVEN"), ("UNB", "UNB"), ("ODD", "UNB")],
         layout="CELL", n=16, green="hej2"),
    # mixed-BC NODE without batch: the N+1 uneven split through every
    # strategy including the chunk-padded overlap path
    dict(bcs=[("ODD", "ODD"), ("EVEN", "ODD"), ("PER", "PER")],
         layout="NODE", n=12, green="chat2"),
]


@pytest.mark.parametrize("cfg", CASES, ids=lambda c: f"{c['layout']}-{c['bcs'][0][0]}{c['bcs'][2][0]}-n{c['n']}")
def test_distributed_matches_reference(cfg):
    _run(cfg)


_HLO_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.core.bc import BCType
from repro.core.comm import CommConfig
from repro.distributed.pencil import DistributedPoissonSolver
from repro.launch.hlo_stats import comm_interleave_stats

U = (BCType.UNB, BCType.UNB)
mesh = jax.make_mesh((2, 4), ("data", "model"))
NC = 4
stats = {}
for strat, nc in (("a2a", 1), ("pipelined", NC), ("overlap", NC)):
    ds = DistributedPoissonSolver((16,) * 3, 1.0, (U, U, U), mesh=mesh,
                                  comm=CommConfig(strat, nc),
                                  lazy_green=True)
    stats[strat] = comm_interleave_stats(ds.lower().as_text())
a2a, pipe, ov = stats["a2a"], stats["pipelined"], stats["overlap"]
# 4 topology switches per solve (2 forward + 2 backward)
assert a2a["all_to_all"] == 4, a2a
assert pipe["all_to_all"] == 4 * NC, pipe
assert ov["all_to_all"] >= 4 * NC, ov
# the overlap signature: 1-D transform ops are scheduled BETWEEN the chunked
# collectives of a switch (chunk k's transform after chunk k+1's all-to-all)
assert ov["gaps_with_compute"] >= 4 * (NC - 2), ov
# pipelined chunks the collective only -- compute sits at switch
# boundaries, never inside a chunk train
assert ov["gaps_with_compute"] > pipe["gaps_with_compute"], (ov, pipe)
print("OK")
"""


def test_overlap_hlo_interleaves_transforms_with_collectives():
    _run_script(_HLO_SCRIPT)


_PRIME_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from jax.sharding import PartitionSpec as P
from repro.core.comm import CommConfig, topology_switch

mesh = jax.make_mesh((2,), ("ax",))
shard_map = jax.shard_map

# uninvolved (chunk) axis has PRIME length 7: n_chunks=2 cannot divide it.
# The seed silently fell back to one monolithic collective; now the axis is
# zero-padded to the next multiple (and cropped back) with a warning.
x = np.random.default_rng(0).standard_normal((4, 6, 7))

def run(cfg):
    fn = shard_map(lambda xl: topology_switch(xl, "ax", 0, 1, cfg),
                   mesh=mesh, in_specs=P(None, "ax", None),
                   out_specs=P("ax", None, None))
    return np.asarray(jax.jit(fn)(x))

want = run(CommConfig("a2a", 1))
with warnings.catch_warnings(record=True) as rec:
    warnings.simplefilter("always")
    got = run(CommConfig("pipelined", 2))
msgs = [str(w.message) for w in rec if "zero-padding" in str(w.message)]
assert msgs, "non-dividing chunk axis must warn"
np.testing.assert_allclose(got, want, rtol=0, atol=0)

# the chunked path must emit n_chunks collectives, not a silent single one
lowered = jax.jit(shard_map(
    lambda xl: topology_switch(xl, "ax", 0, 1, CommConfig("pipelined", 2)),
    mesh=mesh, in_specs=P(None, "ax", None),
    out_specs=P("ax", None, None))).lower(x).as_text()
assert lowered.count("all_to_all") + lowered.count("all-to-all") >= 2, \
    "pipelined must keep its chunked collectives on a non-dividing axis"

# overlap shares the padding path
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    got_ov = run(CommConfig("overlap", 2))
np.testing.assert_allclose(got_ov, want, rtol=0, atol=0)
print("OK")
"""


def test_pipelined_prime_chunk_axis_pads_and_warns():
    _run_script(_PRIME_SCRIPT)
