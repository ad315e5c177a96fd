"""The whole run path on host devices at a small size, with the timed
path broken underneath: each fault a cell can have must turn ``correct``
false, and the unbroken path must stay correct.  (A step has no batch to
halve: each cell solves one field per step.)"""
import os
import subprocess
import sys
import time

import pytest

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(workload, hook=None, n=16):
    cell = harness.load_cell(workload)
    run, compared = harness.run_cell(
        cell, seed=2 ** 33 + 12345, seconds=0.5, trace=False,
        t_start=time.perf_counter(), allow_cpu=True, n=n, hook=hook)
    return run, compared, harness.is_correct(compared)


ONE_CHIP = harness.workloads(chips=1)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_path_is_correct(workload):
    run, compared, ok = _run(workload)
    assert ok, compared
    assert run.attempted > 0 and run.failed == 0
    assert compared["rel_gap"][0] < 1e-5


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_state_returned_unchanged_is_caught(workload):
    # the step hands back its input instead of solving
    _, compared, ok = _run(workload, hook=lambda s, f: f)
    assert not ok and compared["rel_gap"][0] > 0.5


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_one_altered_answer_is_caught(workload):
    # one value of each solution altered by 1% of the field's largest
    def hook(s, f):
        u = s.solve(f)
        return u.at[3, 5, 7].add(0.01 * abs(u).max())
    _, compared, ok = _run(workload, hook=hook)
    assert not ok
    assert compared["rel_gap"][0] == pytest.approx(0.01, rel=0.05)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_raising_solve_counts_as_failed(workload):
    calls = []
    setup_calls = 1 + harness.load_cell(workload).mix["warmup_steps"]

    def hook(s, f):
        # set-up's first solve and warm-up steps pass, the window's raise
        calls.append(1)
        if len(calls) > setup_calls:
            raise RuntimeError("injected")
        return s.solve(f)
    run, compared, ok = _run(workload, hook=hook)
    assert not ok and run.failed == run.attempted > 0


_NO_EXCHANGE = r"""
import sys, time
sys.path.insert(0, sys.argv[1]); sys.path.insert(1, sys.argv[2])
import harness
from jax import numpy as jnp
if sys.argv[3] == "broken":
    from repro.core import comm

    def local_only(x, axis_name, split_axis, concat_axis):
        # every chip keeps its own pieces: same shapes, no exchange
        p = comm.lax.psum(1, axis_name)
        return jnp.concatenate(jnp.split(x, p, axis=split_axis),
                               axis=concat_axis)

    comm._a2a = local_only
import json, os, traffic
with open(os.path.join(sys.argv[1], "configs",
                       "caseB-unb-node-512-2x2.json")) as fh:
    config = json.load(fh)
# the 2x2 configuration, run as a four-chip cell of the same traffic
cell = harness.Cell({"name": "caseB-512-2x2.step", "chips": 4}, config,
                    traffic.load(traffic.path(sys.argv[1], "closed_loop")),
                    [], [])
run, compared = harness.run_cell(cell, 99, 0.5, False, time.perf_counter(),
                                 allow_cpu=True, n=16)
print("RESULT", harness.is_correct(compared), compared["rel_gap"][0])
"""


@pytest.mark.parametrize("mode", ["sound", "broken"])
def test_exchange_left_out_is_caught(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(BENCH), "src")
    out = subprocess.run([sys.executable, "-c", _NO_EXCHANGE, BENCH, src,
                          mode], env=env, capture_output=True, text=True,
                         timeout=600, check=True)
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT")][-1]
    _, ok, gap = line.split()
    assert (ok == "True") == (mode == "sound"), line
    if mode == "broken":
        assert float(gap) > 1e-2
