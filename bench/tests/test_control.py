"""The control in the program's place, through the whole run path at a
size a test run holds: the plain reference computed in bfloat16, the
precision below the configuration's float32, stands in for every solve,
and the harness's own comparison has to call the run not correct on every
seed (``test_faults.py`` shows the program's solve of the same fields
passing)."""
import os
import time

import numpy as np
import pytest

import harness


def bf16_reference(config):
    ref = harness._load_module(
        os.path.join(harness.BENCH, "references",
                     config["reference"] + ".py"), "control_reference")

    def hook(solver, f):
        import jax
        u = ref.solve(np.asarray(f, np.float64), config["L"],
                      precision="bf16")
        return jax.device_put(u.astype(f.dtype), f.sharding)
    return hook


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 5, 123456789012])
@pytest.mark.parametrize("workload", harness.workloads(chips=1))
def test_control_fails_the_check(workload, seed):
    cell = harness.load_cell(workload)
    run, compared = harness.run_cell(
        cell, seed, 0.5, False, time.perf_counter(), allow_cpu=True, n=32,
        hook=bf16_reference(cell.config))
    assert run.attempted > 0 and run.failed == 0
    assert not harness.is_correct(compared)
    gap, limit = compared["rel_gap"]
    assert gap > 3 * limit, compared
