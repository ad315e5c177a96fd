"""The work functions against hand counts, and the predicted collective
bytes against the program's lowered HLO on four host devices."""
import json
import os
import subprocess
import sys

import pytest

import work

UNB = [["unb", "unb"]] * 3


def cfg(n, bcs=UNB, layout="node", dtype="float32"):
    return dict(n=n, bcs=bcs, layout=layout, dtype=dtype)


def test_unbounded_node_stages_by_hand():
    # n = 4 node cells: 5 points per axis, doubled transforms of length 8,
    # the r2c axis keeps 5 bins, the c2c axes 8
    st = work.transform_stages(cfg(4), (0, 1, 2))
    assert [s.name for s in st] == ["fwd.0", "fwd.1", "fwd.2",
                                    "bwd.2", "bwd.1", "bwd.0"]
    # fwd.0: 5^3 f32 in, 5^3 c64 out; 25 real rows of length 8
    assert st[0].bytes == 125 * 4 + 125 * 8
    assert st[0].flops == 25 * 2.5 * 8 * 3
    # fwd.1: 5x5x5 c64 in, 5x8x5 c64 out; 25 complex rows
    assert st[1].bytes == 125 * 8 + 200 * 8
    assert st[1].flops == 25 * 5 * 8 * 3
    # fwd.2: 5x8x5 in, 5x8x8 out; 40 complex rows
    assert st[2].bytes == 200 * 8 + 320 * 8
    assert st[2].flops == 40 * 5 * 8 * 3
    # the backward sweep mirrors it; bwd.0 is the c2r stage
    assert [s.bytes for s in st[3:]] == [s.bytes for s in st[2::-1]]
    assert st[5].bytes == 125 * 8 + 125 * 4
    assert st[5].flops == 25 * 2.5 * 8 * 3


def test_cell_layout_and_float64():
    st = work.transform_stages(cfg(4, layout="cell", dtype="float64"),
                               (2, 0, 1))
    # 4 points per axis, r2c on axis 2: 4x4x4 f64 in, 4x4x5 c128 out
    assert st[0].name == "fwd.2"
    assert st[0].bytes == 64 * 8 + 80 * 16


def test_least_work_is_order_free_on_a_cube():
    works = {work.transform_stages(cfg(8), o)[0].bytes
             for o in work.valid_orders(cfg(8))}
    assert len(works) == 1
    assert len(work.valid_orders(cfg(8))) == 6


def test_symmetric_directions_go_first():
    bcs = [["even", "even"], ["odd", "even"], ["periodic", "periodic"]]
    orders = work.valid_orders(cfg(4, bcs=bcs))
    assert all(o[-1] == 2 for o in orders)
    d = work.directions(cfg(4, bcs=bcs), (0, 1, 2))
    # node DCT-I keeps n+1 points, DST-III n, periodic r2c n/2+1 bins
    assert (d[0].n_fft, d[1].n_fft, d[2].n_out) == (5, 4, 3)
    st = work.transform_stages(cfg(4, bcs=bcs), (0, 1, 2))
    # the r2r stages are real, the DFT stage turns the field complex
    assert st[0].bytes == 125 * 4 + 125 * 4
    assert st[2].bytes == 5 * 4 * 5 * 4 + 5 * 4 * 3 * 8


def test_switch_bytes_by_hand():
    # 16 node cells on 2x2: 17 points padded to 18 on the sharded axes,
    # r2c axis 17 bins (padded to 18), c2c axes 32 bins
    b = work.switch_bytes(cfg(16), (2, 0, 1), 2, 2)
    assert b == [18 * 9 * 9 * 8, 9 * 32 * 9 * 8, 9 * 16 * 18 * 8,
                 9 * 18 * 9 * 8]
    assert work.switch_bytes(cfg(16), (2, 0, 1), 1, 1) == []


_HLO_PROBE = r"""
import json, sys
sys.path.insert(0, sys.argv[1]); sys.path.insert(1, sys.argv[2])
import numpy as np, jax
from jax.sharding import Mesh
from harness import solver_kwargs
from repro.core.solver import get_solver
from repro.launch.hlo_stats import comm_bytes_stats
cfg = json.loads(sys.argv[3])
mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
s = get_solver(**solver_kwargs(cfg, mesh))
st = comm_bytes_stats(s.lower().as_text())
print(json.dumps({"order": list(s.plan.order),
                  "bytes": [c["bytes"] for c in st["per_collective"]]}))
"""


@pytest.mark.parametrize("n", [16, 24])
def test_switch_bytes_match_the_lowered_program(n):
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(os.path.dirname(bench), "src")
    with open(os.path.join(bench, "configs",
                           "caseB-unb-node-512-2x2.json")) as fh:
        c = dict(json.load(fh), n=n)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _HLO_PROBE, bench, src,
                          json.dumps(c)], env=env, capture_output=True,
                         text=True, timeout=300, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = work.switch_bytes(c, tuple(got["order"]), 2, 2)
    assert got["bytes"] == want
