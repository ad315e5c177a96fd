"""Batched multi-RHS solve sweep: B in {1, 2, 4, 8, 16}, batched pipeline
vs B sequential solves, for the solver on one device and on a mesh, on
the paper's fully-unbounded workload -- the amortization a vortex-method
driver (several RHS per timestep over one plan) gets for free from the
batch axis.

``DistributedPoissonSolver`` runs without a mesh (one device, row
"poisson") and on an 8-device host-platform (2 x 4) pencil mesh (row
"pencil"), in a subprocess (same pattern as bench_comm).  The headline number -- the acceptance bar of the batched
execution PR -- is the distributed B=8 speedup: one batched solve vs 8
sequential solves on the host mesh.  Plus one Biot-Savart row: the
uniform-plan batched 3-component pipeline vs the sequential per-component
implementation.

Full sweep lands in ``BENCH_batch.json`` (quick mode:
``BENCH_batch.quick.json``), rendered in EXPERIMENTS.md section
"Batched multi-RHS execution".
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from repro.core.bc import BCType, DataLayout
from repro.core.comm import CommConfig
from repro.distributed.pencil import DistributedPoissonSolver
from repro.core.biot_savart import BiotSavartSolver

cfg = json.loads(sys.argv[1])
n, reps, bs = cfg["n"], cfg["reps"], cfg["bs"]
U = (BCType.UNB, BCType.UNB)
mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
fb = rng.standard_normal((max(bs), n, n, n)).astype(np.float32)
rows = []


def best(fn, reps):
    fn()                                  # warm (compile both paths first)
    t = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        t = min(t, time.perf_counter() - t0)
    return t


def sweep(name, solver):
    for b in bs:
        f1 = jnp.asarray(fb[:b])
        t_loop = best(lambda: [solver.solve(f1[i]).block_until_ready()
                               for i in range(b)], reps)
        t_batch = best(lambda: solver.solve(f1).block_until_ready(), reps)
        rows.append({"solver": name, "B": b,
                     "loop_ms": t_loop * 1e3, "batch_ms": t_batch * 1e3,
                     "speedup": t_loop / t_batch})

sweep("poisson", DistributedPoissonSolver((n, n, n), 1.0, (U, U, U),
                                          layout=DataLayout.CELL))
sweep("pencil", DistributedPoissonSolver(
    (n, n, n), 1.0, (U, U, U), mesh=mesh,
    comm=CommConfig("overlap", 2)))

# Biot-Savart: the component axis IS the batch -- batched uniform-plan
# pipeline vs the sequential 3-solve implementation
bsolver = BiotSavartSolver((n, n, n), 1.0, [[U, U, U]] * 3,
                           layout=DataLayout.CELL)
assert bsolver.batched
fv = jnp.asarray(fb[:3])
seq = jax.jit(bsolver._solve_impl)
t_seq = best(lambda: seq(fv).block_until_ready(), reps)
t_bat = best(lambda: bsolver._solve(fv).block_until_ready(), reps)
rows.append({"solver": "biot_savart", "B": 3,
             "loop_ms": t_seq * 1e3, "batch_ms": t_bat * 1e3,
             "speedup": t_seq / t_bat})
print("BENCH_JSON " + json.dumps(rows))
"""


def _sweep(n, reps, bs):
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT,
         json.dumps({"n": n, "reps": reps, "bs": bs})],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    line = [l for l in out.stdout.splitlines()
            if l.startswith("BENCH_JSON ")][-1]
    return json.loads(line[len("BENCH_JSON "):])


def run(quick=True):
    n = 32
    bs = [1, 2, 4, 8] if quick else [1, 2, 4, 8, 16]
    try:
        rows = _sweep(n, 3 if quick else 5, bs)
    except RuntimeError as e:
        return [("batch_error", 0.0, str(e)[-200:])]
    headline = next(r for r in rows
                    if r["solver"] == "pencil" and r["B"] == 8)
    payload = {"mode": "quick" if quick else "full", "grid": n,
               "mesh": [2, 4], "bcs": "unb", "comm": "overlap:2",
               "rows": rows,
               "headline": {"solver": "pencil", "B": 8,
                            "speedup_vs_sequential": headline["speedup"]}}
    fname = "BENCH_batch.quick.json" if quick else "BENCH_batch.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, fname), "w") as fh:
        json.dump(payload, fh, indent=2)
    return [(f"batch_{r['solver']}_B{r['B']}", r["batch_ms"] * 1e3,
             f"{r['speedup']:.2f}x_vs_loop") for r in rows]


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import emit
    out_rows = run(quick="--full" not in sys.argv)
    emit(out_rows)
    # standalone/CI runs must FAIL loudly when the sweep crashed (run()
    # returns an error row for the benchmark-harness aggregation instead
    # of raising); otherwise the acceptance headline silently vanishes
    if any(name == "batch_error" for name, _, _ in out_rows):
        sys.exit(1)
