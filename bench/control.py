#!/usr/bin/env python3
"""Read the control of a cell's correctness check: the plain reference
computed in bfloat16, the precision below the configuration's float32,
in the program's place.

    python3 bench/control.py --workload <name> --seeds 11,12,13

For each seed it makes the cell's base right-hand side at the cell's own
size exactly as a run does (on the first device), solves it with the
reference in float64 and in bfloat16, and prints the relative gap that a
run's ``rel_gap`` would read for the bfloat16 solve, beside the cell's
limit.  The check is sound only where every seed reads above the limit.
The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def control_gaps(cell, seeds, n=None):
    import numpy as np
    import jax
    from jax.sharding import SingleDeviceSharding
    import harness
    import traffic
    config = dict(cell.config, **({"n": n} if n is not None else {}))
    ref = harness._load_module(
        os.path.join(HERE, "references", config["reference"] + ".py"),
        "reference_" + config["reference"])
    nc = config["n"]
    n_pts = (nc + 1,) * 3 if config["layout"] == "node" else (nc,) * 3
    out = []
    for seed in seeds:
        gen = traffic.ClosedLoop(cell.mix, n_pts, config["L"],
                                 config["layout"], np.dtype(config["dtype"]),
                                 seed)
        base = np.asarray(gen.make_base(
            SingleDeviceSharding(jax.devices()[0])), np.float64)
        t = time.perf_counter()
        want = ref.solve(base, config["L"])
        got = ref.solve(base, config["L"], precision="bf16")
        out.append({"seed": seed, "rel_gap": harness.rel_gap(got, 1.0, want),
                    "limit": config["check"]["rel_gap"],
                    "seconds": time.perf_counter() - t})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    for row in control_gaps(cell, [int(s) for s in args.seeds.split(",")],
                            args.n):
        print(json.dumps(dict(row, workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
