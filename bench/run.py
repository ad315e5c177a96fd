#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix.  The run builds the solver through ``get_solver``, makes the
seeded right-hand sides on the device, warms every program up (set-up),
then drives ``get_solver(...).solve(f)`` for ``--seconds``, one field per
step, waiting for each solution.  After the window it reads the device's
peak memory, compares the checked steps with the plain reference and, with
``--trace 1``, reduces the profiler trace of the first traced steps to the
cell's per-layer metrics.  The last line of stdout is one JSON object; the
numbers compared and their limits are also the last lines of stderr.

Without a TPU, or with fewer chips than the cell needs, it exits nonzero
and prints no result.  ``--n`` runs the same path at a smaller grid on
whatever devices JAX has (a rehearsal): it prints no result either.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--n", type=int, default=None,
                    help="cells per side instead of the configuration's "
                         "(a rehearsal: prints no result)")
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    run, compared = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_START,
        allow_cpu=args.n is not None, n=args.n)
    line = harness.result_line(run, compared, bool(args.trace))
    for k, (v, lim) in compared.items():
        print(f"compared {k} = {v!r} limit {lim!r}", file=sys.stderr)
    if args.n is not None or run.platform != "tpu":
        harness.log(f"rehearsal at n={args.n} on {run.platform}: "
                    f"{json.dumps(line)}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
