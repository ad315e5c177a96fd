#!/usr/bin/env python3
"""Run one cell as ``bench/run.py --trace 1`` does, with the solver's host
spans on, and print what only those spans can show.

    python3 bench/spans_run.py --workload <name> --seed <n> --seconds <s>
                               [--n <cells>] [--keep <path>]

The benchmark's own runs leave the spans off.  This runner turns them on
(``repro.runtime.spans.enable()``) before the solver is built, then drives
the cell through ``harness.run_cell`` with its first steps traced.  The
last line of stdout is one JSON object: the cell's metrics as a traced run
reads them, and the readings of ``spanreduce.py``: ``green_build_s``,
``solve_host_ms``, ``solver_idle_ms``, the clock offset of each chip and
the longest idle gaps labelled on the aligned clock.  ``--keep`` copies
the raw trace to a path (the recorded traces of ``bench/tests/data``).
``--n`` runs the cell at a smaller grid on whatever devices JAX has.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    import harness
    import reduce
    import spanreduce
    from repro.runtime import spans

    cell = harness.load_cell(args.workload)
    trace_dir = os.path.join(harness.ROOT, ".bench", "spans_trace")
    spans.enable()
    run, compared = harness.run_cell(
        cell, args.seed, args.seconds, True, T_START,
        allow_cpu=args.n is not None, n=args.n, trace_dir=trace_dir)
    recorded = spans.drain()
    spans.disable()
    path = reduce.find(trace_dir)
    host = spanreduce.load_host(path)
    if args.keep:
        shutil.copyfile(path, args.keep)
    first = 1 + int(cell.mix.get("warmup_steps", 2))
    line = harness.result_line(run, compared, True)
    line["metrics"].update(harness.read_metrics(run, cell.end_to_end))
    readings = {
        "green_build_s": spanreduce.green_build_s(recorded),
        "solve_host_ms": spanreduce.solve_host_ms(recorded, first),
        "solver_idle_ms": spanreduce.solver_idle_ms(run.trace, host),
        "clock_offset_us": {d: spanreduce.clock_offset(host, d)
                            for d in sorted(run.trace.devices)},
        "idle_gaps": spanreduce.idle_gaps(run.trace, host),
    }
    setup = {}
    for s in recorded:
        if s.step < first:
            setup[s.name] = setup.get(s.name, 0.0) + s.end - s.start
    readings["setup_spans_s"] = setup
    line["spans"] = readings
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
