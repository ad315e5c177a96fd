"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick mode
    PYTHONPATH=src python -m benchmarks.run --full     # paper-sized grids

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: convergence,comm,scaling,biot,"
                         "kernels,train,batch,solve")
    args = ap.parse_args()
    quick = not args.full

    import jax
    jax.config.update("jax_enable_x64", True)

    from common import emit
    jobs = {
        "convergence": "bench_convergence",
        "biot": "bench_biot_savart",
        "comm": "bench_comm",
        "scaling": "bench_scaling",
        "kernels": "bench_kernels",
        "train": "bench_train",
        "batch": "bench_batch",
        "solve": "bench_solve",
    }
    only = args.only.split(",") if args.only else list(jobs)
    # the trailing column tags interpret-mode (CPU-emulated Pallas) timings,
    # which are excluded from every speedup claim -- see common.emit
    print("name,us_per_call,derived,timing")
    for key in only:
        mod = __import__(jobs[key])
        try:
            emit(mod.run(quick=quick))
        except Exception as e:  # keep the harness going
            emit([(f"{key}_ERROR", 0.0,
                   f"{type(e).__name__}: {e}")])


if __name__ == "__main__":
    main()
