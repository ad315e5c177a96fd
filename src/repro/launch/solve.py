"""Distributed Poisson solve launcher (the paper's workload).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.solve --n 32 --p1 2 --p2 4 \
        --bcs unb --comm pipelined

Builds the pencil-decomposed solver on a (p1, p2) process grid, solves the
paper's fully-unbounded Gaussian-bump case and reports the error against
the analytical solution plus per-strategy timing.  On the TPU it solves in
f32 (x64 stays off); elsewhere the default is f64.  A solve that the
degradation ladder had to rescue (a degraded engine, comm strategy, layout
or doubling mode, or a retry) fails the run: the printed ``engine=`` is
the engine that ran.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--p1", type=int, default=1)
    ap.add_argument("--p2", type=int, default=1)
    ap.add_argument("--bcs", default="unb", choices=["unb", "per", "mix"])
    ap.add_argument("--layout", default="node", choices=["node", "cell"])
    ap.add_argument("--comm", default="a2a",
                    choices=["a2a", "pipelined", "fused", "overlap", "auto"])
    ap.add_argument("--chunks", type=int, default=2,
                    help="pipelined/overlap granularity (paper's n_batch)")
    ap.add_argument("--green", default="chat2")
    ap.add_argument("--engine", default="xla", choices=["xla", "pallas"],
                    help="transform engine: pure XLA or the Pallas kernels")
    ap.add_argument("--dtype", default=None, choices=["float32", "float64"],
                    help="solve precision (default: float32 on the TPU, "
                         "float64 elsewhere)")
    ap.add_argument("--doubling", default="deferred",
                    choices=["deferred", "upfront"],
                    help="Hockney doubling: deferred (pruned transforms + "
                         "valid-extent switches, default) or upfront (dense "
                         "textbook baseline -- the bench_solve comparison)")
    ap.add_argument("--relayout", default="scheduled",
                    choices=["scheduled", "baseline"],
                    help="data-layout policy: scheduled (plan-time layout "
                         "schedule, relayouts folded into the topology "
                         "switches, default) or baseline (per-direction "
                         "moveaxis round trips -- the A/B reference)")
    ap.add_argument("--batch", type=int, default=1,
                    help="right-hand sides per solve (batched multi-RHS "
                         "pipeline when > 1)")
    ap.add_argument("--steps", type=int, default=1,
                    help="driver steps; each step re-acquires the solver "
                         "through the global plan cache (CFD-loop shape)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint directory; enables the survivable "
                         "--steps loop (periodic save, restart/resume, "
                         "elastic rebuild on injected device loss)")
    ap.add_argument("--ckpt-every", type=int, default=2,
                    help="checkpoint every k steps (with --ckpt)")
    ap.add_argument("--search", default="guided",
                    choices=["guided", "brute"],
                    help="comm=auto candidate policy: guided (cost-model "
                         "shortlist, times ~1/6 of the space) or brute "
                         "(exhaustive sweep -- the oracle reference)")
    ap.add_argument("--verify", default=None,
                    choices=["nan", "residual", "abft"],
                    help="opt-in per-solve health guard: nan/residual "
                         "(runtime.health) or abft (checksum-sandwiched "
                         "pipeline with localize-and-recompute, "
                         "runtime.abft / DESIGN.md #13)")
    args = ap.parse_args(argv)

    import os
    n_dev = args.p1 * args.p2
    if "XLA_FLAGS" not in os.environ:  # must precede the first jax import
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n_dev}"

    import jax
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    platform = jax.devices()[0].platform
    dtype = solve_dtype(args.dtype)
    import jax.numpy as jnp
    from repro.core.analytic import case_a, case_b
    from repro.core.bc import BCType, DataLayout
    from repro.core.comm import CommConfig
    from repro.core.solver import get_solver, solver_cache_info

    E, O, P, U = BCType.EVEN, BCType.ODD, BCType.PER, BCType.UNB
    bcs = {"unb": ((U, U),) * 3,
           "per": ((P, P),) * 3,
           "mix": ((E, E), (O, E), (P, P))}[args.bcs]
    layout = DataLayout.NODE if args.layout == "node" else DataLayout.CELL

    n_dev = args.p1 * args.p2
    assert n_dev <= len(jax.devices()), (
        f"need {n_dev} devices; run with "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={n_dev}")
    mesh = jax.make_mesh((args.p1, args.p2), ("data", "model"))
    comm = ("auto" if args.comm == "auto"
            else CommConfig(strategy=args.comm, n_chunks=args.chunks))
    solver = get_solver(
        (args.n,) * 3, 1.0, bcs, layout=layout, green_kind=args.green,
        mesh=mesh, comm=comm, dtype=jnp.dtype(dtype),
        engine=args.engine, doubling=args.doubling,
        relayout=args.relayout, autotune_search=args.search)
    if args.comm == "auto":
        picked = (f"{solver.comm.strategy}"
                  f"(n_chunks={solver.comm.n_chunks})")
        cen = solver.autotune_census
        if args.search == "guided" and cen.get("shortlist") is not None:
            print(f"[solve] guided search: {cen['space']} candidates -> "
                  f"{len(cen['shortlist'])} timed "
                  f"({len(cen.get('pruned_padding', []))} pruned on "
                  "padding overhead)")
        if solver.autotune_results:
            print(f"[solve] comm=auto -> {picked}, candidates: " +
                  ", ".join(f"{k}={v*1e3:.1f}ms"
                            for k, v in sorted(
                                solver.autotune_results.items())))
        else:
            print(f"[solve] comm=auto -> {picked} (cached winner, "
                  "sweep skipped)")

    # rhs: the paper's validation field for the chosen BCs
    rhs, sol = (case_b if args.bcs == "unb" else case_a)(args.n, layout)
    if args.bcs == "per":
        # simple periodic field
        h = 1.0 / args.n
        pts = (np.arange(args.n + (layout == DataLayout.NODE)) *
               h if layout == DataLayout.NODE
               else (np.arange(args.n) + 0.5) * h)
        x, y, z = np.meshgrid(pts, pts, pts, indexing="ij")
        sol = np.sin(2 * np.pi * x) * np.sin(4 * np.pi * y) * \
            np.cos(2 * np.pi * z)
        rhs = -(4 + 16 + 4) * np.pi ** 2 * sol

    rhs = rhs.astype(dtype)
    if args.batch > 1:
        rhs = np.broadcast_to(rhs, (args.batch,) + rhs.shape).copy()

    if args.ckpt is not None:
        return _run_survivable(args, solver, mesh, comm, rhs, sol, bcs,
                               layout)

    u = solver.solve(rhs)          # compile + warm
    u.block_until_ready()
    t0 = time.perf_counter()
    for step in range(max(args.repeats, args.steps)):
        # CFD-driver shape: every step re-acquires the (cached) solver
        solver = get_solver(
            (args.n,) * 3, 1.0, bcs, layout=layout, green_kind=args.green,
            mesh=mesh, comm=comm, dtype=jnp.dtype(dtype),
            engine=args.engine, doubling=args.doubling,
            relayout=args.relayout, autotune_search=args.search)
        u = solver.solve(rhs)
        u.block_until_ready()
    reps = max(args.repeats, args.steps)
    dt = (time.perf_counter() - t0) / reps
    require_clean(solver)
    u0 = np.asarray(u[0] if args.batch > 1 else u, dtype=np.float64)
    err = float(np.max(np.abs(u0 - sol)))
    thr = rhs.nbytes / dt / 1e6 / n_dev
    ci = solver_cache_info()
    print(f"[solve] n={args.n}^3 grid, ({args.p1}x{args.p2}) pencils, "
          f"comm={solver.comm.strategy}, engine={solver.engine.name}, "
          f"dtype={dtype}, batch={args.batch}, on {n_dev} {platform}: "
          f"{dt*1e3:.1f} ms/solve, E_inf={err:.3e}, "
          f"rel E_inf={err / np.max(np.abs(sol)):.3e}, "
          f"throughput {thr:.1f} MB/s/rank, "
          f"plan-cache {ci['hits']} hits / {ci['misses']} misses")
    return err


def solve_dtype(requested=None) -> str:
    """The solve dtype: ``requested``, else float32 on the TPU and float64
    elsewhere.  x64 is turned on only for a float64 solve."""
    import jax
    dtype = requested or ("float32" if jax.devices()[0].platform == "tpu"
                          else "float64")
    if dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    return dtype


def require_clean(solver):
    """Raise unless every solve so far ran the configuration asked for:
    no degradation-ladder rung and no retry.  The ladder is for runs that
    arm faults; on the main path a rescue would hide a broken engine."""
    st = solver.stats
    if st["degradations"] or st.get("retries", 0):
        raise RuntimeError(
            f"the solve did not run as configured: {st.get('retries', 0)} "
            f"retries, degradations {st['degradations']}")


def _run_survivable(args, solver, mesh, comm, rhs, sol, bcs, layout):
    """The --ckpt variant of the --steps loop: a long-running CFD-style
    driver that checkpoints every ``--ckpt-every`` steps, restarts from the
    last valid step, and survives an injected device loss by rebuilding the
    solver on the shrunken surviving mesh (elastic recovery) and resuming
    from the last checkpoint.  Faults are armed via ``$REPRO_FAULTS``."""
    import contextlib
    import os

    import jax
    from jax.sharding import Mesh
    from repro.ckpt import checkpoint as ck
    from repro.runtime import faults

    plan = faults.plan_from_env()
    with (plan if plan is not None else contextlib.nullcontext()):
        # the driver state: an accumulated field (the stand-in for the
        # evolving CFD solution) -- what checkpoints must preserve
        acc = np.zeros(np.shape(rhs), dtype=np.float64)
        last = ck.latest_step(args.ckpt)
        step = 0
        if last is not None:
            acc = np.array(ck.restore(args.ckpt, last, acc),
                           dtype=np.float64)
            step = last + 1
            print(f"[solve] resuming from checkpoint step {last}")
        p1, p2 = args.p1, args.p2
        losses = 0
        while step < args.steps:
            if faults.should_fire("device_loss", step=step) and \
                    hasattr(solver, "rebuild"):
                # half the devices are gone: shrink to the survivors,
                # re-plan (Green + autotune cache reused), roll back to the
                # last checkpoint and resume there
                losses += 1
                if p1 > 1:
                    p1 //= 2
                elif p2 > 1:
                    p2 //= 2
                devs = np.array(jax.devices()[:p1 * p2]).reshape(p1, p2)
                mesh = Mesh(devs, mesh.axis_names)
                print(f"[solve] device loss at step {step}: rebuilding on "
                      f"({p1}x{p2}) surviving mesh")
                solver = solver.rebuild(mesh)
                last = ck.latest_step(args.ckpt)
                if last is None:
                    acc = np.zeros_like(acc)
                    step = 0
                else:
                    acc = np.array(ck.restore(args.ckpt, last, acc),
                           dtype=np.float64)
                    step = last + 1
                print(f"[solve] resumed at step {step}")
                continue
            # per-step rhs scaling: steps are distinguishable, so a resume
            # from the wrong step shows up in the final accumulated field
            u = solver.solve(rhs * (1.0 / (1 + step)), verify=args.verify)
            acc += np.asarray(u, dtype=np.float64)
            if (step + 1) % args.ckpt_every == 0:
                ck.save(args.ckpt, step, acc)
            step += 1

    if plan is None:                 # no faults armed: nothing to rescue
        require_clean(solver)
    scale = sum(1.0 / (1 + k) for k in range(args.steps))
    acc0 = acc[0] if args.batch > 1 else acc
    err = float(np.max(np.abs(acc0 / scale - sol)))
    stats = getattr(solver, "stats", {})
    ndeg = len(stats.get("degradations", ()))
    print(f"[solve] survivable loop: {args.steps} steps on final "
          f"({p1}x{p2}) mesh, {losses} device losses, "
          f"{ndeg} degradations, E_inf={err:.3e}")
    report_path = os.environ.get("REPRO_CHAOS_LOG")
    if report_path:
        # the CI chaos job uploads this as its artifact: what was injected,
        # what fired, what the ladder did about it, and the final error
        import json
        with open(report_path, "w") as fh:
            json.dump({"steps": args.steps, "final_mesh": [p1, p2],
                       "device_losses": losses, "err_inf": err,
                       "fault_log": plan.log if plan is not None else [],
                       "retries": stats.get("retries", 0),
                       "degradations": stats.get("degradations", []),
                       "integrity": stats.get("integrity", [])},
                      fh, indent=2)
        print(f"[solve] chaos report written to {report_path}")
    return err


if __name__ == "__main__":
    main()
