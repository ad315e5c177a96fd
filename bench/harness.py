"""The benchmark's engine: finds a cell's files by name, sets up the
system under test, drives the timed window, checks the window's solves
against the plain reference, and reduces the trace.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found through ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration as it is run, and the
  name of its plain reference, ``bench/references/<reference>.py``;
* ``bench/traffic/<traffic>.json``: the traffic mix, read by ``traffic.py``;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number, or None when it finds nothing to read;
* ``bench/peaks.json``: the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import resource
import shutil
import time
from dataclasses import dataclass, field

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

import reduce as tracing         # noqa: E402
import traffic                   # noqa: E402
import work                      # noqa: E402


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _applies(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def _spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def workloads(chips: int) -> list:
    """Names of the cells that ask for ``chips`` chips."""
    return [w["name"] for w in _spec()["workloads"] if w["chips"] == chips]


def load_cell(workload: str) -> Cell:
    spec = _spec()
    wl = {w["name"]: w for w in spec["workloads"]}
    if workload not in wl:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(wl)}")
    w = wl[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as fh:
        config = json.load(fh)
    mix = traffic.load(traffic.path(BENCH, w["traffic"]))
    return Cell(w, config, mix,
                [m for m in spec["end_to_end"] if _applies(m, workload)],
                [m for m in spec["per_layer"] if _applies(m, workload)])


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads through JAX's
    monitoring events.  Listeners cannot be removed, so one counter is
    registered per process and read as differences."""

    _instance = None

    def __init__(self):
        import jax
        self.requests = 0      # backend compiles, including cache loads
        self.hits = 0          # loaded from the persistent cache
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def snapshot(self):
        return (self.requests, self.hits)


@dataclass
class Run:
    """Everything one run measured; the metric readers read it."""
    cell: Cell
    config: dict                 # the configuration as run
    device_kind: str = ""
    platform: str = ""
    n_devices: int = 0
    setup_s: float = 0.0
    plan_build_s: float = 0.0
    compile_s: float = 0.0
    step_s: list = field(default_factory=list)       # call to u ready
    host_call_s: list = field(default_factory=list)  # get_solver + solve
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    peak_bytes: list = field(default_factory=list)   # per device
    trace: object = None         # reduce.Trace of the traced steps
    mesh: tuple = (1, 1)
    peaks: dict = field(default_factory=dict)


def solver_kwargs(config: dict, mesh):
    """``repro.core.solver.get_solver`` arguments of a configuration."""
    import jax.numpy as jnp
    from repro.core.bc import BCType, DataLayout
    from repro.core.comm import CommConfig
    bct = {"unb": BCType.UNB, "periodic": BCType.PER,
           "even": BCType.EVEN, "odd": BCType.ODD}
    n = config["n"]
    shape = tuple(n) if isinstance(n, list) else (n, n, n)
    return dict(shape=shape, L=config["L"],
                bcs=tuple((bct[a], bct[b]) for a, b in config["bcs"]),
                layout=DataLayout(config["layout"]),
                green_kind=config["green"], engine=config["engine"],
                doubling=config["doubling"], relayout=config["relayout"],
                mesh=mesh, comm=CommConfig(config["comm"],
                                           config["comm_chunks"]),
                dtype=jnp.dtype(config["dtype"]).type)


def peaks_for(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def _no_span(name):
    return contextlib.nullcontext()


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile (the arithmetic of repro.serve.stats)."""
    xs = sorted(xs)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[min(rank, len(xs)) - 1]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, allow_cpu: bool = False, n: int | None = None,
             trace_dir: str | None = None, hook=None):
    """One run of ``cell``: returns ``(run, compared)`` where ``compared``
    maps each checked number to ``(value, limit)``; raises SystemExit with
    no result when the devices do not fit the cell.

    ``allow_cpu`` and ``n`` let a rehearsal or a test drive the same path
    on host devices at a small size.  ``hook(solver, f) -> u``, when
    given, stands in for ``solver.solve(f)`` (the fault tests break the
    timed path through it)."""
    import jax
    config = dict(cell.config)
    if n is not None:
        config["n"] = n
    chips = int(cell.workload["chips"])
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not allow_cpu:
        raise SystemExit(f"no TPU: JAX found {len(devs)} {platform} "
                         "device(s)")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    # every program, however quick to compile, goes to the cache, so a
    # warm run loads all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter.get()
    run = Run(cell, config, devs[0].device_kind, platform, len(devs))
    run.peaks = peaks_for(run.device_kind) if platform == "tpu" else {}
    log(f"{len(devs)} x {run.device_kind} ({platform}), jax "
        f"{jax.__version__}; cell {cell.workload['name']} on {chips} "
        f"chip(s); compile cache {cache_dir}; "
        f"{time.perf_counter() - t_start:.3f} s since start")

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.solver import clear_solver_cache, get_solver
    p1, p2 = config["mesh"]
    run.mesh = (p1, p2)
    mesh = Mesh(np.array(devs[:chips]).reshape(p1, p2),
                tuple(config["mesh_axes"]))
    kw = solver_kwargs(config, mesh)
    c0 = counter.snapshot()

    t = time.perf_counter()
    solver = get_solver(**kw)
    run.plan_build_s = time.perf_counter() - t
    order = tuple(solver.plan.order)
    log(f"plan build {run.plan_build_s:.3f} s; execution order {order}")
    sb = work.switch_bytes(config, order, p1, p2,
                           cell.mix.get("fields_per_step", 1))
    log(f"predicted collective bytes per step (per chip, a2a): "
        f"{sb} = {sum(sb)}")

    n_pts = solver.input_shape
    gen = traffic.ClosedLoop(cell.mix, n_pts, config["L"], config["layout"],
                             np.dtype(config["dtype"]), seed)
    # the first field is replicated; the rest follow the solver's output
    sharding = NamedSharding(mesh, P())
    gen.make_base(sharding)
    solve = hook or (lambda s, f: s.solve(f))
    t = time.perf_counter()
    u = solve(get_solver(**kw), gen.field(0))
    u.block_until_ready()
    run.compile_s = time.perf_counter() - t
    log(f"the solver returns {u.shape} {u.dtype} as {u.sharding.spec}; the "
        f"right-hand sides are made in that layout")
    if u.sharding != sharding:
        sharding = u.sharding
        gen.make_base(sharding)
    for k in range(int(cell.mix.get("warmup_steps", 2))):
        solve(get_solver(**kw), gen.field(k)).block_until_ready()
    del u
    c1 = counter.snapshot()
    run.setup_s = time.perf_counter() - t_start
    log(f"set-up {run.setup_s:.3f} s: plan build {run.plan_build_s:.3f} s, "
        f"first solve (compile or load) {run.compile_s:.3f} s; programs "
        f"compiled {c1[0] - c0[0] - (c1[1] - c0[1])}, loaded from the "
        f"cache {c1[1] - c0[1]}")

    # -- the timed window ------------------------------------------------
    rng = np.random.default_rng(list(traffic._words(seed)) + [1])
    pick = set(rng.choice(int(cell.mix["check_among_first"]),
                          size=int(cell.mix["check_steps"]),
                          replace=False).tolist())
    held = {}
    trace_steps = int(cell.mix["trace_steps"]) if trace else 0
    if trace:
        trace_dir = trace_dir or os.path.join(ROOT, ".bench", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
    stats = solver.stats
    config_at_start = dict(solver._cfg)
    cw = counter.snapshot()
    gc_s = []
    gc_clock = []

    def gc_timer(phase, info):
        if phase == "start":
            gc_clock[:] = [time.perf_counter()]
        elif gc_clock:
            gc_s.append(time.perf_counter() - gc_clock[0])

    gc.callbacks.append(gc_timer)
    k = 0
    u = None
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    load0 = os.getloadavg()[0]
    t_win = time.perf_counter()
    deadline = t_win + seconds
    while True:
        span = (jax.profiler.TraceAnnotation if k < trace_steps
                else _no_span)
        before = (len(stats["degradations"]), stats["retries"])
        t0 = time.perf_counter()
        with span("bench.step"):
            with span("bench.make_rhs"):
                f = gen.field(k)
            t1 = time.perf_counter()
            try:
                with span("bench.host_call"):
                    u = solve(get_solver(**kw), f)
                t2 = time.perf_counter()
                with span("bench.wait"):
                    u.block_until_ready()
                ok = True
            except Exception as e:   # a failed solve counts; the loop goes on
                log(f"step {k}: solve raised {type(e).__name__}: {e}")
                t2 = time.perf_counter()
                ok = False
        t3 = time.perf_counter()
        if (len(stats["degradations"]), stats["retries"]) != before:
            ok = False
        run.attempted += 1
        run.failed += 0 if ok else 1
        run.step_s.append(t3 - t0)
        run.host_call_s.append(t2 - t1)
        if ok and k in pick:
            held[k] = u
        k += 1
        if k == trace_steps:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if t3 >= deadline:
            break
    run.window_s = time.perf_counter() - t_win
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    gc.callbacks.remove(gc_timer)
    if k < trace_steps:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    if u is not None and run.failed == 0:
        held[k - 1] = u
    cw1 = counter.snapshot()
    log(f"window: {run.attempted} steps in {run.window_s:.3f} s, "
        f"{run.failed} failed; programs compiled inside the window: "
        f"{cw1[0] - cw[0]}")
    slow = sorted(range(len(run.step_s)), key=lambda i: -run.step_s[i])[:5]
    log(f"longest steps (index: ms) "
        f"{[(i, round(run.step_s[i] * 1e3, 3)) for i in slow]}; median "
        f"{float(np.median(run.step_s)) * 1e3:.3f} ms; {len(gc_s)} garbage "
        f"collections in the window took {sum(gc_s) * 1e3:.3f} ms, the "
        f"longest {max(gc_s, default=0) * 1e3:.3f} ms")
    q = {p: round(percentile(run.step_s, p) * 1e3, 3)
         for p in (5, 25, 50, 75, 90, 95, 99)}
    med = percentile(run.step_s, 50)
    log(f"step ms by percentile {q}; steps over 1.01x / 1.05x / 1.5x the "
        f"median: {sum(s > 1.01 * med for s in run.step_s)} / "
        f"{sum(s > 1.05 * med for s in run.step_s)} / "
        f"{sum(s > 1.5 * med for s in run.step_s)}; host call ms p50 "
        f"{percentile(run.host_call_s, 50) * 1e3:.3f} p95 "
        f"{percentile(run.host_call_s, 95) * 1e3:.3f}; context switches in "
        f"the window: {ru1.ru_nivcsw - ru0.ru_nivcsw} involuntary, "
        f"{ru1.ru_nvcsw - ru0.ru_nvcsw} voluntary; host load average "
        f"{load0:.2f} -> {os.getloadavg()[0]:.2f} on {os.cpu_count()} cores")
    run.peak_bytes = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs[:chips]]

    # -- correctness, after the window and the memory reading --------------
    held_host = {i: np.asarray(v, np.float64) for i, v in held.items()}
    base = np.asarray(gen.base, np.float64)
    scales = gen.scales
    stage_map = solver.stage_map()
    config_held = dict(solver._cfg) == config_at_start
    del held, u, f, gen, solver, stats
    clear_solver_cache()
    gc.collect()
    ref_mod = _load_module(
        os.path.join(BENCH, "references", config["reference"] + ".py"),
        "reference_" + config["reference"])
    t = time.perf_counter()
    ref = ref_mod.solve(base, config["L"])
    gaps = {i: rel_gap(ui, float(scales[i % len(scales)]), ref)
            for i, ui in sorted(held_host.items())}
    log(f"reference ({config['reference']}) took "
        f"{time.perf_counter() - t:.3f} s; relative gap per checked step "
        f"{gaps}")
    if run.trace is None and trace:
        path = tracing.find(trace_dir)
        run.trace = tracing.load(path) if path else None
    limit = float(config["check"]["rel_gap"])
    compared = {
        "rel_gap": (max(gaps.values()) if gaps else float("inf"), limit),
        "failed_solves": (run.failed, 0),
        "config_changed": (0 if config_held else 1, 0),
    }
    log(f"stage map: {stage_map}")
    return run, compared


def rel_gap(u: np.ndarray, s: float, ref: np.ndarray) -> float:
    """How far a solution ``u`` of ``s * base`` lies from ``s * ref``,
    the reference's solution of ``base``: the largest absolute gap over
    the largest absolute value of ``s * ref``."""
    return float(np.max(np.abs(u - s * ref)) / (abs(s) * np.max(np.abs(ref))))


def is_correct(compared: dict) -> bool:
    return all(v <= lim for v, lim in compared.values())


def read_metrics(run: Run, entries: list) -> dict:
    """Each metric's reader, by name: ``bench/metrics/<name>.py``."""
    out = {}
    for m in entries:
        mod = _load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                           "metric_" + m["name"].replace(".", "_"))
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(run: Run, compared: dict, trace: bool) -> dict:
    metrics = read_metrics(
        run, run.cell.per_layer if trace else run.cell.end_to_end)
    device = {"platform": run.platform, "kind": run.device_kind,
              "count": run.n_devices,
              "memory_peak_bytes": int(max(run.peak_bytes or [0]))}
    out = {"correct": is_correct(compared), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        tr = run.trace
        busy = tracing.per_device_mean(
            tr, lambda ops: tracing.length(
                tracing.union((o.start, o.end) for o in ops))) if tr else None
        w = tracing.window(tr) if tr else None
        device["busy_s"] = (busy or 0.0) * 1e-6
        device["window_s"] = (w[1] - w[0]) * 1e-6 if w else 0.0
        bd = tracing.breakdown(tr) if tr else {}
        if bd:
            out["breakdown"] = bd
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    return out
