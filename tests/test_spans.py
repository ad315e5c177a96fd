"""The solver's host spans (``repro.runtime.spans``) and the device scopes
between its stages.

* off (the default), ``span`` records nothing, calls no profiler and
  reads no clock;
* on, a ``get_solver`` that misses records the build under it, one that
  hits records only itself, and each solve records its pad, launch and
  crop, all of one step;
* the lowered 2x2 solve names its pins, relayouts and switches
  (subprocess, 4 host devices).
"""
import os
import subprocess
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.bc import BCType, DataLayout
from repro.core.solver import clear_solver_cache, get_solver
from repro.runtime import spans

U = BCType.UNB


@pytest.fixture
def spans_off():
    yield
    spans.disable()
    spans.drain()


def _boom(*a, **k):
    raise AssertionError("called while spans are off")


def _kw(n=16):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    return dict(shape=(n, n, n), L=1.0, bcs=((U, U),) * 3,
                layout=DataLayout.NODE, mesh=mesh, dtype=jnp.float32)


def test_off_calls_no_profiler_and_reads_no_clock(monkeypatch, spans_off):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _boom)
    monkeypatch.setattr(spans, "time", types.SimpleNamespace(
        perf_counter=_boom))
    with spans.span("flups.x", a=1) as sp:
        sp.set("hit", True)
    clear_solver_cache()
    kw = _kw(8)
    f = np.ones(get_solver(**kw).input_shape, np.float32)
    get_solver(**kw).solve(f).block_until_ready()
    assert spans.drain() == []
    # on, the same span reaches both
    spans.enable()
    with pytest.raises(AssertionError, match="spans are off"):
        with spans.span("flups.x"):
            pass


def test_on_annotates_the_profiler(monkeypatch, spans_off):
    seen = []

    class Ann:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **args):
            seen.append(("set", args))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    spans.enable()
    with spans.span("flups.get_solver", k=2) as sp:
        sp.set("hit", False)
    (s,) = spans.drain()
    assert seen == [("flups.get_solver", {"k": 2}), ("set", {"hit": False})]
    assert s.name == "flups.get_solver" and s.args == {"k": 2, "hit": False}
    assert s.start <= s.end and s.parent is None and s.step == 0
    assert spans.drain() == []


def _tree(recorded):
    return [(s.name, s.parent, s.step, s.args) for s in recorded]


def test_spans_of_a_miss_and_a_hit(spans_off):
    clear_solver_cache()
    kw = _kw()
    spans.enable()
    s = get_solver(**kw)
    f = np.random.default_rng(0).standard_normal(s.input_shape)
    get_solver(**kw).solve(f.astype(np.float32)).block_until_ready()
    first = spans.drain()
    assert _tree(first) == [
        ("flups.plan", "flups.build", 0, {}),
        ("flups.green.build", "flups.build", 0, {}),
        ("flups.green.layout", "flups.build", 0, {}),
        ("flups.build", "flups.get_solver", 0, {}),
        ("flups.get_solver", None, 0, {"hit": False}),
        ("flups.get_solver", None, 0, {"hit": True}),
        ("flups.pad", "flups.solve", 0, {}),
        ("flups.green.put", "flups.solve", 0, {}),
        ("flups.launch", "flups.solve", 0, {}),
        ("flups.crop", "flups.solve", 0, {}),
        ("flups.solve", None, 0, {}),
    ]
    for k in (1, 2):
        get_solver(**kw).solve(f.astype(np.float32)).block_until_ready()
        hit = spans.drain()
        assert _tree(hit) == [
            ("flups.get_solver", None, k, {"hit": True}),
            ("flups.pad", "flups.solve", k, {}),
            ("flups.launch", "flups.solve", k, {}),
            ("flups.crop", "flups.solve", k, {}),
            ("flups.solve", None, k, {}),
        ]
        solve = hit[-1]
        assert hit[0].end <= solve.start
        for c in hit[1:4]:
            assert solve.start <= c.start <= c.end <= solve.end
    # the single-process solver: its solve holds its launch
    spans.drain()
    kw.pop("mesh"), kw.pop("dtype")
    get_solver(**kw).solve(f).block_until_ready()
    assert [(s.name, s.parent) for s in spans.drain()
            if s.name in ("flups.launch", "flups.solve")] == [
        ("flups.launch", "flups.solve"), ("flups.solve", None)]


_LOWER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
from repro.core.bc import BCType, DataLayout
from repro.core.comm import CommConfig
from repro.distributed.pencil import DistributedPoissonSolver

U = BCType.UNB
mesh = jax.make_mesh((2, 2), ("data", "model"))
for strat in ("a2a", "overlap"):
    s = DistributedPoissonSolver(
        (16,) * 3, 1.0, ((U, U),) * 3, DataLayout.NODE, mesh=mesh,
        dtype=jnp.float32, comm=CommConfig(strat, 2),
        order_policy="natural", lazy_green=True)
    text = s.lower().as_text(debug_info=True)
    for name in ("pin/layout_constraint", "relayout/transpose",
                 f"comm.{strat}/all_to_all", "fwd.0/", "green/"):
        assert f'"{name}' in text, (strat, name)
print("OK")
"""


def test_lowered_2x2_solve_names_pins_relayouts_and_switches():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _LOWER_SCRIPT], capture_output=True,
        text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
