"""The reductions over the program's scopes and spans and the aligned
clock (``spanreduce.py``), on a hand-made trace where every number is
known and on traces recorded on a TPU v5e (``data/``)."""
import gzip
import json
import os

import pytest

import reduce
import spanreduce
from harness import _load_module
from repro.runtime.spans import Span

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = os.path.join(DATA, "tpu_v5e_1chip_n32.trace.json.gz")


class _Run:
    def __init__(self, tr):
        self.trace = tr


def metric(name, tr):
    mod = _load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "m_" + name)
    return mod.read(_Run(tr))


def _x(pid, tid, name, ts, dur, scope=None, cat="loop fusion"):
    e = {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
         "name": name}
    if scope is not None:
        e["args"] = {"tf_op": scope, "hlo_category": cat}
    return e


@pytest.fixture
def handmade(tmp_path):
    """One device whose clock runs 10 us behind the host's.  Two steps in
    a 100 us host window; the device runs one module a step, device 0-30
    and 50-80 (host 10-40 and 60-90), launched at host 10 and 60 and
    completed at host 45 and 95, so the offset lies in [10, 15].

    Step 1's ops (device clock): fwd 0-10, pin 10-14, relayout 14-16,
    switch 16-19, a pin inside a stage 19-22, an unscoped copy 22-25,
    bwd 25-30.  Step 2: pin 50-52, bwd 52-80.  The host's solver spans:
    get_solver 1-3, solve 3-12 (pad 3-6, launch 8-12); get_solver 47-49,
    solve 49-62 (pad 49-53, launch 57-62)."""
    ev = [{"ph": "M", "pid": 1, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
           "args": {"name": "XLA Modules"}},
          {"ph": "M", "pid": 1, "tid": 3, "name": "thread_name",
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 9, "name": "process_name",
           "args": {"name": "/host:CPU"}}]
    p = "jit(_local_solve_scheduled)/"
    ops = [("fusion.1", 0, 10, p + "fwd.0/jit(fft):"),
           ("copy.1", 10, 4, p + "pin/layout_constraint:"),
           ("fusion.2", 14, 2, p + "relayout/transpose:"),
           ("fusion.3", 16, 3, p + "comm.a2a/transpose:"),
           ("copy.2", 19, 3, p + "fwd.1/pin/layout_constraint:"),
           ("copy.3", 22, 3, ""),
           ("fusion.4", 25, 5, p + "bwd.0/jit(fft):"),
           ("copy.4", 50, 2, p + "pin/layout_constraint:"),
           ("fusion.5", 52, 28, p + "bwd.1/jit(fft):")]
    ev += [_x(1, 3, *o) for o in ops]
    ev += [_x(1, 2, "jit__local_solve_scheduled(1)", 0, 30),
           _x(1, 2, "jit__local_solve_scheduled(1)", 50, 30),
           _x(9, 5, spanreduce.LAUNCH, 10, 1),
           _x(9, 5, spanreduce.LAUNCH, 60, 1),
           _x(9, 6, spanreduce.DONE, 45, 1),
           _x(9, 6, spanreduce.DONE, 95, 1)]
    spans = [("bench.window", 0, 100), ("bench.step", 0, 46),
             ("bench.host_call", 1, 11), ("flups.get_solver", 1, 2),
             ("flups.solve", 3, 9), ("flups.pad", 3, 3),
             ("flups.launch", 8, 4), ("bench.wait", 12, 34),
             ("bench.step", 46, 54), ("bench.host_call", 46, 16),
             ("flups.get_solver", 47, 2), ("flups.solve", 49, 13),
             ("flups.pad", 49, 4), ("flups.launch", 57, 5),
             ("bench.wait", 62, 36)]
    ev += [_x(9, 1, *s) for s in spans]
    path = tmp_path / "h.trace.json.gz"
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": ev}, fh)
    return reduce.load(str(path)), spanreduce.load_host(str(path))


def test_handmade_clock_and_solver_idle(handmade):
    tr, h = handmade
    assert spanreduce.clock_offset(h, "/device:TPU:0") == 10
    # aligned busy 10-40 and 60-90: gaps 0-10, 40-60, 90-100; the solver's
    # spans hold 1-10 of the first and 47-60 of the second: 22 us, 2 steps
    assert spanreduce.solver_idle_ms(tr, h) == pytest.approx(11e-3)
    assert spanreduce.idle_gaps(tr, h) == [
        ["flups.pad", pytest.approx(20e-6)],
        ["flups.pad", pytest.approx(10e-6)],
        ["bench.wait", pytest.approx(10e-6)]]
    # on the raw clock every gap looks like the host's wait
    assert {g[0] for g in reduce.breakdown(tr)["idle_gaps"]} == \
        {"bench.wait"}


def test_handmade_clock_fails_loudly(handmade):
    tr, h = handmade
    # a completion before its module could end: no offset fits
    h.dones[0] = (35.0, 36.0)
    assert spanreduce.clock_offset(h, "/device:TPU:0") is None
    assert spanreduce.solver_idle_ms(tr, h) is None
    assert spanreduce.idle_gaps(tr, h) is None
    h.dones.pop()
    assert spanreduce.clock_offset(h, "/device:TPU:0") is None
    # a trace without launch events keeps the raw clock
    h.launches, h.dones = [], []
    assert spanreduce.clock_offset(h, "/device:TPU:0") == 0


def test_handmade_pins_and_relayouts(handmade):
    tr, _ = handmade
    # pins outside a stage 4 + 2 us; relayout 2 + switch 3 us; 2 steps
    assert metric("pin_ms", tr) == pytest.approx(3e-3)
    assert metric("relayout_ms", tr) == pytest.approx(2.5e-3)
    # the unscoped copy (3 us) is the remainder of pipeline_other_ms
    assert metric("pipeline_other_ms", tr) == pytest.approx(7e-3)


def test_in_memory_spans():
    rec = [Span("flups.green.build", 1.0, 3.5, "flups.build", 0, {}),
           Span("flups.solve", 4.0, 6.0, None, 0, {}),
           Span("flups.solve", 7.0, 7.002, None, 1, {}),
           Span("flups.solve", 8.0, 8.004, None, 2, {})]
    assert spanreduce.green_build_s(rec) == 2.5
    assert spanreduce.solve_host_ms(rec, 1) == pytest.approx(3.0)
    assert spanreduce.green_build_s(rec[1:]) is None
    assert spanreduce.solve_host_ms(rec, 3) is None


def test_recorded_trace_clock_offset():
    """The n = 32 trace recorded before the program had spans: its modules
    start up to 232 us before the host launches them on the raw clock."""
    tr, h = reduce.load(OLD), spanreduce.load_host(OLD)
    dev = "/device:TPU:0"
    off = spanreduce.clock_offset(h, dev)
    # the launch bound; the completions allow up to 455.9 us
    assert off == pytest.approx(279.567, abs=1e-3)
    assert len(h.modules[dev]) == len(h.launches) == len(h.dones) == 24
    for (ms, me), (ls, _), (ds, _) in zip(h.modules[dev], h.launches,
                                          h.dones):
        assert ms + off >= ls and me + off <= ds
    # a program without pipeline scopes reads nothing, and no solver span
    assert metric("pin_ms", tr) is None and metric("relayout_ms", tr) is None
    assert spanreduce.solver_idle_ms(tr, h) is None
    # the raw clock puts all ten longest gaps in the host's wait; aligned,
    # most fall while the host makes the next right-hand side
    raw = [g[0] for g in reduce.breakdown(tr)["idle_gaps"]]
    aligned = [g[0] for g in spanreduce.idle_gaps(tr, h)]
    assert raw == ["bench.wait"] * 10
    assert aligned.count("bench.wait") == 1
    assert aligned.count("bench.make_rhs") == 7


def test_recorded_trace_with_spans():
    """A TPU v5e trace of 12 steps at n = 32 recorded with the solver's
    spans on (``bench/spans_run.py``; source paths removed from its op
    metadata): the offset puts every module between its launch and its
    completion, each traced step holds one lookup and one solve with its
    pad, launch and crop, the pipeline split lies inside
    ``pipeline_other_ms`` and the solver's idle inside the chip's."""
    path = os.path.join(DATA, "tpu_v5e_1chip_n32_spans.trace.json.gz")
    tr, h = reduce.load(path), spanreduce.load_host(path)
    dev = "/device:TPU:0"
    off = spanreduce.clock_offset(h, dev)
    assert off == pytest.approx(1192.776, abs=1e-3)
    for (ms, me), (ls, _), (ds, _) in zip(h.modules[dev], h.launches,
                                          h.dones):
        assert ms + off >= ls and me + off <= ds
    assert reduce.steps(tr) == 12
    flups = [s.name for s in h.spans if s.name.startswith("flups.")]
    for name in ("flups.get_solver", "flups.solve", "flups.pad",
                 "flups.launch", "flups.crop"):
        assert flups.count(name) == 12
    assert len(flups) == 60
    stages = {o.stage for o in reduce.device_ops(tr)[dev]} - {None}
    assert stages == {"fwd.0", "fwd.1", "fwd.2", "bwd.0", "bwd.1", "bwd.2"}
    # at n = 32 the pins compile to no copy; the switch's permutes remain
    pin, rel = metric("pin_ms", tr), metric("relayout_ms", tr)
    assert pin == 0 and rel > 0
    assert pin + rel <= metric("pipeline_other_ms", tr)
    w = reduce.window(tr)
    idle = reduce.length(reduce.gaps(
        spanreduce.aligned_ops(tr, h)[dev], *w)) / 12 * 1e-3
    assert 0 < spanreduce.solver_idle_ms(tr, h) < idle
