"""The trace reduction on a hand-made trace, where every number is known,
and on a small trace recorded on a TPU v5e (``data/``)."""
import gzip
import json
import os

import pytest

import reduce
from harness import _load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _meta(pid, name, threads):
    ev = [{"ph": "M", "pid": pid, "name": "process_name",
           "args": {"name": name}}]
    for tid, tname in threads.items():
        ev.append({"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                   "args": {"name": tname}})
    return ev


def _op(pid, name, ts, dur, scope, cat="convolution fusion", tid=3):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": scope, "hlo_category": cat}}


def _span(name, ts, dur):
    return {"ph": "X", "pid": 9, "tid": 1, "ts": ts, "dur": dur,
            "name": name}


@pytest.fixture
def handmade(tmp_path):
    """Two devices, two steps in a 100 us window.  Device 0: fwd 10-30,
    all-to-all 30-50 overlapped by a transpose 40-45, green 50-55, bwd
    55-75 and 80-90 (step 2), an unscoped copy 90-95.  Device 1: the
    same shifted by +2 us, without the copy."""
    ev = _meta(1, "/device:TPU:0", {3: "XLA Ops", 4: "Async XLA Ops"})
    ev += _meta(2, "/device:TPU:1", {3: "XLA Ops"})
    ev += _meta(9, "/host:CPU", {1: "python3"})
    for pid, sh in ((1, 0.0), (2, 2.0)):
        p = "jit(_local_solve_scheduled)/"
        ev += [_op(pid, "fusion.1", 10 + sh, 20, p + "fwd.2/jit(fft):"),
               _op(pid, "all-to-all.3", 30 + sh, 20, p + "all_to_all:",
                   "collective"),
               _op(pid, "fusion.9", 40 + sh, 5, p + "transpose:",
                   "data formatting"),
               _op(pid, "fusion.2", 50 + sh, 5, p + "green/mul:",
                   "loop fusion"),
               _op(pid, "fusion.3", 55 + sh, 20, p + "bwd.2/jit(fft):"),
               _op(pid, "fusion.4", 80 + sh, 10, p + "bwd.1/jit(fft):")]
    ev += [_op(1, "copy.7", 90, 5, "", "data formatting"),
           # async copies and ops outside the window do not count
           _op(1, "copy-start", 0, 200, "", "data formatting", tid=4),
           _op(1, "fusion.0", -50, 20, "jit(f)/fwd.0/jit(fft):"),
           _span("bench.window", 0, 100), _span("bench.step", 0, 48),
           _span("bench.host_call", 1, 9), _span("bench.wait", 10, 38),
           _span("bench.step", 50, 50), _span("bench.wait", 95, 5)]
    path = tmp_path / "h.trace.json.gz"
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": ev}, fh)
    return reduce.load(str(path))


class _Run:
    def __init__(self, tr):
        self.trace = tr


def metric(name, tr):
    mod = _load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "m_" + name)
    return mod.read(_Run(tr))


def test_interval_arithmetic():
    assert reduce.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert reduce.length([[1, 4], [5, 8]]) == 6
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 12)]) == 2 + 2
    assert reduce.subtract([(0, 10), (20, 30)], []) == 20
    assert reduce.gaps([reduce.Op("a", 2, 4, "", ""),
                        reduce.Op("b", 6, 7, "", "")], 0, 10) == \
        [(0, 2), (4, 6), (7, 10)]


def test_handmade_trace(handmade):
    tr = handmade
    assert set(tr.devices) == {"/device:TPU:0", "/device:TPU:1"}
    assert reduce.window(tr) == (0.0, 100.0) and reduce.steps(tr) == 2
    # per step, mean over the two devices, in ms
    assert metric("transforms_ms", tr) == pytest.approx((20 + 20 + 10) / 2e3)
    assert metric("green_ms", tr) == pytest.approx(5 / 2e3)
    # device 0: transpose 5 + copy 5; device 1: transpose 5
    assert metric("pipeline_other_ms", tr) == pytest.approx(7.5 / 2e3)
    assert reduce.per_step_ms(tr, reduce.collective_time) == \
        pytest.approx(20 / 2e3)
    # 5 of the 20 us of all-to-all overlap the transpose
    assert reduce.per_step_ms(tr, reduce.exposed_collective_time) == \
        pytest.approx(15 / 2e3)
    # device 0 busy 10-75, 80-95: 80 of 100; device 1 busy 12-77, 82-92:
    # 75 -> the idler one, 25%
    assert metric("device_idle_pct", tr) == pytest.approx(25.0)
    bd = reduce.breakdown(tr)
    top = dict(map(tuple, bd["device_ops"][:3]))
    assert set(top) == {"fwd.2", "bwd.2", "collective:all-to-all"}
    assert all(v == pytest.approx(20e-6) for v in top.values())
    # the longest gap: device 0 at 0-10, while the host called the solve
    assert bd["idle_gaps"][0] == ["bench.host_call", pytest.approx(12e-6)]


def test_one_chip_has_no_switch(handmade):
    assert reduce.has_collectives(handmade)
    for ops in handmade.devices.values():
        ops[:] = [o for o in ops if not o.collective]
    assert not reduce.has_collectives(handmade)
    assert reduce.per_step_ms(handmade, reduce.collective_time) == 0


def test_no_trace_reads_nothing():
    for name in ("transforms_ms", "green_ms", "pipeline_other_ms",
                 "device_idle_pct"):
        assert metric(name, None) is None


def test_recorded_one_chip_trace():
    """A TPU v5e trace of 12 steps of the one-chip cell at n = 32: every
    op of the solve sits under a stage scope or outside all of them, the
    per-stage times add up to the busy time, and no collective runs."""
    tr = reduce.load(os.path.join(DATA, "tpu_v5e_1chip_n32.trace.json.gz"))
    assert list(tr.devices) == ["/device:TPU:0"]
    assert reduce.steps(tr) == 12
    ops = reduce.device_ops(tr)["/device:TPU:0"]
    stages = {o.stage for o in ops} - {None}
    assert stages == {"fwd.0", "fwd.1", "fwd.2", "bwd.0", "bwd.1", "bwd.2"}
    assert not reduce.has_collectives(tr)
    parts = [metric(m, tr) for m in ("transforms_ms", "green_ms",
                                     "pipeline_other_ms")]
    busy = reduce.length(reduce.union((o.start, o.end) for o in ops))
    # ops on the XLA Ops line do not overlap, so the parts tile the busy time
    assert sum(parts) == pytest.approx(busy / 12 * 1e-3, rel=1e-9)
    w = reduce.window(tr)
    idle = metric("device_idle_pct", tr)
    assert idle == pytest.approx(100 * (1 - busy / (w[1] - w[0])))
    assert 0 < idle < 100
