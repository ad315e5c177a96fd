"""Reduce a profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.trace.json.gz``
(Chrome trace format).  In it every device is a process named
``/device:<KIND>:<i>``; its thread ``XLA Ops`` holds one complete event per
executed HLO operation, with the operation's ``tf_op`` (the ``named_scope``
path it was traced under, e.g. ``jit(_local_solve_scheduled)/fwd.2/jit(fft):``)
and ``hlo_category`` in ``args``.  The benchmark's own host spans are the
events named ``bench.*`` on the host's threads.  Times are in microseconds
on one clock; device and host timestamps agree to within about a
millisecond, so a device interval is matched to host spans only to label
idle gaps.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

# a stage scope of the solve pipeline: fwd.<d>, bwd.<d>, fwd.<d>+green, green
_STAGE = re.compile(r"^(fwd|bwd)\.\d(\+green)?$|^green$")
_COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|ragged-all-to-all|send|recv)")


@dataclass(frozen=True)
class Op:
    name: str          # HLO instruction name, e.g. "fusion.24"
    start: float       # us
    end: float         # us
    scope: str         # tf_op: named-scope path of the op
    category: str      # hlo_category

    @property
    def stage(self) -> str | None:
        """The pipeline stage scope the op ran under, innermost first, or
        None outside every stage."""
        for part in reversed(self.scope.rstrip(":").split("/")):
            if _STAGE.match(part):
                return part
        return None

    @property
    def collective(self) -> bool:
        return bool(_COLLECTIVE.match(self.name)) or \
            "collective" in self.category


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # device name -> [Op]
    spans: list = field(default_factory=list)     # [Span], host clock


def find(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    tr = Trace()
    for e in events:
        if e.get("ph") != "X":
            continue
        pname = procs.get(e["pid"], "")
        start, end = float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0))
        if pname.startswith("/device:"):
            if threads.get((e["pid"], e["tid"])) != "XLA Ops":
                continue
            a = e.get("args", {})
            tr.devices.setdefault(pname, []).append(
                Op(e["name"], start, end, a.get("tf_op", ""),
                   a.get("hlo_category", "")))
        elif e["name"].startswith("bench."):
            tr.spans.append(Span(e["name"], start, end))
    for ops in tr.devices.values():
        ops.sort(key=lambda o: o.start)
    tr.spans.sort(key=lambda s: s.start)
    return tr


def union(intervals) -> list:
    """Merged, sorted, disjoint intervals covering ``intervals``."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def clip(ops, lo: float, hi: float) -> list:
    """Ops that overlap [lo, hi], cut to it."""
    out = []
    for o in ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a:
            out.append(Op(o.name, a, b, o.scope, o.category))
    return out


def subtract(intervals, cover) -> float:
    """Length of the parts of ``intervals`` that ``cover`` leaves free."""
    cover = union(cover)
    free = 0.0
    for a, b in union(intervals):
        t = a
        for c, d in cover:
            if d <= t or c >= b:
                continue
            if c > t:
                free += c - t
            t = max(t, d)
            if t >= b:
                break
        if t < b:
            free += b - t
    return free


def window(tr: Trace) -> tuple | None:
    """The traced window: the benchmark's ``bench.window`` span."""
    w = [s for s in tr.spans if s.name == "bench.window"]
    return (w[0].start, w[0].end) if w else None


def steps(tr: Trace) -> int:
    return sum(1 for s in tr.spans if s.name == "bench.step")


def device_ops(tr: Trace) -> dict:
    """Each device's ops inside the traced window."""
    w = window(tr)
    if w is None:
        return {}
    return {d: clip(ops, *w) for d, ops in tr.devices.items()}


def per_device_mean(tr: Trace, fn) -> float | None:
    """Mean over devices of ``fn(ops)`` (microseconds), or None when the
    trace holds no device."""
    ops = device_ops(tr)
    if not ops:
        return None
    return sum(fn(o) for o in ops.values()) / len(ops)


def stage_time(ops, pred) -> float:
    """Busy time (union) of the ops for which ``pred(op)`` holds."""
    return length(union((o.start, o.end) for o in ops if pred(o)))


def gaps(ops, lo: float, hi: float) -> list:
    """Idle intervals of one device inside [lo, hi]."""
    out, t = [], lo
    for a, b in union((o.start, o.end) for o in ops):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def host_label(tr: Trace, t: float) -> str:
    """The innermost benchmark span that holds time ``t``."""
    best = None
    for s in tr.spans:
        if s.start <= t <= s.end and s.name != "bench.window":
            if best is None or s.end - s.start < best.end - best.start:
                best = s
    return best.name if best else "outside bench spans"


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The longest device ops (by stage scope, collective, or op name;
    seconds summed over the window and averaged over devices) and the
    longest idle gaps (named by what the host was doing)."""
    ops = device_ops(tr)
    w = window(tr)
    if not ops or w is None:
        return {}
    tot = {}
    for dev_ops in ops.values():
        for o in dev_ops:
            key = o.stage or ("collective:" + o.name.split(".")[0]
                              if o.collective else o.name.split(".")[0]
                              + ":" + (o.scope.split("/")[-1].rstrip(":")
                                       or o.category))
            tot[key] = tot.get(key, 0.0) + (o.end - o.start) * 1e-6
    n = len(ops)
    dev = sorted(((k, v / n) for k, v in tot.items()), key=lambda kv: -kv[1])
    idle = []
    for dev_ops in ops.values():
        for a, b in gaps(dev_ops, *w):
            idle.append((host_label(tr, (a + b) / 2), (b - a) * 1e-6))
    idle.sort(key=lambda kv: -kv[1])
    return {"device_ops": [list(x) for x in dev[:top]],
            "idle_gaps": [list(x) for x in idle[:top]]}


def per_step_ms(tr: Trace, fn) -> float | None:
    """Mean over devices of ``fn(ops)`` (microseconds over the window),
    per traced step, in milliseconds; None without a device or a step."""
    n = steps(tr) if tr is not None else 0
    if not n:
        return None
    v = per_device_mean(tr, fn)
    return None if v is None else v / n * 1e-3


def in_stage(prefixes):
    """Busy time of the ops under a stage scope starting with one of
    ``prefixes``."""
    return lambda ops: stage_time(
        ops, lambda o: o.stage is not None and o.stage.startswith(prefixes))


def collective_time(ops) -> float:
    """Busy time of the collective ops (the topology switches)."""
    return stage_time(ops, lambda o: o.collective)


def exposed_collective_time(ops) -> float:
    """The part of the collective time during which no other op runs."""
    return subtract([(o.start, o.end) for o in ops if o.collective],
                    [(o.start, o.end) for o in ops if not o.collective])


def has_collectives(tr: Trace | None) -> bool:
    return tr is not None and any(
        o.collective for ops in device_ops(tr).values() for o in ops)
