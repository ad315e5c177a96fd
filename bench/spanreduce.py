"""Reductions over what the program itself marks: its device scopes between
the stages, its host spans (``repro.runtime.spans``), and one clock for
the device and the host.

``reduce.py`` reads the stage scopes and the benchmark's ``bench.*`` spans
on the profiler's raw timestamps.  This module adds:

* the pipeline scopes: an op that no stage scope holds is a row-major pin
  when its innermost ``pin``/``relayout``/``comm.<strategy>`` scope is
  ``pin``, a relayout when it is ``relayout`` or ``comm.<strategy>`` (on
  one chip the switch is the local permute and crop);
* the clock offset: the device's ``XLA Modules`` events and the host's
  ``tpu::System::Execute`` (launch) and ``tpu::System::Execute=>Done``
  (completion) events, matched in order, bound the offset to add to
  device times (a module starts after its launch and ends before its
  completion);
* the idle gaps on the aligned clock, labelled by the innermost
  ``bench.*`` or ``flups.*`` span, and the part of the idle that falls
  inside the solver's own host spans;
* the in-memory spans of a run: the Green's function's build in set-up and
  the host time of the window's solves.
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field

import reduce

_PIPE = re.compile(r"^(pin|relayout|comm\..+)$")
LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"


# -- device scopes between the stages --------------------------------------

def pipeline_scope(op: reduce.Op) -> str | None:
    """The innermost ``pin``, ``relayout`` or ``comm.<strategy>`` part of
    the op's scope path, or None."""
    for part in reversed(op.scope.rstrip(":").split("/")):
        if _PIPE.match(part):
            return part
    return None


def _pipeline(op: reduce.Op) -> bool:
    """What ``pipeline_other_ms`` counts: no stage scope, no collective."""
    return op.stage is None and not op.collective


def is_pin(op: reduce.Op) -> bool:
    return _pipeline(op) and pipeline_scope(op) == "pin"


def is_relayout(op: reduce.Op) -> bool:
    s = pipeline_scope(op)
    return _pipeline(op) and s is not None and s != "pin"


def pipeline_ms(tr, pred) -> float | None:
    """Device time per traced step of the ops for which ``pred`` holds,
    mean over the chips; None when no op in the window carries a pipeline
    scope (a program without them)."""
    if tr is None or not any(pipeline_scope(o) for ops in
                             reduce.device_ops(tr).values() for o in ops):
        return None
    return reduce.per_step_ms(tr, lambda ops: reduce.stage_time(ops, pred))


# -- host events and one clock ---------------------------------------------

@dataclass
class Host:
    """The host's launch and completion events, the device's modules (both
    on the profiler's raw clock) and every ``bench.*``/``flups.*`` span."""
    launches: list = field(default_factory=list)   # [(start, end)], us
    dones: list = field(default_factory=list)
    modules: dict = field(default_factory=dict)    # device -> [(s, e)]
    spans: list = field(default_factory=list)      # [reduce.Span]


def load_host(path: str) -> Host:
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    h = Host()
    for e in events:
        if e.get("ph") != "X":
            continue
        iv = (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0)))
        pname = procs.get(e["pid"], "")
        if pname.startswith("/device:"):
            if threads.get((e["pid"], e["tid"])) == "XLA Modules":
                h.modules.setdefault(pname, []).append(iv)
        elif e["name"] == LAUNCH:
            h.launches.append(iv)
        elif e["name"] == DONE:
            h.dones.append(iv)
        elif e["name"].startswith(("bench.", "flups.")):
            h.spans.append(reduce.Span(e["name"], *iv))
    for xs in (h.launches, h.dones, *h.modules.values()):
        xs.sort()
    h.spans.sort(key=lambda s: s.start)
    return h


def clock_offset(h: Host, device: str) -> float | None:
    """Microseconds to add to ``device``'s times to put them on the host's
    clock: the least offset at which every module starts at or after its
    launch, the k-th module matched to the k-th launch and completion.
    0 when the trace has no launch events; None when the counts differ or
    that offset would end a module after its completion."""
    if not h.launches:
        return 0.0
    mods = h.modules.get(device, [])
    if not mods or not len(mods) == len(h.launches) == len(h.dones):
        return None
    lo = max(ls - ms for (ls, _), (ms, _) in zip(h.launches, mods))
    hi = min(ds - me for (ds, _), (_, me) in zip(h.dones, mods))
    return lo if lo <= hi else None


def aligned_ops(tr, h: Host) -> dict | None:
    """Each device's ops on the host's clock, inside the traced window;
    None when a device's offset cannot be bounded."""
    w = reduce.window(tr) if tr is not None else None
    if w is None or not tr.devices:
        return None
    out = {}
    for dev, ops in tr.devices.items():
        off = clock_offset(h, dev)
        if off is None:
            return None
        out[dev] = reduce.clip(
            [reduce.Op(o.name, o.start + off, o.end + off, o.scope,
                       o.category) for o in ops], *w)
    return out


def host_label(h: Host, t: float) -> str:
    """The innermost ``bench.*`` or ``flups.*`` span that holds ``t``."""
    best = None
    for s in h.spans:
        if s.start <= t <= s.end and s.name != "bench.window":
            if best is None or s.end - s.start < best.end - best.start:
                best = s
    return best.name if best else "outside spans"


def idle_gaps(tr, h: Host, top: int = 10) -> list | None:
    """The longest idle gaps on the aligned clock, ``[label, seconds]``."""
    ops = aligned_ops(tr, h)
    if ops is None:
        return None
    w = reduce.window(tr)
    out = [[host_label(h, (a + b) / 2), (b - a) * 1e-6]
           for dev in ops.values() for a, b in reduce.gaps(dev, *w)]
    out.sort(key=lambda kv: -kv[1])
    return out[:top]


def solver_idle_ms(tr, h: Host) -> float | None:
    """Device idle per traced step, on the aligned clock, that falls inside
    the solver's host spans (``flups.get_solver``, ``flups.solve`` and
    what they hold), mean over the chips; None without those spans."""
    ops = aligned_ops(tr, h)
    inside = reduce.union((s.start, s.end) for s in h.spans
                          if s.name.startswith("flups."))
    n = reduce.steps(tr) if ops is not None else 0
    if not inside or not n:
        return None
    w = reduce.window(tr)
    total = 0.0
    for dev in ops.values():
        idle = reduce.gaps(dev, *w)
        total += reduce.length(idle) - reduce.subtract(idle, inside)
    return total / len(ops) / n * 1e-3


# -- the in-memory spans of a run (repro.runtime.spans.drain()) -------------

def green_build_s(spans) -> float | None:
    """Seconds in ``flups.green.build``: the Green's function's build."""
    xs = [s.end - s.start for s in spans if s.name == "flups.green.build"]
    return sum(xs) if xs else None


def solve_host_ms(spans, first_step: int) -> float | None:
    """Mean ``flups.solve`` span, in ms, over the solves of step
    ``first_step`` on (the window's, after the set-up's)."""
    xs = [s.end - s.start for s in spans
          if s.name == "flups.solve" and s.step >= first_step]
    return sum(xs) / len(xs) * 1e3 if xs else None
