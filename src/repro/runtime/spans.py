"""Host spans of the solver: named intervals at its layer boundaries.

    from repro.runtime import spans
    spans.enable()
    u = get_solver(...).solve(f)
    for s in spans.drain():
        print(s.name, s.end - s.start, s.parent, s.step)

``span(name, **args)`` is a context manager.  Off (the default) it costs
one test of a module-level boolean: no profiler call, no clock read.  On,
it enters ``jax.profiler.TraceAnnotation(name, **args)``, so the span lands
in a profiler trace beside the device's ops, and on exit appends a
``Span`` to an in-memory list that ``drain()`` hands back and clears.
Times come from ``time.perf_counter()``.  ``parent`` is the name of the
enclosing span on the same thread.  ``step`` numbers the solves: a span
takes its parent's step, a top-level span the current one, and a span
opened with ``closes_step=True`` (the solve) advances it on exit, so a
``get_solver`` and the solve that follows it share a step.

The solver's spans (``flups.*``):

===================== ==================================================
``flups.get_solver``  plan-cache lookup (arg ``hit``); on a miss it holds
``flups.build``         the solver's construction:
``flups.plan``            plan and transform schedule,
``flups.green.build``     the Green's function (float64, on the host),
``flups.green.layout``    its cast, pad and transpose
``flups.green.put``   the Green's function's copy to the device (first
                      solve)
``flups.solve``       one solve, holding
``flups.pad``           the input's pad and device placement,
``flups.launch``        the jitted call (with its compile or cache load
                        on the first call),
``flups.crop``          the output's crop
===================== ==================================================
"""
from __future__ import annotations

import threading
import time
from typing import NamedTuple

import jax

__all__ = ["Span", "span", "enable", "disable", "drain"]


class Span(NamedTuple):
    name: str
    start: float          # time.perf_counter(), s
    end: float
    parent: str | None    # the enclosing span's name on this thread
    step: int
    args: dict


_on = False
_spans: list = []
_step = 0
_local = threading.local()


class _Off:
    """What ``span`` returns while spans are off: does nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        pass


_OFF = _Off()


class _On:
    __slots__ = ("name", "args", "closes", "parent", "step", "start",
                 "_ann")

    def __init__(self, name, closes_step, args):
        self.name = name
        self.args = args
        self.closes = closes_step

    def set(self, key, value):
        """Record ``key=value`` on the span once it is open (a lookup's
        outcome, known only at its end)."""
        self.args[key] = value
        self._ann.set_metadata(**{key: value})

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        self.parent = stack[-1] if stack else None
        self.step = self.parent.step if self.parent else _step
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _step
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        _spans.append(Span(self.name, self.start, end,
                           self.parent.name if self.parent else None,
                           self.step, self.args))
        if self.closes:
            _step = self.step + 1
        return False


def span(name: str, closes_step: bool = False, **args):
    """A context manager timing ``name``; see the module docstring."""
    if not _on:
        return _OFF
    return _On(name, closes_step, args)


def enable():
    """Turn spans on, from step 0, with an empty list."""
    global _on, _step
    _spans.clear()
    _step = 0
    _on = True


def disable():
    global _on
    _on = False


def drain() -> list:
    """The spans recorded so far, in the order they ended; clears them."""
    out = list(_spans)
    del _spans[:len(out)]
    return out
