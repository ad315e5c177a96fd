"""Graceful-degradation ladder + retry policy (DESIGN.md #10).

Every configurable fast path of the solve has a documented slower-but-safer
fallback; on failure the runtime walks them one knob at a time:

    retry (bounded exponential backoff, transient errors only)
      -> engine    pallas    -> xla        (kernel lowering / exec faults)
      -> comm      overlap   -> pipelined -> a2a   (collective faults)
                   fused     -> pipelined
      -> relayout  scheduled -> baseline   (fused-transpose faults)
      -> doubling  deferred  -> upfront    (pruned-extent faults)

Each downgrade is recorded as a structured dict in the solver's
``stats["degradations"]`` (and warned once); when the ladder is exhausted a
``SolveError`` carrying the stage provenance and the full degradation trail
is raised.  The ladder is deliberately one-directional and monotonic: a
solve only ever gets more conservative, so a deterministic fault (e.g. a
Pallas kernel that cannot lower) is routed around in at most
``len(ladder)`` rebuilds and the result -- all rungs are numerically
equivalent pipelines -- matches the fault-free baseline.
"""
from __future__ import annotations

import os
import random
import time
import warnings
from dataclasses import dataclass

__all__ = ["SolveError", "RetryPolicy", "LADDER", "next_rung",
           "is_transient", "run_with_ladder", "reset_warn_once"]


# knob -> (from, to) downgrades, walked in priority order; one downgrade
# per failed attempt (the "step down one rung" contract)
LADDER = (
    ("engine",   (("pallas", "xla"),)),
    ("comm",     (("overlap", "pipelined"), ("fused", "pipelined"),
                  ("pipelined", "a2a"))),
    ("relayout", (("scheduled", "baseline"),)),
    ("doubling", (("deferred", "upfront"),)),
)


class SolveError(RuntimeError):
    """Terminal solve failure: the ladder is exhausted (or the error is not
    one a config downgrade can address).  Carries the failing stage, the
    final config, and the structured degradation trail."""

    def __init__(self, msg: str, *, stage=None, config=None,
                 degradations=()):
        super().__init__(msg)
        self.stage = stage
        self.config = dict(config or {})
        self.degradations = list(degradations)


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff for transient failures (the whole-solve
    budget: ``retries`` attempts across all rungs).

    ``jitter="decorrelated"`` (default) draws each delay uniformly from
    ``[base_delay, 3 * previous_delay]`` capped at ``max_delay`` (the AWS
    decorrelated-jitter schedule) so co-batched tenants that trip on the
    same transient do NOT retry in lockstep; ``jitter="none"`` restores
    the fixed doubling schedule.  ``seed`` pins the jitter RNG (falling
    back to ``$REPRO_RETRY_SEED``, then entropy) for deterministic tests.
    """

    retries: int = 2
    base_delay: float = 0.05
    max_delay: float = 1.0
    jitter: str = "decorrelated"
    seed: int | None = None

    def delay_rng(self):
        if self.jitter == "none":
            return None
        seed = self.seed
        if seed is None:
            env = os.environ.get("REPRO_RETRY_SEED", "").strip()
            seed = int(env) if env else None
        return random.Random(seed)


def next_rung(cfg: dict):
    """One downgrade below ``cfg``: ``(new_cfg, action)`` or None when the
    config is already fully conservative."""
    for knob, downs in LADDER:
        cur = cfg.get(knob)
        for frm, to in downs:
            if cur == frm:
                new = dict(cfg)
                new[knob] = to
                return new, f"{knob}:{frm}->{to}"
    return None


# substrings marking an execution error as transient (retry-worthy) when it
# does not carry an explicit ``transient`` attribute -- the runtime-level
# statuses a TPU fleet surfaces for preemptions and flaky links.
# RESOURCE_EXHAUSTED is not among them: on the chip it is an out-of-memory,
# which the same program on the same device hits again
_TRANSIENT_MARKERS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED")


def is_transient(e: BaseException) -> bool:
    t = getattr(e, "transient", None)
    if t is not None:
        return bool(t)
    msg = str(e)
    return any(m in msg for m in _TRANSIENT_MARKERS)


_WARNED: set = set()


def _warn_once(msg: str):
    if msg not in _WARNED:
        _WARNED.add(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def reset_warn_once():
    """Re-arm the one-shot degradation warnings (see
    ``comm.reset_warn_once`` -- same long-lived-process rationale).
    Called from ``solver.clear_solver_cache`` and the test fixtures."""
    _WARNED.clear()


def run_with_ladder(attempt, *, config: dict, reconfigure, stats: dict,
                    policy: RetryPolicy = None, describe: str = "solve",
                    diagnose=None, sleep=time.sleep):
    """Run ``attempt()`` under the degradation ladder.

    ``attempt()`` performs one full try (dispatch + optional verify) under
    the CURRENT config and raises on failure.  ``reconfigure(cfg)``
    rebuilds the solver's pipeline for ``cfg`` -- it is also invoked for
    transient retries with the unchanged config, which forces a fresh
    trace/compile (the analogue of re-establishing a collective after a
    link blip).  ``diagnose(exc)`` may return a finer stage-provenance
    string for errors that carry none.  Returns the first successful
    attempt's result; raises ``SolveError`` when the ladder is exhausted.
    """
    policy = policy or RetryPolicy()
    cfg = dict(config)
    retries_left = policy.retries
    delay = policy.base_delay
    rng = policy.delay_rng()
    records = stats.setdefault("degradations", [])
    while True:
        try:
            return attempt()
        except SolveError:
            raise
        except Exception as e:  # noqa: BLE001 -- every failure walks the ladder
            stage = getattr(e, "stage", None)
            if stage is None and diagnose is not None:
                try:
                    stage = diagnose(e)
                except Exception:  # diagnosis is best-effort
                    stage = None
            stage = stage or describe
            if is_transient(e) and retries_left > 0:
                retries_left -= 1
                stats["retries"] = stats.get("retries", 0) + 1
                _warn_once(f"{describe}: transient failure at {stage} "
                           f"({type(e).__name__}); retrying with backoff")
                sleep(delay)
                if rng is None:
                    delay = min(2.0 * delay, policy.max_delay)
                else:
                    delay = min(policy.max_delay,
                                rng.uniform(policy.base_delay,
                                            max(delay, policy.base_delay)
                                            * 3.0))
                reconfigure(dict(cfg))
                continue
            nxt = next_rung(cfg)
            if nxt is None:
                raise SolveError(
                    f"{describe}: failed at stage {stage!r} with the "
                    f"ladder exhausted (config {cfg}): {e!r}",
                    stage=stage, config=cfg, degradations=records) from e
            cfg, action = nxt
            rec = {"stage": stage, "action": action,
                   "error": f"{type(e).__name__}: {e}"[:300],
                   "config": dict(cfg)}
            records.append(rec)
            _warn_once(f"{describe}: degrading {action} after failure at "
                       f"stage {stage!r} ({type(e).__name__})")
            reconfigure(dict(cfg))
