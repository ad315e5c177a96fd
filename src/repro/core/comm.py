"""Topology-switch communication strategies (paper section III, TPU-native).

A topology switch moves the pencil from one active direction to the next:
the local block splits its (previously full) active axis across the ranks of
ONE mesh axis and gathers the next axis -- flups' sub-communicator scoping
maps 1:1 onto named mesh axes.

Each strategy is a ``CommStrategy`` class (see DESIGN.md #2); all are
numerically identical (asserted in tests) and differ only in the HLO they
emit, which is what the §Perf iteration studies:

* ``a2a``       -- one ``lax.all_to_all`` on the whole block, followed by an
                   explicit contiguous materialization (the analogue of the
                   pack/unpack into dedicated communication buffers around
                   ``MPI_Ialltoallv``).  Simple, fully synchronous.
* ``pipelined`` -- the paper's ``nb``: the block is cut into ``n_chunks``
                   along an uninvolved axis and each chunk is exchanged by
                   its own all-to-all; chunk k's local shuffle is independent
                   of chunk k+1's collective, exposing comm/comm overlap to
                   the scheduler (the role of n_batch / MPI_Testsome).  The
                   neighboring transforms stay monolithic.
* ``fused``     -- the paper's ``isr``: no explicit pre/post packing at all;
                   the all-to-all output keeps its natural (strided) layout
                   and downstream ops fold the reorder into their own
                   indexing, i.e. the MPI_Datatype role is played by XLA
                   layout assignment.
* ``overlap``   -- software-pipelined switch+transform stage: the collective
                   for chunk k+1 is issued BEFORE the next direction's 1-D
                   transform of chunk k, so transform compute genuinely
                   overlaps collective latency (flups' non-blocking variants
                   overlapping shuffle with MPI progress).  Requires the
                   caller to hand the per-chunk continuation to ``stage``
                   (the ``TransformSchedule.fwd_chunk``/``bwd_chunk`` API).

On top, ``autotune_comm`` is the analogue of flups' switchsort self-tuning:
it times candidate (strategy, n_chunks) pairs for the actual plan shapes and
mesh and caches the winner per plan/mesh key (in-memory, plus an optional
JSON file given by ``cache_path`` / $REPRO_COMM_CACHE).
"""
from __future__ import annotations

import json
import os
import threading
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from repro.runtime import faults as _faults

STRATEGIES = ("a2a", "pipelined", "fused", "overlap")

__all__ = [
    "STRATEGIES", "FOLDS", "CHUNK_AXES", "CACHE_SCHEMA",
    "CommConfig", "CommStrategy", "as_comm",
    "make_strategy", "cfg_label", "label_to_cfg",
    "topology_switch", "pad_axis", "crop_axis",
    "autotune_comm", "autotune_candidates",
    "cache_load_entries", "cache_store_entry",
    "clear_autotune_cache", "all_reduce_mean", "reset_warn_once",
]


FOLDS = ("pack", "unpack")
# chunk-axis policy of the chunked strategies: "auto" honors the caller's
# preferred free axis (the in-block multi-RHS batch) when it divides
# n_chunks, "grid" always cuts the uninvolved grid axis -- a searchable
# trade (batch chunking never pads; grid chunking keeps per-chunk rows
# contiguous for the neighboring transforms)
CHUNK_AXES = ("auto", "grid")


@dataclass(frozen=True)
class CommConfig:
    strategy: str = "a2a"
    n_chunks: int = 2          # pipelined/overlap granularity (paper n_batch)
    # which side of the collective the layout-scheduled relayout is folded
    # into (DESIGN.md #9): "pack" permutes BEFORE the all-to-all (the
    # collective then splits a contiguous major axis), "unpack" permutes
    # each switched block AFTER it (the collective sees the transform's
    # minor-most layout).  Which is faster is shape- and backend-dependent
    # -- exactly the flups switchsort situation -- so ``autotune_comm``
    # sweeps both for layout-scheduled plans.  Ignored by the baseline
    # (moveaxis) pipelines and by ``permute=None`` call sites.
    fold: str = "pack"
    chunk_axis: str = "auto"   # see CHUNK_AXES

    def __post_init__(self):
        assert self.strategy in STRATEGIES, self.strategy
        assert self.n_chunks >= 1, self.n_chunks
        assert self.fold in FOLDS, self.fold
        assert self.chunk_axis in CHUNK_AXES, self.chunk_axis


def cfg_label(cfg: CommConfig) -> str:
    """Canonical candidate label: ``strategy:n_chunks`` plus non-default
    knobs (``:unpack``, ``:ca=grid``).  Stable across releases -- labels
    are cache-key material (the candidate grid is part of the autotune
    identity) and census/diagnostic keys."""
    lbl = f"{cfg.strategy}:{cfg.n_chunks}"
    if cfg.fold != "pack":
        lbl += f":{cfg.fold}"
    if cfg.chunk_axis != "auto":
        lbl += f":ca={cfg.chunk_axis}"
    return lbl


def label_to_cfg(label: str) -> CommConfig:
    parts = label.split(":")
    fold, ca = "pack", "auto"
    for p in parts[2:]:
        if p.startswith("ca="):
            ca = p[3:]
        elif p in FOLDS:
            fold = p
    return CommConfig(parts[0], int(parts[1]), fold, ca)


def as_comm(comm) -> CommConfig:
    """Accept ``CommConfig`` / strategy name / None (``"auto"`` is resolved
    by the solver via ``autotune_comm`` before this point)."""
    if comm is None:
        return CommConfig()
    if isinstance(comm, CommConfig):
        return comm
    return CommConfig(strategy=str(comm))


# ---------------------------------------------------------------------------
# chunking helpers
# ---------------------------------------------------------------------------

def _uninvolved_axis(ndim: int, split_axis: int, concat_axis: int) -> int:
    for ax in range(ndim - 1, -1, -1):
        if ax not in (split_axis, concat_axis):
            return ax
    raise ValueError("need >= 3 axes for a chunked strategy")


_WARNED: set = set()


def _warn_once(msg: str):
    if msg not in _WARNED:
        _WARNED.add(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=4)


def reset_warn_once():
    """Re-arm every one-shot diagnostic.  The module-global ``_WARNED`` set
    otherwise never resets, so a long-lived serve process would suppress
    per-plan warnings forever (and tests would pass/fail by execution
    order).  Wired into ``solver.clear_solver_cache`` and the test-session
    fixtures; servers may also call it on a stats epoch."""
    _WARNED.clear()


def _split_chunks(x, ax: int, n: int):
    """Cut ``x`` into ``n`` equal chunks along ``ax``, zero-padding the axis
    to the next multiple when it does not divide (warned once per shape --
    the seed silently fell back to a single collective here)."""
    ln = x.shape[ax]
    if ln % n:
        target = -(-ln // n) * n
        _warn_once(
            f"comm: chunk axis {ax} (length {ln}) does not divide into "
            f"{n} chunks; zero-padding to {target} (cropped after the "
            f"switch)")
        x = pad_axis(x, ax, target)
    return jnp.split(x, n, axis=ax), ln


def pad_axis(x, ax: int, target: int):
    """Zero-pad ``ax`` up to ``target`` (no-op when already there)."""
    if x.shape[ax] == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[ax] = (0, target - x.shape[ax])
    return jnp.pad(x, pad)


def crop_axis(x, ax: int, ln: int):
    """Slice ``ax`` down to ``ln`` (no-op when already there)."""
    if x.shape[ax] == ln:
        return x
    sl = [slice(None)] * x.ndim
    sl[ax] = slice(0, ln)
    return x[tuple(sl)]


def _a2a(x, axis_name, split_axis, concat_axis):
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------

class CommStrategy:
    """One topology-switch execution policy.

    ``stage(x, axis_name, split_axis, concat_axis, post=...)`` performs the
    switch and then applies ``post`` -- the crop + next direction's 1-D
    transform continuation handed down by the solver.  Monolithic strategies
    run ``post`` on the whole switched block; ``overlap`` interleaves it
    chunk-wise with the collectives.  ``switch`` is the plain transpose
    (``post=None``), the API the MoE/attention layers use.

    ``chunk_axis`` (stage/switch keyword) is a PREFERRED chunk axis for the
    chunked strategies -- the batched multi-RHS solve passes its leading
    batch axis here, a free chunk dimension that is never split or gathered
    by any topology switch.  The preference is honored when ``n_chunks``
    divides the axis length exactly (such an axis never needs
    zero-padding); otherwise the usual uninvolved grid axis is used.

    ``valid_extent`` (stage/switch keyword) is the number of LIVE entries
    along ``split_axis`` -- the pruned/deferred-doubling execution model's
    contract: anything past it is padding the wire never needs to carry.
    The strategy crops the split axis down to it and re-pads to the
    equal-split multiple XLA's all-to-all requires (``axis_sizes``, the
    {mesh axis name: size} map handed to the constructor).  ``None`` ships
    the axis as-is (the dense path, and the historical call sites).

    ``permute`` (stage/switch keyword) is an axis permutation (jnp.transpose
    spec over the FULL array rank) applied to the block as part of the
    switch's PACK, before the crop/pad and the collective --
    ``split_axis``/``concat_axis``/``chunk_axis`` are therefore in the
    PERMUTED frame.  The layout-scheduled pipelines (DESIGN.md #9) fold the
    one relayout between consecutive directions in here, arranged so the
    collective always splits a contiguous major axis and gathers straight
    into the next transform's minor axis -- the solve then emits ZERO
    standalone transposes between stages.  ``None`` keeps the incoming
    axis order (the baseline / historical call sites).
    """

    name: str = "?"

    def __init__(self, n_chunks: int = 1, axis_sizes=None,
                 fold: str = "pack", abft=None):
        self.n_chunks = max(int(n_chunks), 1)
        self.axis_sizes = dict(axis_sizes or {})
        assert fold in FOLDS, fold
        self.fold = fold
        # checksum-carrying mode (DESIGN.md #13): ``(collector, tol)`` or
        # None.  When set, every collective this strategy issues ships a
        # length-P checksum row (one reduction per destination rank over
        # the prepared payload) through a sidecar all_to_all and verifies
        # it receive-side -- corruption on the wire is then attributed to
        # ``wire.<axis>`` instead of a compute stage.
        self.abft = abft

    def _collective(self, x, axis_name, split_axis, concat_axis):
        """One (possibly checksum-carrying) all-to-all.  The wire fault
        hook sits between the sender-side checksum and the exchange:
        exactly the window a real link flip occupies."""
        ab = self.abft
        p = self.axis_sizes.get(axis_name)
        if ab is not None and p and x.shape[split_axis % x.ndim] % p == 0:
            from repro.runtime import abft as _abft
            col, tol = ab
            cs = _abft.wire_checksums(x, split_axis, p)
            y = _a2a(_faults.taint(f"comm.wire.{self.name}", x),
                     axis_name, split_axis, concat_axis)
            cs_recv = lax.all_to_all(cs, axis_name, 0, 0, tiled=True)
            return _abft.wire_verify(y, cs_recv, concat_axis, p, col,
                                     f"wire.{axis_name}", tol)
        return _a2a(_faults.taint(f"comm.wire.{self.name}", x),
                    axis_name, split_axis, concat_axis)

    @staticmethod
    def _permute(x, permute):
        return x if permute is None else jnp.transpose(x, permute)

    def _pack(self, x, split_axis, concat_axis, chunk_axis, permute):
        """Resolve the relayout fold: returns ``(x, split, concat, chunk,
        unpack)`` where the coordinates address the frame the collective
        runs in and ``unpack`` is the permutation still owed AFTER it
        (None under fold="pack", which transposes up front).  Caller
        coordinates are always in the PERMUTED (post-relayout) frame."""
        if permute is None:
            return x, split_axis, concat_axis, chunk_axis, None
        if self.fold == "pack":
            return (self._permute(x, permute), split_axis, concat_axis,
                    chunk_axis, None)
        # fold="unpack": the collective runs in the incoming frame; map the
        # permuted-frame coordinates back through the permutation
        return (x, permute[split_axis], permute[concat_axis],
                None if chunk_axis is None else permute[chunk_axis],
                permute)

    def _prepare(self, x, axis_name, split_axis: int, valid_extent):
        """Crop ``split_axis`` to its valid extent, then zero-pad to the
        equal-split length of ``axis_name`` (no-ops when already there)."""
        if valid_extent is None:
            return x
        x = crop_axis(x, split_axis, valid_extent)
        p = self.axis_sizes.get(axis_name)
        if p:
            x = pad_axis(x, split_axis, -(-x.shape[split_axis] // p) * p)
        return x

    def _chunk_axis(self, x, split_axis: int, concat_axis: int,
                    chunk_axis) -> int:
        if (chunk_axis is not None
                and chunk_axis not in (split_axis, concat_axis)
                and x.shape[chunk_axis] % self.n_chunks == 0):
            return chunk_axis
        return _uninvolved_axis(x.ndim, split_axis, concat_axis)

    def _scope(self):
        """The named scope of the switch's ops: the fault site's name,
        ``comm.<strategy>``.  The ``post`` continuation runs outside it."""
        return jax.named_scope(f"comm.{self.name}")

    # -- to be overridden -------------------------------------------------
    def _switch(self, x, axis_name, split_axis, concat_axis,
                chunk_axis=None):
        raise NotImplementedError

    # -- shared surface ----------------------------------------------------
    def switch(self, x, axis_name, split_axis, concat_axis,
               chunk_axis=None, valid_extent=None, permute=None):
        return self.stage(x, axis_name, split_axis, concat_axis, post=None,
                          chunk_axis=chunk_axis, valid_extent=valid_extent,
                          permute=permute)

    def stage(self, x, axis_name, split_axis, concat_axis, post=None,
              chunk_axis=None, valid_extent=None, permute=None):
        # fault-injection hook: an armed spec for this strategy simulates
        # the collective dying at trace time (chaos suite; no-op otherwise)
        _faults.fail_point(f"comm.{self.name}")
        with self._scope():
            # the scheduled relayout rides the switch (pack or unpack side
            # per ``fold``): one transpose, adjacent to the collective
            # either way
            x, split_axis, concat_axis, chunk_axis, unpack = self._pack(
                x, split_axis, concat_axis, chunk_axis, permute)
            x = self._prepare(x, axis_name, split_axis, valid_extent)
            y = self._switch(x, axis_name, split_axis, concat_axis,
                             chunk_axis=chunk_axis)
            y = self._permute(y, unpack)
        return post(y) if post is not None else y


@jax.custom_vjp
def _buffer_barrier(y):
    """``optimization_barrier`` as a differentiable identity: the barrier
    is a scheduling hint with no math, but it carries no differentiation
    rule, and the ABFT sandwich weight (``w = S^T r``, DESIGN.md #13) is
    built by one vjp through the whole distributed pipeline -- so the
    cotangent passes straight through."""
    return lax.optimization_barrier(y)


def _buffer_barrier_fwd(y):
    return _buffer_barrier(y), None


def _buffer_barrier_bwd(_, ct):
    return (ct,)


_buffer_barrier.defvjp(_buffer_barrier_fwd, _buffer_barrier_bwd)


class A2AStrategy(CommStrategy):
    name = "a2a"

    def _switch(self, x, axis_name, split_axis, concat_axis,
                chunk_axis=None):
        y = self._collective(x, axis_name, split_axis, concat_axis)
        # explicit pack/unpack materialization: force a contiguous copy so
        # the collective is surrounded by dedicated buffer ops (flups a2a)
        try:
            y = _buffer_barrier(y)
        except NotImplementedError:
            # older jax has no batching rule for optimization_barrier (hit
            # under the multi-pod vmap); the barrier is a scheduling hint
            # only, so dropping it preserves semantics
            pass
        return y


class FusedStrategy(CommStrategy):
    name = "fused"

    def _switch(self, x, axis_name, split_axis, concat_axis,
                chunk_axis=None):
        return self._collective(x, axis_name, split_axis, concat_axis)


class PipelinedStrategy(CommStrategy):
    """Chunked collectives only; neighboring transforms stay monolithic."""

    name = "pipelined"

    def _switch(self, x, axis_name, split_axis, concat_axis,
                chunk_axis=None):
        if self.n_chunks <= 1:
            return self._collective(x, axis_name, split_axis, concat_axis)
        ax = self._chunk_axis(x, split_axis, concat_axis, chunk_axis)
        chunks, ln = _split_chunks(x, ax, self.n_chunks)
        outs = [self._collective(c, axis_name, split_axis, concat_axis)
                for c in chunks]
        return crop_axis(jnp.concatenate(outs, axis=ax), ax, ln)


class OverlapStrategy(CommStrategy):
    """Software-pipelined switch: collective k+1 is issued before the
    post-stage (next direction's transform) of chunk k, so the transform of
    one chunk overlaps the wire time of the next."""

    name = "overlap"

    def _switch(self, x, axis_name, split_axis, concat_axis,
                chunk_axis=None):
        # plain transpose (no continuation): same wire pattern as pipelined
        return PipelinedStrategy(self.n_chunks, axis_sizes=self.axis_sizes,
                                 fold=self.fold, abft=self.abft)._switch(
            x, axis_name, split_axis, concat_axis, chunk_axis=chunk_axis)

    def stage(self, x, axis_name, split_axis, concat_axis, post=None,
              chunk_axis=None, valid_extent=None, permute=None):
        _faults.fail_point(f"comm.{self.name}")
        with self._scope():
            x, split_axis, concat_axis, chunk_axis, unpack = self._pack(
                x, split_axis, concat_axis, chunk_axis, permute)
            x = self._prepare(x, axis_name, split_axis, valid_extent)
        if post is None or self.n_chunks <= 1:
            with self._scope():
                y = self._switch(x, axis_name, split_axis, concat_axis,
                                 chunk_axis=chunk_axis)
                y = self._permute(y, unpack)
            return post(y) if post is not None else y
        ax = self._chunk_axis(x, split_axis, concat_axis, chunk_axis)
        # under fold="unpack" each chunk is permuted as it lands (in the
        # gap its successor's collective is in flight) and the concat axis
        # rides the same permutation into the post frame
        ax_out = ax if unpack is None else unpack.index(ax)
        outs = []
        with self._scope():
            chunks, ln = _split_chunks(x, ax, self.n_chunks)
            inflight = self._collective(chunks[0], axis_name, split_axis,
                                        concat_axis)
        for k in range(1, self.n_chunks):
            with self._scope():
                nxt = self._collective(chunks[k], axis_name, split_axis,
                                       concat_axis)
                landed = self._permute(inflight, unpack)
            # overlaps chunk k's wire time
            outs.append(post(landed))
            inflight = nxt
        with self._scope():
            landed = self._permute(inflight, unpack)
        outs.append(post(landed))
        with self._scope():
            return crop_axis(jnp.concatenate(outs, axis=ax_out), ax_out, ln)


_STRATEGY_CLASSES = {
    cls.name: cls
    for cls in (A2AStrategy, PipelinedStrategy, FusedStrategy,
                OverlapStrategy)
}


def make_strategy(cfg: CommConfig, axis_sizes=None,
                  abft=None) -> CommStrategy:
    return _STRATEGY_CLASSES[cfg.strategy](cfg.n_chunks,
                                           axis_sizes=axis_sizes,
                                           fold=cfg.fold, abft=abft)


def topology_switch(x, axis_name, split_axis: int, concat_axis: int,
                    cfg: CommConfig, chunk_axis=None, valid_extent=None,
                    axis_sizes=None, permute=None):
    """Distributed transpose: split ``split_axis`` over ``axis_name`` ranks,
    gather ``concat_axis``.  Must run inside shard_map.  ``valid_extent``
    (with ``axis_sizes``) crops the split axis to its live entries before
    the exchange; ``permute`` folds a relayout into the unpack -- see
    ``CommStrategy``."""
    return make_strategy(cfg, axis_sizes=axis_sizes).switch(
        x, axis_name, split_axis, concat_axis, chunk_axis=chunk_axis,
        valid_extent=valid_extent, permute=permute)


# ---------------------------------------------------------------------------
# plan-time autotuner (flups switchsort analogue)
# ---------------------------------------------------------------------------

_AUTOTUNE_CACHE: dict = {}
_AUTOTUNE_LOCK = threading.Lock()


def autotune_candidates(max_chunks: int = 4, folds=("pack",)):
    """Default (strategy, n_chunks) sweep: monolithic strategies once,
    chunked strategies at 2, 4, ... up to ``max_chunks``.  ``folds`` widens
    the grid across relayout fold sides (layout-scheduled solvers sweep
    ``("pack", "unpack")`` -- which side of the collective the fused
    transpose is cheaper on is shape- and backend-dependent)."""
    cands = []
    for fold in folds:
        cands += [CommConfig("a2a", 1, fold), CommConfig("fused", 1, fold)]
        nc = 2
        while nc <= max_chunks:
            cands.append(CommConfig("pipelined", nc, fold))
            cands.append(CommConfig("overlap", nc, fold))
            nc *= 2
    return tuple(cands)


def clear_autotune_cache():
    with _AUTOTUNE_LOCK:
        _AUTOTUNE_CACHE.clear()


# on-disk JSON layout: {"schema": CACHE_SCHEMA, "entries": {key: entry}}.
# Schema 1 (the seed through PR 7) was the flat {key: entry} dict with no
# version field and no ``fold`` in early entries; it is migrated in memory
# on load (warned ONCE per file, counted in ``census["migrated"]``) and
# rewritten as the current schema on the next store.
CACHE_SCHEMA = 2


def _cache_file_load(path: str) -> dict:
    try:
        with open(path) as fh:
            data = json.load(fh)
    except OSError:                 # absent cache: normal first-run state
        return {}
    except ValueError:
        # torn/corrupt JSON (e.g. a write interrupted before the atomic
        # store below landed, or on-disk rot): warn once and fall through
        # to a live sweep instead of raising at startup
        _warn_once(f"comm: autotune cache {path} is corrupt/truncated; "
                   "ignoring it (a live sweep will rewrite it)")
        return {}
    if not isinstance(data, dict):
        _warn_once(f"comm: autotune cache {path} holds non-dict JSON; "
                   "ignoring it (a live sweep will rewrite it)")
        return {}
    # chaos hook: an armed ``corrupt_cache`` spec rots the loaded entries
    # in place; the consumer must treat them as malformed and re-sweep
    return _faults.mangle_cache_entry(data)


def cache_load_entries(path: str, census=None) -> dict:
    """Load the cache file and return its ENTRIES dict, migrating legacy
    (schema-1, flat) files in memory.  ``census["migrated"]`` counts the
    entries carried across a migration (0 on a current-schema file)."""
    data = _cache_file_load(path)
    if census is not None:
        census.setdefault("migrated", 0)
    if not data:
        return {}
    if "schema" in data or "entries" in data:
        entries = data.get("entries")
        if data.get("schema") == CACHE_SCHEMA and isinstance(entries, dict):
            return entries
        _warn_once(f"comm: autotune cache {path} has unsupported schema "
                   f"{data.get('schema')!r}; ignoring it (a live sweep "
                   "will rewrite it)")
        return {}
    # legacy schema-1 flat file: every value that looks like an entry is
    # carried over; pre-fold entries pick up the historical default
    entries = {}
    for k, v in data.items():
        if isinstance(v, dict):
            e = dict(v)
            if "strategy" in e:
                e.setdefault("fold", "pack")
            entries[k] = e
    if entries:
        _warn_once(f"comm: autotune cache {path} uses the legacy flat "
                   f"schema; migrated {len(entries)} entries in memory "
                   f"(rewritten as schema {CACHE_SCHEMA} on the next "
                   "store)")
    if census is not None:
        census["migrated"] += len(entries)
    return entries


_CACHE_FILE_LOCK = threading.Lock()


def cache_store_entry(path: str, key: str, entry: dict):
    """Read-merge-write one entry into the schema-versioned JSON cache,
    atomically.

    Concurrent server workers (threads in this process via the lock,
    sibling processes via tmp+``os.replace``) never interleave partial
    writes: a reader sees either the old file or the new one, complete --
    a crash mid-store leaves at worst a stray ``*.tmp.<pid>`` file, never
    a truncated cache that breaks the next startup's ``json.load``.
    Storing into a legacy flat file migrates it to the current schema."""
    with _CACHE_FILE_LOCK:
        entries = cache_load_entries(path)
        entries[key] = entry
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(tmp, "w") as fh:
                json.dump({"schema": CACHE_SCHEMA, "entries": entries},
                          fh, indent=1, sort_keys=True)
            os.replace(tmp, path)   # atomic commit (same filesystem)
        except OSError as e:        # cache is best-effort, never fatal
            _warn_once(f"comm: cannot persist autotune cache to {path}: {e}")
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _cache_file_store(path: str, key: str, cfg: CommConfig, timings: dict,
                      skipped=()):
    entry = {"strategy": cfg.strategy, "n_chunks": cfg.n_chunks,
             "fold": cfg.fold,
             "timings_us": {k: round(v * 1e6, 1)
                            for k, v in timings.items()}}
    if cfg.chunk_axis != "auto":
        entry["chunk_axis"] = cfg.chunk_axis
    if skipped:                     # budget-abandoned candidates, on record
        entry["skipped_budget"] = list(skipped)
    cache_store_entry(path, key, entry)


def _timed_call(fn, arg, budget_s):
    """Run ``fn(arg)`` with a wall-clock budget.  Returns (value, None) or
    (None, "timeout").  The call runs in a worker thread so a pathological
    candidate (hung collective, runaway compile) cannot stall plan
    construction -- on timeout the sweep moves on and the stray thread is
    abandoned (it holds no locks the sweep needs)."""
    if not budget_s or budget_s <= 0:
        return fn(arg), None
    import concurrent.futures as cf
    ex = cf.ThreadPoolExecutor(max_workers=1)
    fut = ex.submit(fn, arg)
    try:
        return fut.result(timeout=budget_s), None
    except cf.TimeoutError:
        fut.cancel()
        return None, "timeout"
    finally:
        ex.shutdown(wait=False)


def autotune_comm(key, time_fn, candidates=None, cache_path=None,
                  results=None, budget_s=None, census=None) -> CommConfig:
    """Pick the fastest (strategy, n_chunks) pair for one plan/mesh key.

    ``time_fn(cfg) -> seconds`` lowers+times one solve under ``cfg`` (the
    solver provides it); the winner is cached in-memory per ``key`` and,
    when ``cache_path`` (default $REPRO_COMM_CACHE) is set, persisted as
    JSON so later processes skip the sweep.  ``results``, when a dict, is
    filled with the per-candidate timings of a live sweep (empty on a cache
    hit).  A candidate that raises is skipped; if every candidate fails the
    default ``a2a`` is returned.

    ``budget_s`` (default $REPRO_COMM_BUDGET, unset = unlimited) is the
    per-candidate wall-clock budget: a candidate that does not produce a
    timing within it is skipped (warned once) so ONE pathological
    (strategy, n_chunks, fold) pair cannot stall plan construction.
    ``census``, when a dict, records the sweep's full account:
    ``timed`` (label -> seconds), ``failed`` (label -> error),
    ``skipped_budget`` (labels abandoned on budget) and ``migrated``
    (entries carried across a legacy cache-schema migration).
    """
    if candidates is None:
        candidates = autotune_candidates()
    if budget_s is None:
        try:
            budget_s = float(os.environ.get("REPRO_COMM_BUDGET", "") or 0)
        except ValueError:
            budget_s = 0
    # the candidate grid is part of the identity: widening the sweep (e.g.
    # raising comm_autotune_max_chunks, adding fold sides, or a guided
    # search shortlisting a different frontier) must invalidate the cached
    # winner
    labels = tuple(cfg_label(c) for c in candidates)
    key = repr((key, labels))
    if cache_path is None:
        cache_path = os.environ.get("REPRO_COMM_CACHE") or None
    with _AUTOTUNE_LOCK:
        hit = _AUTOTUNE_CACHE.get(key)
    if hit is not None:
        return hit
    if cache_path:
        entry = cache_load_entries(cache_path, census=census).get(key)
        if entry is not None:
            try:
                cfg = CommConfig(entry["strategy"], int(entry["n_chunks"]),
                                 str(entry.get("fold", "pack")),
                                 str(entry.get("chunk_axis", "auto")))
            except (KeyError, TypeError, ValueError, AssertionError):
                # malformed / older-schema entry: fall through to a live
                # sweep (the cache is best-effort, never fatal)
                cfg = None
            if cfg is not None:
                with _AUTOTUNE_LOCK:
                    _AUTOTUNE_CACHE[key] = cfg
                return cfg

    timings: dict = {}
    skipped, failed = [], {}
    for cfg, label in zip(candidates, labels):
        try:
            t, why = _timed_call(time_fn, cfg, budget_s)
        except Exception as e:      # noqa: BLE001 -- candidate may not lower
            failed[label] = f"{type(e).__name__}: {e}"[:200]
            _warn_once(f"comm: autotune candidate {label} failed: {e}")
            continue
        if why == "timeout":
            skipped.append(label)
            _warn_once(f"comm: autotune candidate {label} exceeded the "
                       f"{budget_s:g}s budget; skipped")
            continue
        timings[label] = float(t)
    if results is not None:
        results.update(timings)
    if census is not None:
        census.update(timed=dict(timings), failed=failed,
                      skipped_budget=list(skipped))
    if not timings:
        return CommConfig()
    best_label = min(timings, key=timings.get)
    best = label_to_cfg(best_label)
    with _AUTOTUNE_LOCK:
        _AUTOTUNE_CACHE[key] = best
    if cache_path:
        _cache_file_store(cache_path, key, best, timings, skipped)
    return best


def all_reduce_mean(x, axis_name):
    return lax.pmean(x, axis_name)
