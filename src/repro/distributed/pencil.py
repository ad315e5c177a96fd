"""Distributed pencil-decomposition Poisson solver (shard_map + collectives).

The 2-D process grid (P1, P2) lives on two named mesh axes; every topology
switch is scoped to exactly ONE axis (the paper's sub-communicators).  The
per-direction math is ``repro.core.engine``'s, unchanged; only the axis
shuffles become ``CommStrategy`` collectives.

The local solve is a software pipeline of fused transform+switch STAGES:
each topology switch carries the next direction's 1-D transform as its
``post`` continuation (``TransformSchedule.fwd_chunk``/``bwd_chunk``), so
the ``overlap`` strategy can interleave chunk k's transform with chunk k+1's
collective -- the paper's non-blocking variants, where shuffle compute hides
wire time.  Monolithic strategies run the same continuation on the whole
switched block, so all strategies share one code path and are numerically
identical.

Every stage is VALID-EXTENT aware (DESIGN.md #8): the split axis's live
extent (``Plan1D.valid_in``/``n_out``) is handed to
``CommStrategy.stage(valid_extent=...)``, which crops and re-pads to the
equal-split multiple internally.  Under the default ``doubling="deferred"``
the Hockney zero extension of unbounded directions exists only inside each
direction's own 1-D transform, so the early switches ship the n-point
physical axes; ``doubling="upfront"`` materializes the doubling in the
input field (the dense baseline ``benchmarks/bench_solve.py`` measures
against).

``comm="auto"`` resolves the strategy at plan time with
``repro.core.comm.autotune_comm`` (the flups switchsort analogue): each
candidate (strategy, n_chunks) pair is compiled and timed for THIS plan's
shapes and mesh, and the winner is cached per (shape, bcs, layout, mesh)
key.

Uneven data counts (the node-centered N+1 problem the paper's Appendix A
load balancing solves for MPI) are handled on TPU by padding the *inactive*
(sharded) axes to a multiple of the mesh axis size: XLA's all-to-all
requires equal splits.  The active axis is always local and exact, so the
transforms, paddings and boundary conventions are identical to the
reference solver.  ``repro.core.partition`` remains the source of truth for
how a real uneven MPI partition would be laid out (and is what the
CPU-cluster deployment path would use).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding

from repro.core.bc import DataLayout
from repro.core import green as gr
from repro.core.comm import (CommConfig, as_comm, autotune_comm,
                             autotune_candidates as _default_candidates,
                             crop_axis, make_strategy, pad_axis)
from repro.core.engine import (RELAYOUT_MODES, as_engine, build_schedule,
                               relayout)
from repro.core.solver import make_plan, build_green
from repro.runtime import spans

__all__ = ["DistributedPoissonSolver"]


def _pad_to(n: int, p: int) -> int:
    return -(-n // p) * p


def _auto_axes(mesh):
    """``mesh`` with every axis Auto.  ``jax.make_mesh`` builds Explicit
    axes, under which the eager pads and crops around the shard_map (a
    slice of a sharded axis) raise ``ShardingTypeError``; the solver's
    arrays only need XLA to place them, and the shard_map body is manual
    whatever the axis type."""
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# axis pad/crop shared with the comm chunking layer
_pad_dim = pad_axis
_crop_dim = crop_axis


class DistributedPoissonSolver:
    """Pencil-distributed flups solve over a (P1, P2) mesh-axis pair.

    ``axes``: the two mesh axis names forming the process grid.
    ``batch_axis``: optional extra mesh axis (e.g. "pod"): the solver then
    takes a leading batch dimension sharded over that axis (data-parallel
    fields, the multi-pod configuration).
    ``comm``: a ``CommConfig``, a strategy name, or ``"auto"`` (plan-time
    autotuned; see module docstring).
    ``relayout``: ``"scheduled"`` (default; plan-time ``LayoutSchedule``,
    relayouts folded into the topology switches -- DESIGN.md #9) or
    ``"baseline"`` (per-direction moveaxis round trips, the A/B
    reference).  Bit-exact vs each other on the XLA engine.
    ``order_policy``: ``"layout"`` (default; the execution order within
    each BC category is chosen to minimize edge relayouts) or
    ``"natural"`` (historical ascending order -- with
    ``relayout="baseline"`` this reproduces the PR-4 pipeline exactly).

    Batched multi-RHS execution: ``solve`` also accepts ``f`` with ONE
    extra leading batch dimension carried in-block (replicated over the
    mesh, not sharded): ``(B, *grid)``, or ``(B_pod, B, *grid)`` when
    ``batch_axis`` is set.  All B right-hand sides ride through the same
    topology switches -- same number of collectives, B-fold payload -- and
    the chunked comm strategies treat the batch axis as a free chunk axis
    (no zero-padding when ``B % n_chunks == 0``).  One jit specialization
    exists per input rank; plan, Green and autotuned comm are shared.
    """

    def __init__(self, shape, L, bcs, layout=DataLayout.CELL,
                 green_kind=gr.GreenKind.CHAT2, *, mesh, axes=("data", "model"),
                 comm=CommConfig(), batch_axis=None,
                 eps_factor: float = 2.0, dtype=jnp.float32,
                 lazy_green: bool = False, engine="xla",
                 doubling: str = "deferred", relayout: str = "scheduled",
                 order_policy: str = "layout",
                 autotune_candidates=None, autotune_cache=None,
                 autotune_batch=None, autotune_budget=None,
                 autotune_search: str = "guided",
                 verify=None, verify_rtol=0.5, abft_rtol=0.0,
                 _green_cache=None):
        assert relayout in RELAYOUT_MODES, relayout
        assert verify in (None, "nan", "residual", "abft",
                          "abft-stages"), verify
        assert autotune_search in ("guided", "brute"), autotune_search
        # the engine routes FFT lengths by the platform the mesh's devices
        # are on (they may be described devices of an ahead-of-time compile)
        self._platform = mesh.devices.flat[0].platform
        # full construction identity, kept for _configure (ladder rebuilds)
        # and rebuild(mesh) (elastic recovery re-plans)
        self._ctor = dict(shape=tuple(shape), L=L, bcs=bcs, layout=layout,
                          green_kind=green_kind, axes=tuple(axes),
                          batch_axis=batch_axis, eps_factor=eps_factor,
                          dtype=dtype, lazy_green=lazy_green,
                          order_policy=order_policy, comm_req=comm,
                          engine_obj=self._engine(engine),
                          autotune_candidates=autotune_candidates,
                          autotune_cache=autotune_cache,
                          autotune_batch=autotune_batch,
                          autotune_budget=autotune_budget,
                          autotune_search=autotune_search)
        self.verify = verify
        self.verify_rtol = float(verify_rtol)
        # ABFT checksum tolerance; 0.0 = auto per data dtype (abft.tol_for)
        self.abft_rtol = float(abft_rtol)
        self.stats = {"solves": 0, "retries": 0, "verify_failures": 0,
                      "degradations": []}
        # the mesh as the caller passed it keys the get_solver cache (and
        # its eviction on rebuild); the solver places its arrays on the
        # Auto-axis view of it
        self._caller_mesh = mesh
        self.mesh = _auto_axes(mesh)
        self.axes = tuple(axes)
        self.batch_axis = batch_axis
        self.dtype = dtype
        # raw (unpadded, natural-layout, f64) transformed Green: computed
        # once and reused across ladder rebuilds AND elastic rebuilds --
        # the O(N^3) assembly never reruns on a recovery path
        self._green_raw = _green_cache
        self._configure({"engine": as_engine(engine).name, "comm": None,
                         "doubling": doubling, "relayout": relayout})

    def _engine(self, engine):
        return dataclasses.replace(as_engine(engine), platform=self._platform)

    def _configure(self, cfg: dict):
        """(Re)build plan, Green layout, comm strategy and jits for one
        runtime config (the degradation ladder's rebuild hook).  The first
        build (``cfg["comm"] is None``) resolves the user's comm request
        (possibly ``"auto"`` -- the plan-time tuner); ladder rebuilds carry
        the degraded strategy name and keep n_chunks/fold."""
        c = self._ctor
        shape, L, bcs = c["shape"], c["L"], c["bcs"]
        layout, green_kind = c["layout"], c["green_kind"]
        eps_factor, order_policy = c["eps_factor"], c["order_policy"]
        lazy_green, dtype = c["lazy_green"], c["dtype"]
        axes, mesh = self.axes, self.mesh
        self._cfg = dict(cfg)
        with spans.span("flups.plan"):
            self.plan = make_plan(shape, L, bcs, layout, green_kind,
                                  eps_factor, doubling=cfg["doubling"],
                                  order_policy=order_policy)
            # keep the constructor's engine OBJECT (it may carry a
            # non-default max_radix) as long as the ladder has not degraded
            # the engine name
            base_eng = c.get("engine_obj")
            self.engine = (base_eng if base_eng is not None
                           and base_eng.name == cfg["engine"]
                           else self._engine(cfg["engine"]))
            self.schedule = build_schedule(self.plan, self.engine)
        self.relayout = cfg["relayout"]
        e = self.plan.order
        d0, d1, d2 = e
        p1 = mesh.shape[axes[0]]
        p2 = mesh.shape[axes[1]]
        self._axis_sizes = {axes[0]: p1, axes[1]: p2}
        dirs = self.plan.dirs
        # per-dim live physical extent OUTSIDE the dim's own transform:
        # n_pts under deferred (pruned) doubling, n_fft when padded up front
        U = [p.valid_in for p in dirs]
        S = [p.n_out for p in dirs]
        self._U, self._S = U, S
        self._PU1 = _pad_to(U[d1], p1)
        self._PU2 = _pad_to(U[d2], p2)
        self._PS0 = _pad_to(S[d0], p1)
        self._PS1 = _pad_to(S[d1], p2)

        gdtype = np.float64 if dtype == jnp.float64 else np.float32
        gshape = tuple(
            self._PS0 if d == d0 else (self._PS1 if d == d1 else S[d])
            for d in range(3))
        # layout-scheduled pipelines hold the spectral block in the layout
        # the LAST forward stage leaves it in (active axis minor-most); the
        # Green's function is materialized directly in that layout at plan
        # time, so the pointwise multiply never relayouts anything
        gperm = (self.schedule.layouts.spectral
                 if self.relayout == "scheduled" else (0, 1, 2))
        if lazy_green:
            # dry-run: the kernel is an argument, never materialized
            self._green_np = jax.ShapeDtypeStruct(
                tuple(gshape[d] for d in gperm), gdtype)
        else:
            if self._green_raw is None:
                with spans.span("flups.green.build"):
                    self._green_raw = build_green(self.plan)
            with spans.span("flups.green.layout"):
                g = self._green_raw.astype(gdtype)
                gp = np.zeros(gshape, dtype=gdtype)
                gp[tuple(slice(0, s) for s in g.shape)] = g
                self._green_np = np.ascontiguousarray(
                    np.transpose(gp, gperm))

        spec_in = [None, None, None]
        spec_in[d1], spec_in[d2] = axes[0], axes[1]
        self._spec_in_tail = tuple(spec_in)
        spec_g = [None, None, None]
        spec_g[d0], spec_g[d1] = axes[0], axes[1]
        # the Green's function never carries the batch axis (vmap broadcasts
        # it), so its spec is the same with or without batch parallelism
        self.g_spec = P(*(spec_g[d] for d in gperm))
        self.in_spec = self.input_spec(local_batch=False)
        self._green_dev = None

        if cfg["comm"] is None:
            # first build: resolve the user's request (incl. "auto")
            comm_req = c["comm_req"]
            if isinstance(comm_req, str) and comm_req == "auto":
                self.comm = self._autotune(c["autotune_candidates"],
                                           c["autotune_cache"],
                                           c["autotune_batch"],
                                           budget=c["autotune_budget"])
            else:
                self.comm = as_comm(comm_req)
            self._cfg["comm"] = self.comm.strategy
        elif getattr(self, "comm", None) is None \
                or cfg["comm"] != self.comm.strategy:
            # ladder rebuild: degraded strategy, n_chunks/fold carried over
            prev = getattr(self, "comm", None) or CommConfig()
            nc = prev.n_chunks if cfg["comm"] in ("pipelined", "overlap") \
                else 1
            self.comm = CommConfig(cfg["comm"], max(nc, 1), prev.fold,
                                   prev.chunk_axis)
        self._green_dev = None
        self._jits = {}
        # checked (verify="abft-stages" / localization) traces live apart
        # from the clean jits: they emit checksum sandwiches, sidecar
        # collectives and a report output, so the clean path stays
        # bit-exact with checks compiled out.  verify="abft" shares the
        # clean jits -- its sandwich is entirely host-side -- and
        # ``_lite_weights`` holds the plan-time Freivalds material
        # (rank-1 probe factors, w = S^T C^T r)
        self._abft_jits = {}
        self._lite_weights = {}
        self._jit = self.jit_for(local_batch=False)

    # -- local (per-shard) pipeline ----------------------------------------

    def _local_solve(self, x, green, *, cfg: CommConfig,
                     col=None, tol=None):
        sched = self.schedule
        d0, d1, d2 = self.plan.order
        a1, a2 = self.axes
        U, S = self._U, self._S
        strat = make_strategy(cfg, axis_sizes=self._axis_sizes,
                              abft=None if col is None else (col, tol))
        # leading batch axes (multi-RHS) shift every grid-dim index; they
        # are also the chunked strategies' preferred (free) chunk axis --
        # unless the config pins the uninvolved grid axis (chunk_axis="grid")
        off = x.ndim - len(self.plan.dirs)
        ca = 0 if off and cfg.chunk_axis == "auto" else None
        e0, e1, e2 = d0 + off, d1 + off, d2 + off

        # forward sweep: every switch carries the next direction's transform
        # as its post continuation (crop the gathered axis, then transform).
        # ``valid_extent`` is the split axis's live extent (deferred-doubling
        # pruning: the first switches ship the n-point physical axes, never
        # a 2n Hockney extension); the strategy crops + re-pads to the
        # equal-split multiple internally.
        x = sched.fwd_chunk(x, d0, col, tol)
        x = strat.stage(
            x, a1, e0, e1, chunk_axis=ca, valid_extent=S[d0],
            post=lambda c: sched.fwd_chunk(_crop_dim(c, e1, U[d1]), d1,
                                           col, tol))
        x = strat.stage(
            x, a2, e1, e2, chunk_axis=ca, valid_extent=S[d1],
            post=lambda c: sched.fwd_chunk(_crop_dim(c, e2, U[d2]), d2,
                                           col, tol))

        x = sched.green_multiply(x, green, col, tol)

        x = sched.bwd_chunk(x, d2, col, tol)
        x = strat.stage(
            x, a2, e2, e1, chunk_axis=ca, valid_extent=U[d2],
            post=lambda c: sched.bwd_chunk(_crop_dim(c, e1, S[d1]), d1,
                                           col, tol))
        x = strat.stage(
            x, a1, e1, e0, chunk_axis=ca, valid_extent=U[d1],
            post=lambda c: sched.bwd_chunk(_crop_dim(c, e0, S[d0]), d0,
                                           col, tol))
        if jnp.iscomplexobj(x):
            x = x.real
        return x.astype(self.dtype)

    def _local_solve_scheduled(self, x, green, *, cfg: CommConfig,
                               col=None, tol=None):
        """The layout-SCHEDULED local pipeline (DESIGN.md #9): every stage
        keeps its active axis minor-most, so the 1-D transforms move no
        data, and the single relayout between consecutive directions is
        folded into the topology switch's pack (``permute=``) -- after it
        the collective always splits the retiring dim as a contiguous
        MAJOR axis and gathers the incoming dim straight into the
        minor-most slot the next transform consumes.  The only standalone
        transposes left are the two edge adapters (natural user layout in,
        natural layout out) -- asserted on lowered HLO via
        ``hlo_stats.transpose_stats``.  Numerically identical to
        ``_local_solve`` (bit-exact on the XLA engine: transposes reorder
        rows, the per-row transform and pointwise math is unchanged).
        """
        sched = self.schedule
        d0, d1, d2 = self.plan.order
        a1, a2 = self.axes
        U, S = self._U, self._S
        lay = sched.layouts
        L0, L1, L2 = lay.fwd
        B0, B1, B2 = lay.bwd                 # B0 == L2 (spectral layout)
        strat = make_strategy(cfg, axis_sizes=self._axis_sizes,
                              abft=None if col is None else (col, tol))
        off = x.ndim - len(self.plan.dirs)
        ca = 0 if off and cfg.chunk_axis == "auto" else None
        nat = tuple(range(len(self.plan.dirs)))
        first, last = off, x.ndim - 1        # switch frame: split major,
                                             # gather minor (switch_layout)

        def pm(src, dst):
            # transpose spec (full array rank) folded into the pack
            return (tuple(range(off))
                    + tuple(off + src.index(d) for d in dst))

        x = relayout(x, nat, L0)             # edge adapter (identity when
                                             # d0 is already minor-most)
        x = sched.fwd_last(x, d0, col, tol)
        x = strat.stage(
            x, a1, first, last, chunk_axis=ca,
            valid_extent=S[d0], permute=pm(L0, L1),
            post=lambda c: sched.fwd_last(_crop_dim(c, last, U[d1]), d1,
                                          col, tol))
        if col is None and sched.can_fuse_green(d2):
            # Pallas: the last forward FFT runs the Green multiply in its
            # final-stage registers -- the stage continuation only crops,
            # the fused kernel runs on the whole switched block
            x = strat.stage(
                x, a2, first, last, chunk_axis=ca,
                valid_extent=S[d1], permute=pm(L1, L2),
                post=lambda c: _crop_dim(c, last, U[d2]))
            x = sched.fwd_last_green(x, d2, green)
        else:
            x = strat.stage(
                x, a2, first, last, chunk_axis=ca,
                valid_extent=S[d1], permute=pm(L1, L2),
                post=lambda c: sched.fwd_last(_crop_dim(c, last, U[d2]), d2,
                                              col, tol))
            x = sched.green_multiply(x, green, col, tol)

        x = sched.bwd_last(x, d2, col, tol)  # spectral layout: d2 last
        x = strat.stage(
            x, a2, first, last, chunk_axis=ca,
            valid_extent=U[d2], permute=pm(B0, B1),
            post=lambda c: sched.bwd_last(_crop_dim(c, last, S[d1]), d1,
                                          col, tol))
        x = strat.stage(
            x, a1, first, last, chunk_axis=ca,
            valid_extent=U[d1], permute=pm(B1, B2),
            post=lambda c: sched.bwd_last(_crop_dim(c, last, S[d0]), d0,
                                          col, tol))
        x = relayout(x, B2, nat)             # edge adapter back
        if jnp.iscomplexobj(x):
            x = x.real
        return x.astype(self.dtype)

    # -- jit assembly --------------------------------------------------------

    def input_spec(self, local_batch: bool = False) -> P:
        """PartitionSpec of the input field: optional pod-sharded batch,
        optional replicated in-block batch, then the pencil grid."""
        parts = []
        if self.batch_axis is not None:
            parts.append(self.batch_axis)
        if local_batch:
            parts.append(None)
        return P(*parts, *self._spec_in_tail)

    def jit_for(self, local_batch: bool = False, donate: bool = True):
        """The jitted distributed solve for one input rank (cached).

        The cache key includes the active fault-plan token, so arming a
        ``FaultPlan`` forces a retrace (the trace-time taint/fail_point
        hooks run) and a tainted trace never shadows the clean entry."""
        from repro.runtime import faults
        key = (bool(local_batch), bool(donate), faults.plan_token())
        fn = self._jits.get(key)
        if fn is None:
            fn = self._build_jit(self.comm, donate=donate,
                                 local_batch=local_batch)
            self._jits[key] = fn
        return fn

    def _build_jit(self, cfg: CommConfig, donate: bool,
                   local_batch: bool = False):
        """shard_map + jit of the local pipeline under one comm config."""
        body = (self._local_solve_scheduled if self.relayout == "scheduled"
                else self._local_solve)
        local = partial(body, cfg=cfg)
        if self.batch_axis is not None:
            local = jax.vmap(local, in_axes=(0, None))
        in_spec = self.input_spec(local_batch)
        # the varying-manual-axes checker is off: pallas_call has no rule
        # for it, and the transpose of jnp.fft drops the annotation, so
        # the ABFT sandwich's vjp of this body fails type checking
        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(in_spec, self.g_spec),
            out_specs=in_spec, check_vma=False)
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    def _abft_tol(self) -> float:
        from repro.runtime import abft
        return self.abft_rtol or abft.tol_for(self.dtype)

    def abft_jit_for(self, local_batch: bool = False):
        """The CHECKED distributed solve (``verify="abft"``): returns
        ``(fn, names)`` where ``fn(f, green) -> (u, report)``.  The local
        body runs with an ``abft.Collector`` threaded through every
        transform stage and topology switch (the comm strategy ships the
        checksum sidecars), each shard's mismatch vector is max-combined
        across both pencil axes with ``lax.pmax``, and the stage names are
        captured into ``names`` at trace time."""
        from repro.runtime import faults
        key = (bool(local_batch), faults.plan_token())
        ent = self._abft_jits.get(key)
        if ent is None:
            ent = self._abft_jits[key] = self._build_abft_jit(
                self.comm, local_batch=local_batch)
        return ent

    def _build_abft_jit(self, cfg: CommConfig, local_batch: bool = False):
        from repro.runtime import abft
        body = (self._local_solve_scheduled if self.relayout == "scheduled"
                else self._local_solve)
        a1, a2 = self.axes
        tol = self._abft_tol()
        holder: list = []

        def local(x, green):
            col = abft.Collector()
            y = body(x, green, cfg=cfg, col=col, tol=tol)
            # every rank checks its own rows; one pmax per axis folds the
            # mesh's K-vector reports into a replicated worst-case vector
            rep = col.stacked()
            rep = jax.lax.pmax(jax.lax.pmax(rep, a1), a2)
            holder[:] = col.names
            return y, rep

        if self.batch_axis is not None:
            # pod-sharded batch: each batch element keeps its own report
            # row ((B, K) global); the host audits the max over rows
            local = jax.vmap(local, in_axes=(0, None))
            rep_spec = P(self.batch_axis, None)
        else:
            rep_spec = P()
        in_spec = self.input_spec(local_batch)
        # the report is replicated by construction (pmax over both axes);
        # the checker cannot see that, so it is off
        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(in_spec, self.g_spec),
            out_specs=(in_spec, rep_spec), check_vma=False)
        return jax.jit(fn, donate_argnums=(0,)), holder

    def _lite_pair(self, fp_shape, local_batch: bool):
        """Plan-time Freivalds material for one padded input signature:
        rank-1 probe factors ``q0, q1, q2`` over the USER grid and the
        host copy of the weight ``w = S^T C^T r`` -- one vjp of the
        linear distributed solve with the probe zero-embedded through the
        output crop ``C``, traced under fault suppression -- restricted
        to the valid input corner.  Both sandwich sides then run on the
        HOST: ``<r, u>`` is three chained BLAS contractions of the
        cropped output against the factors, ``<w, f>`` one dot against
        the raw user field, and the device pipeline is the SAME jit as
        ``verify=off`` -- zero graph changes, zero extra collectives (on
        a host-device mesh, in-graph scalar plumbing costs more in op
        dispatch than the reductions themselves).  Returns None when the
        sandwich is unavailable -- lazy-green dry runs (no real kernel to
        differentiate through) or an engine whose kernels carry no vjp
        rules -- and ``solve`` falls back to the checked pipeline."""
        from repro.runtime import abft, faults
        key = tuple(fp_shape)
        if key in self._lite_weights:
            return self._lite_weights[key]
        rw = None
        if not self._ctor["lazy_green"]:
            sh = NamedSharding(self.mesh, self.input_spec(local_batch))
            user_grid = tuple(p.n_pts for p in self.plan.dirs)
            qs = abft.lite_probe_axes(user_grid, self.dtype)
            # cotangent: the rank-1 probe over the user grid, zero-padded
            # into the padded output shape (probing the CROPPED output --
            # corruption confined to cropped-away padding cannot reach
            # the solution and needs no alarm)
            r_user = np.einsum("i,j,k->ijk", *qs)
            r_pad = np.zeros(fp_shape, r_user.dtype)
            r_pad[(Ellipsis,) + tuple(slice(0, m) for m in user_grid)] = \
                r_user
            r = jax.device_put(r_pad, sh)
            zero = jax.device_put(
                np.zeros(fp_shape, jnp.dtype(self.dtype)), sh)
            base = self._build_jit(self.comm, donate=False,
                                   local_batch=local_batch)
            try:
                with faults.suppressed():
                    w = jax.jit(lambda rr, gg, z: jax.vjp(
                        lambda x: base(x, gg), z)[1](rr)[0])(
                            r, self.green_device(), zero)
                    jax.block_until_ready(w)
                # padding is zeros, so <w, pad(f)> == <w_valid, f>: keep
                # only the valid corner, in the solve dtype -- the host
                # dot is then one BLAS sdot/ddot with no conversion pass
                wh = np.asarray(w)
                valid = (Ellipsis,) + tuple(
                    slice(0, m) for m in user_grid)
                wv = np.ascontiguousarray(wh[valid])
                wf = wv.reshape(wv.shape[:-3] + (-1,)).astype(np.float64)
                wn = np.sqrt(np.einsum("...i,...i->...", wf, wf))
                rw = (qs, wv, wn)
            except NotImplementedError:
                # an engine kernel without a differentiation rule (pallas):
                # no sandwich weight; verify="abft" degrades to the checked
                # pipeline for this config
                rw = None
        self._lite_weights[key] = rw
        return rw

    # -- plan-time comm autotuner (flups switchsort analogue) ----------------

    def autotune_key(self):
        """Canonical, repr-stable identity of (shape, bcs, layout, mesh).

        ``doubling`` is part of the identity: a pruned (deferred) plan and a
        dense (up-front) plan ship different extents through every switch,
        so a persisted winner for one must never be replayed for the other
        (the $REPRO_COMM_CACHE staleness guard, tested in test_comm.py).
        """
        dirs = self.plan.dirs
        eng = self.engine.name + ("" if self.engine.max_radix == 4
                                  else f"@r{self.engine.max_radix}")
        return (
            tuple(p.n for p in dirs),
            tuple((p.bc.left.name, p.bc.right.name) for p in dirs),
            dirs[0].layout.name,
            tuple((a, int(self.mesh.shape[a])) for a in self.mesh.axis_names),
            tuple(self.axes), self.batch_axis,
            jnp.dtype(self.dtype).name, eng,
            ("doubling", self.plan.doubling),
            # the layout schedule changes what every candidate compiles to
            # (relayouts folded into the switches vs standalone moveaxis,
            # and the execution order the layouts were chosen for), so the
            # tuner must time what will actually run
            ("relayout", self.relayout),
            ("order", self.plan.order),
        )

    def comm_time_fn(self, batch=None, reps: int = 3):
        """``time_fn(cfg) -> seconds`` over THIS solver's plan/mesh: build
        the jitted pipeline under one comm config, compile + warm, return
        the best of ``reps`` wall-clock solves.  What the autotuner (and
        the guided-vs-brute oracle tests / ``bench_comm.py --search``)
        time candidates with.  ``batch`` follows ``_autotune``'s
        convention: the pod-sharded extent when ``batch_axis`` is set,
        else the in-block multi-RHS extent (None = unbatched)."""
        local_batch = self.batch_axis is None and batch is not None
        fshape = self.padded_input_shape(batch)
        gsd = self._green_np
        in_spec = self.input_spec(local_batch)

        def time_cfg(cfg):
            fn = self._build_jit(cfg, donate=False, local_batch=local_batch)
            f = jax.device_put(jnp.ones(fshape, self.dtype),
                               NamedSharding(self.mesh, in_spec))
            # lazy_green dry-runs autotune against a zero kernel: comm cost
            # does not depend on the Green's values, only its layout
            if isinstance(gsd, jax.ShapeDtypeStruct):
                g = jax.device_put(jnp.zeros(gsd.shape, gsd.dtype),
                                   NamedSharding(self.mesh, self.g_spec))
            else:
                g = self.green_device()
            fn(f, g).block_until_ready()          # compile + warm
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                fn(f, g).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            return best

        return time_cfg

    def _autotune(self, candidates, cache_path, batch=None,
                  reps: int = 3, budget=None) -> CommConfig:
        # timed workload must match the production rank: the pod-sharded
        # batch (default: the pod mesh extent) when ``batch_axis`` is set,
        # or the IN-BLOCK multi-RHS batch when the caller states it
        # (``autotune_batch`` on a 2-axis mesh) -- otherwise the tuner
        # would time the unbatched pipeline and could cache an n_chunks
        # that does not divide B, silently losing the free batch-axis
        # chunking in production.  The timed extent is part of the cache
        # key, so differently-sized tunings never collide.
        if self.batch_axis is not None and batch is None:
            batch = self.mesh.shape[self.batch_axis]
        time_cfg = self.comm_time_fn(batch, reps=reps)
        self.autotune_results = {}
        self.autotune_census = {}
        if candidates is None:
            # layout-scheduled plans also sweep the relayout fold side:
            # whether the switch-fused transpose is cheaper on the pack or
            # the unpack side of the collective is shape-dependent
            folds = (("pack", "unpack") if self.relayout == "scheduled"
                     else ("pack",))
            if self._ctor.get("autotune_search", "guided") == "guided":
                # DESIGN.md #12: rank the comm sub-space with the analytic
                # cost model and hand only the shortlisted frontier to the
                # timer.  The shortlist labels are cache-key material, so
                # a guided pick never shadows (or replays) a brute one.
                from repro.plan.search import guided_comm_candidates
                p1 = self.mesh.shape[self.axes[0]]
                p2 = self.mesh.shape[self.axes[1]]
                in_block = batch if self.batch_axis is None else None
                candidates = guided_comm_candidates(
                    self.plan, p1, p2, self.dtype, batch=in_block,
                    folds=folds, relayout=self.relayout,
                    max_radix=self.engine.max_radix,
                    census=self.autotune_census)
            else:
                candidates = _default_candidates(folds=folds)
        key = self.autotune_key() + (("tuned_batch", batch),)
        return autotune_comm(key, time_cfg,
                             candidates=candidates, cache_path=cache_path,
                             results=self.autotune_results,
                             budget_s=budget, census=self.autotune_census)

    # -- public API ----------------------------------------------------------

    @property
    def input_shape(self):
        return self.plan.input_shape

    def padded_input_shape(self, batch=None):
        d0, d1, d2 = self.plan.order
        shp = [0, 0, 0]
        shp[d0] = self._U[d0]
        shp[d1] = self._PU1
        shp[d2] = self._PU2
        shp = tuple(shp)
        return ((batch,) + shp) if batch is not None else shp

    def _pad_input(self, f):
        from repro.core.engine import materialize_doubling
        d0, d1, d2 = self.plan.order
        off = f.ndim - 3
        # dense (up-front) plans materialize the Hockney zero extension in
        # the global field before the mesh-divisibility padding; deferred
        # plans skip this and every switch ships the n-point extents
        f = materialize_doubling(f, self.plan.dirs)
        f = _pad_dim(f, d1 + off, self._PU1)
        f = _pad_dim(f, d2 + off, self._PU2)
        return f

    def green_device(self):
        if self._green_dev is None:
            with spans.span("flups.green.put"):
                self._green_dev = jax.device_put(
                    self._green_np,
                    NamedSharding(self.mesh, self.g_spec))
        return self._green_dev

    def _dispatch(self, f, local_batch: bool, abft: bool = False,
                  lite: bool = False):
        """One solve attempt under the CURRENT config: pad, shard, run the
        jitted pipeline, crop.  Re-entered by the degradation ladder after
        ``_configure`` rebuilds -- padded extents/specs may differ per rung,
        so everything derives from the raw user array each attempt.  Under
        ``abft`` the checked jit runs and ``(u, names, report)`` returns;
        under ``lite`` the SAME jit as verify-off runs (the sandwich is
        entirely host-side) and ``(u, qs, w_valid, w_norm)`` returns (or
        None when the sandwich is unavailable for this config)."""
        with spans.span("flups.pad"):
            fp = self._pad_input(f)
            spec = self.input_spec(local_batch)
            fp = jax.device_put(fp, NamedSharding(self.mesh, spec))
        names = rep = None
        if lite:
            ent = self._lite_pair(fp.shape, local_batch)
            if ent is None:
                return None
            qs, wv, wn = ent
        green = self.green_device()
        with spans.span("flups.launch"):
            if abft:
                fn, names = self.abft_jit_for(local_batch)
                out, rep = fn(fp, green)
            else:
                out = self.jit_for(local_batch)(fp, green)
        from repro.core.engine import crop_doubling
        with spans.span("flups.crop"):
            d0, d1, d2 = self.plan.order
            off = out.ndim - 3
            out = _crop_dim(out, d1 + off, self._U[d1])
            out = _crop_dim(out, d2 + off, self._U[d2])
            out = crop_doubling(out, self.plan.dirs)
        if lite:
            return (out,) + ent
        return (out, names, rep) if abft else out

    @staticmethod
    def _lite_contract(out, qs):
        """Host side of ``<r, u>`` for the rank-1 probe: contract every
        addressable shard of the (cropped, sharded) output against the
        factor slices its global index selects, and accumulate into the
        leading (batch) dims.  Zero-copy on a host-device mesh; shards
        are deduped by index in case a mesh axis replicates them."""
        off = out.ndim - 3
        acc = np.zeros(out.shape[:off], np.float64)
        seen = set()
        for shard in out.addressable_shards:
            idx = shard.index
            key = tuple((sl.start, sl.stop) for sl in idx)
            if key in seen:
                continue
            seen.add(key)
            t = np.asarray(shard.data)
            for ax in (2, 1, 0):             # minor-most first
                t = np.tensordot(t, qs[ax][idx[off + ax]],
                                 axes=([t.ndim - 1], [0]))
            acc[idx[:off]] += t
        return acc

    def solve(self, f, verify=None):
        """f: global field, optionally with leading batch dims.

        Accepted ranks: ``(*grid)``; ``(B, *grid)`` (in-block multi-RHS
        batch, or the pod-sharded batch when ``batch_axis`` is set);
        ``(B_pod, B, *grid)`` (both).

        ``verify`` (default: the constructor's setting) opts into post-solve
        health checks ("nan" | "residual" | "abft"); any failure --
        injected fault, comm error, non-finite output, surviving checksum
        mismatch -- walks the degradation ladder (engine, comm strategy,
        relayout schedule, doubling) before raising a
        :class:`repro.runtime.SolveError` with stage provenance.  Under
        ``"abft"`` every transform stage and topology switch is checksum-
        sandwiched (DESIGN.md #13): transient flips are repaired in place
        by the inline selective recompute, repairs are recorded in
        ``stats["integrity"]``, and wire-attributed corruption retries as
        a transient before degrading.
        """
        with spans.span("flups.solve", closes_step=True):
            return self._run_solve(f, verify)

    def _run_solve(self, f, verify):
        from repro.runtime import abft as _abft
        from repro.runtime import faults, health, resilience
        f_host = f if (isinstance(f, np.ndarray)
                       and f.dtype == np.dtype(self.dtype)) else None
        f = jnp.asarray(f, dtype=self.dtype)
        base = 3 + (1 if self.batch_axis is not None else 0)
        assert f.ndim in (base, base + 1), (f.shape, base)
        local_batch = f.ndim == base + 1
        verify = self.verify if verify is None else verify

        def checked():
            out, names, rep = self._dispatch(f, local_batch, abft=True)
            _abft.verify_report(
                list(names), np.asarray(rep), tol=self._abft_tol(),
                stats=self.stats, describe="dist.solve")
            return out

        def attempt():
            faults.fail_point("dist.dispatch")
            if verify == "abft-stages":
                return checked()
            if verify == "abft":
                res = self._dispatch(f, local_batch, lite=True)
                if res is None:       # sandwich unavailable: checked mode
                    return checked()
                out, qs, wv, wn = res
                # on a host-platform mesh the "device" threads share the
                # machine's cores with this thread, so overlapping the host
                # dots with the async solve just causes cache/CPU
                # contention -- let the solve finish, then run both dots on
                # an uncontended machine (measured faster than overlap)
                jax.block_until_ready(out)
                # the <w,f> side: one BLAS dot against the raw user field
                # (the caller's numpy buffer when dtypes match: no device
                # round trip, no conversion pass)
                fh = f_host if f_host is not None else np.asarray(f)
                fw = fh.reshape(fh.shape[:-3] + (-1,))
                wf = wv.reshape(wv.shape[:-3] + (-1,))
                if fw.ndim == 1:
                    b = np.float64(np.dot(wf, fw))
                else:
                    b = np.einsum("...i,...i->...", wf, fw,
                                  dtype=np.float64)
                # the <r,u> side: per-shard chained BLAS contractions
                # against the rank-1 factors, on zero-copy host views of
                # each device buffer -- skips the (slow) full-array gather
                a = self._lite_contract(out, qs)
                a = a.reshape(np.shape(b))
                tol = self._abft_tol() * _abft.LITE_HEADROOM
                m = _abft.lite_mismatch_ab(a, b, np.zeros_like(wn))
                if m > tol:
                    # near-cancelling dots: only now pay for the noise
                    # floor ||w||*||f||/sqrt(N) before calling it a trip
                    fnorm = np.sqrt(np.einsum("...i,...i->...", fw, fw,
                                              dtype=np.float64))
                    floor = wn * fnorm / np.sqrt(wf.shape[-1])
                    m = _abft.lite_mismatch_ab(a, b, floor)
                if m <= tol:
                    return out
                # sandwich tripped: localize via the checked pipeline
                # (inline selective repair; persistent corruption raises
                # IntegrityError out of verify_report into the ladder)
                self.stats["verify_failures"] += 1
                self.stats.setdefault("integrity", []).append({
                    "stage": "solve.linearity", "kind": "linearity",
                    "mismatch": float(m), "tol": float(tol),
                    "action": "localize", "describe": "dist.solve"})
                return checked()
            out = self._dispatch(f, local_batch)
            if verify:
                locate = None
                if not self._ctor["lazy_green"]:
                    locate = lambda: health.locate_nonfinite_stage(
                        self.plan, self.schedule, f, self._green_raw)
                health.check_solution(out, f, self.plan, mode=verify,
                                      rtol=self.verify_rtol,
                                      stats=self.stats, locate=locate)
            return out

        out = resilience.run_with_ladder(
            attempt, config=self._cfg, reconfigure=self._configure,
            stats=self.stats, describe="dist.solve")
        self.stats["solves"] += 1
        return out

    # -- elastic recovery ----------------------------------------------------

    def rebuild(self, mesh, *, axes=None, comm=None):
        """Re-plan on a (possibly shrunken) surviving mesh.

        Returns a NEW solver for ``mesh``: the full construction identity is
        replayed (so pencil splits, padding, specs and jits all match the
        new device topology) while the expensive plan-time state is reused
        -- the raw transformed Green's function is handed over (never
        reassembled) and a comm ``"auto"`` request re-resolves through the
        persisted autotune JSON cache keyed by the new mesh.  Ladder state
        carries over: the current (possibly degraded) engine/relayout/
        doubling config seeds the new solver, and stale ``get_solver``
        entries for the OLD mesh are evicted so no caller can obtain a
        solver bound to dead devices.
        """
        from repro.core.solver import evict_solver_entries
        evict_solver_entries(self._caller_mesh)
        c = self._ctor
        new = DistributedPoissonSolver(
            c["shape"], c["L"], c["bcs"], c["layout"], c["green_kind"],
            mesh=mesh, axes=tuple(axes) if axes is not None else self.axes,
            comm=comm if comm is not None else c["comm_req"],
            batch_axis=self.batch_axis, eps_factor=c["eps_factor"],
            dtype=self.dtype, lazy_green=c["lazy_green"],
            engine=(c["engine_obj"]
                    if c["engine_obj"].name == self._cfg["engine"]
                    else self._cfg["engine"]),
            doubling=self._cfg["doubling"],
            relayout=self._cfg["relayout"],
            order_policy=c["order_policy"],
            autotune_candidates=c["autotune_candidates"],
            autotune_cache=c["autotune_cache"],
            autotune_batch=c["autotune_batch"],
            autotune_budget=c["autotune_budget"],
            autotune_search=c.get("autotune_search", "guided"),
            verify=self.verify, verify_rtol=self.verify_rtol,
            abft_rtol=self.abft_rtol, _green_cache=self._green_raw)
        new.stats["degradations"] = list(self.stats["degradations"])
        return new

    def lower(self, batch=None, dtype=None, *, local_batch: bool = False):
        """Lower the jitted distributed solve with ShapeDtypeStructs (dry-run).

        ``batch`` sizes the leading batch dims: an int for the single one
        in play (the pod-sharded dim when ``batch_axis`` is set, else the
        in-block multi-RHS dim under ``local_batch=True``), or a
        ``(pod, local)`` pair when both are present.  Missing leading dims
        default to 1 so the lowered rank always matches the input spec.
        """
        dtype = dtype or self.dtype
        defaults = []           # leading dims in order: pod-sharded, local
        if self.batch_axis is not None:
            defaults.append(int(self.mesh.shape[self.batch_axis]))
        if local_batch:
            defaults.append(1)
        n_lead = len(defaults)
        lead = () if batch is None else (
            tuple(batch) if isinstance(batch, (tuple, list)) else (batch,))
        if len(lead) < n_lead:
            lead = tuple(defaults[:n_lead - len(lead)]) + lead
        assert len(lead) == n_lead, (batch, self.batch_axis, local_batch)
        shp = lead + self.padded_input_shape()
        spec = self.input_spec(local_batch)
        f = jax.ShapeDtypeStruct(shp, dtype,
                                 sharding=NamedSharding(self.mesh, spec))
        return self.jit_for(local_batch).lower(f, self._green_shape())

    def _green_shape(self):
        return jax.ShapeDtypeStruct(
            self._green_np.shape, self._green_np.dtype,
            sharding=NamedSharding(self.mesh, self.g_spec))

    def stage_map(self) -> dict:
        """Which stages of the (unbatched) solve run a Pallas kernel and
        which run XLA, read off the traced pipeline (``engine.stage_map``):
        ``{"fwd.0": "pallas", ..., "green": "pallas", ...}``."""
        from repro.core.engine import stage_map
        f = jax.ShapeDtypeStruct(
            self.padded_input_shape(), self.dtype,
            sharding=NamedSharding(self.mesh, self.input_spec()))
        return stage_map(jax.make_jaxpr(self.jit_for())(
            f, self._green_shape()))
