"""FFT-based Poisson solver (the flups pipeline), single-process reference.

The solve is the paper's algorithm:

  forward:  for each direction (r2r dirs first, then semi-unbounded r2r,
            then the DFT dirs -- the first DFT dir is real-to-complex):
            shuffle the direction to the last axis, pad / slice per the BC
            convention (section II), 1-D transform;
  multiply: pointwise with the transformed Green's function (+ quadrature
            weight h per unbounded-ish direction and the r2r normalization);
  backward: inverse transforms in reverse order, crop, write back the
            convention-overwritten boundary values.

The distributed version (``repro.core.comm`` + ``repro.distributed``) swaps
the axis shuffles for pencil topology switches; the per-direction math here
is reused unchanged.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import scipy.fft as sfft

from .bc import (BCType, DataLayout, DirBC, TransformKind, r2r_kind,
                 INVERSE_KIND)
from . import transforms as tr
from . import green as gr
from repro.runtime import spans
from .engine import (RELAYOUT_MODES, as_engine, build_schedule,
                     folded_normfact, fwd_1d, bwd_1d, relayout as _relayout,
                     schedule_layouts)

__all__ = ["Plan1D", "PoissonPlan", "PoissonSolver", "make_plan",
           "get_solver", "clear_solver_cache", "solver_cache_info",
           "set_solver_cache_capacity", "evict_solver_entries",
           "evict_solver_instance"]


@dataclass(frozen=True)
class Plan1D:
    dim: int
    bc: DirBC
    layout: DataLayout
    n: int                  # number of cells; node layout owns n+1 points
    L: float
    category: str           # "sym" | "semi" | "per" | "unb"
    kind: TransformKind | None
    dft: str | None         # "r2c" | "c2c" | None
    n_pts: int              # points in the user array along this dim
    in_start: int           # first user point handed to the transform
    n_in: int               # number of user points handed to the transform
    n_fft: int              # transform length (after padding)
    n_out: int              # spectral storage size
    flip: bool
    koffset: int            # storage index -> mode index offset
    normfact: float
    modes: tuple            # omega per storage index (length n_out)
    zero_left: bool = False   # backward writes 0 at user index 0
    zero_right: bool = False  # backward writes 0 at the last user index
    per_dup: bool = False     # node-periodic: copy u_0 into u_N
    # Hockney-doubling execution mode of this direction (PoissonPlan
    # ``doubling``): False = deferred/pruned (default; the transform pads
    # n_in -> n_fft itself, so every stage before it sees only the n_in
    # live points), True = the zero extension is materialized UP FRONT in
    # the user array (dense textbook Hockney: transforms and topology
    # switches all see the doubled extent).
    pre_padded: bool = False

    @property
    def h(self) -> float:
        return self.L / self.n

    @property
    def is_unbounded_like(self) -> bool:
        return self.category in ("semi", "unb")

    @property
    def valid_in(self) -> int:
        """Live physical extent of this axis anywhere OUTSIDE the 1-D
        transform: what the solvers carry through topology switches before
        the forward and after the backward transform of this direction
        (the spectral counterpart is the plain ``n_out`` field)."""
        return self.n_fft if self.pre_padded else self.n_pts


def _sym_plan(dim, bc, layout, n, L) -> Plan1D:
    kind = r2r_kind(bc, layout)
    h = L / n
    if layout == DataLayout.NODE:
        n_pts = n + 1
        table = {
            TransformKind.DST1: (1, n - 1, True, True),
            TransformKind.DST3: (1, n, True, False),
            TransformKind.DCT3: (0, n, False, True),
            TransformKind.DCT1: (0, n + 1, False, False),
        }
        in_start, n_in, zl, zr = table[kind]
    else:
        n_pts, in_start, n_in, zl, zr = n, 0, n, False, False
    half = kind in (TransformKind.DCT3, TransformKind.DCT4,
                    TransformKind.DST3, TransformKind.DST4)
    koff = 1 if kind in (TransformKind.DST1, TransformKind.DST2) else 0
    k = np.arange(n_in) + koff
    modes = (k + 0.5) * np.pi / L if half else k * np.pi / L
    return Plan1D(dim, bc, layout, n, L, "sym", kind, None, n_pts,
                  in_start, n_in, n_in, n_in, False, koff,
                  tr.r2r_normfact(kind, n_in), tuple(modes), zl, zr)


def _per_plan(dim, bc, layout, n, L, dft) -> Plan1D:
    n_pts = n + 1 if layout == DataLayout.NODE else n
    if dft == "r2c":
        n_out = n // 2 + 1
        modes = 2.0 * np.pi * np.arange(n_out) / L
    else:
        n_out = n
        modes = 2.0 * np.pi * np.fft.fftfreq(n) * n / L
    return Plan1D(dim, bc, layout, n, L, "per", None, dft, n_pts, 0, n, n,
                  n_out, False, 0, 1.0, tuple(modes),
                  per_dup=(layout == DataLayout.NODE))


def _unb_plan(dim, bc, layout, n, L, dft) -> Plan1D:
    n_pts = n + 1 if layout == DataLayout.NODE else n
    n_in = n_pts
    n_fft = 2 * n
    if dft == "r2c":
        n_out = n + 1
        modes = 2.0 * np.pi * np.arange(n_out) / (2.0 * L)
    else:
        n_out = n_fft
        modes = 2.0 * np.pi * np.fft.fftfreq(n_fft) * n_fft / (2.0 * L)
    return Plan1D(dim, bc, layout, n, L, "unb", None, dft, n_pts, 0, n_in,
                  n_fft, n_out, False, 0, 1.0, tuple(modes))


def _semi_plan(dim, bc, layout, n, L) -> Plan1D:
    """Semi-unbounded: doubled domain + same-symmetry r2r at both ends.

    The rhs support [0, L] inside the 2L transform domain makes the far-end
    image exact (Hockney doubling, see tests/test_poisson.py oracle).
    """
    flip = bc.right != BCType.UNB          # symmetry end on the right
    sym = bc.right if flip else bc.left
    pair = DirBC(sym, sym)
    kind = r2r_kind(pair, layout)          # on the doubled domain
    if layout == DataLayout.NODE:
        n_pts = n + 1
        if kind == TransformKind.DST1:     # odd: interior of doubled domain
            in_start, n_in, n_fft = 1, n, 2 * n - 1
            zl, zr = True, False
        else:                              # DCT1 on 2n+1 points
            in_start, n_in, n_fft = 0, n + 1, 2 * n + 1
            zl = zr = False
    else:
        n_pts, in_start, n_in, n_fft = n, 0, n, 2 * n
        zl = zr = False
    koff = 1 if kind in (TransformKind.DST1, TransformKind.DST2) else 0
    modes = (np.arange(n_fft) + koff) * np.pi / (2.0 * L)
    return Plan1D(dim, bc, layout, n, L, "semi", kind, None, n_pts,
                  in_start, n_in, n_fft, n_fft, flip, koff,
                  tr.r2r_normfact(kind, n_fft), tuple(modes), zl, zr)


DOUBLING_MODES = ("deferred", "upfront")
ORDER_POLICIES = ("layout", "natural")


def _choose_order(groups, ndim: int, policy: str):
    """Execution order of the dims, grouped by BC category (sym, then
    semi, then DFT -- the grouping is a correctness constraint; the order
    WITHIN each group is free).

    ``policy="natural"`` keeps the historical ascending order.
    ``policy="layout"`` (default) picks, among all grouping-consistent
    orders, the one whose ``schedule_layouts`` needs the fewest edge
    relayouts -- e.g. single-category plans run ``(2, 0, 1)``, which both
    starts AND ends the layout-scheduled pipeline in the user's natural
    layout, so the only transposes left are the ones fused into the
    topology switches.  Ties break to the lexicographically smallest
    order, so mixed-BC plans keep their historical order and results.
    """
    if policy == "natural":
        return tuple(d for g in groups for d in g)
    from itertools import permutations, product
    nat = tuple(range(ndim))
    best = None
    for combo in product(*[tuple(permutations(g)) for g in groups]):
        order = tuple(d for g in combo for d in g)
        lay = schedule_layouts(order, ndim)
        cost = int(lay.fwd[0] != nat) + int(lay.bwd[-1] != nat)
        if best is None or (cost, order) < best:
            best = (cost, order)
    return best[1]


@dataclass(frozen=True)
class PoissonPlan:
    dirs: tuple            # Plan1D per logical dim (0..2)
    order: tuple           # execution order of dims (forward)
    green_kind: str
    eps_factor: float
    # Hockney-doubling placement for the fully-unbounded directions:
    #   "deferred" (default) -- pruned execution: the length-2n zero
    #       extension exists only inside that direction's own 1-D transform,
    #       so every other stage (other-direction transforms, topology
    #       switches) sees the n live points;
    #   "upfront"  -- dense textbook Hockney: the input field is padded to
    #       2n in every unbounded direction before the first transform (the
    #       bench_solve baseline; spectral storage is identical either way).
    doubling: str = "deferred"

    @property
    def input_shape(self):
        return tuple(p.n_pts for p in self.dirs)


def make_plan(shape, L, bcs, layout=DataLayout.CELL,
              green_kind=gr.GreenKind.CHAT2, eps_factor=2.0,
              doubling: str = "deferred",
              order_policy: str = "layout") -> PoissonPlan:
    """``shape`` = cells per dim; ``bcs`` = 3 (left,right) BCType pairs."""
    assert doubling in DOUBLING_MODES, doubling
    assert order_policy in ORDER_POLICIES, order_policy
    ndim = len(shape)
    bcs = tuple(DirBC(*b) if not isinstance(b, DirBC) else b for b in bcs)
    for b in bcs:
        b.validate()
    sym_dims, semi_dims, dft_dims = [], [], []
    for d, b in enumerate(bcs):
        if b.is_unbounded or b.is_periodic:
            dft_dims.append(d)
        elif b.is_semi_unbounded:
            semi_dims.append(d)
        else:
            sym_dims.append(d)
    order = _choose_order([g for g in (sym_dims, semi_dims, dft_dims) if g],
                          ndim, order_policy)
    plans = [None] * ndim
    # the real-to-complex direction is the first DFT direction the solve
    # EXECUTES (order-dependent: everything before it is real r2r)
    first_dft = next((d for d in order if d in dft_dims), None)
    for d, b in enumerate(bcs):
        Ld = L[d] if isinstance(L, (tuple, list)) else L
        if b.is_periodic:
            dft = "r2c" if d == first_dft else "c2c"
            plans[d] = _per_plan(d, b, layout, shape[d], Ld, dft)
        elif b.is_unbounded:
            dft = "r2c" if d == first_dft else "c2c"
            plans[d] = _unb_plan(d, b, layout, shape[d], Ld, dft)
        elif b.is_semi_unbounded:
            plans[d] = _semi_plan(d, b, layout, shape[d], Ld)
        else:
            plans[d] = _sym_plan(d, b, layout, shape[d], Ld)
    if doubling == "upfront":
        import dataclasses as _dc
        # dense Hockney applies to the fully-unbounded dirs only (semi dirs
        # keep their r2r in_start/flip slicing, sym/per dirs never pad), so
        # periodic-only plans are bit-identical across both modes
        plans = [_dc.replace(p, pre_padded=True) if p.category == "unb"
                 else p for p in plans]
    return PoissonPlan(tuple(plans), order, green_kind, eps_factor, doubling)


# ---------------------------------------------------------------------------
# Green's function assembly (numpy, plan time)
# ---------------------------------------------------------------------------

def _green_phys_coord(p: Plan1D) -> np.ndarray:
    """Physical sample offsets (units of h index) for an unbounded-ish dir."""
    if p.category == "unb":
        j = np.arange(p.n_fft)
        return np.minimum(j, p.n_fft - j).astype(np.float64)
    # semi: node-sampled kernel on [0, 2L]: DCT-I grid with 2n+1 points
    return np.arange(2 * p.n + 1, dtype=np.float64)


def _green_dct1_align(gh: np.ndarray, axis: int, p: Plan1D) -> np.ndarray:
    """DCT-I transform of the kernel along a semi dir + koffset alignment."""
    gh = sfft.dct(gh, type=1, axis=axis, norm=None)
    sl = [slice(None)] * gh.ndim
    sl[axis] = slice(p.koffset, p.koffset + p.n_out)
    return gh[tuple(sl)]


def build_green(plan: PoissonPlan) -> np.ndarray:
    """Transformed Green's function aligned with the rhs spectral storage.

    The combined normalization of every backward r2r transform (the product
    of the per-direction ``normfact``) is folded in HERE, once at plan time:
    the backward pass then runs unnormalized transforms and the solve
    performs a single pointwise multiply total (see ``TransformSchedule``).
    """
    dirs = plan.dirs
    norm = folded_normfact(plan)
    unb = [p for p in dirs if p.is_unbounded_like]
    spec = [p for p in dirs if not p.is_unbounded_like]
    n_unb = len(unb)
    kind = plan.green_kind
    hs = [p.h for p in dirs]
    h_ref = float(np.min([p.h for p in unb])) if unb else float(np.min(hs))

    if n_unb == 0:
        w = [np.asarray(p.modes) for p in dirs]
        grids = np.meshgrid(*w, indexing="ij")
        w2 = sum(g * g for g in grids)
        gh = gr.spectral_symbol(kind, w2, h_ref, w_axes=w,
                                eps_factor=plan.eps_factor)
        return gh * norm

    # physical axes for unbounded-ish dirs, mode axes for spectral dirs
    axes_coord = []
    for p in dirs:
        if p.is_unbounded_like:
            axes_coord.append(("phys", _green_phys_coord(p) * p.h))
        else:
            axes_coord.append(("mode", np.asarray(p.modes)))
    shape = tuple(len(c[1]) for c in axes_coord)
    g = np.zeros(shape, dtype=np.float64)

    phys_dims = [d for d, p in enumerate(dirs) if p.is_unbounded_like]
    mode_dims = [d for d, p in enumerate(dirs) if not p.is_unbounded_like]

    def bcast(arr1d, d):
        sh = [1] * len(dirs)
        sh[d] = len(arr1d)
        return np.asarray(arr1d).reshape(sh)

    if n_unb == 3:
        if kind == gr.GreenKind.LGF2:
            idx = [np.abs(np.rint(axes_coord[d][1] / dirs[d].h)).astype(int)
                   for d in range(3)]
            ii = [bcast(ix, d) for d, ix in enumerate(idx)]
            ii = np.broadcast_arrays(*ii)
            g = gr.lgf3_on_grid(tuple(ii), h_ref)
        else:
            r2 = sum(bcast(axes_coord[d][1], d) ** 2 for d in range(3))
            g = gr.kernel_3unb(kind, np.sqrt(r2), h_ref,
                               eps_factor=plan.eps_factor)
    elif n_unb == 2:
        (dm,) = mode_dims
        modes = np.asarray(axes_coord[dm][1])
        r2 = sum(bcast(axes_coord[d][1], d) ** 2 for d in phys_dims)
        r = np.sqrt(np.squeeze(r2, axis=dm))          # (n1, n2) radial grid
        gk = gr.kernel_2unb_batch(kind, modes, r, h_ref,
                                  eps_factor=plan.eps_factor)  # (nkz, n1, n2)
        g = np.moveaxis(gk, 0, dm)
    elif n_unb == 1:
        (dp,) = phys_dims
        x = axes_coord[dp][1]
        g = np.zeros(shape)
        # generic: iterate over mode combinations (cheap: O(N^2) combos)
        it = np.ndindex(*[shape[d] if d != dp else 1 for d in range(len(dirs))])
        for idx in it:
            kperp2 = 0.0
            for d in mode_dims:
                kperp2 += axes_coord[d][1][idx[d]] ** 2
            sl = list(idx)
            sl[dp] = slice(None)
            g[tuple(sl)] = gr.kernel_1unb(kind, kperp2, x, h_ref,
                                          eps_factor=plan.eps_factor)
    else:
        raise AssertionError

    # quadrature weight: h per unbounded-ish direction
    for d in phys_dims:
        g = g * dirs[d].h

    # transform along unbounded-ish dirs; the kernel is even-symmetric, so
    # every spectrum is real.  The r2c dir goes first, by a real FFT that
    # keeps only its n_out = n_fft//2 + 1 bins, so the later transforms
    # run on half the data; all of them use every core (at the four-chip
    # size the kernel holds 2^30 points)
    for d in sorted(phys_dims, key=lambda d: dirs[d].dft != "r2c"):
        p = dirs[d]
        if p.category == "unb":
            if p.dft == "r2c":
                g = sfft.rfft(g, axis=d, workers=-1).real
            else:
                g = sfft.fft(g, axis=d, workers=-1).real
        else:  # semi
            g = _green_dct1_align(g, d, p)
    return g * norm


# ---------------------------------------------------------------------------
# forward / backward 1-D ops -- the implementations live in repro.core.engine
# (``fwd_1d`` / ``bwd_1d``, also the distributed stage API); these aliases
# keep the historical import surface for standalone callers.
# ---------------------------------------------------------------------------

_fwd_1d = fwd_1d
_bwd_1d = bwd_1d


# ---------------------------------------------------------------------------
# solver
# ---------------------------------------------------------------------------

def _fresh_jit(impl):
    """``jax.jit`` over a FRESH function object.  jitting a bound method
    directly shares jax's global trace cache across wrappers of the same
    method, so a post-reconfigure ``jax.jit(self._solve_impl)`` can silently
    replay a stale (or fault-tainted) trace whenever the call signature
    coincides; a unique closure per wrapper guarantees the retrace."""
    def call(f):
        return impl(f)
    return jax.jit(call)


class PoissonSolver:
    """u = solve(f): FFT-based solution of lap(u) = f with mixed BCs.

    ``engine``: "xla" (default) or "pallas" -- see ``repro.core.engine``.

    ``solve`` accepts ``f`` of shape ``(*grid)`` (one rhs) or ``(B, *grid)``
    (B independent right-hand sides sharing this plan, solved in ONE fused
    pipeline -- same transform count, bigger row batches).  One jit
    specialization exists per input rank/shape; the plan, schedule and
    Green's function are shared by all of them.

    Resilience (DESIGN.md #10): every ``solve`` runs under the graceful-
    degradation ladder -- on failure the solver retries transient errors
    with bounded backoff, then steps its config down one rung at a time
    (``pallas -> xla``, ``scheduled -> baseline``, ``deferred -> upfront``),
    rebuilding the pipeline each rung; the trail lands in
    ``self.stats["degradations"]`` and a terminal failure raises
    ``repro.runtime.SolveError`` with stage provenance.  ``verify``
    ("nan" | "residual", default off) arms the numerical health guards on
    every solve; a tripped guard walks the same ladder.
    """

    def __init__(self, shape, L, bcs, layout=DataLayout.CELL,
                 green_kind=gr.GreenKind.CHAT2, eps_factor=2.0,
                 engine="xla", doubling="deferred", relayout="scheduled",
                 order_policy="layout", verify=None, verify_rtol=0.5,
                 abft_rtol=0.0):
        assert relayout in RELAYOUT_MODES, relayout
        assert verify in (None, "nan", "residual", "abft",
                          "abft-stages"), verify
        self._base = dict(shape=tuple(shape), L=L, bcs=bcs, layout=layout,
                          green_kind=green_kind, eps_factor=eps_factor,
                          order_policy=order_policy)
        self.verify = verify
        self.verify_rtol = float(verify_rtol)
        # ABFT checksum tolerance; 0.0 = auto per data dtype (abft.tol_for)
        self.abft_rtol = float(abft_rtol)
        self.stats = {"solves": 0, "retries": 0, "verify_failures": 0,
                      "degradations": []}
        self._configure({"engine": as_engine(engine).name,
                         "doubling": doubling, "relayout": relayout})

    def _configure(self, cfg: dict):
        """(Re)build the whole pipeline for one runtime config -- the
        degradation ladder's rebuild hook (also the constructor's builder).
        A fresh ``jax.jit`` wrapper is installed every time, so a retry
        after a trace-time fault re-traces instead of replaying a poisoned
        cache entry."""
        b = self._base
        self._cfg = dict(cfg)
        with spans.span("flups.plan"):
            self.plan = make_plan(b["shape"], b["L"], b["bcs"], b["layout"],
                                  b["green_kind"], b["eps_factor"],
                                  doubling=cfg["doubling"],
                                  order_policy=b["order_policy"])
            self.engine = as_engine(cfg["engine"])
            self.schedule = build_schedule(self.plan, self.engine)
        self.relayout = cfg["relayout"]
        # ONE Green copy, held in the layout the selected pipeline
        # multiplies in: natural for baseline, the spectral LAYOUT (active
        # axis of the last forward stage minor-most) for scheduled --
        # permuted once, at plan time
        with spans.span("flups.green.build"):
            g = build_green(self.plan)
        self._green_nat = g          # natural layout: health diagnosis
        if self.relayout == "scheduled":
            with spans.span("flups.green.layout"):
                g = np.ascontiguousarray(
                    np.transpose(g, self.schedule.layouts.spectral))
        self._green = g
        # jit wrappers are keyed by the active fault-plan token: arming a
        # FaultPlan forces a retrace (the taint/fail_point hooks run at
        # trace time), and the clean entry is never polluted by a tainted
        # trace.  ``self._solve`` stays the clean-path jit (public-ish: the
        # batch benchmark calls it directly).
        self._solve = _fresh_jit(self._solve_impl)
        self._solve_jits = {None: self._solve}
        # ABFT wrappers live in their own caches: they trace DIFFERENT
        # programs (checksum sandwiches + report outputs), so the clean jit
        # above stays bit-exact with the checks compiled out.  ``_abft_jits``
        # holds the fully-checked pipeline (verify="abft-stages" and the
        # localization re-run); ``_lite_jits`` the cheap end-to-end
        # linearity sandwich (verify="abft"); ``_lite_weights`` the
        # plan-time Freivalds pairs (r, w = S^T r), rebuilt per config
        self._abft_jits = {}
        self._lite_jits = {}
        self._lite_weights = {}

    def _jitted(self):
        from repro.runtime import faults
        tok = faults.plan_token()
        fn = self._solve_jits.get(tok)
        if fn is None:
            fn = _fresh_jit(self._solve_impl)
            self._solve_jits[tok] = fn
        return fn

    def _abft_tol(self, dtype) -> float:
        from repro.runtime import abft
        return self.abft_rtol or abft.tol_for(dtype)

    def _abft_fresh_jit(self):
        """Jit wrapper of the CHECKED pipeline: returns ``(u, report)``
        where the report vector stacks every stage's mismatch scalar; the
        stage names are captured into ``holder`` at trace time."""
        from repro.runtime import abft
        impl = self._solve_impl
        holder: list = []

        def call(f):
            col = abft.Collector()
            u = impl(f, col=col, tol=self._abft_tol(f.dtype))
            holder[:] = col.names
            return u, col.stacked()

        return jax.jit(call), holder

    def _abft_jitted(self):
        from repro.runtime import faults
        tok = faults.plan_token()
        ent = self._abft_jits.get(tok)
        if ent is None:
            ent = self._abft_jits[tok] = self._abft_fresh_jit()
        return ent

    def _lite_reference_impl(self):
        """XLA baseline pipeline used only to build the sandwich weight
        ``w = S^T r`` via vjp.  Autodiff-safe regardless of the active
        engine (Pallas kernels carry no vjp rules) and within sandwich
        tolerance of every engine/relayout rung: same linear operator up
        to roundoff."""
        from .engine import (build_schedule, crop_doubling,
                             materialize_doubling)
        plan = self.plan
        sched = build_schedule(plan, as_engine("xla"))
        green = self._green_nat

        def impl(f):
            g = jnp.asarray(green).astype(f.dtype)
            y = materialize_doubling(f, plan.dirs)
            for d in plan.order:
                y = sched.fwd_chunk(y, d)
            y = sched.green_multiply(y, g)
            for d in reversed(plan.order):
                y = sched.bwd_chunk(y, d)
            if jnp.iscomplexobj(y):
                y = y.real
            return crop_doubling(y, plan.dirs).astype(f.dtype)

        return impl

    def _lite_pair(self, shape, dtype):
        """Plan-time Freivalds pair for one input signature: the fixed
        probe ``r`` and the weight ``w = S^T r`` (one vjp of the linear
        solve, traced under fault suppression so an armed plan cannot
        poison the reference side)."""
        from repro.runtime import abft, faults
        key = (tuple(shape), jnp.dtype(dtype).name)
        rw = self._lite_weights.get(key)
        if rw is None:
            r = jnp.asarray(abft.lite_probe(shape, dtype))
            ref = self._lite_reference_impl()
            with faults.suppressed():
                w = jax.jit(lambda rr: jax.vjp(
                    ref, jnp.zeros(shape, dtype))[1](rr)[0])(r)
                jax.block_until_ready(w)
            rw = self._lite_weights[key] = (r, w)
        return rw

    def _lite_jitted(self, shape, dtype):
        """Jit of the clean pipeline plus the end-to-end linearity
        sandwich: returns ``(u, [<r,u>, <w,f>, ||u||^2])`` -- two fused
        multiply-reduces on top of the solve, nothing per-stage."""
        from repro.runtime import faults
        tok = faults.plan_token()
        key = (tuple(shape), jnp.dtype(dtype).name, tok)
        fn = self._lite_jits.get(key)
        if fn is None:
            r, w = self._lite_pair(shape, dtype)
            impl = self._solve_impl

            def call(f):
                u = impl(f)
                rep = jnp.stack([jnp.sum(r * u), jnp.sum(w * f),
                                 jnp.sum(u * u)])
                return u, rep

            fn = self._lite_jits[key] = jax.jit(call)
        return fn

    @property
    def input_shape(self):
        return self.plan.input_shape

    def _solve_impl(self, f, col=None, tol=None):
        if self.relayout == "scheduled":
            return self._solve_scheduled(f, col, tol)
        from .engine import crop_doubling, materialize_doubling
        plan = self.plan
        sched = self.schedule
        green = jnp.asarray(self._green).astype(f.dtype)
        y = materialize_doubling(f, plan.dirs)   # no-op when deferred
        for d in plan.order:
            y = sched.fwd_chunk(y, d, col, tol)
        y = sched.green_multiply(y, green, col, tol)
        for d in reversed(plan.order):
            y = sched.bwd_chunk(y, d, col, tol)
        if jnp.iscomplexobj(y):
            y = y.real
        y = crop_doubling(y, plan.dirs)
        return y.astype(f.dtype)

    def _solve_scheduled(self, f, col=None, tol=None):
        """Layout-scheduled pipeline (DESIGN.md #9): one composed transpose
        per direction change (where the baseline moveaxis round trips paid
        two), transforms always on the minor-most axis, Green multiplied in
        the spectral layout, and -- on the Pallas engine -- the last
        forward FFT running the Green multiply as an in-register epilogue.
        Bit-exact vs the baseline path on the XLA engine (transposes only
        reorder rows; the per-row math is identical)."""
        from .engine import crop_doubling, materialize_doubling
        plan = self.plan
        sched = self.schedule
        lay = sched.layouts
        nat = tuple(range(len(plan.dirs)))
        green = jnp.asarray(self._green).astype(f.dtype)
        y = materialize_doubling(f, plan.dirs)   # no-op when deferred
        cur = nat
        for i, d in enumerate(plan.order[:-1]):
            y = _relayout(y, cur, lay.fwd[i])
            cur = lay.fwd[i]
            y = sched.fwd_last(y, d, col, tol)
        d_last = plan.order[-1]
        y = _relayout(y, cur, lay.spectral)
        y = sched.fwd_last_green(y, d_last, green, col, tol)
        cur = lay.spectral
        for i, d in enumerate(reversed(plan.order)):
            y = _relayout(y, cur, lay.bwd[i])
            cur = lay.bwd[i]
            y = sched.bwd_last(y, d, col, tol)
        y = _relayout(y, cur, nat)
        if jnp.iscomplexobj(y):
            y = y.real
        y = crop_doubling(y, plan.dirs)
        return y.astype(f.dtype)

    def solve(self, f, verify=None):
        """Solve for ``f``; ``verify`` overrides the constructor-level
        health-guard mode for this call ("nan" | "residual" | "abft" |
        "abft-stages" | None).  ``"abft"`` (DESIGN.md #13) is the
        two-phase guard: every solve runs the cheap end-to-end linearity
        sandwich, and only a tripped sandwich re-dispatches through the
        fully-checked pipeline to localize the stage, selectively repair
        it, and raise ``IntegrityError`` into the degradation ladder if
        the corruption persists.  ``"abft-stages"`` runs the checked
        pipeline unconditionally (per-stage sandwiches with inline
        selective recompute -- the chaos net's mode)."""
        with spans.span("flups.solve", closes_step=True):
            return self._run_solve(f, verify)

    def _run_solve(self, f, verify):
        from repro.runtime import abft, faults, health, resilience
        f = jnp.asarray(f)
        grid = self.input_shape
        assert (f.ndim in (len(grid), len(grid) + 1)
                and f.shape[f.ndim - len(grid):] == grid), (f.shape, grid)
        verify = self.verify if verify is None else verify
        self.stats["solves"] += 1

        def checked():
            fn, names = self._abft_jitted()
            u, rep = fn(f)
            abft.verify_report(
                list(names), np.asarray(rep),
                tol=self._abft_tol(f.dtype), stats=self.stats,
                describe="solve")
            return u

        def attempt():
            faults.fail_point("solve.dispatch")
            if verify == "abft-stages":
                return checked()
            if verify == "abft":
                u, rep = self._lite_jitted(f.shape, f.dtype)(f)
                m = abft.lite_mismatch(np.asarray(rep))
                tol = self._abft_tol(f.dtype) * abft.LITE_HEADROOM
                if m <= tol:
                    return u
                # sandwich tripped: localize via the checked pipeline
                # (selective inline repair; persistent corruption raises
                # IntegrityError out of verify_report into the ladder)
                self.stats["verify_failures"] += 1
                self.stats.setdefault("integrity", []).append({
                    "stage": "solve.linearity", "kind": "linearity",
                    "mismatch": float(m), "tol": float(tol),
                    "action": "localize", "describe": "solve"})
                return checked()
            with spans.span("flups.launch"):
                u = self._jitted()(f)
            if verify:
                health.check_solution(
                    u, f, self.plan, mode=verify, rtol=self.verify_rtol,
                    stats=self.stats,
                    locate=lambda: health.locate_nonfinite_stage(
                        self.plan, self.schedule, f, self._green_nat))
            return u

        return resilience.run_with_ladder(
            attempt, config=self._cfg, reconfigure=self._configure,
            stats=self.stats, describe="solve")


# ---------------------------------------------------------------------------
# global plan/solver cache
# ---------------------------------------------------------------------------
#
# A CFD-style driver (e.g. a vortex-method timestepper, or the launch CLI
# re-entered every step) constructs the SAME solver over and over: identical
# shape/L/bcs/layout/green/engine/comm.  Planning is not free -- Green's
# function assembly is O(N^3) numpy work, autotuning compiles candidate
# pipelines, and every fresh ``jax.jit`` wrapper restarts XLA compilation.
# ``get_solver`` memoizes fully-constructed solvers in a module-level LRU
# keyed by the complete plan identity, so repeated construction costs a
# dict lookup and the jit/plan/Green work happens once per process.

_SOLVER_CACHE: OrderedDict = OrderedDict()
_SOLVER_CACHE_LOCK = threading.Lock()
# key -> in-flight construction (single-flight): N concurrent misses for
# the same key build the solver ONCE; the other N-1 callers park on the
# builder's event and are handed the same instance ("coalesced" in stats).
# Without this the miss path built outside the lock, so a thundering herd
# paid plan+autotune+jit N times and the last insert silently overwrote
# the N-1 siblings (skewing hit/miss/eviction accounting on top).
_SOLVER_BUILDS: dict = {}
_SOLVER_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0,
                       "coalesced": 0, "build_failures": 0}
_SOLVER_CACHE_CAPACITY = 16


class _SolverBuild:
    """One in-flight get_solver construction: the builder thread fills
    ``result``/``exc`` and sets ``done``; coalesced waiters block on it."""

    __slots__ = ("done", "result", "exc")

    def __init__(self):
        self.done = threading.Event()
        self.result = None
        self.exc = None


def _freeze(v):
    """Canonical hashable form of one get_solver argument."""
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    try:
        hash(v)
        return v
    except TypeError:
        return repr(v)


def _build_solver(shape, L, bcs, layout, green_kind, eps_factor, engine,
                  doubling, relayout, order_policy, mesh, kw):
    if mesh is not None:
        from repro.distributed.pencil import DistributedPoissonSolver
        return DistributedPoissonSolver(shape, L, bcs, layout, green_kind,
                                        mesh=mesh, eps_factor=eps_factor,
                                        engine=engine, doubling=doubling,
                                        relayout=relayout,
                                        order_policy=order_policy, **kw)
    assert set(kw) <= {"verify", "verify_rtol", "abft_rtol"}, \
        f"unexpected single-process solver kwargs: {kw}"
    return PoissonSolver(shape, L, bcs, layout, green_kind, eps_factor,
                         engine=engine, doubling=doubling,
                         relayout=relayout, order_policy=order_policy, **kw)


def get_solver(shape, L, bcs, layout=DataLayout.CELL,
               green_kind=gr.GreenKind.CHAT2, eps_factor=2.0,
               engine="xla", doubling="deferred", relayout="scheduled",
               order_policy="layout", *, mesh=None, **kw):
    """Construct-or-fetch a solver from the global plan cache.

    Returns a ``PoissonSolver``, or a ``DistributedPoissonSolver`` when
    ``mesh`` is given (extra distributed keywords -- ``comm``, ``axes``,
    ``batch_axis``, ``dtype``, autotune knobs, ... -- pass through and are
    part of the cache key, as is the mesh itself: same devices + same axis
    names hit the same entry).  Entries are evicted least-recently-used
    beyond ``set_solver_cache_capacity`` (default 16 solvers).

    Construction is SINGLE-FLIGHT per key: when N threads miss the same
    key concurrently (the serve thundering herd), exactly one of them
    builds -- the rest park on the builder and receive the same instance
    (counted as ``coalesced`` in ``solver_cache_info``).  A failed build
    re-raises in every parked caller and leaves no cache entry behind, so
    the next request retries cleanly.
    """
    with spans.span("flups.get_solver") as sp:
        from repro.runtime import faults
        key = ("dist" if mesh is not None else "single",
               _freeze(shape), _freeze(L), _freeze(bcs), _freeze(layout),
               _freeze(green_kind), float(eps_factor),
               as_engine(engine), str(doubling), str(relayout),
               str(order_policy), _freeze(mesh), _freeze(kw),
               # solvers traced under an armed fault plan must never be
               # served to fault-free callers (their jit cache may carry
               # the fault)
               ("faults", faults.plan_token()))
        builder = False
        with _SOLVER_CACHE_LOCK:
            s = _SOLVER_CACHE.get(key)
            if s is not None:
                _SOLVER_CACHE.move_to_end(key)
                _SOLVER_CACHE_STATS["hits"] += 1
                sp.set("hit", True)
                return s
            build = _SOLVER_BUILDS.get(key)
            if build is None:
                build = _SOLVER_BUILDS[key] = _SolverBuild()
                _SOLVER_CACHE_STATS["misses"] += 1
                builder = True
            else:
                # another thread is already constructing this key: park on
                # its build instead of duplicating the plan/autotune/jit work
                _SOLVER_CACHE_STATS["coalesced"] += 1
        if not builder:
            sp.set("hit", False)
            build.done.wait()
            if build.exc is not None:
                raise build.exc
            return build.result
        sp.set("hit", False)
        try:
            with spans.span("flups.build"):
                s = _build_solver(shape, L, bcs, layout, green_kind,
                                  eps_factor, engine, doubling, relayout,
                                  order_policy, mesh, kw)
        except BaseException as e:
            with _SOLVER_CACHE_LOCK:
                _SOLVER_BUILDS.pop(key, None)
                _SOLVER_CACHE_STATS["build_failures"] += 1
            build.exc = e
            build.done.set()
            raise
        with _SOLVER_CACHE_LOCK:
            _SOLVER_CACHE[key] = s
            _SOLVER_CACHE.move_to_end(key)
            while len(_SOLVER_CACHE) > _SOLVER_CACHE_CAPACITY:
                _SOLVER_CACHE.popitem(last=False)
                _SOLVER_CACHE_STATS["evictions"] += 1
            _SOLVER_BUILDS.pop(key, None)
        build.result = s
        build.done.set()
        return s


def clear_solver_cache():
    """Drop every cached solver and reset cache stats.  Also resets the
    process-wide warn-once state (``comm`` + ``resilience`` diagnostics):
    a fresh cache means fresh plans, and their one-shot warnings must be
    able to fire again -- long-lived servers and test fixtures both call
    this as THE runtime reset hook."""
    with _SOLVER_CACHE_LOCK:
        _SOLVER_CACHE.clear()
        for k in _SOLVER_CACHE_STATS:
            _SOLVER_CACHE_STATS[k] = 0
    from . import comm as _comm
    from repro.runtime import resilience as _resilience
    _comm.reset_warn_once()
    _resilience.reset_warn_once()


def evict_solver_instance(solver) -> int:
    """Drop the cache entries holding exactly ``solver`` (identity, not
    equality).  The serve warm pool calls this when its memory budget
    evicts a plan, so the global LRU cannot keep the Green's function and
    jit executables alive behind the pool's back.  Returns the eviction
    count."""
    with _SOLVER_CACHE_LOCK:
        stale = [k for k, v in _SOLVER_CACHE.items() if v is solver]
        for k in stale:
            del _SOLVER_CACHE[k]
            _SOLVER_CACHE_STATS["evictions"] += 1
    return len(stale)


def evict_solver_entries(mesh) -> int:
    """Drop every cached solver planned against ``mesh`` (elastic
    recovery: after a device loss the old mesh's solvers hold dead
    devices and must never be served again).  Returns the eviction
    count."""
    frozen = _freeze(mesh)
    with _SOLVER_CACHE_LOCK:
        stale = [k for k in _SOLVER_CACHE if frozen in k]
        for k in stale:
            del _SOLVER_CACHE[k]
            _SOLVER_CACHE_STATS["evictions"] += 1
    return len(stale)


def solver_cache_info() -> dict:
    with _SOLVER_CACHE_LOCK:
        return dict(_SOLVER_CACHE_STATS, size=len(_SOLVER_CACHE),
                    capacity=_SOLVER_CACHE_CAPACITY)


def set_solver_cache_capacity(n: int):
    global _SOLVER_CACHE_CAPACITY
    assert n >= 1, n
    with _SOLVER_CACHE_LOCK:
        _SOLVER_CACHE_CAPACITY = int(n)
        while len(_SOLVER_CACHE) > _SOLVER_CACHE_CAPACITY:
            _SOLVER_CACHE.popitem(last=False)
            _SOLVER_CACHE_STATS["evictions"] += 1
