"""Pluggable transform engine: the single hot path of both solvers.

The paper's pipeline is (per direction) 1-D transform -> pointwise Green
multiply -> inverse transforms; this module decides HOW each stage executes:

  engine="xla"     pure jnp/XLA ops (rfft/irfft half-spectrum transforms,
                   fused elementwise) -- the default everywhere.
  engine="pallas"  the hand-written TPU kernels take over the hot loops:
                   ``twiddle_pack`` for the r2r post-twiddle,
                   ``fft_stockham`` for the (r)FFT lengths it compiles for
                   on the engine's platform (``fft_stockham.fits``: powers
                   of two, 512..1024 on the TPU), and
                   ``spectral_scale``/``green_multiply`` for the fused
                   Green multiply.  Every other FFT length runs XLA's FFT
                   by that plan-time rule, so any plan works on any engine.

Every stage runs under a ``jax.named_scope`` named after its fault site
(``fwd.<d>``, ``bwd.<d>``, ``green``); ``stage_map`` reads off a traced
solve which stages run a Pallas kernel and which run XLA.  Between the
stages, the row-major pins run under ``pin``, the edge adapters under
``relayout`` and the topology switches under ``comm.<strategy>``
(``repro.core.comm``).

A plan is compiled once into a ``TransformSchedule``: per-direction twiddle
tables (plan-time numpy constants handed to the kernels) plus the combined
normalization of every backward r2r transform.  That normalization is folded
into the Green's function by ``build_green`` (one multiply for the whole
solve), so the backward pass emits ZERO standalone normalization multiplies
-- see tests/test_engine.py which counts them in the jaxpr.

The schedule is also the distributed solver's STAGE API: ``fwd_chunk`` /
``bwd_chunk`` apply one direction's 1-D transform to the full local block or
to any chunk of it cut along an uninvolved axis -- the unit the ``overlap``
comm strategy interleaves with the per-chunk collectives of a topology
switch (see ``repro.core.comm``).

Layout scheduling (DESIGN.md #9): data layout is a PLAN-TIME quantity.  A
``LayoutSchedule`` assigns every stage the axis permutation it runs in
(active dim minor-most); the scheduled pipelines call the ``fwd_last`` /
``bwd_last`` stage API (no per-direction moveaxis round trips) and fold
the one relayout per direction change into the topology switch
(``CommStrategy.stage(permute=...)``) -- or, single-process, into one
composed transpose.  ``fwd_last_green`` additionally fuses the Green
multiply into the last forward direction's Pallas FFT as an in-register
epilogue.  The ``fwd_1d``/``bwd_1d`` moveaxis adapters remain the
natural-layout API (baseline pipelines, spectral differentiation,
standalone callers).

Batched multi-RHS execution: every op here is rank-polymorphic.  A plan
describes ``len(plan.dirs)`` grid dimensions; any leading axes of the array
are batch axes (``B`` independent right-hand sides sharing one plan), and a
direction's array axis is ``batch_ndim + p.dim``.  The 1-D transforms are
last-axis ops over flattened rows, so a batched solve runs the SAME number
of (bigger) FFT calls as a single solve -- the multi-RHS amortization of
the original FLUPS / P3DFFT batched transform APIs.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.runtime import faults as _faults

__all__ = ["TransformEngine", "TransformSchedule", "LayoutSchedule",
           "as_engine", "build_schedule", "schedule_layouts", "relayout",
           "on_last_axis", "folded_normfact", "fwd_1d", "bwd_1d",
           "materialize_doubling", "crop_doubling", "stage_map", "ENGINES",
           "RELAYOUT_MODES"]

RELAYOUT_MODES = ("scheduled", "baseline")

ENGINES = ("xla", "pallas")


@dataclass(frozen=True)
class TransformEngine:
    """Execution backend selection for the transform + pointwise stages.

    ``max_radix``: Stockham FFT radix cap (4 = mixed radix-4/2, the
    default; 2 = pure radix-2, twice the stages at half the per-stage
    arithmetic) -- a plan-space search dimension (DESIGN.md #12); only
    the Pallas kernels consume it, the XLA engine ignores it.
    ``platform``: the platform the solve runs on (default: JAX's default
    backend; the distributed solver passes its mesh's).  It selects the
    FFT lengths the Stockham kernel takes (``fft_stockham.fits``); whether
    a kernel is compiled or interpreted follows the platform the program
    is lowered for (``kernels.platform``).
    """

    name: str = "xla"
    max_radix: int = 4
    platform: str = field(default_factory=jax.default_backend)

    def __post_init__(self):
        if self.name not in ENGINES:
            raise ValueError(
                f"unknown engine {self.name!r}; expected one of {ENGINES}")
        if self.max_radix not in (2, 4):
            raise ValueError(f"max_radix must be 2 or 4, "
                             f"got {self.max_radix!r}")

    @property
    def use_pallas(self) -> bool:
        return self.name == "pallas"

    def kernel_fft(self, n: int) -> bool:
        """Whether a length-``n`` FFT runs in the Stockham kernel (the
        plan-time routing rule); False on the XLA engine."""
        from repro.kernels.fft_stockham import fits
        return self.use_pallas and fits(n, self.platform)


def as_engine(engine) -> TransformEngine:
    """Accept ``"xla"`` / ``"pallas"`` / TransformEngine / None."""
    if engine is None:
        return TransformEngine()
    if isinstance(engine, TransformEngine):
        return engine
    return TransformEngine(str(engine))


# ---------------------------------------------------------------------------
# per-direction 1-D ops (jnp, last axis; natural-layout callers go through
# the ``on_last_axis`` moveaxis adapter)
# ---------------------------------------------------------------------------

def _batch_ndim(x, sched) -> int:
    """Leading batch axes of ``x`` relative to the schedule's grid rank."""
    if sched is None or not sched.dirs:
        return 0
    bnd = x.ndim - len(sched.dirs)
    assert 0 <= bnd, (x.shape, len(sched.dirs))
    return bnd


def on_last_axis(x, axis, fn):
    """Run ``fn`` on ``x`` with ``axis`` shuffled minor-most, restoring the
    axis afterwards -- the mirrored moveaxis plumbing shared by ``fwd_1d``/
    ``bwd_1d`` here and ``spectral.apply_derivative``.

    Measured (EXPERIMENTS.md section Perf, flups cell): transforming along
    the native axis (jnp.fft axis=d) REGRESSES bytes by 11% -- XLA
    transposes internally for non-minor FFT axes and loses the fusion of
    the explicit moveaxis (a no-op when ``axis`` is already last).  The
    layout-SCHEDULED pipelines (DESIGN.md #9) avoid this adapter entirely:
    they keep the active axis minor-most and fold the one real relayout
    into the topology switch's unpack.
    """
    y = fn(jnp.moveaxis(x, axis, -1))
    return jnp.moveaxis(y, -1, axis)


def _fwd_last(x, p, sched=None):
    """Forward 1-D transform of direction ``p`` applied to the LAST axis
    of ``x`` (the layout-scheduled hot path: the caller guarantees the
    active axis is minor-most).

    Valid-extent contract: the incoming axis carries ``p.valid_in`` live
    points (``n_pts`` deferred, ``n_fft`` when the plan pre-padded the
    Hockney doubling up front) and the outgoing axis carries ``p.n_out``.
    """
    with jax.named_scope(f"fwd.{p.dim}"):
        from . import transforms as tr
        engine = sched.engine if sched is not None else None
        x = _faults.taint(f"fwd.{p.dim}", x)
        if engine is not None and engine.use_pallas:
            _faults.fail_point(f"pallas.fwd.{p.dim}")
        if p.pre_padded:
            # dense up-front doubling: the zero extension is already in the
            # array, the transform is a plain full-length one
            if p.category in ("sym", "semi"):
                raise AssertionError("pre_padded is a DFT-direction mode")
            return (tr._rfft(x, engine) if p.dft == "r2c"
                    else tr._cfft(x, engine))
        if p.flip:
            x = x[..., ::-1]
        x = x[..., p.in_start:p.in_start + p.n_in]
        if p.category in ("sym", "semi"):
            if p.n_fft > p.n_in:
                pad = [(0, 0)] * (x.ndim - 1) + [(0, p.n_fft - p.n_in)]
                x = jnp.pad(x, pad)
            tables = sched.fwd_tables[p.dim] if sched is not None else None
            return tr.r2r_forward(x, p.kind, engine=engine, tables=tables)
        if p.dft == "r2c":
            # pruned forward: the length-n_fft spectrum from the n_in nonzero
            # inputs (Pallas skips the zero tail; XLA pads -- bit-identical)
            return tr._rfft_padded(x, p.n_fft, engine)
        return tr._cfft_padded(x, p.n_fft, engine)


def _bwd_last(y, p, sched=None):
    """Inverse 1-D transform of direction ``p`` on the LAST axis; emits
    ``p.valid_in`` points (the ``n_pts`` user axis under deferred doubling,
    the full ``n_fft`` reconstruction when the plan padded up front)."""
    with jax.named_scope(f"bwd.{p.dim}"):
        # NOTE: no normalization multiply here -- every direction's
        # normfact is folded into the Green's function at plan time
        # (build_green).
        from . import transforms as tr
        engine = sched.engine if sched is not None else None
        y = _faults.taint(f"bwd.{p.dim}", y)
        if engine is not None and engine.use_pallas:
            _faults.fail_point(f"pallas.bwd.{p.dim}")
        if p.category in ("sym", "semi"):
            tables = sched.bwd_tables[p.dim] if sched is not None else None
            x = tr.r2r_backward(y, p.kind, engine=engine, tables=tables)
            x = x[..., :p.n_in]
        elif p.pre_padded:
            # dense mode keeps the doubled extent; cropped once at solve end
            return (tr._irfft(y, p.n_fft, engine) if p.dft == "r2c"
                    else tr._cfft(y, engine, inverse=True))
        elif p.dft == "r2c":
            # pruned backward: reconstruct only the n_in retained samples
            x = tr._irfft_crop(y, p.n_fft, p.n_in, engine)
        else:
            x = tr._icfft_crop(y, p.n_in, engine)
        # place into the user-sized axis
        left = p.in_start
        right = p.n_pts - p.in_start - p.n_in - (1 if p.per_dup else 0)
        if left or right:
            pad = [(0, 0)] * (x.ndim - 1) + [(left, right)]
            x = jnp.pad(x, pad)
        if p.per_dup:  # node-periodic: duplicate the first point at the end
            x = jnp.concatenate([x, x[..., :1]], axis=-1)
        if p.flip:
            x = x[..., ::-1]
        return x


def fwd_1d(x, p, sched=None):
    """Forward 1-D transform of direction ``p`` (a ``Plan1D``), applied to
    the whole block or to any chunk cut along an axis other than ``p.dim``,
    in NATURAL layout (the axis is shuffled minor-most and back).  Leading
    batch axes (multi-RHS) pass through untouched -- the schedule is what
    knows the grid rank, so batched arrays REQUIRE ``sched``; with
    ``sched=None`` the array rank must equal the plan's.
    """
    return on_last_axis(x, _batch_ndim(x, sched) + p.dim,
                        lambda v: _fwd_last(v, p, sched))


def bwd_1d(y, p, sched=None):
    """Inverse 1-D transform of direction ``p`` in natural layout;
    chunk-safe like ``fwd_1d`` (and like it, batched arrays require
    ``sched``)."""
    return on_last_axis(y, _batch_ndim(y, sched) + p.dim,
                        lambda v: _bwd_last(v, p, sched))


# ---------------------------------------------------------------------------
# layout scheduling (DESIGN.md #9): data layout as a plan-time quantity
# ---------------------------------------------------------------------------

def to_last(perm, d):
    """The permutation ``perm`` with logical dim ``d`` shuffled minor-most
    and every other dim left in place (one transpose away from ``perm``)."""
    return tuple(x for x in perm if x != d) + (d,)


def switch_layout(perm, a, b):
    """Layout after the topology switch retiring active dim ``a`` for
    ``b``: ``a`` goes MAJOR-most (the axis the switch splits, so every
    rank's share is one contiguous slab) and ``b`` MINOR-most (the
    gathered axis, exactly where the next 1-D transform consumes it).
    One transpose away from any ``(.., .., a)`` stage layout."""
    rest = [d for d in perm if d not in (a, b)]
    return (a, *rest, b)


@dataclass(frozen=True)
class LayoutSchedule:
    """Plan-time axis-permutation schedule of one solve.

    ``fwd[i]`` / ``bwd[i]`` is the grid-axis permutation the block is in
    DURING forward/backward stage ``i`` (executed in pipeline order):
    ``perm[a]`` is the logical dim stored at array axis ``a`` (batch axes
    lead and are never permuted).  Every stage keeps its active dim
    minor-most, so the 1-D transforms never move data; every switch
    target is a ``switch_layout`` (outgoing dim major, incoming dim
    minor), so the one relayout between consecutive stages is a single
    composed transpose folded into the switch's PACK -- after it, the
    collective splits a contiguous major axis and gathers straight into
    the next transform's minor axis, and the pipeline emits zero
    standalone transposes between stages (``hlo_stats.transpose_stats``).
    ``bwd[0] == spectral``: the first backward stage reuses the spectral
    layout, so the Green multiply and both last-direction transforms
    share it.
    """

    fwd: tuple
    bwd: tuple

    @property
    def spectral(self):
        """Layout of the pointwise Green multiply (== ``fwd[-1]``)."""
        return self.fwd[-1]


def schedule_layouts(order, ndim: int = 3) -> LayoutSchedule:
    """The minimal-relayout schedule: stage 0 moves only the first active
    dim minor-most; every later stage is the ``switch_layout`` of the
    direction pair it sits between (one fused transpose per switch)."""
    perm = to_last(tuple(range(ndim)), order[0])
    fwd = [perm]
    for a, b in zip(order, order[1:]):
        perm = switch_layout(perm, a, b)
        fwd.append(perm)
    bwd = [perm]                      # spectral layout reused by bwd[0]
    rev = tuple(reversed(order))
    for a, b in zip(rev, rev[1:]):
        perm = switch_layout(perm, a, b)
        bwd.append(perm)
    return LayoutSchedule(tuple(fwd), tuple(bwd))


def relayout(x, src, dst):
    """One composed transpose taking the grid layout ``src`` to ``dst``
    (identity-free: returns ``x`` unchanged when the layouts agree).
    Leading batch axes pass through untouched."""
    src, dst = tuple(src), tuple(dst)
    if src == dst:
        return x
    off = x.ndim - len(src)
    axes = tuple(range(off)) + tuple(off + src.index(d) for d in dst)
    with jax.named_scope("relayout"):
        return jnp.transpose(x, axes)


def materialize_doubling(x, dirs):
    """Zero-pad every ``pre_padded`` direction of a user-shaped array from
    ``n_pts`` to ``n_fft`` (the dense up-front Hockney doubling; a no-op on
    deferred plans).  Leading batch axes pass through."""
    off = x.ndim - len(dirs)
    for d, p in enumerate(dirs):
        if p.pre_padded and x.shape[off + d] < p.n_fft:
            pad = [(0, 0)] * x.ndim
            pad[off + d] = (0, p.n_fft - x.shape[off + d])
            x = jnp.pad(x, pad)
    return x


def crop_doubling(x, dirs):
    """Crop every ``pre_padded`` direction back to its user extent (the
    final slice of a dense solve; a no-op on deferred plans)."""
    off = x.ndim - len(dirs)
    for d, p in enumerate(dirs):
        if p.pre_padded and x.shape[off + d] > p.n_pts:
            sl = [slice(None)] * x.ndim
            sl[off + d] = slice(0, p.n_pts)
            x = x[tuple(sl)]
    return x


def pin_row_major(x):
    """``x`` with its layout pinned to row-major (values unchanged).

    Every stage of the schedule returns its output through this.  Left to
    choose, XLA on a TPU v5e (jax 0.9.0) gave the backward stages of the
    256^3 unbounded solve transposed layouts, and the crop-and-switch
    fusion it emitted for them computed a wrong field (relative E_inf 0.25
    where the scheme gives 1.3e-4); with row-major stage outputs the same
    program is exact to f32, for about 6% more time per solve.  The TPU's
    layout constraint takes no complex operand, so a complex array is
    pinned plane by plane.  The ops run under the scope ``pin``."""
    def pin(a):
        return with_layout_constraint(a, Layout(tuple(range(a.ndim))))

    with jax.named_scope("pin"):
        if jnp.iscomplexobj(x):
            return jax.lax.complex(pin(x.real), pin(x.imag))
        return pin(x)


def _pinned(stage):
    @functools.wraps(stage)
    def run(*args, **kwargs):
        return pin_row_major(stage(*args, **kwargs))
    return run


@dataclass(frozen=True)
class TransformSchedule:
    """Plan-time constants for one solve: per-direction twiddle tables, the
    folded normalization (quadrature h weights stay in build_green) and the
    layout schedule of the scheduled pipelines."""

    engine: TransformEngine
    fwd_tables: tuple    # per logical dim: twiddle dict for the forward kind
    bwd_tables: tuple    # per logical dim: twiddle dict for the inverse kind
    norm: float          # prod of r2r normfacts, folded into the Green
    dirs: tuple = ()     # per logical dim: the plan's Plan1D
    order: tuple = ()    # the plan's forward execution order
    layouts: LayoutSchedule = None   # per-stage axis permutations

    # -- fused transform+switch stage API (chunk-safe by construction) -----
    #
    # Every stage takes an optional ABFT collector (DESIGN.md #13): with
    # ``col=None`` (the default everywhere) the plain stage is traced --
    # not one checksum op is emitted, so the verify-off pipelines stay
    # bit-exact.  With a collector the stage runs under its linearity /
    # Parseval sandwich with inline selective recompute.

    @_pinned
    def fwd_chunk(self, x, d: int, col=None, tol=None):
        """Forward 1-D transform of logical direction ``d`` on a full block
        or an uninvolved-axis chunk (the overlap strategy's stage unit), in
        NATURAL layout (moveaxis round trip -- the baseline pipelines)."""
        if col is not None:
            from repro.runtime import abft
            return abft.checked_fwd_chunk(x, d, self, col, tol)
        return fwd_1d(x, self.dirs[d], self)

    @_pinned
    def bwd_chunk(self, x, d: int, col=None, tol=None):
        """Inverse 1-D transform of logical direction ``d``; chunk-safe."""
        if col is not None:
            from repro.runtime import abft
            return abft.checked_bwd_chunk(x, d, self, col, tol)
        return bwd_1d(x, self.dirs[d], self)

    @_pinned
    def fwd_last(self, x, d: int, col=None, tol=None):
        """Forward 1-D transform of direction ``d`` on the LAST axis (the
        layout-scheduled stage unit: the pipeline guarantees the active
        axis is already minor-most, so no data moves here)."""
        if col is not None:
            from repro.runtime import abft
            return abft.checked_fwd_last(x, d, self, col, tol)
        return _fwd_last(x, self.dirs[d], self)

    @_pinned
    def bwd_last(self, x, d: int, col=None, tol=None):
        """Inverse 1-D transform of direction ``d`` on the LAST axis."""
        if col is not None:
            from repro.runtime import abft
            return abft.checked_bwd_last(x, d, self, col, tol)
        return _bwd_last(x, self.dirs[d], self)

    # live-extent bookkeeping lives on the plan: ``self.dirs[d].valid_in``
    # is the physical extent a topology switch ships for dim ``d`` (see
    # Plan1D; spectral extents are the plain ``n_out`` field)

    @_pinned
    def green_multiply(self, yhat, green, col=None, tol=None):
        """The fused pointwise pass (Green x normalization in one multiply)."""
        if col is not None:
            from repro.runtime import abft
            return abft.checked_green(yhat, green, self, col, tol)
        with jax.named_scope("green"):
            yhat = _faults.taint("green", yhat)
            if self.engine.use_pallas:
                _faults.fail_point("pallas.green")
                from repro.kernels import ops
                return ops.green_multiply(yhat, green)
            if jnp.iscomplexobj(yhat):
                return yhat * green
            return yhat * green.astype(yhat.dtype)

    def can_fuse_green(self, d: int) -> bool:
        """True when the forward transform of ``d`` can run the Green
        multiply as a Pallas FFT epilogue: a power-of-two DFT direction
        whose live extent is either the full FFT length or its pruned half
        (the Hockney zero-tail first stage composes with the epilogue)."""
        p = self.dirs[d]
        n = p.n_fft
        return (self.engine.kernel_fft(n)
                and p.category in ("per", "unb")
                and not p.flip and p.in_start == 0
                and (p.n_in == n or n == 2 * p.n_in))

    @_pinned
    def fwd_last_green(self, x, d: int, green, col=None, tol=None):
        """Forward transform of the LAST forward direction fused with the
        Green multiply: on the Pallas engine the ``spectral_scale`` pass
        runs in the FFT's final-stage registers (one HBM round trip for
        transform + pointwise); anywhere else it is the plain transform
        followed by ``green_multiply``.  ``green`` must be in the same
        layout as ``x`` with the spectral ``d`` axis minor-most."""
        if col is not None:
            # the checksum sandwich needs the spectral field BEFORE the
            # Green multiply, so checking bypasses the fused epilogue
            return self.green_multiply(self.fwd_last(x, d, col, tol), green,
                                       col, tol)
        p = self.dirs[d]
        want_cplx = p.dft == "c2c"
        if (not self.can_fuse_green(d)
                or bool(jnp.iscomplexobj(x)) != want_cplx):
            return self.green_multiply(self.fwd_last(x, d), green)
        with jax.named_scope(f"fwd.{p.dim}+green"):
            x = _faults.taint(f"fwd.{p.dim}", x)
            x = _faults.taint("green", x)
            _faults.fail_point(f"pallas.fwd.{p.dim}")
            _faults.fail_point("pallas.green")
            from repro.kernels import ops
            n_live = p.n_fft if p.pre_padded else p.n_in
            x = x[..., :n_live]
            pad_to = None if n_live == p.n_fft else p.n_fft
            assert green.shape[-1] == p.n_out, (green.shape, p.n_out)
            fused = ops.rfft_green if p.dft == "r2c" else ops.fft1d_green
            return fused(x, green, pad_to=pad_to,
                         max_radix=self.engine.max_radix)


_STAGE = re.compile(r"(?:^|/)((?:fwd|bwd)\.\d(?:\+green)?|green)(?=/|$)")


def stage_map(jaxpr) -> dict:
    """Which stages of a traced solve run a Pallas kernel and which XLA.

    ``jaxpr`` is the ``jax.make_jaxpr`` of a solve.  Every equation inside
    a stage's named scope counts: a ``pallas_call`` marks the stage
    "pallas", an XLA ``fft`` marks it "xla" (a stage with both, e.g. a
    kernel FFT beside an XLA FFT of a routed length, is "pallas+xla"; a
    stage with neither -- the XLA engine's Green multiply -- is "xla").
    Returns ``{stage: kind}`` in the order the stages were traced."""
    found: dict = {}

    def walk(jx, prefix):
        for eqn in jx.eqns:
            stack = prefix + "/" + str(eqn.source_info.name_stack)
            m = _STAGE.findall(stack)
            kinds = found.setdefault(m[-1], set()) if m else None
            name = eqn.primitive.name
            if name == "pallas_call":
                if kinds is not None:
                    kinds.add("pallas")
                continue                  # the kernel body is not a stage
            if name == "fft" and kinds is not None:
                kinds.add("xla")
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, stack)

    walk(jaxpr.jaxpr, "")
    return {k: "+".join(sorted(v)) or "xla" for k, v in found.items()}


def folded_normfact(plan) -> float:
    """The combined backward normalization of a plan -- the single factor
    ``build_green`` folds into the Green's function (every direction, DFT
    included; their normfact is 1.0)."""
    norm = 1.0
    for p in plan.dirs:
        norm *= p.normfact
    return norm


def build_schedule(plan, engine=None) -> TransformSchedule:
    """Compile a ``PoissonPlan`` into its per-direction transform schedule."""
    from . import transforms as tr
    from .bc import INVERSE_KIND

    engine = as_engine(engine)
    fwd, bwd = [], []
    for p in plan.dirs:
        if p.kind is None:       # DFT direction: no r2r twiddles
            fwd.append(None)
            bwd.append(None)
        else:
            fwd.append(tr.twiddle_tables(p.kind, p.n_fft))
            bwd.append(tr.twiddle_tables(INVERSE_KIND[p.kind], p.n_fft))
    return TransformSchedule(engine, tuple(fwd), tuple(bwd),
                             folded_normfact(plan), plan.dirs, plan.order,
                             schedule_layouts(plan.order, len(plan.dirs)))
