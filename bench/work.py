"""Work of one solve, computed from a configuration's shapes alone.

Two yardsticks, both independent of how the program implements a stage:

* ``transform_stages``: every 1-D transform stage of the forward and
  backward sweeps, with the bytes it must move (it reads its input and
  writes its output once) and the operations it must do (5 N log2 N per
  complex transform of length N, half that for a real one).  The shapes
  follow the paper's conventions (arXiv 2211.07777, section II and Table
  I) under deferred ("pruned") Hockney doubling: a direction carries its
  ``n_pts`` physical points until its own forward transform and its
  ``n_out`` spectral points after it.
* ``switch_bytes``: the operand bytes of the four topology switches of a
  (p1, p2) pencil grid, the arithmetic of ``repro.plan.costmodel``'s
  ``switch_traces``/``predict_collectives`` for a monolithic all-to-all,
  copied here so that the yardstick does not move with the program.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from itertools import permutations, product

# (left, right) -> r2r kind of a symmetric direction (paper Table I)
_KIND = {
    "node": {("odd", "odd"): "dst1", ("odd", "even"): "dst3",
             ("even", "odd"): "dct3", ("even", "even"): "dct1"},
    "cell": {("odd", "odd"): "dst2", ("odd", "even"): "dst4",
             ("even", "odd"): "dct4", ("even", "even"): "dct2"},
}
# node layout: points of an r2r kind's input (out of n+1)
_NODE_N_IN = {"dst1": lambda n: n - 1, "dst3": lambda n: n,
              "dct3": lambda n: n, "dct1": lambda n: n + 1}


@dataclass(frozen=True)
class Dir:
    n_pts: int         # physical points held outside the transform
    n_fft: int         # transform length
    n_out: int         # spectral points after the forward transform
    real_out: bool     # the forward transform's output is real (r2r)


def directions(config: dict, order: tuple) -> list[Dir]:
    """Per-dimension transform shapes of ``config`` for an execution
    ``order`` (the first DFT direction executed is the real-to-complex
    one)."""
    n = config["n"]
    ns = n if isinstance(n, list) else [n] * 3
    layout = config["layout"]
    node = layout == "node"
    cats = [category(bc) for bc in config["bcs"]]
    first_dft = next((d for d in order if cats[d] in ("per", "unb")), None)
    out = []
    for d, (bc, cat, nd) in enumerate(zip(config["bcs"], cats, ns)):
        n_pts = nd + 1 if node else nd
        if cat in ("per", "unb"):
            n_fft = nd if cat == "per" else 2 * nd
            n_out = n_fft // 2 + 1 if d == first_dft else n_fft
            out.append(Dir(n_pts, n_fft, n_out, False))
        elif cat == "sym":
            kind = _KIND[layout][tuple(bc)]
            n_in = _NODE_N_IN[kind](nd) if node else nd
            out.append(Dir(n_pts, n_in, n_in, True))
        else:   # semi: the symmetric end's pair on the doubled domain
            sym = bc[1] if bc[0] == "unb" else bc[0]
            if node:
                n_fft = 2 * nd - 1 if sym == "odd" else 2 * nd + 1
            else:
                n_fft = 2 * nd
            out.append(Dir(n_pts, n_fft, n_fft, True))
    return out


def category(bc) -> str:
    left, right = bc
    if left == "periodic":
        return "per"
    if left == right == "unb":
        return "unb"
    if "unb" in (left, right):
        return "semi"
    return "sym"


def valid_orders(config: dict) -> list[tuple]:
    """Execution orders the paper allows: symmetric directions first, then
    semi-unbounded, then the DFT directions; any order inside a group."""
    cats = [category(bc) for bc in config["bcs"]]
    groups = [[d for d in range(3) if cats[d] in g] for g in
              (("sym",), ("semi",), ("per", "unb"))]
    groups = [g for g in groups if g]
    return [tuple(d for g in combo for d in g)
            for combo in product(*[list(permutations(g)) for g in groups])]


@dataclass(frozen=True)
class Stage:
    name: str          # "fwd.<d>" | "bwd.<d>"
    bytes: float       # read once + written once
    flops: float       # 5 N log2 N complex, 2.5 N log2 N real


def transform_stages(config: dict, order: tuple, fields: int = 1) -> list:
    """The six transform stages of one solve of ``fields`` right-hand
    sides, in execution order."""
    item = 8 if config["dtype"] == "float64" else 4
    dirs = directions(config, order)
    ext = [p.n_pts for p in dirs]
    cplx = False
    stages = []

    def elems():
        return fields * math.prod(ext)

    first_dft = next((d for d in order if not dirs[d].real_out), None)

    def stage(name, d, out_cplx, out_ext):
        nonlocal cplx
        p = dirs[d]
        b_in = elems() * item * (2 if cplx else 1)
        rows = elems() / ext[d]
        # a complex-to-complex transform costs 5 N log2 N per row; a
        # real one (r2c, c2r, r2r) half that
        per_row = (5.0 if cplx and out_cplx else 2.5) * p.n_fft * \
            math.log2(p.n_fft)
        ext[d] = out_ext
        b_out = elems() * item * (2 if out_cplx else 1)
        stages.append(Stage(name, b_in + b_out, rows * per_row))
        cplx = out_cplx

    for d in order:
        stage(f"fwd.{d}", d, cplx or not dirs[d].real_out, dirs[d].n_out)
    for d in reversed(order):
        stage(f"bwd.{d}", d, cplx and d != first_dft, dirs[d].n_pts)
    return stages


def least_transform_work(config: dict, fields: int = 1):
    """(bytes, flops) of the transform stages under the execution order
    that needs the fewest bytes: a lower bound whatever order the program
    chooses."""
    best = None
    for order in valid_orders(config):
        st = transform_stages(config, order, fields)
        w = (sum(s.bytes for s in st), sum(s.flops for s in st))
        if best is None or w < best:
            best = w
    return best


def switch_bytes(config: dict, order: tuple, p1: int, p2: int,
                 fields: int = 1) -> list:
    """Per-rank operand bytes of each topology switch that emits a
    collective (a mesh axis of size 1 emits none), in program order, for
    the monolithic all-to-all strategy."""
    item = 8 if config["dtype"] == "float64" else 4
    dirs = directions(config, order)
    d0, d1, d2 = order
    U = [p.n_pts for p in dirs]
    S = [p.n_out for p in dirs]

    def up(n, p):
        return -(-n // p) * p

    PU1, PU2 = up(U[d1], p1), up(U[d2], p2)
    PS0, PS1 = up(S[d0], p1), up(S[d1], p2)
    n_dft = sum(1 for d in order if not dirs[d].real_out)
    sw = [
        (p1, PS0 * (PU1 // p1) * (PU2 // p2), not dirs[d0].real_out),
        (p2, (PS0 // p1) * PS1 * (PU2 // p2), n_dft >= 2),
        (p2, (PS0 // p1) * (PS1 // p2) * PU2, n_dft >= 2),
        (p1, (PS0 // p1) * PU1 * (PU2 // p2), n_dft >= 3),
    ]
    return [fields * e * item * (2 if c else 1)
            for p, e, c in sw if p > 1]
