"""The one traffic generator: builds a cell's right-hand sides from its
traffic file (``bench/traffic/<name>.json``) and ``--seed``.

A closed-loop mix solves ``fields_per_step`` right-hand sides per step and
starts the next step when the previous solution is ready, as the time
step of a Poisson-based CFD code does.  Each step's right-hand side is
``f_k = s_k * base``: ``base`` is a seeded field made on the device in one
jitted call during set-up (``bumps`` compact bumps of the paper's case B
profile at seeded centres, radii and signs, plus seeded uniform noise of
amplitude ``noise`` so that every mode of the spectrum carries signal),
and ``s_k`` is a seeded scale in ``scale``.  The solver consumes its input
buffer (it is donated), so each step makes its field anew, on the device,
in the layout the solver returns its output in.  Every seed gives the same
sizes and the same work; only the values change.
"""
from __future__ import annotations

import json
import os

import numpy as np

LOOPS = ("closed",)


def load(path: str) -> dict:
    with open(path) as fh:
        mix = json.load(fh)
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: unknown loop {mix.get('loop')!r}")
    return mix


def _words(seed: int):
    return int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF


class ClosedLoop:
    """Right-hand sides of one run: ``field(k)`` is step k's input."""

    def __init__(self, mix: dict, n_pts: tuple, L: float, layout: str,
                 dtype, seed: int):
        self.mix = mix
        self.layout = layout
        self.n_pts = tuple(n_pts)
        self.L = float(L)
        self.dtype = dtype
        self.seed = int(seed)
        rng = np.random.default_rng(list(_words(seed)))
        lo, hi = mix["scale"]
        # enough scales for any window; the sequence repeats past it
        self.scales = rng.uniform(lo, hi, size=100000)
        self.fields = int(mix.get("fields_per_step", 1))
        self.base = None
        self._scale = None

    def make_base(self, sharding):
        """The seeded base field on the device, made in one jitted call."""
        import jax
        import jax.numpy as jnp

        mix, L, n_pts = self.mix, self.L, self.n_pts
        shape = ((self.fields,) if self.fields > 1 else ()) + n_pts

        def gen(key):
            kc, kr, ks, kn = jax.random.split(key, 4)
            nb = int(mix["bumps"])
            lead = shape[:-3]
            r_lo, r_hi = mix["bump_radius"]
            rad = jax.random.uniform(kr, lead + (nb,), minval=r_lo,
                                     maxval=r_hi) * L
            # centres keep the whole bump inside the cube
            c = jax.random.uniform(kc, lead + (nb, 3))
            cen = rad[..., None] + c * (L - 2 * rad[..., None])
            sign = jnp.where(jax.random.bernoulli(ks, 0.5, lead + (nb,)),
                             1.0, -1.0)
            if self.layout == "node":     # x_i = i h, i = 0..n
                axes = [jnp.arange(m, dtype=jnp.float32) * (L / (m - 1))
                        for m in n_pts]
            else:                         # x_i = (i + 1/2) h, i < n
                axes = [(jnp.arange(m, dtype=jnp.float32) + 0.5) * (L / m)
                        for m in n_pts]
            x = axes[0][:, None, None]
            y = axes[1][None, :, None]
            z = axes[2][None, None, :]
            f = jnp.zeros(shape, jnp.float32)
            for b in range(nb):
                cb = cen[..., b, :]
                r2 = ((x - cb[..., 0, None, None, None]) ** 2
                      + (y - cb[..., 1, None, None, None]) ** 2
                      + (z - cb[..., 2, None, None, None]) ** 2)
                s2 = r2 / rad[..., b, None, None, None] ** 2
                inside = s2 < 1.0
                bump = jnp.exp(10.0 * (1.0 - 1.0 / (1.0 - jnp.where(
                    inside, s2, 0.0))))
                f = f + sign[..., b, None, None, None] * jnp.where(
                    inside, bump, 0.0)
            f = f + mix["noise"] * jax.random.uniform(
                kn, shape, minval=-1.0, maxval=1.0)
            return f.astype(self.dtype)

        w0, w1 = _words(self.seed)
        key = jax.random.fold_in(jax.random.key(w0), w1)
        self.base = jax.jit(gen, out_shardings=sharding)(key)
        self._scale = jax.jit(lambda b, s: (b * s).astype(b.dtype),
                              out_shardings=sharding)
        return self.base

    def scale(self, k: int) -> float:
        return float(self.scales[k % len(self.scales)])

    def field(self, k: int):
        """Step k's right-hand side, a new device buffer."""
        return self._scale(self.base, np.asarray(self.scale(k), np.float32))


def path(bench_dir: str, name: str) -> str:
    return os.path.join(bench_dir, "traffic", name + ".json")
