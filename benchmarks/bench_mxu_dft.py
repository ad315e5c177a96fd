"""Chip micro-benchmark behind ``transforms.MXU_DFT_LENGTHS``: XLA's FFT
against DFT-matrix products, one DFT stage at a time.

    python3 benchmarks/bench_mxu_dft.py            # on a TPU
    JAX_PLATFORMS=cpu python3 benchmarks/bench_mxu_dft.py --rows 8
                                                   # CPU rehearsal

Each case is one pruned DFT stage of a solve: the forward reads ``n_in``
live points of a length-``n_fft`` transform, the inverse keeps the first
``n_in`` outputs.  ``fft`` runs the XLA engine's pruned wrappers
(``jnp.fft`` after a zero pad, or before a crop), ``mxu`` the products of
``transforms.mxu_dft_forward``/``mxu_dft_backward``.  The shapes are the
stages of the one-chip 256^3 node-centred unbounded solve (length 512),
the local length of 512^3 on a 2x2 mesh (1024) and a periodic or
cell-centred 256.  Each line prints the device time per call (median of
``--reps`` timed blocks of back-to-back calls) and the compiled program's
temporary bytes; the last line is a JSON object of all of them.  A CPU run
rehearses the script and times nothing of the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402

from repro.core import transforms as tr      # noqa: E402

# (case, dft, direction, n_fft, n_in, leading shape at full size)
CASES = [
    ("B256.fwd.2", "r2c", "fwd", 512, 257, (257, 257)),
    ("B256.fwd.0", "c2c", "fwd", 512, 257, (257, 257)),
    ("B256.fwd.1", "c2c", "fwd", 512, 257, (257, 512)),
    ("B256.bwd.1", "c2c", "bwd", 512, 257, (257, 512)),
    ("B256.bwd.0", "c2c", "bwd", 512, 257, (257, 257)),
    ("B256.bwd.2", "r2c", "bwd", 512, 257, (257, 257)),
    ("B512x4.fwd.r2c", "r2c", "fwd", 1024, 513, (257, 257)),
    ("B512x4.fwd.c2c", "c2c", "fwd", 1024, 513, (257, 257)),
    ("B512x4.bwd.c2c", "c2c", "bwd", 1024, 513, (257, 257)),
    ("B512x4.bwd.c2r", "r2c", "bwd", 1024, 513, (257, 257)),
    ("P256.fwd.r2c", "r2c", "fwd", 256, 256, (256, 256)),
    ("P256.fwd.c2c", "c2c", "fwd", 256, 256, (256, 129)),
    ("P256.bwd.c2c", "c2c", "bwd", 256, 256, (256, 129)),
    ("P256.bwd.c2r", "r2c", "bwd", 256, 256, (256, 256)),
]


def fft_path(dft, direction, n_fft, n_in):
    """The XLA engine's pruned ``jnp.fft`` wrapper of one stage."""
    if direction == "fwd":
        if dft == "r2c":
            return lambda x: tr._rfft_padded(x, n_fft, None)
        return lambda x: tr._cfft_padded(x, n_fft, None)
    if dft == "r2c":
        return lambda y: tr._irfft_crop(y, n_fft, n_in, None)
    return lambda y: tr._icfft_crop(y, n_in, None)


def mxu_path(dft, direction, n_fft, n_in):
    """The MXU route of one stage, against its plan-time matrices."""
    mats = tr.dft_matrices(dft, n_fft, n_in)
    if direction == "fwd":
        return lambda x: tr.mxu_dft_forward(x, mats)
    return lambda y: tr.mxu_dft_backward(y, mats)


def stage_input(dft, direction, n_fft, n_in, lead, seed=0):
    """A device array the stage consumes, made on the device."""
    n_out = n_fft // 2 + 1 if dft == "r2c" else n_fft
    width = n_in if direction == "fwd" else n_out
    complex_in = not (direction == "fwd" and dft == "r2c")

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        re = jax.random.normal(k1, lead + (width,), jnp.float32)
        if not complex_in:
            return re
        return jax.lax.complex(re, jax.random.normal(k2, re.shape,
                                                     jnp.float32))
    return jax.block_until_ready(make(jax.random.key(seed)))


def time_call(fn, x, reps, calls):
    """Median over ``reps`` blocks of seconds per call, ``calls``
    back-to-back calls a block (the device's time, once dispatch runs
    ahead of it)."""
    jax.block_until_ready(fn(x))
    per = []
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(x)
        jax.block_until_ready(out)
        per.append((time.perf_counter() - t) / calls)
    return statistics.median(per)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=0,
                    help="cut every leading axis to this many rows "
                         "(a rehearsal); 0 keeps the full shapes")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--only", default="",
                    help="comma list of case-name prefixes")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    results = []
    for case, dft, direction, n_fft, n_in, lead in CASES:
        if args.only and not any(case.startswith(p)
                                 for p in args.only.split(",")):
            continue
        if args.rows:
            lead = tuple(min(r, args.rows) for r in lead)
        x = stage_input(dft, direction, n_fft, n_in, lead)
        row = {"case": case, "dft": dft, "dir": direction, "n_fft": n_fft,
               "n_in": n_in, "lead": list(lead)}
        outs = {}
        for name, make in (("fft", fft_path), ("mxu", mxu_path)):
            fn = jax.jit(make(dft, direction, n_fft, n_in))
            mem = fn.lower(x).compile().memory_analysis()
            row[f"{name}_temp_bytes"] = (int(mem.temp_size_in_bytes)
                                         if mem is not None else None)
            row[f"{name}_ms"] = 1e3 * time_call(fn, x, args.reps,
                                                args.calls)
            outs[name] = np.asarray(fn(x))
        ref = outs["fft"]
        row["rel_diff"] = float(np.max(np.abs(outs["mxu"] - ref))
                                / np.max(np.abs(ref)))
        row["speedup"] = row["fft_ms"] / row["mxu_ms"]
        print(f"{case}: fft {row['fft_ms']:.4f} ms, mxu {row['mxu_ms']:.4f}"
              f" ms ({row['speedup']:.3f}x); temp fft "
              f"{row['fft_temp_bytes']} mxu {row['mxu_temp_bytes']}; "
              f"rel diff {row['rel_diff']:.3e}", flush=True)
        results.append(row)
        del x, outs
    print(json.dumps({"device": dev.device_kind, "cases": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
