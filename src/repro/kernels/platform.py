"""Where a Pallas kernel runs: compiled by Mosaic on the TPU, interpreted
everywhere else.

The choice is made when the program is LOWERED, for the platform it is
lowered for (``jax.lax.platform_dependent``): a solve on the chip always
runs the compiled kernel, the CPU test suite runs the same kernel body in
the Pallas interpreter, and an ahead-of-time compile for a described TPU
from a CPU process gets the Mosaic kernel.  There is no user option.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kw):
    """``pl.pallas_call(kernel, **kw)``, compiled on the TPU and run by
    the interpreter on any other platform."""
    compiled = pl.pallas_call(kernel, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)

    return call
