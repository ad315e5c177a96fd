"""Ahead-of-time compiles for a described TPU v5e, made in this process.

The Pallas kernels of the main path must compile with Mosaic (not run in
the interpreter) at the lengths the chip solves use: 512 for the one-chip
256^3 solve, 1024 for the four-chip 512^3 solve, with the pruned
zero-tail first stage (``pad_to``) and the fused twiddle and Green
epilogues.  The FFT lengths the plan-time rule (``fft_stockham.fits``)
sends to XLA on the TPU must be sent there.  Nothing here runs on a chip:
a compile that passes is not a chip run.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import transforms as tr
from repro.core.engine import TransformEngine
from repro.kernels.fft_stockham import (TPU_LENGTHS, fft_stockham,
                                        fft_stockham_scale,
                                        fft_stockham_twiddle, fits)
from repro.kernels.spectral_scale import spectral_scale
from repro.kernels.twiddle_pack import twiddle_pack

ROWS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip):
    """``compile_tpu(fn, *shapes)`` -> the compiled HLO text of ``fn`` for
    the described chip, as the chip path runs it: x64 off (the suite turns
    it on for the f64 CPU tests; the chip solves in f32), and the
    persistent compile cache off (a TPU executable written here could not
    be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    jax.config.update("jax_enable_x64", prev_x64)
    cc.reset_cache()


STOCKHAM = {
    "fft": (lambda r, i: fft_stockham(r, i),
            lambda n: [(ROWS, n), (ROWS, n)]),
    "ifft": (lambda r, i: fft_stockham(r, i, inverse=True),
             lambda n: [(ROWS, n), (ROWS, n)]),
    "pad_to": (lambda r, i: fft_stockham(r, i, pad_to=2 * r.shape[-1]),
               lambda n: [(ROWS, n // 2), (ROWS, n // 2)]),
    "twiddle": (lambda r, i, a, b: fft_stockham_twiddle(r, i, a, b, start=1),
                lambda n: [(ROWS, n), (ROWS, n), (n // 2,), (n // 2,)]),
    "scale": (lambda r, i, g: fft_stockham_scale(r, i, g),
              lambda n: [(ROWS, n), (ROWS, n), (ROWS, n // 2 + 1)]),
}


@pytest.mark.parametrize("variant", sorted(STOCKHAM))
@pytest.mark.parametrize("n", [512, 1024])
def test_stockham_compiles_for_v5e(compile_tpu, n, variant):
    assert fits(n, "tpu")
    fn, shapes = STOCKHAM[variant]
    hlo = compile_tpu(fn, *shapes(n))
    assert hlo.count("tpu_custom_call") >= 1, (n, variant)


def test_spectral_scale_compiles_for_v5e(compile_tpu):
    # an odd lane count (the r2c half spectrum of a 512 FFT), unbatched
    # and batched over a shared Green plane
    hlo = compile_tpu(lambda r, i, g: spectral_scale(r, i, g, 1.0),
                      (512, 257), (512, 257), (512, 257))
    assert hlo.count("tpu_custom_call") == 1
    hlo = compile_tpu(lambda r, i, g: spectral_scale(r, i, g, 1.0),
                      (2, 512, 512), (2, 512, 512), (512, 512))
    assert hlo.count("tpu_custom_call") == 1


def test_twiddle_pack_compiles_for_v5e(compile_tpu):
    hlo = compile_tpu(lambda r, i, a, b: twiddle_pack(r, i, a, b),
                      (300, 257), (300, 257), (257,), (257,))
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("n", [64, 128, 256, 2048, 4096])
def test_routed_lengths_run_xla_fft(compile_tpu, n):
    """Lengths outside ``TPU_LENGTHS`` are routed to XLA's FFT by the
    plan-time rule: the compiled program holds no Mosaic kernel."""
    assert not (TPU_LENGTHS[0] <= n <= TPU_LENGTHS[1])
    eng = TransformEngine("pallas", platform="tpu")
    assert not fits(n, "tpu") and not eng.kernel_fft(n)
    assert fits(n, "cpu")            # interpret mode takes every pow2
    hlo = compile_tpu(lambda x: tr._rfft(x, eng).real, (ROWS, n))
    assert "tpu_custom_call" not in hlo


def test_kernel_lengths_run_the_kernel(compile_tpu):
    """The same transform entry point at a kernel length compiles the
    Mosaic kernel -- the pruned Hockney forward and its parity-split
    inverse included."""
    eng = TransformEngine("pallas", platform="tpu")
    hlo = compile_tpu(lambda x: tr._rfft_padded(x, 512, eng).real,
                      (ROWS, 256))
    assert hlo.count("tpu_custom_call") == 1
    hlo = compile_tpu(
        lambda x: tr._icfft_crop(tr._cfft(x, eng), 512, eng).real,
        (ROWS, 1024))
    assert hlo.count("tpu_custom_call") == 3


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("dft", ["r2c", "c2c"])
def test_mxu_dft_products_compile_for_v5e(compile_tpu, dft, direction):
    """The MXU route's products at the one-chip 256^3 solve's widths (a
    512-point DFT with 257 live points) compile for a v5e as convolutions
    at the highest precision, with no FFT left."""
    mats = tr.dft_matrices(dft, 512, 257)
    n_out = 257 if dft == "r2c" else 512
    if direction == "fwd" and dft == "r2c":
        hlo = compile_tpu(lambda x: tr.mxu_dft_forward(x, mats).imag,
                          (ROWS, 257))
    elif direction == "fwd":
        hlo = compile_tpu(lambda r, i: tr.mxu_dft_forward(
            jax.lax.complex(r, i), mats).imag, (ROWS, 257), (ROWS, 257))
    else:
        hlo = compile_tpu(lambda r, i: jnp.real(tr.mxu_dft_backward(
            jax.lax.complex(r, i), mats)), (ROWS, n_out), (ROWS, n_out))
    assert "convolution" in hlo and " fft(" not in hlo
    assert "operand_precision={highest,highest}" in hlo
