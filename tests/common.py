"""Shared harness for running test files as benchmark scripts.

Mirrors /root/reference/test/common.py:41-76: every operator test file has
a ``__main__`` block that doubles as a per-kernel microbenchmark via
:func:`pystella_tpu.timer`, parametrized by the same ``--grid_shape`` /
``--proc_shape`` CLI the pytest suite uses. Run e.g.::

    python tests/test_derivs.py -grid 256 256 256 --h 2

Importing this module only enables 64-bit mode (the reference defaults
to float64). The pytest suite runs on the CPU — ``conftest.py`` defaults
``JAX_PLATFORMS`` to ``cpu`` with 8 virtual devices before jax is first
imported. A script run takes the devices jax finds, and
:func:`parse_args` refuses anything but a TPU: a microbenchmark's number
is a device number or it is nothing.
"""

import argparse
import os

os.environ["JAX_ENABLE_X64"] = "1"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)  # reference defaults to float64

import numpy as np  # noqa: E402

parser = argparse.ArgumentParser(add_help=False)
parser.add_argument("--help", action="help")
parser.add_argument("-proc", "--proc_shape", type=int, nargs=3,
                    default=(1, 1, 1))
parser.add_argument("-grid", "--grid_shape", type=int, nargs=3,
                    default=(128, 128, 128))
parser.add_argument("--h", type=int, default=2, metavar="h")
parser.add_argument("--dtype", type=np.dtype, default=np.float64)
parser.add_argument("--ntime", type=int, default=50)


def parse_args(argv=None):
    args = parser.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"the test files' __main__ blocks time kernels on a TPU; jax "
            f"found {dev.platform} ({dev.device_kind}). The tests "
            "themselves run under pytest, on the CPU.")
    args.proc_shape = tuple(args.proc_shape)
    args.grid_shape = tuple(args.grid_shape)
    args.dtype = np.dtype(args.dtype)  # normalize the non-CLI default too
    return args


def script_decomp(proc_shape):
    import pystella_tpu as ps
    n = int(np.prod(proc_shape))
    if n > len(jax.devices()):
        raise SystemExit(
            f"mesh {proc_shape} needs {n} devices, have {len(jax.devices())}")
    return ps.DomainDecomposition(proc_shape, devices=jax.devices()[:n])


def script_fft(args, box=5.0):
    """Shared benchmark setup: ``(decomp, lattice, fft)`` for the parsed
    CLI args (used by the fourier-stack test files' ``__main__`` blocks)."""
    import pystella_tpu as ps
    decomp = script_decomp(args.proc_shape)
    lattice = ps.Lattice(args.grid_shape, (box,) * 3, dtype=args.dtype)
    fft = ps.DFT(decomp, grid_shape=args.grid_shape, dtype=args.dtype)
    return decomp, lattice, fft


def report(name, ms, nbytes=None, nsites=None):
    """Print one benchmark line: ms/call, optional GB/s and sites/s."""
    extra = ""
    if nbytes is not None:
        extra += f"  {nbytes / ms / 1e6:8.1f} GB/s"
    if nsites is not None:
        extra += f"  {nsites / ms * 1e3:.3e} sites/s"
    print(f"{name:<28s} {ms:8.3f} ms{extra}")
