"""``benchmark/spectral_mesh_reference.py``, the plain reference of the
cell ``preheat-spectral-mesh4-f32.spectral-stage-loop``: on a
``(2, 2, 1)`` mesh of four CPU devices at (32, 32, 16) its Laplacian,
gradient, round trip and two steps of ``run`` equal
``benchmark/spectral_reference.py``'s on one device to float32
round-off; on one device it is that reference; no program of it holds an
``all-gather`` (the transform a sharded ``jnp.fft.fftn`` would gather
for); it imports nothing of ``pystella_tpu``; and a component that is
not laid out in one (x, y) block a chip is refused."""

import ast
import os
import re
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spectral_mesh_reference as mesh_reference  # noqa: E402
from benchmark import spectral_reference as reference  # noqa: E402

GRID = (32, 32, 16)
BOX = (10 * 32 / 1024, 10 * 32 / 1024, 5 * 16 / 512)
PHYS = dict(mphi=1.2e-6, mchi=0.0, gsq=2.5e-7, sigma=0.0, lambda4=0.0)
BACKGROUND = {"mode": "coupled", "a": 1.0, "adot": 0.47, "mpl": 1.0}

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="the cell's mesh takes four devices")


@pytest.fixture(autouse=True)
def chip_precision():
    """The chip's 32-bit mode (the suite turns x64 on)."""
    with jax.enable_x64(False):
        yield


@pytest.fixture
def fields():
    """``(f, dfdt)`` as the cell's: phi with its offset, chi without."""
    rng = np.random.default_rng(11)
    f = 1e-4 * rng.standard_normal((2,) + GRID)
    f[0] += 0.193
    dfdt = 1e-4 * rng.standard_normal((2,) + GRID)
    dfdt[0] -= 0.142231
    return f.astype(np.float32), dfdt.astype(np.float32)


def placed(array, proc_shape):
    """``array[comp, x, y, z]`` in (x, y) blocks over a mesh of
    ``proc_shape`` CPU devices (the program's mesh types its axes
    explicitly; the reference must take either)."""
    n = int(np.prod(proc_shape))
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(proc_shape),
                ("x", "y", "z"))
    return jax.device_put(array, NamedSharding(mesh, P(None, "x", "y")))


def close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


@pytest.mark.parametrize("proc_shape", [(2, 2, 1), (1, 1, 1), (4, 1, 1)])
@pytest.mark.parametrize("what", ["laplacian", "gradient", "roundtrip",
                                  "bf16_pass"])
def test_derivatives_equal_the_one_device_reference(fields, proc_shape, what):
    f, _ = fields
    ks = reference.momenta(GRID, BOX)
    one, many = jnp.asarray(f), placed(f, proc_shape)
    if what == "laplacian":
        for got, want in zip(mesh_reference.laplacian(many, ks),
                             reference.laplacian(one, ks)):
            assert close(got, want, 2e-6)
            assert got.sharding.spec == P("x", "y", None)
    elif what == "gradient":
        for c in range(2):
            want = reference.gradient(one[c], ks)
            for mu, got in enumerate(mesh_reference.gradient(many[c], ks)):
                assert close(got, want[mu], 2e-6), (c, mu)
                assert close(mesh_reference.partial_derivative(
                    many[c], ks, mu), want[mu], 2e-6), (c, mu)
    elif what == "roundtrip":
        got = mesh_reference.roundtrip_gap(many)
        assert got < 2e-6 and abs(got - reference.roundtrip_gap(one)) < 1e-6
    else:
        got = mesh_reference.roundtrip_gap(many, "matmul_bf16")
        want = reference.roundtrip_gap(one, "matmul_bf16")
        assert 1e-3 < got < 1e-2 and abs(got - want) < 0.05 * want
        for c, lap in enumerate(mesh_reference.laplacian(
                many, ks, "matmul_bf16")):
            assert close(lap, reference.laplacian(
                one, ks, "matmul_bf16")[c], 1e-4)


@pytest.mark.parametrize("kw", [{}, {"carry_dtype": jnp.bfloat16},
                                {"inverse": "matmul_bf16"}],
                         ids=["f32", "bf16_carry", "matmul_bf16"])
def test_two_steps_equal_the_one_device_reference(fields, kw):
    f, dfdt = fields
    ks = reference.momenta(GRID, BOX)
    args = (2, 0.1 * BOX[0] / GRID[0], PHYS, ks, float(np.prod(GRID)),
            BACKGROUND)
    want = reference.run(jnp.asarray(f), jnp.asarray(dfdt), *args, **kw)
    got = mesh_reference.run(placed(f, (2, 2, 1)), placed(dfdt, (2, 2, 1)),
                             *args, **kw)
    tol = 2e-4 if kw else 2e-5
    assert close(got[0], want[0], tol) and close(got[1], want[1], tol)
    # two float32 sums of the energy in another order: 1e-8 on a CPU
    assert abs(got[2] - want[2]) < 1e-7 * abs(want[2] - 1)
    assert abs(got[3] / want[3] - 1) < 1e-7
    assert len(got[0].sharding.device_set) == 4


@pytest.mark.parametrize("inverse", mesh_reference.INVERSES)
def test_no_program_gathers_a_component(fields, inverse):
    """The compiled modules move blocks by ``all-to-all`` and hold no
    ``all-gather`` and no array of a whole component's size."""
    f, _ = fields
    fc = mesh_reference.at_home(placed(f, (2, 2, 1))[0])
    lay = mesh_reference.layouts(fc)
    forward, lap_of, pd_of, roundtrip = mesh_reference._programs(
        lay, inverse)
    (kx, k1), (ky, _), (kz, _) = reference.momenta(GRID, BOX)
    fk = forward(fc)
    whole = int(np.prod(GRID))
    for name, fn, args in (("forward", forward, (fc,)),
                           ("lap_of", lap_of, (fk, kx, ky, kz)),
                           ("pd_of", pd_of, (fk, k1)),
                           ("roundtrip", roundtrip, (fc,))):
        hlo = fn.lower(*args).compile().as_text()
        assert "all-gather" not in hlo, name
        assert " all-to-all(" in hlo, name
        largest = max(
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"= \w+\[([\d,]*)\]", hlo))
        assert largest <= whole // 4, (name, largest)
    # and what the issue replaced does gather
    plain = jax.jit(jnp.fft.fftn).lower(
        fc.astype(jnp.complex64)).compile().as_text()
    assert "all-gather" in plain


def test_a_component_that_is_not_in_blocks_is_refused(fields):
    f, _ = fields
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("x", "y"))
    for spec in (P(None, "x", None, "y"), P(), P(None, ("x", "y"))):
        laid = jax.device_put(f, NamedSharding(mesh, spec))
        if spec == P(None, ("x", "y")):
            mesh_reference.layouts(laid[0])     # four x slabs are blocks
            continue
        with pytest.raises(ValueError, match="one a chip"):
            mesh_reference.layouts(laid[0])


def test_imports_nothing_of_the_program():
    path = os.path.join(REPO, "benchmark", "spectral_mesh_reference.py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.startswith("pystella_tpu")], names
    assert {"benchmark.reference", "benchmark.spectral_reference"} \
        <= set(names)
