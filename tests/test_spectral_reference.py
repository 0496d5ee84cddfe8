"""The ``--halo-shape 0`` system against its plain reference, off the chip.

``benchmark/spectral_reference.py`` (``jax.numpy``, nothing of
``pystella_tpu``) is what the benchmark cell
``preheat-spectral-f32.spectral-stage-loop`` holds the program to on the
chip at 512**3. Here the same comparison runs at 16**3 and 32**3 on the
CPU, through the benchmark's own family module
(``benchmark/families/spectral_preheat.py``: the collocator, the generic
``LowStorageRK54(full_rhs)`` and the ``Expansion`` as the example builds
them, the seeded WKB state), so that a change to ``fourier/derivs.py``,
``fourier/dft.py`` or ``step.py`` that breaks the mathematics fails tier-1
before it costs chip time. The control (the reference with its inverse
transform in one bfloat16 pass) is put in the program's place and has to
fail the same bounds.

Tolerances, each with its reason, are at the cases.
"""

import json
import os
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import drivers, families, reference  # noqa: E402
from benchmark import spectral_reference  # noqa: E402
from benchmark.spans import Spans  # noqa: E402

NSTEPS = 2
SEED = 2**31 + 11
#: the loop body's parameters: no statistics, no health rows
TRAFFIC = {"driver": "spectral_stage_loop", "block_steps": 4,
           "chunk_steps": 1, "check_steps": NSTEPS}


def _system(n, dtype="float32"):
    """The cell's system on an ``n**3`` patch of its lattice: the cell's
    own spacing (box 5 n / 512), fewer sites."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "preheat-spectral-f32.json")) as f:
        config = json.load(f)
    config.update(grid_shape=[n] * 3, box_dim=[5.0 * n / 512] * 3,
                  dtype=dtype)
    family = families.of(config)
    return family, family.System(config, jax.devices()[:1])


#: dtype -> largest allowed (field gap, a gap) after two steps. A field
#: gap is max |got - ref| / max |ref| per component, taken in float32.
#:
#: float64: both sides compute the same formulas, the program on the half
#: spectrum of a real transform and the reference on the whole spectrum
#: of a complex one; what is left is the comparison's float32 floor: read
#: 0 (16**3) and 7.4e-8 (32**3); `a` to 1.4e-10 and 3.2e-9 (the
#: reference's energy sums are float32 at every precision). A wrong
#: symbol, a Nyquist mode dropped from `lap` or a stage fed the wrong
#: energy reads 1e-3 and more.
#:
#: float32: chi has no background, and both sides' transforms carry
#: float32's own rounding into its time derivative through dt * lap:
#: read 3.6e-5 and 4.0e-5 (the bound is the cell's own limit); `a` to
#: 3.8e-10 and 1.4e-8.
TOLERANCE = {"float64": (1e-6, 1e-7), "float32": (1.3e-4, 2e-7)}


@pytest.mark.parametrize("n", [16, 32])
@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
def test_stage_loop_follows_the_plain_reference(dtype, n):
    """The example's spectral loop body (one generic-stepper dispatch a
    stage, the energy from a second Laplacian, ``Expansion`` stepped on
    the host) from the seeded WKB state against
    ``spectral_reference.run`` from the same state."""
    family, system = _system(n, dtype)
    state, expand, energy = system.initial_state(SEED)
    driver = drivers.load(TRAFFIC["driver"])(system, TRAFFIC, Spans(False))
    driver.start(state, expand, energy)
    background = driver.background()
    driver.first_steps()
    ref, a_ref, _, roundtrip = family.reference_state(
        system, SEED, background, NSTEPS)
    tol_f, tol_a = TOLERANCE[dtype]
    assert reference.field_gap(driver.state, ref) < tol_f
    assert abs(float(expand.a) - a_ref) / abs(a_ref - 1.0) < tol_a
    assert roundtrip < 2e-6
    assert driver.spans.count(["spectral_lap"], "setup") == 5 * NSTEPS


def _field_with_offset(n, seed):
    """``(2, n, n, n)`` float32: 0.193 + 4e-5 noise and plain 1e-6 noise,
    the sizes of the cell's phi and chi."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((2, n, n, n))
    return (np.array([0.193, 0.0]).reshape(2, 1, 1, 1)
            + np.array([4e-5, 1e-6]).reshape(2, 1, 1, 1) * x
            ).astype(np.float32)


@pytest.fixture(scope="module")
def derivatives():
    """At 32**3: a random field with an offset, its ``lap`` and ``grad``
    by the program's collocator and by the control in its place, and the
    family's comparison of each with the reference."""
    family, system = _system(32)
    f = _field_with_offset(32, 7)
    dev = system.decomp.shard(f)
    ks = family.momenta(system)
    made = {
        "program": (np.asarray(system.derivs.lap(dev)),
                    np.asarray(system.derivs.grad(dev))),
        "control": (
            np.stack([np.asarray(x) for x in spectral_reference.laplacian(
                dev, ks, "matmul_bf16")]),
            np.stack([np.stack([np.asarray(x) for x in
                                spectral_reference.gradient(
                                    dev[c], ks, "matmul_bf16")])
                      for c in range(2)]))}
    return {who: family.derivative_gaps(system, dev, lap, grad)
            for who, (lap, grad) in made.items()}


#: the bounds of a sound derivative: chi's is float32's rounding of the
#: transforms (read 2.6e-7 and 3.0e-7); phi's carries the offset's
#: round-off times k**2 (read 3.0e-4 and 2.9e-4). One bfloat16 pass in
#: the inverse reads 3.4e-3 to 4.8e-3 on either field.
BOUND = {"lap_gap.0": 1e-3, "lap_gap.1": 3e-6,
         "grad_gap.0": 1e-3, "grad_gap.1": 3e-6}


@pytest.mark.parametrize("number", sorted(BOUND))
@pytest.mark.parametrize("who", ["program", "control"])
def test_derivatives_against_the_reference(derivatives, who, number):
    """``derivs.lap`` and ``derivs.grad`` of a field with an offset
    agree with the reference's; the control in the program's place does
    not."""
    value = derivatives[who][number]
    assert np.isfinite(value)
    if who == "program":
        assert value < BOUND[number], value
    else:
        assert value > BOUND[number], value


def test_the_reference_zeroes_the_nyquist_mode_of_the_odd_derivative():
    """The Nyquist rule is upstream's: ``d_x`` of the Nyquist mode
    ``(-1)**i`` is zero, its Laplacian is ``-k_N**2`` times itself."""
    n, box = 16, 5.0
    ks = spectral_reference.momenta((n,) * 3, (box,) * 3)
    x = ((-1.0) ** np.arange(n)).reshape(n, 1, 1) * np.ones((n, n, n))
    f = jnp.asarray(x[None], jnp.float32)
    grad = spectral_reference.gradient(f[0], ks)
    assert max(float(jnp.max(jnp.abs(g))) for g in grad) < 1e-4
    (lap,) = spectral_reference.laplacian(f, ks)
    k_ny = 2 * np.pi / box * (n // 2)
    assert np.allclose(np.asarray(lap), -k_ny**2 * x, rtol=1e-5)
