"""The program at stencil radius 4 against the plain reference of
upstream's ``--halo-shape 4`` run (``benchmark/wide_reference.py``, which
imports nothing of ``pystella_tpu`` and has its coefficient rows typed in
from the published tables): ``FiniteDifferencer(h).lap`` / ``.grad``
through the streaming kernels (interpret mode) and the XLA halo path, and
two energy-coupled steps of ``FusedScalarStepper(halo_shape=4)`` on the
pair tier and on the single-stage tier, on seeded random float32 fields.
Every comparison is also made against the reference's rows one radius
narrower (``h3``, the control of the cell
``preheat-h4-f32.coupled-steps``), which has to fail it: a run that takes
a narrower stencil than it states is a different result.

Then what PR 40 added beside the cell: the refusal of a radius the
tables do not hold, and ``block_choice``'s ``h`` and ``taps``.
"""

import os
import sys
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pystella_tpu as ps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference as scalar_reference  # noqa: E402
from benchmark import wide_reference as reference  # noqa: E402

_TPU_SESSION = jax.default_backend() == "tpu"
_XKW = {"interpret": True} if _TPU_SESSION else {}

#: X a multiple of the largest radius (``bx >= h``), Y of 8 (the y halo)
GRID = (16, 24, 32)
DX = (0.3, 0.25, 0.2)

#: float32, zero-mean fields: a derivative is a sum of 2h or 6h + 1
#: products of a site's neighbours, taken in another order by the kernel
#: (taps accumulated axis by axis, coefficient times 1/dx^2 first) than by
#: the reference (offset by offset, 1/dx^2 last): a few ulps of the
#: largest term, 6e-8 each, against a largest value of the same size
OPERATOR_TOL = 3e-6
#: two steps = ten stages, each adding dt * lap f to the registers: the
#: operators' ulps through ten updates, relative to the field's largest
#: value (the cell reads 6.5e-5 after four steps at its spacing, where
#: dt * lap f is far larger against the fields)
STEP_TOL = 2e-5


@pytest.fixture(scope="module")
def decomp():
    devs = (jax.devices("cpu") if _TPU_SESSION else jax.devices())[:1]
    return ps.DomainDecomposition((1, 1, 1), devices=devs)


def _fields(seed, ncomp=2, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        scale * rng.standard_normal((ncomp,) + GRID).astype(np.float32))


# -- the rows ----------------------------------------------------------------

@pytest.mark.parametrize("h", [1, 2, 3, 4])
def test_rows_meet_their_order_conditions(h):
    """The typed-in rows are the centred differences of order ``2h``:
    applied to ``x^p`` at 0 they give the derivative exactly for every
    ``p <= 2h`` (second difference: ``p <= 2h + 1``)."""
    lap = [Fraction(c).limit_denominator(10**5)
           for c in reference.LAP_COEFS[h]]
    grad = [Fraction(c).limit_denominator(10**5)
            for c in reference.GRAD_COEFS[h]]
    assert len(lap) == h + 1 and len(grad) == h
    assert lap[0] + 2 * sum(lap[1:]) == 0
    for m in range(1, h + 1):
        moment = 2 * sum(c * s ** (2 * m) for s, c in enumerate(lap) if s)
        assert moment == (2 if m == 1 else 0), (m, moment)
    for m in range(h):
        moment = 2 * sum(c * s ** (2 * m + 1)
                         for s, c in enumerate(grad, 1))
        assert moment == (1 if m == 0 else 0), (m, moment)


@pytest.mark.parametrize("h", [1, 2, 3])
def test_energy_density_is_the_scalar_references_at_its_radii(h):
    """Where ``benchmark/reference.py`` has rows too, the two plain
    references' ``rho / rho_bar`` agree to float32's rounding (3/4 is
    45/60 to an ulp)."""
    f, dfdt = _fields(3, scale=0.1), _fields(4, scale=0.01)
    phys = {"mphi": 1.0, "mchi": 0.5, "gsq": 0.25, "sigma": 0.1,
            "lambda4": 0.05}
    args = (1.1, 0.2, phys, DX, h, 1.0)
    ours = reference.energy_density(f, dfdt, *args)
    theirs = scalar_reference.energy_density(f, dfdt, *args)
    assert reference.gap(ours, theirs) < 1e-6


# -- the operators -----------------------------------------------------------

def _operator_gaps(decomp, h, mode, rows):
    """``(lap_gap, grad_gap)`` of ``FiniteDifferencer(h)`` against the
    reference's rows of radius ``rows``."""
    fd = ps.FiniteDifferencer(decomp, h, DX, mode=mode)
    f = _fields(10 + h)
    lap, grad = fd.lap(f), fd.grad(f)
    assert lap.shape == f.shape and grad.shape == (2, 3) + GRID
    ref_lap = reference.laplacian(f, DX, rows)
    lap_gap = max(reference.gap(lap[c], ref_lap[c]) for c in range(2))
    grad_gap = max(
        reference.gap(grad[c][mu], r) for c in range(2)
        for mu, r in enumerate(reference.gradient(f[c], DX, rows)))
    return lap_gap, grad_gap


@pytest.mark.parametrize("mode, h", [
    ("pallas", 1), ("pallas", 2), ("pallas", 3), ("pallas", 4),
    ("halo", 4)])
def test_operators_match_the_plain_reference(decomp, mode, h):
    lap_gap, grad_gap = _operator_gaps(decomp, h, mode, h)
    assert lap_gap < OPERATOR_TOL and grad_gap < OPERATOR_TOL, \
        (lap_gap, grad_gap)


@pytest.mark.parametrize("mode", ["pallas", "halo"])
def test_operators_fail_the_narrower_rows(decomp, mode):
    """On white noise the sixth-order rows are percents from the
    eighth-order ones, four orders over the tolerance."""
    lap_gap, grad_gap = _operator_gaps(decomp, 4, mode, 3)
    assert lap_gap > 1e-2 and grad_gap > 1e-2, (lap_gap, grad_gap)


# -- two coupled steps -------------------------------------------------------

PHYS = {"mphi": 1.0, "mchi": 0.5, "gsq": 0.25, "sigma": 0.1,
        "lambda4": 0.05}


def _potential(f):
    phi, chi = f[0], f[1]
    return (PHYS["mphi"]**2 / 2 * phi**2 + PHYS["mchi"]**2 / 2 * chi**2
            + PHYS["gsq"] / 2 * phi**2 * chi**2
            + PHYS["sigma"] / 2 * phi * chi**2
            + PHYS["lambda4"] / 4 * chi**4) / PHYS["mphi"]**2


@pytest.fixture(scope="module")
def stepped(decomp):
    """Two coupled steps of the h = 4 stepper by both tiers, and the
    references' (the configuration's rows, and one radius narrower)
    from the same seeded state and background."""
    nsteps, dt = 2, np.float32(0.01)
    grid_size = float(np.prod(GRID))
    stepper = ps.FusedScalarStepper(
        ps.ScalarSector(2, potential=_potential), decomp, GRID, DX, 4,
        dtype=jnp.float32, dt=dt, **_XKW)
    assert stepper._ensure_coupled_pair_calls() is not None

    def state():
        return {"f": _fields(21, scale=0.1), "dfdt": _fields(22, scale=0.01)}

    out = {}
    for tier, pair in (("pair", True), ("single", False)):
        expand = ps.Expansion(0.02, ps.LowStorageRK54, mpl=1.0)
        background = {"mode": "coupled", "a": float(expand.a),
                      "adot": float(expand.adot), "mpl": 1.0}
        got = stepper.coupled_multi_step(state(), nsteps, expand, 0.0, dt,
                                         grid_size=grid_size, pair=pair)
        out[tier] = (got, float(expand.a), float(expand.hubble))
    for rows in (4, 3):
        st = state()
        f, dfdt, a, hub = reference.run(
            st["f"], st["dfdt"], nsteps, float(dt), PHYS, DX, rows,
            grid_size, background)
        out[rows] = ({"f": f, "dfdt": dfdt}, a, hub)
    return out


@pytest.mark.parametrize("tier", ["pair", "single"])
def test_two_coupled_steps_match_the_plain_reference(stepped, tier):
    got, a, hub = stepped[tier]
    ref, a_ref, hub_ref = stepped[4]
    assert scalar_reference.field_gap(got, ref) < STEP_TOL
    # the background: float64 scalars on both sides under the suite's
    # x64, fed by float32 sums of the energy over 12,288 sites
    assert abs(a - a_ref) / abs(a_ref - 1.0) < 1e-5
    assert abs(hub / hub_ref - 1.0) < 1e-5


@pytest.mark.parametrize("tier", ["pair", "single"])
def test_two_coupled_steps_fail_the_narrower_rows(stepped, tier):
    got, _, _ = stepped[tier]
    narrow, _, _ = stepped[3]
    assert scalar_reference.field_gap(got, narrow) > 100 * STEP_TOL


# -- what PR 40 added beside the cell ----------------------------------------

@pytest.mark.parametrize("h", [0, 5, -1, 9])
def test_a_radius_outside_the_tables_is_refused_by_name(decomp, h):
    """A ``ValueError`` that names the range, where ``_lap_coefs[5]``
    gave a ``KeyError``."""
    with pytest.raises(ValueError, match="1-4"):
        ps.FiniteDifferencer(decomp, h, DX)
    with pytest.raises(ValueError, match="FusedScalarStepper.*1-4"):
        ps.FusedScalarStepper(ps.ScalarSector(2, potential=_potential),
                              decomp, GRID, DX, h, dtype=jnp.float32)


def test_the_examples_flag_is_refused_past_four():
    sys.path.insert(0, os.path.join(REPO, "examples"))
    try:
        import scalar_preheating
    finally:
        sys.path.pop(0)
    with pytest.raises(ValueError, match="--halo-shape 5: takes 0-4"):
        scalar_preheating.main(["--halo-shape", "5"])
    assert "1-4" in scalar_preheating.parser.format_help()


def test_a_chunks_window_is_refused_with_the_radius_and_hy():
    """A depth-6 chunk at radius 4 wants 12 window rows of the 8 the
    aligned y halo has: the refusal says both."""
    from pystella_tpu.ops import pallas_stencil as psten
    with pytest.raises(ValueError,
                       match=r"6 stage\(s\) at stencil radius 4.*HY = 8"):
        psten.choose_blocks(8, (512,) * 3, 4, 4, 0, 8, win_halo=12,
                            stages=6)
    # depth 4 sits exactly on the limit: the window itself is taken (x
    # blocks of 8 rows, the ring supplying the halo) ...
    assert psten.choose_blocks(
        8, (64, 64, 128), 4, 4, 0, 8, win_halo=8, stages=4,
        budget=psten.BLOCK_BUDGET_BYTES) == (8, 32)
    # ... but its 12 stage-temporaries of a (24, by + 16, Z) window are
    # over the tier figure at any lattice, and at Z = 512 over the
    # limit the kernels compile under: no depth-4 chunk at radius 4
    for lattice, budget in (((64, 64, 128), None), ((512,) * 3, None),
                            ((512,) * 3, psten.VMEM_LIMIT_BYTES)):
        with pytest.raises(ValueError, match="fits the (24|100) MB"):
            psten.choose_blocks(8, lattice, 4, 4, 0, 8, win_halo=8,
                                stages=4, budget=budget)


@pytest.mark.parametrize("h", [1, 2, 3, 4])
def test_block_choice_says_radius_and_taps(decomp, h):
    from test_kernel_choice import _watch_events
    with _watch_events() as seen:
        stepper = ps.FusedScalarStepper(
            ps.ScalarSector(2, potential=_potential), decomp, GRID, DX, h,
            dtype=jnp.float32, **_XKW)
        stepper._ensure_coupled_pair_calls()
        stepper._ensure_energy_call()
    taps = {d["kernel"]: (d["h"], d["taps"])
            for d in seen.of("block_choice")}
    lap = 6 * h + 1
    assert taps == {"stage": (h, lap), "energy": (h, lap),
                    "pair": (h, 2 * lap), "coupled_pair": (h, 2 * lap)}
    assert stepper.kernel_tier_report()["h"] == h
