"""The multigrid solve against its plain reference.

``benchmark/mg_reference.py`` is what decides ``correct`` in the cell
``multigrid-512-f32.vcycle`` on the chip at 512**3; here the system
(``FullApproximationScheme`` over ``NewtonIterator``, the public API as
``tests/test_multigrid.py::test_multigrid`` uses it) is held to it at
32**3 on the CPU (depth 2: 32**3, 16**3, 8**3), after one cycle and after
four, in both precisions and through both smoothers (the Pallas one in
interpret mode).
"""

import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu.multigrid import FullApproximationScheme, NewtonIterator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import mg_reference as reference  # noqa: E402

N, BOX, DEPTH = 32, 10.0, 2
PROBLEMS = (("f", "rho", 0), ("f2", "rho2", 1))

#: per precision: the solution's gap (max|got - ref| / max|ref|), the
#: returned norms' relative gap while the residual is still far above
#: the precision's floor (after one cycle), and after four cycles.
#:
#: The system and the reference do the same sweeps with their sums in
#: another order, and a sweep contracts differences, so the unknowns
#: stay a few roundings apart: read 4.2e-7 in float32 (7 ulp) and 3.4e-15
#: in float64; the limits leave ten and thirty times that and lie four
#: orders under what bfloat16 arithmetic reads (0.1). The residual is a
#: difference of numbers 6/dx**2 = 61 times larger than the unknowns, so
#: each evaluation of it carries its own rounding of about eps * |f| *
#: 61: after one cycle the residual (1e-3) is far above that and the
#: norms agree to 2.3e-5 (float32) and 2e-14 (float64; 3.5e-8 by cycle
#: four, at 1e-8 of where it started); after four cycles float32 sits on
#: its floor (L2 4e-8, where it stays in cycles 5 and 6), where two right
#: evaluations differ by half their size (read: 0.25 to 0.58): there the
#: norms are held to a factor of three of each other, and to the floor.
TOLERANCES = {
    "float32": {"solution": 5e-6, "norms": 2e-4, "norms_at_floor": 3.0,
                "floor_l2": 2e-7},
    "float64": {"solution": 1e-13, "norms": 1e-6, "norms_at_floor": None,
                "floor_l2": None},
}


def seeded(dtype):
    rng = np.random.default_rng(5521)
    out = {}
    for name in ("f", "rho", "f2", "rho2"):
        a = rng.random((N,) * 3).astype(dtype)
        out[name] = jnp.asarray(a - a.mean())
    return out


@pytest.mark.parametrize("cycles", [1, 4])
@pytest.mark.parametrize("smoother", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_system_follows_the_plain_reference(dtype, smoother, cycles):
    dtype = np.dtype(dtype)
    tol = TOLERANCES[dtype.name]
    dx = BOX / N
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    solver = NewtonIterator(
        decomp,
        {ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho")),
         ps.Field("f2"): (ps.Field("lap_f2") - ps.Field("f2"),
                          ps.Field("rho2"))},
        halo_shape=1, dtype=dtype, smoother=smoother,
        fixed_parameters=dict(omega=1 / 2))
    mg = FullApproximationScheme(solver=solver, halo_shape=1)
    arrays = seeded(dtype)
    got = {n: arrays[n] for n, _, _ in PROBLEMS}
    for _ in range(cycles):
        errs, got = mg(decomp, dx0=dx, rho=arrays["rho"],
                       rho2=arrays["rho2"], **got)
    assert [lv for lv, _ in errs] == [0, 0, 1, 1, 2, 2, 1, 1, 0, 0]
    at_floor = dtype == np.float32 and cycles == 4
    for name, rho, mass in PROBLEMS:
        assert got[name].dtype == dtype
        ref, _, after = reference.solve(arrays[name], arrays[rho], dx, mass,
                                        DEPTH, cycles)
        assert ref.dtype == dtype
        gap = reference.solution_gap(got[name], ref, mean_free=not mass)
        assert gap < tol["solution"], (name, gap)
        for mine, theirs in zip(errs[-1][1][name], after):
            theirs = float(theirs)
            if at_floor:
                assert (1 / tol["norms_at_floor"] < mine / theirs
                        < tol["norms_at_floor"]), (name, mine, theirs)
            else:
                assert abs(mine / theirs - 1) < tol["norms"], \
                    (name, mine, theirs)
        if at_floor:
            assert errs[-1][1][name][1] < tol["floor_l2"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "mg_reference.py")) as f:
        source = f.read()
    imports = re.findall(r"^\s*(?:import|from)\s+([\w.]+)", source, re.M)
    assert imports and not [m for m in imports
                            if m.split(".")[0] in ("pystella_tpu",
                                                   "benchmark")], imports


def test_reference_transfers_are_upstreams():
    """Full weighting is the 27-point average centred on (2i, 2j, 2k);
    linear interpolation keeps the coarse sites and halves between."""
    rng = np.random.default_rng(3)
    fine = rng.random((8, 8, 8))
    expect = np.zeros((4, 4, 4))
    w = {-1: 0.25, 0: 0.5, 1: 0.25}
    for a, ca in w.items():
        for b, cb in w.items():
            for c, cc in w.items():
                expect += ca * cb * cc * np.roll(
                    fine, (-a, -b, -c), (0, 1, 2))[::2, ::2, ::2]
    assert np.allclose(reference.restrict(jnp.asarray(fine)), expect,
                       atol=1e-14)
    coarse = rng.random((4, 4, 4))
    up = np.asarray(reference.interpolate(jnp.asarray(coarse)))
    assert np.array_equal(up[::2, ::2, ::2], coarse)
    assert np.allclose(up[1::2, ::2, ::2],
                       0.5 * (coarse + np.roll(coarse, -1, 0)), atol=1e-15)
    assert np.allclose(reference.restrict(jnp.full((8, 8, 8), 2.5)), 2.5)


def test_the_cells_configuration_states_the_programs_default_cycle():
    """``benchmark/configs/multigrid-512-f32.json`` says what the cell
    runs, and the cell passes no ``cycle``: the cycle, sweeps and depth
    it states are the default ``FullApproximationScheme.__call__`` picks
    for its lattice, its operators the constructors' defaults, and its
    traffic one solve of ``cycles_per_solve`` cycles a block."""
    import json
    from unittest import mock
    from pystella_tpu import multigrid
    with open(os.path.join(REPO, "benchmark", "configs",
                           "multigrid-512-f32.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "vcycle.json")) as f:
        traffic = json.load(f)
    assert (cfg["grid_shape"], cfg["proc_shape"], cfg["dtype"]) == (
        [512] * 3, [1, 1, 1], "float32")
    assert (cfg["box_dim"], cfg["halo_shape"], cfg["omega"]) == (
        [10.0] * 3, 1, 0.5)
    assert traffic["block_steps"] == traffic["check_steps"] \
        == cfg["cycles_per_solve"] * traffic["chunk_steps"] == 4

    # the default cycle for a lattice of the configuration's extent, seen
    # where the walk asks for its levels (nothing of that size is made)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    solver = NewtonIterator(
        decomp, {ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho"))},
        halo_shape=1, fixed_parameters=dict(omega=cfg["omega"]))
    mg = FullApproximationScheme(solver=solver, halo_shape=1)
    assert type(mg.restrictor).__name__ == cfg["restriction"]
    assert type(mg.interpolator).__name__ == cfg["interpolation"]
    assert (type(solver).__name__, type(mg).__name__) == (
        cfg["solver"], cfg["scheme"])
    seen = {}

    class Stop(Exception):
        pass

    def levels(self, decomp, grid_shape, dx0, depth):
        seen.update(grid_shape=grid_shape, dx0=dx0, depth=depth)
        raise Stop

    shaped = jax.ShapeDtypeStruct(tuple(cfg["grid_shape"]), jnp.float32)
    with mock.patch.object(FullApproximationScheme, "_make_levels", levels), \
            pytest.raises(Stop):
        mg(decomp, dx0=10 / 512, f=shaped, rho=shaped)
    assert seen["depth"] == cfg["depth"] == 6
    assert multigrid.v_cycle(*cfg["nu"], cfg["depth"]) == (
        [(i, 25) for i in range(6)] + [(i, 50) for i in range(6, -1, -1)])
