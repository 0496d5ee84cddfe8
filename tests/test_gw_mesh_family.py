"""The cell ``preheat-gw-mesh4-f32.gw-coupled-run`` off the chip:
upstream's ``-gws`` run on the ``(2, 2, 1)`` mesh as ``BENCHMARK.json``
holds it, cut to a ``(64, 64, 32)`` patch of its lattice on four virtual
devices and driven through ``benchmark/run.main``: set-up, blocks, an
output and the check against ``benchmark/gw_mesh_reference.py``. A sound
rehearsal is ``correct``; bfloat16 RK registers, a dropped tensor source,
a gravitational-wave spectrum of an unprojected ``dhijdt`` and blocks
stepped as ``(1, 1, 1)`` lattices of their own (no slab from a
neighbour) are each not ``correct``; the control's readings, put through
the harness's own comparison, miss the cell's limits; and the sharded
``FusedPreheatStepper`` agrees with the ``(1, 1, 1)`` stepper on the
same state (one reduced case each of the two ``slow`` tests of
``tests/test_fused.py``).

The cut keeps the cell's lattice spacing (``dx`` = 5/384), so the time
step, the largest momentum and with them the size of the seeded
fluctuations beside phi's background are the cell's, and its limits are
rehearsed as they stand (``benchmark/selftest/test_gw_family.py`` says
why of the one-chip cell), but for the numbers a CPU cannot hold at this
size, which ``benchmark/limits/rehearsal.json`` holds to the CPU's own
floor instead: ``stats_gap`` (a CPU sums 131,072 float32 values to
2e-4), ``hist_gap`` and ``hist_edge_gap`` (a handful of sites a bin),
``spectra_gap.scalar0``, ``.scalar1`` and ``.rho`` (a handful of modes
in the corner bins).
"""

import contextlib
import functools
import io
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

from benchmark import check, run  # noqa: E402

CELL = "preheat-gw-mesh4-f32.gw-coupled-run"
PATCH = {"grid_shape": [64, 64, 32],
         "box_dim": [64 * 5 / 384, 64 * 5 / 384, 32 * 5 / 384]}
COMPARED = {"field_gap", "a_gap", "hubble_gap", "constraint_per_step",
            "stats_gap", "hij_gap", "dhij_gap", "spectra_gap.scalar0",
            "spectra_gap.scalar1", "spectra_gap.rho", "spectra_gap.gw",
            "hist_gap", "hist_edge_gap", "spectra_nonfinite",
            "fallback_events", "compiled_in_window"}
REGISTER_NUMBERS = ("field_gap", "hij_gap", "dhij_gap")

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="the cell's mesh takes four devices")


@pytest.fixture(autouse=True)
def chip_precision(isolated_cache, monkeypatch, tmp_path):
    """The chip's 32-bit mode; the harness's compile cache placed from
    outside, as it asks, and its run directory (HDF5 file, profile) a
    test's own: every run empties the one in the checkout at its start,
    and other files rehearse in other workers at the same time."""
    monkeypatch.setattr(run, "SCRATCH", str(tmp_path / "benchmark_cache"))
    with jax.enable_x64(False):
        yield


def rehearse(patch=None, seed=2**31 + 7, **override):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", "1", "--rehearse",
                       "--config-override",
                       json.dumps(dict(PATCH, **override))], patch=patch)
    return rc, out.getvalue().strip().splitlines(), \
        err.getvalue().strip().splitlines()


def verdicts(err):
    """``{number: True | False}`` of the ``check`` lines a run prints for
    the numbers it compared."""
    return {ln.split()[1].rstrip(":"): ln.endswith(" ok")
            for ln in err if ln.startswith("check ")
            and "not compared" not in ln}


def test_the_cell_rehearses_correct_and_says_its_transform():
    rc, lines, err = rehearse()
    ok = verdicts(err)
    assert set(ok) == COMPARED
    assert rc == 0 and all(ok.values()), "\n".join(lines[-30:])
    assert '"failed": 0' in lines[-1]
    # what the outputs' transforms take on the mesh, one line a consumer
    plans = [ln for ln in lines if ln.startswith("[bench] spectra_plan")]
    assert len(plans) == 2, lines[:30]
    for plan, consumer in zip(sorted(plans), ("PowerSpectra", "Projector")):
        assert f"{consumer} on DFT (pencil)" in plan
        assert "real inverse matmul" in plan and "mesh (2, 2, 1)" in plan
        assert "3 + 3 transposes" in plan and " 0 bytes" not in plan
    # the host-span metrics of the traced line (device numbers are
    # never printed off the chip)
    for name in ("gw_spectra_ms", "spectra_ms", "output_other_ms",
                 "feedback_ms_per_step", "step_call_ms_per_step"):
        assert f'"{name}"' in lines[-1]
    assert "roofline" not in lines[-1]
    assert "collective_ms_per_step" not in lines[-1]


def no_source(system, driver):
    """The tensors stepped without their anisotropic stress."""
    sound = system.stepper._sij_eval
    system.stepper._sij_eval = lambda *a, **kw: 0 * sound(*a, **kw)


def unprojected(system, driver):
    """The gravitational-wave spectrum of ``dhijdt`` as it stands: the
    transverse-traceless projection left out."""
    system.observables()["projector"].transverse_traceless = \
        lambda hij_k, *a, **kw: hij_k


def local_wrap(monkeypatch):
    """Every chip steps its block as a periodic lattice of its own: the
    kernels built without slab edges, as on ``(1, 1, 1)``."""
    from pystella_tpu.ops.fused import FusedPreheatStepper
    monkeypatch.setattr(
        FusedPreheatStepper, "_halo_kw",
        property(lambda self: {"x_slab": False, "y_slab": False,
                               "interpret": self._interpret}))


@pytest.mark.parametrize("broken", ["bf16_carry", "no_source",
                                    "unprojected", "local_wrap"])
def test_a_broken_run_is_not_correct(monkeypatch, broken):
    patch, override = None, {}
    if broken == "bf16_carry":
        override = {"carry_dtype": "bfloat16"}
    elif broken == "local_wrap":
        local_wrap(monkeypatch)
    else:
        patch = {"no_source": no_source, "unprojected": unprojected}[broken]
    rc, lines, err = rehearse(patch, **override)
    ok = verdicts(err)
    assert rc == 1, "\n".join(lines[-30:])
    if broken == "bf16_carry":
        # the registers do not touch the energy, nor the outputs of the
        # state they reached
        for name in REGISTER_NUMBERS:
            assert not ok.pop(name), name
        assert all(ok.values()), ok
    elif broken == "no_source":
        assert ok["field_gap"] and ok["a_gap"] and ok["spectra_gap.rho"]
        assert not ok["hij_gap"] and not ok["dhij_gap"]
    elif broken == "unprojected":
        assert not ok.pop("spectra_gap.gw")
        assert all(ok.values()), ok
    else:
        # the rows beside a block's faces took the block's own far side
        assert not ok["field_gap"] and not ok["hij_gap"] \
            and not ok["dhij_gap"]


@pytest.mark.parametrize("level", ["bf16", "bf16_carry"])
def test_the_control_through_the_harness_comparison(level):
    """``control.py``'s readings for this family (the mesh references in
    bfloat16, and with bfloat16 registers only, in the program's place)
    under the names ``compare`` gives, judged by ``check.judge`` against
    the cell's limits as a run's numbers are: not ``correct``, by the
    fields and by both tensors; the reference twice reads zero."""
    row = _control_row()
    numbers = {"field_gap": row[level], "hij_gap": row[level + "_hij_gap"],
               "dhij_gap": row[level + "_dhij_gap"],
               "a_gap": row[level + "_a_gap"],
               "hubble_gap": row[level + "_hubble_gap"]}
    if level == "bf16":
        numbers.update({k[len("bf16_"):]: v for k, v in row.items()
                        if k.startswith(("bf16_spectra_gap.", "bf16_hist",
                                         "bf16_stats"))})
    judged = {name: ok for name, _, _, ok in check.judge(
        numbers, check.limits_for(CELL, rehearse=True))}
    for name in REGISTER_NUMBERS:
        assert judged[name] is False, (name, numbers)
    if level == "bf16":
        assert judged["a_gap"] is False and judged["hubble_gap"] is False
        assert judged["spectra_gap.gw"] is False
        assert judged["spectra_gap.scalar1"] is False
        # the mode powers alone in bfloat16 fail the GW spectrum too
        assert check.judge(
            {"spectra_gap.gw": row["bf16_power_spectra_gap.gw"]},
            check.limits_for(CELL, rehearse=True))[0][3] is False
    else:
        # the registers do not touch the energy
        assert judged["a_gap"] and judged["hubble_gap"]
    assert all(row[k] == 0.0 for k in row if k.startswith("f32_again"))


@functools.lru_cache(maxsize=None)
def _control_row():
    """One seed of the control, read once for both levels."""
    from benchmark import control
    row, = control.readings(CELL, seeds=[2**31 + 11], override=PATCH,
                            rehearse=True)
    return row


def _potential(f):
    return 0.5 * f[0] ** 2 + 0.25 * f[0] ** 2 * f[1] ** 2


@pytest.mark.parametrize("proc", [(2, 2, 1), (2, 1, 1)])
def test_sharded_preheat_stepper_matches_single(proc):
    """The coupled chunk of the scalar + tensor system (what the cell
    drives: single-stage energy kernels fed by exchanged slabs, energy
    sums reduced over the mesh) on ``proc`` against the ``(1, 1, 1)``
    stepper from the same state, in float64 to round-off: a reduced
    case each of ``tests/test_fused.py::
    test_fused_preheat_sharded_2d_matches_single`` and
    ``..._sharded_x_matches_single``, which are ``slow``."""
    import pystella_tpu as ps
    grid_shape = (16, 16, 16)
    h, dx, dt = 2, 0.3, 0.01
    rng = np.random.default_rng(10)
    with jax.enable_x64(True):
        state_h = {
            "f": rng.standard_normal((2,) + grid_shape),
            "dfdt": 0.1 * rng.standard_normal((2,) + grid_shape),
            "hij": 1e-3 * rng.standard_normal((6,) + grid_shape),
            "dhijdt": 1e-4 * rng.standard_normal((6,) + grid_shape)}
        sector = ps.ScalarSector(2, potential=_potential)
        gw = ps.TensorPerturbationSector([sector])
        results = {}
        for shape in ((1, 1, 1), proc):
            ndev = int(np.prod(shape))
            dp = ps.DomainDecomposition(shape, devices=jax.devices()[:ndev])
            fp = ps.FusedPreheatStepper(sector, gw, dp, grid_shape, dx, h,
                                        dtype=jnp.float64, bx=4, by=8)
            st = {k: dp.shard(jnp.asarray(v)) for k, v in state_h.items()}
            expand = ps.Expansion(1e-3, ps.LowStorageRK54)
            out = fp.coupled_multi_step(st, 1, expand, 0.0, dt)
            results[shape] = ({k: np.asarray(v) for k, v in out.items()},
                              float(expand.a))
        (ref, ref_a), (got, got_a) = results[(1, 1, 1)], results[proc]
        for name in state_h:
            assert np.allclose(got[name], ref[name], rtol=1e-12,
                               atol=1e-13), name
        assert abs(got_a - ref_a) / ref_a < 1e-13
