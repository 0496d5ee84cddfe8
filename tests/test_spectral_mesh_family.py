"""The cell ``preheat-spectral-mesh4-f32.spectral-stage-loop`` off the
chip: upstream's ``--halo-shape 0`` run on the ``(2, 2, 1)`` mesh as
``BENCHMARK.json`` holds it, cut to a ``(64, 64, 32)`` patch of its
lattice on four virtual devices and driven through ``benchmark/run.main``.
A sound rehearsal is ``correct``; one bfloat16 pass in the program's
inverse transform underneath, bfloat16 RK registers underneath, and a
collocator on a transform that replicates are each not ``correct``; a
program whose ``spectral_plan`` says nothing of the mesh (PR 46's parent)
is stopped in set-up; and the control's readings, put through the
harness's own comparison, miss the cell's limits.

The cut keeps the cell's lattice spacing (``dx`` = 5/512), so ``k^2``
reaches the cell's and its limits are rehearsed as they stand: all but
``a_gap``'s, the gap between two float32 sums of the energy, 1.2e-8 over
a few 10^5 sites on a CPU and above what the control reads on the chip
(``benchmark/selftest/test_spectral_family.py`` says the same of the
one-chip cell), which is held here to the CPU's own floor.
"""

import contextlib
import io
import json
import os
import sys

import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import check, run  # noqa: E402

CELL = "preheat-spectral-mesh4-f32.spectral-stage-loop"
PATCH = {"grid_shape": [64, 64, 32],
         "box_dim": [64 * 5 / 512, 64 * 5 / 512, 32 * 5 / 512]}
COMPARED = {"field_gap", "a_gap", "constraint_per_step", "lap_gap",
            "grad_gap", "reference_roundtrip_gap", "fallback_events",
            "compiled_in_window"}
#: ``a_gap`` of a sound run at this size on a CPU reads 1.1e-8 to 1.3e-8
A_GAP_ON_A_CPU = 1e-7

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="the cell's mesh takes four devices")


@pytest.fixture(autouse=True)
def chip_precision(isolated_cache, monkeypatch, tmp_path):
    """The chip's 32-bit mode; the harness's compile cache placed from
    outside, as it asks, and its run directory (HDF5 file, profile) a
    test's own: every run empties the one in the checkout at its start,
    and ``tests/test_slab_family.py`` rehearses in another worker at the
    same time."""
    monkeypatch.setattr(run, "SCRATCH", str(tmp_path / "benchmark_cache"))
    with jax.enable_x64(False):
        yield


def rehearse(patch=None, seed=2**31 + 7, override=PATCH):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", "1", "--rehearse",
                       "--config-override", json.dumps(override)],
                      patch=patch)
    return rc, out.getvalue().strip().splitlines(), \
        err.getvalue().strip().splitlines()


def verdicts(err):
    ok = {ln.split()[1].rstrip(":"): ln.endswith(" ok")
          for ln in err if ln.startswith("check ")
          and "not compared" not in ln}
    a_gap = next(float(ln.split()[2]) for ln in err
                 if ln.startswith("check a_gap:"))
    ok["a_gap"] = a_gap < A_GAP_ON_A_CPU
    return ok


def test_the_cell_rehearses_correct_and_says_its_transform():
    rc, lines, err = rehearse()
    ok = verdicts(err)
    assert set(ok) == COMPARED
    assert all(ok.values()), "\n".join(lines[-24:])
    assert '"failed": 0' in lines[-1]
    # each field's own reading is printed beside the worst
    assert any(ln.startswith("check grad_gap.1:") for ln in err)
    # one line a built collocator, in set-up: the transform, the mesh,
    # and what a transform pair moves between chips
    (plan,) = [ln for ln in lines if ln.startswith("[bench] spectral_plan")]
    assert "inverse matmul" in plan and "mesh (2, 2, 1)" in plan
    assert "transposes a transform pair" in plan and " 0 bytes" not in plan
    for name in ("spectral_lap_ms_per_step", "feedback_ms_per_step",
                 "step_call_ms_per_step"):
        assert f'"{name}"' in lines[-1]
    assert "collective_ms_per_step" not in lines[-1]    # a device number


def lowered_products(monkeypatch):
    """The program's inverse transforms in one bfloat16 pass: what the
    TPU's default matmul precision would make of them."""
    import jax.numpy as jnp
    from pystella_tpu.fourier import dft

    class OnePass:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def einsum(sub, m, v, precision=None):
            return jnp.einsum(sub, m.astype(jnp.bfloat16),
                              v.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)

    monkeypatch.setattr(dft, "jnp", OnePass())


def bfloat16_registers(system, driver):
    """The stepper the window drives with its 2N-storage registers kept
    in bfloat16 between stages (the reference's ``bf16_carry``)."""
    import jax.numpy as jnp

    class Rounded(system.Stepper):
        def stage(self, s, carry, t, dt, rhs_args):
            y, k = super().stage(s, carry, t, dt, rhs_args)
            return y, jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16).astype(x.dtype), k)

    system.stepper = Rounded(system.stepper.rhs, dt=system.dt)


#: a lattice the mesh axes divide and the device count does not: the
#: declarative transform falls to its ``partial`` tier (each stage's long
#: axis shared by one mesh axis, the other replicating), and ``make_dft``
#: falls to the same class
NOT_BY_FOUR = {"grid_shape": [34, 34, 32],
               "box_dim": [34 * 5 / 512, 34 * 5 / 512, 32 * 5 / 512]}


@pytest.mark.parametrize("broken", ["matmul_bf16", "bf16_carry",
                                    "replicating"])
def test_a_run_one_step_down_is_not_correct(monkeypatch, broken):
    patch, override = None, PATCH
    if broken == "matmul_bf16":
        lowered_products(monkeypatch)
    elif broken == "bf16_carry":
        patch = bfloat16_registers
    else:
        override = NOT_BY_FOUR
    rc, lines, err = rehearse(patch, override=override)
    ok = verdicts(err)
    assert rc == 1, err
    if broken == "matmul_bf16":
        assert not ok["lap_gap"] and not ok["grad_gap"] \
            and not ok["field_gap"], ok
        assert ok["fallback_events"] and ok["reference_roundtrip_gap"]
    elif broken == "bf16_carry":
        assert not ok.pop("field_gap")
        assert all(ok.values()), ok
    else:
        # everything it computes is right; it holds more than its share
        assert not ok.pop("fallback_events")
        assert all(ok.values()), ok
        assert any("spectral_plan: partial transform" in ln
                   for ln in lines), lines[:20]


def test_a_plan_that_says_nothing_of_the_mesh_stops_in_set_up(monkeypatch):
    """PR 46's parent emits ``spectral_plan`` without ``proc_shape`` and
    the transposes: given this cell it fails at once, before anything is
    compiled, with the missing fields in its message."""
    from pystella_tpu.fourier import derivs
    emit = derivs._events.emit

    def old_emit(kind, **data):
        if kind == "spectral_plan":
            data = {k: v for k, v in data.items()
                    if k in ("scheme", "inverse", "grid_shape", "dtype",
                             "fields_a_call")}
        return emit(kind, **data)

    monkeypatch.setattr(derivs._events, "emit", old_emit)
    with pytest.raises(SystemExit, match="says nothing of the mesh"):
        rehearse()


def test_the_control_through_the_harness_comparison():
    """``control.py``'s readings for this family under the names
    ``compare`` gives, judged by ``check.judge`` against the cell's
    limits as a run's numbers are: one bfloat16 pass in the inverse is
    not ``correct`` by the fields, by both derivatives and by the round
    trip; bfloat16 registers by the fields alone; the reference twice
    reads zero."""
    from benchmark import control
    row, = control.readings(CELL, seeds=[2**31 + 11], override=PATCH,
                            rehearse=True)
    limits = check.limits_for(CELL, rehearse=True)

    def judged(numbers):
        return {name: ok for name, _, _, ok in check.judge(numbers, limits)}

    lowered = judged({
        "field_gap": row["matmul_bf16"],
        "lap_gap": max(row["matmul_bf16_lap_gap.0"],
                       row["matmul_bf16_lap_gap.1"]),
        "grad_gap": max(row["matmul_bf16_grad_gap.0"],
                        row["matmul_bf16_grad_gap.1"]),
        "reference_roundtrip_gap": row["matmul_bf16_roundtrip_gap"]})
    assert set(lowered.values()) == {False}, (lowered, row)
    carries = judged({"field_gap": row["bf16_carry"],
                      "a_gap": row["bf16_carry_a_gap"]})
    assert carries["field_gap"] is False and carries["a_gap"], row
    assert all(row[k] == 0.0 for k in row if k.startswith("f32_again"))
    assert judged({"reference_roundtrip_gap":
                   row["reference_roundtrip_gap"]})[
                       "reference_roundtrip_gap"]
