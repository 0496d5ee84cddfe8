"""The cell ``preheat-mesh4x-f32.fixed-bg`` off the chip: upstream's
example on a slab decomposition (``-proc 4 1 1``) as ``BENCHMARK.json``
holds it, cut to a ``(64, 16, 32)`` patch of its lattice on four virtual
devices and driven through ``benchmark/run.main`` with the halo-overlap
policy at its own default (the suite pins it off; a run's is ``auto``,
which is ON for a sharded mesh): it comes out ``correct`` on the split,
says so in its ``overlap_plan`` and ``block_choice`` lines, is not
``correct`` with bfloat16 RK registers underneath, and runs all the same
on a program that emits no ``overlap_plan`` event (PR 42's parent). Then
why the family ``benchmark/families/slab_preheat.py`` exists: the plain
reference cannot wrap an axis four chips share, and the family hands it
the same seeded state on another layout of the same chips.

The cut keeps the cell's lattice spacing (``dx`` = 5/512), so the time
step and the largest momentum are the cell's and its ``field_gap`` limit
is rehearsed as it stands.
"""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import check, run  # noqa: E402

CELL = "preheat-mesh4x-f32.fixed-bg"
PATCH = {"grid_shape": [64, 16, 32],
         "box_dim": [64 * 5 / 512, 16 * 5 / 512, 32 * 5 / 512]}

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="the cell's mesh takes four devices")


@pytest.fixture(autouse=True)
def default_policy(monkeypatch, isolated_cache):
    """The run's own halo-overlap policy and the chip's 32-bit mode;
    the harness's compile cache placed from outside, as it asks."""
    monkeypatch.delenv("PYSTELLA_HALO_OVERLAP", raising=False)
    with jax.enable_x64(False):
        yield


def rehearse(patch=None, seed=2**31 + 7):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", "1", "--rehearse",
                       "--config-override", json.dumps(PATCH)], patch=patch)
    return rc, out.getvalue().strip().splitlines(), \
        err.getvalue().strip().splitlines()


def verdicts(err):
    return {ln.split()[1].rstrip(":"): ln.endswith(" ok")
            for ln in err if ln.startswith("check ")
            and "not compared" not in ln}


def test_slab_cell_rehearses_correct_on_the_split():
    rc, lines, err = rehearse()
    ok = verdicts(err)
    assert rc == 0 and all(ok.values()), (ok, err)
    assert set(ok) == {"field_gap", "fallback_events", "compiled_in_window"}
    said = "\n".join(lines)
    assert "overlap_plan pair: split; interior [12, 16, 32]" in said
    assert "shell [2, 16, 32] (bx, by) = (2, 16)" in said
    for part in ("pair_interior", "pair_shell"):
        assert f"kernel {part}: StreamingStencil (bx, by) = (2, 16) " \
            "from split" in said, part
    assert "tier at multi_step: pair" in said


def test_bfloat16_registers_underneath_are_not_correct():
    """The cell's control, in the program's own place: the stepper the
    window drives built with ``carry_dtype=bfloat16``."""
    import jax.numpy as jnp

    def patch(system, driver):
        ps = system.ps
        system.stepper = ps.FusedScalarStepper(
            system.sector, system.decomp, system.grid_shape,
            system.lattice.dx, system.h, tableau=system.Stepper,
            dtype=system.dtype, dt=system.dt, donate=True,
            carry_dtype=jnp.bfloat16)

    rc, lines, err = rehearse(patch)
    ok = verdicts(err)
    assert rc == 1 and ok["field_gap"] is False, (ok, err)
    assert ok["fallback_events"] and ok["compiled_in_window"]


def test_a_program_without_plan_events_is_measured_all_the_same(
        monkeypatch):
    """PR 42's parent takes the same split and says nothing of it: the
    family prints no plan line and the run is ``correct``."""
    from pystella_tpu.ops import fused
    emit = fused._events.emit
    monkeypatch.setattr(
        fused._events, "emit",
        lambda kind, **data: None if kind == "overlap_plan"
        else emit(kind, **data))
    rc, lines, err = rehearse()
    assert rc == 0 and all(verdicts(err).values()), err
    assert not any("overlap_plan" in ln for ln in lines)


def test_the_controls_through_the_harness_comparison():
    """``control.py``'s readings for this family, judged by
    ``check.judge`` against the cell's limits as a run's numbers are:
    bfloat16 registers are not ``correct`` by the fields, bfloat16
    throughout far less, and the reference twice reads zero."""
    from benchmark import control
    row, = control.readings(CELL, seeds=[2**31 + 11], override=PATCH,
                            rehearse=True)
    limits = check.limits_for(CELL, rehearse=True)
    for name in ("bf16_carry", "bf16"):
        (_, value, limit, ok), = check.judge({"field_gap": row[name]},
                                             limits)
        assert ok is False, (name, value, limit)
    assert row["bf16"] > 100 * row["bf16_carry"]
    assert row["f32_again"] == 0.0


def test_the_plain_reference_cannot_wrap_an_axis_four_chips_share():
    """Why the family lays the reference's copy of the seeded state out
    anew: on the program's explicitly sharded ``(4, 1, 1)`` mesh
    ``jnp.pad(mode="wrap")`` slices ``h`` = 2 rows off an axis of four
    shards, which jax refuses; on ``(2, 2, 1)`` blocks of the same four
    devices (``slab_preheat.System.for_reference``) the reference's
    Laplacian is the periodic one. When this first assertion fails, the
    reference has learnt the layout and the family can go."""
    import pystella_tpu as ps
    from benchmark import reference
    from benchmark.families import slab_preheat
    grid = tuple(PATCH["grid_shape"])
    dx = (5 / 512,) * 3
    rng = np.random.default_rng(5)
    f = rng.standard_normal((2,) + grid).astype(np.float32)
    devices = jax.devices()[:4]
    slab = ps.DomainDecomposition((4, 1, 1), devices=devices)
    with pytest.raises(Exception, match="not divisible by mesh axes"):
        reference.laplacian(slab.shard(f), dx, 2)

    config = run.read_json("benchmark", "configs",
                           "preheat-mesh4x-f32.json")
    system = slab_preheat.System(dict(config, **PATCH), devices,
                                 stepper=False)
    laid = system.for_reference({"f": system.decomp.shard(f)})["f"]
    assert laid.sharding.spec == system._check_decomp.sharding(1).spec
    lap = np.asarray(reference.laplacian(laid, dx, 2))
    rows = {0: -5 / 2, 1: 4 / 3, 2: -1 / 12}
    want = sum(c / dx[0]**2 * (np.roll(f, s, ax) + np.roll(f, -s, ax))
               / (1 if s else 2)
               for s, c in rows.items() for ax in (1, 2, 3))
    assert np.max(np.abs(lap - want)) < 1e-5 * np.max(np.abs(want))
