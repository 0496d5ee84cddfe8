"""The ``wide_preheat`` family driven through ``run.main`` off the chip:
the cell ``preheat-h4-f32.coupled-steps`` as ``BENCHMARK.json`` holds it,
cut to a 32^3 patch of its lattice, comes out ``correct``; not
``correct`` with a stepper and a differencer of radius 3 handed to it,
nor with the coupled pair kernels taken away underneath (the chunk then
runs the single-stage ``energy`` kernel); the controls' readings, put
through the harness's own comparison, miss the cell's limits; and a
program whose ``block_choice`` events do not say the radius (PR 40's
parent) is stopped in set-up. Run with the rest of ``benchmark/selftest``.

The cut keeps the cell's lattice spacing (box 5/16: ``dx`` = 5/512, so
the time step and the largest momentum are the cell's, and with them how
far four steps move the fields and how far the sixth-order rows lie from
the eighth-order ones on the seeded spectrum) and takes fewer sites, so
the cell's own limits are rehearsed: all but ``a_gap``'s. That one is
the gap between two float32 sums of the energy over the lattice relative
to ``a - 1`` after four steps; over 32^3 sites on a CPU it reads
7e-5 to 1.3e-4, over the chip's 6e-5 limit, and
``limits/rehearsal.json`` has no entry for it (an edit: ``PERF.md``
section 7), so it is held here to the CPU's own floor."""

import contextlib
import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, run  # noqa: E402

CELL = "preheat-h4-f32.coupled-steps"
PATCH = {"grid_shape": [32, 32, 32], "box_dim": [5 / 16] * 3}
COMPARED = {"field_gap", "a_gap", "hubble_gap", "constraint_per_step",
            "stats_gap", "lap_gap", "grad_gap", "fallback_events",
            "compiled_in_window"}
#: ``a_gap`` of a sound run at 32^3 on a CPU (read 7e-5 to 1.3e-4)
A_GAP_AT_32 = 5e-4


def rehearse(patch=None, seed=2**31 + 7):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", "1", "--rehearse",
                       "--config-override", json.dumps(PATCH)], patch=patch)
    return rc, out.getvalue().strip().splitlines(), \
        err.getvalue().strip().splitlines()


def verdicts(err):
    """``{number: True | False}`` of the ``check`` lines a run compared;
    ``a_gap``'s against the CPU's floor (the module docstring says why)."""
    ok = {ln.split()[1].rstrip(":"): ln.endswith(" ok")
          for ln in err if ln.startswith("check ")
          and "not compared" not in ln}
    a_gap = next(float(ln.split()[2]) for ln in err
                 if ln.startswith("check a_gap:"))
    ok["a_gap"] = a_gap < A_GAP_AT_32
    return ok


def test_wide_family_runs_correct():
    rc, lines, err = rehearse()
    ok = verdicts(err)
    assert set(ok) == COMPARED
    assert all(ok.values()), "\n".join(lines[-24:])
    assert '"failed": 0' in lines[-1]
    # each field's own reading is printed beside the worst
    assert any(ln.startswith("check lap_gap.1:") for ln in err)
    # one line a built kernel, before the numbers, as its block_choice
    # says: the radius and the taps among them
    built = [ln for ln in lines if ln.startswith("[bench] built ")]
    kinds = [ln.split()[2].rstrip(":") for ln in built]
    assert kinds == ["stage", "pair", "coupled_pair", "coupled_pair",
                     "energy"], built
    assert all(", h 4, " in ln for ln in built)
    assert sum(", taps 50, " in ln for ln in built) == 3
    assert lines.index(built[-1]) < next(
        i for i, ln in enumerate(lines) if "check field_gap" in ln)
    # the host-span metrics of the traced line (device numbers are never
    # printed off the chip)
    assert '"step_call_ms_per_step"' in lines[-1]
    assert '"feedback_ms_per_step"' in lines[-1]
    assert "coupled_pair_roofline" not in lines[-1]


def test_a_narrower_stencil_underneath_is_not_correct():
    """A stepper and a differencer of radius 3 in the cell's system: the
    fields, both derivatives and the count of wrong paths (every kernel
    says ``h`` 3) each miss their limit."""
    def patch(system, driver):
        ps = system.ps
        system.stepper = ps.FusedScalarStepper(
            system.sector, system.decomp, system.grid_shape,
            system.lattice.dx, 3, tableau=system.Stepper,
            dtype=system.dtype, dt=system.dt, donate=True)
        system.derivs = ps.FiniteDifferencer(system.decomp, 3,
                                             system.lattice.dx)

    rc, lines, err = rehearse(patch)
    assert rc == 1
    ok = verdicts(err)
    for name in ("field_gap", "lap_gap", "grad_gap", "fallback_events"):
        assert not ok[name], (name, err)
    assert ok["constraint_per_step"] and ok["compiled_in_window"]


def test_a_program_without_its_coupled_pair_is_stopped():
    """The deferred-drag pair kernels refused underneath: the chunk runs
    the single-stage ``energy`` kernel, every number it computes is
    right, and the run is not ``correct`` by ``fallback_events`` alone."""
    def patch(system, driver):
        system.stepper._pes_tried, system.stepper._pes_call = True, None

    rc, lines, err = rehearse(patch)
    assert rc == 1
    ok = verdicts(err)
    assert not ok.pop("fallback_events")
    assert all(ok.values()), ok
    built = [ln for ln in lines if ln.startswith("[bench] built ")]
    assert not any("coupled_pair" in ln for ln in built)


def test_the_controls_through_the_harness_comparison():
    """``control.py``'s readings for this family under the names
    ``compare`` gives, judged by ``check.judge`` against the cell's limits
    as a run's numbers are. The sixth-order rows are not ``correct`` by
    the fields and by both derivatives, each a factor of three and more
    past its limit; bfloat16 registers by the fields alone; the reference
    twice reads zero."""
    from benchmark import control
    row, = control.readings(CELL, seeds=[2**31 + 11], override=PATCH,
                            rehearse=True)
    limits = check.limits_for(CELL, rehearse=True)

    def judged(numbers):
        return {name: ok for name, _, _, ok in check.judge(numbers, limits)}

    narrow = {"field_gap": row["h3"],
              "lap_gap": max(row["h3_lap_gap.0"], row["h3_lap_gap.1"]),
              "grad_gap": max(row["h3_grad_gap.0"], row["h3_grad_gap.1"])}
    for name, ok in judged(narrow).items():
        assert ok is False and narrow[name] > 3 * limits[name], (name, row)
    carries = judged({"field_gap": row["bf16_carry"],
                      "hubble_gap": row["bf16_carry_hubble_gap"]})
    # (no factor of three asked of it here: the largest difference over
    # 32^3 sites is smaller than over the cell's 512^3, where it is)
    assert carries["field_gap"] is False and carries["hubble_gap"], row
    assert all(row[k] == 0.0 for k in row if k.startswith("f32_again"))


def test_a_program_without_the_radius_in_block_choice_stops_in_set_up(
        monkeypatch):
    """PR 40's parent builds the same kernels and says neither ``h`` nor
    ``taps``: given this cell it fails at once, before anything is
    compiled."""
    from pystella_tpu.ops import fused

    class Quiet:
        def __getattr__(self, name):
            return getattr(fused._events, name)

        @staticmethod
        def emit(kind, **data):
            if kind == "block_choice":
                data.pop("h"), data.pop("taps")
            return events.emit(kind, **data)

    events = fused._events
    monkeypatch.setattr(fused, "_events", Quiet())
    with pytest.raises(SystemExit, match="carry no stencil radius"):
        rehearse()
