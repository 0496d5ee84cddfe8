"""The ``multigrid`` family driven through ``run.main`` off the chip: the
cell ``multigrid-512-f32.vcycle`` as ``BENCHMARK.json`` holds it, cut to a
32^3 patch of its lattice (the Pallas smoother in interpret mode), comes
out ``correct``; not ``correct`` with the sweeps of a smooth halved
underneath, nor with a cycle left out; the control's readings, put
through the harness's own comparison, miss the cell's limits; and a
program that does not report its levels' tiers (PR 32's parent) is
stopped in set-up. Run with the rest of ``benchmark/selftest``.

The cut keeps the cell's lattice spacing (box 10/16: ``dx`` = 10/512, so
the Laplacian's 1/dx^2, which sets the size of the seeded residual and of
float32's rounding in it, is the cell's) and takes fewer sites and
levels: 32^3, 16^3, 8^3, the coarsest the cell's own. A V(25, 50) cycle
takes the residual down by the same factor of 50 to 80 there as at 512^3
on the chip (``PERF.md`` section 2), so the cell's own limits are
rehearsed."""

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

CELL = "multigrid-512-f32.vcycle"
PATCH = {"grid_shape": [32, 32, 32], "box_dim": [10 / 16] * 3,
         "smoother": "pallas"}


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
    """``{number: True | False}`` of the ``check`` lines a run prints."""
    return {ln.split()[1].rstrip(":"): ln.endswith(" ok")
            for ln in err if ln.startswith("check ")}


def test_mg_family_runs_correct():
    rc, lines, err = rehearse()
    assert rc == 0, "\n".join(lines[-24:])
    ok = verdicts(err)
    assert set(ok) == {
        "solution_gap.f", "solution_gap.f2", "residual_gap",
        "residual_drop.f", "residual_drop.f2", "repeat_gap",
        "fallback_events", "compiled_in_window"}
    assert all(ok.values()), ok
    assert '"correct": true' in lines[-1]
    # one line a level, as the program's mg_level_plan events say
    plans = [ln for ln in lines if ln.startswith("[bench] mg_level_plan")]
    assert [ln.split("level ")[1].split(":")[0] for ln in plans] == [
        "(32, 32, 32)", "(16, 16, 16)", "(8, 8, 8)"]
    assert all(": streaming (bx, by) = (1, " in ln for ln in plans)
    # the host-span metrics of the traced line (device numbers are never
    # printed off the chip)
    assert '"step_call_ms_per_step"' in lines[-1]
    assert "roofline" not in lines[-1]


def test_mg_family_with_its_sweeps_halved_is_not_correct():
    """Half the sweeps in every smooth (V(12, 25) in place of V(25, 50)):
    the errors returned are still those of the unknowns returned, the
    solve is deterministic, and the residual stops a factor of a hundred
    short of what the configuration guarantees."""
    def patch(system, driver):
        sound = system.solver.smooth
        system.solver.smooth = (
            lambda level, fs, rhos, aux, iterations, decomp=None:
            sound(level, fs, rhos, aux, iterations // 2, decomp))

    rc, lines, err = rehearse(patch)
    assert rc == 1
    ok = verdicts(err)
    assert ok["residual_gap"] and ok["repeat_gap"] and ok["fallback_events"]
    for name in ("residual_drop.f", "residual_drop.f2", "solution_gap.f",
                 "solution_gap.f2"):
        assert not ok[name], name


def test_mg_family_on_the_xla_path_is_not_correct():
    """A level served by the XLA path though the kernels were asked for
    counts as a fallback; everything it computes is right."""
    def patch(system, driver):
        system.solver._pallas_level = lambda kind, level, decomp, dtype, \
            aux: system.solver._plan_level(
                kind, level, decomp, dtype, "xla", reason="patched out")

    rc, lines, err = rehearse(patch)
    assert rc == 1
    ok = verdicts(err)
    assert not ok.pop("fallback_events")
    assert all(ok.values()), ok
    assert any("xla patched out" in ln for ln in lines)


def test_the_control_through_the_harness_comparison():
    """``control.py``'s readings for this family under the names
    ``compare`` gives, judged by ``check.judge`` against the cell's limits
    as a run's numbers are. The reference in bfloat16 is not ``correct``
    by the unknowns, by the errors it returns and by the residual's drop;
    a cycle fewer and half the sweeps miss the drop; the reference twice
    reads zero and passes."""
    from benchmark import control
    row, = control.readings(CELL, seeds=[2**31 + 11], override=PATCH,
                            rehearse=True)
    limits = check.limits_for(CELL, rehearse=True)

    def judged(label):
        numbers = {k[len(label) + 1:]: v for k, v in row.items()
                   if k.startswith(label + "_")}
        numbers["residual_gap"] = max(numbers.pop("residual_gap.f"),
                                      numbers.pop("residual_gap.f2"))
        return {name: ok for name, _, _, ok in check.judge(numbers, limits)}

    assert all(judged("f32_again").values())
    assert row["f32_again_solution_gap.f"] == 0.0
    assert row["f32_again_solution_gap.f2"] == 0.0
    assert not any(judged("bf16").values()), judged("bf16")
    for label in ("cycles3", "sweeps"):
        ok = judged(label)
        assert not ok["residual_drop.f"] and not ok["residual_drop.f2"], ok
        assert ok["residual_gap"]


def test_a_program_that_does_not_report_its_levels_stops_in_set_up(
        monkeypatch):
    """PR 32's parent has ``multigrid/`` and no ``mg_level_plan``: given
    this cell it fails at once, before anything is built."""
    from pystella_tpu.obs import events
    kinds = events.registered_event_kinds()
    kinds.pop("mg_level_plan")
    monkeypatch.setattr(events, "registered_event_kinds", lambda: kinds)
    with pytest.raises(SystemExit, match="emits no mg_level_plan"):
        rehearse()
