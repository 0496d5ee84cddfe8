"""The family of upstream's example on a slab decomposition
(``zachjweiner/pystella examples/scalar_preheating.py -proc N 1 1``):
``scalar_preheat``'s system, loop bodies, answers and plain reference
(``benchmark/reference.py``) as they are, on a mesh that shards x alone.
What each part has to give the harness is in ``benchmark/README.md``,
"What a family gives".

**Why a module of its own** (PR 42, ``preheat-mesh4x-f32``). The plain
reference wraps an axis with ``jnp.pad(mode="wrap")``, which slices
``h`` rows off each end; on the program's explicitly sharded mesh jax
refuses a slice of 2 rows from an axis four chips share ("out dim (2)
is not divisible by mesh axes (4)"), where ``preheat-mesh4-f32``'s two
chips an axis take it. ``benchmark/reference.py`` is not this PR's to
edit, so the reference here follows the same steps on the same seeded
state *laid out another way over the same chips*: ``(2, px / 2, 1)``
blocks, the layout on which the mesh cell's check already runs it at
the same bytes a chip. The layout is the check's alone: the seeded draw,
the program's steps and the window are on the configuration's
``proc_shape``.

**New files only**: this module, ``configs/preheat-mesh4x-f32.json``
(``"family": "slab_preheat"``), ``limits/preheat-mesh4x-f32.fixed-bg.json``,
``kernels/pallas_stencil_pair_interior.json``, ``..._pair_shell.json``
and the three ``metrics/overlap_*.json``. Loop body, traffic file and
reference are the ones that were there.

**The numbers compared** are ``scalar_preheat``'s, each beside the limit
of its name: ``field_gap`` (the one that decides on a fixed background),
``a_gap``, ``hubble_gap`` and ``constraint_per_step`` under a coupled
loop body, ``stats_gap`` where the traffic writes rows,
``fallback_events``. An output's check gathers whole fields
(``PERF.md`` section 7, row 1) and is refused here.

The program's ``overlap_plan`` events (which launch each sharded kernel
takes, the split or the single one, and why: PR 42) are printed with the
split's two ``block_choice`` lines; a program that emits none (PR 42's
parent) prints none and is measured all the same.
"""

import json

from benchmark.families import scalar_preheat

#: program events the harness listens for
WATCHED = scalar_preheat.WATCHED + ("overlap_plan",)


class System(scalar_preheat.System):
    """``scalar_preheat.System`` on an x-only mesh, with the layout the
    plain reference is given its copy of the seeded state in."""

    def __init__(self, config, devices, outfile=None, stepper=True):
        super().__init__(config, devices, outfile=outfile, stepper=stepper)
        px = self.proc_shape[0]
        if self.proc_shape[1:] != (1, 1) or px not in (2, 4):
            raise ValueError(
                f"slab_preheat: proc_shape {self.proc_shape} is not an "
                "x-only mesh of 2 or 4 chips")
        self._check_decomp = self.ps.DomainDecomposition(
            (2, px // 2, 1), devices=self.devices)

    def for_reference(self, state):
        """``state`` on the layout the reference can wrap (the module
        docstring says why); the arrays given are consumed."""
        sharding = self._check_decomp.sharding(1)
        return {k: self._jax.device_put(v, sharding, donate=True)
                for k, v in state.items()}


first_answers = scalar_preheat.first_answers


def plan_line(d):
    """One line an ``overlap_plan`` event."""
    if d["path"] != "split":
        return (f"overlap_plan {d['kernel']}: single launch "
                f"({d['reason']})")
    parts = "; ".join(
        f"{k} {d[k]['lattice']} (bx, by) = ({d[k]['bx']}, {d[k]['by']}) "
        f"grid {d[k]['grid']} reread {d[k]['reread']:.4f}"
        for k in ("interior", "shell"))
    return (f"overlap_plan {d['kernel']}: split; {parts}; stitch_bytes "
            f"{d['stitch_bytes']}")


class LaidOut:
    """``system`` as the plain reference is to see it: everything is the
    system's own but the seeded state, which comes laid out for the
    reference (``scalar_preheat.reference_state`` draws it through
    ``initial_state``)."""

    def __init__(self, system):
        self._system = system

    def __getattr__(self, name):
        return getattr(self._system, name)

    def initial_state(self, seed):
        state, expand, energy = self._system.initial_state(seed)
        return self._system.for_reference(state), expand, energy


def compare(system, seed, first, background, nsteps, end, found, events,
            keep=None):
    """``scalar_preheat.compare``'s numbers, the reference on its own
    layout; the program's plan lines first."""
    if first.get("output"):
        raise SystemExit("slab_preheat family: an output's check gathers "
                         "whole fields; no traffic with outputs here")
    for e in events:
        if e["kind"] == "overlap_plan":
            print("[bench] " + plan_line(e["data"]), flush=True)
    return scalar_preheat.compare(LaidOut(system), seed, first, background,
                                  nsteps, end, found, events, keep)


# -- the readings ``benchmark/control.py`` takes -----------------------------

def program_readings(cell_name, config, traffic, devices, seeds, outfile,
                     dump=None):
    """Sound runs: from each seed the program's first steps through the
    calls the window makes, against the plain reference: the numbers
    ``compare`` gives, a JSON row per seed."""
    system = System(config, devices, outfile=outfile)
    rows = []
    for seed in seeds:
        driver = scalar_preheat.new_driver(system, traffic, seed, True)
        background = driver.background()
        first, _ = first_answers(driver, False)
        driver.state = driver.energy = None
        row = {"seed": seed}
        row.update(compare(system, seed, first, background,
                           driver.first_nsteps, {}, {}, []))
        del first
        rows.append(row)
        print(json.dumps(row), flush=True)
    system.close()
    return rows


def control_readings(cell_name, config, traffic, devices, seeds, dump=None,
                     kinds=("f32_again", "bf16_carry", "bf16")):
    """The control, a row per seed: the plain reference one precision
    step below the configuration's float32, put in the program's place
    and compared with the float32 reference as the program is:
    ``bf16_carry`` (the RK registers alone in bfloat16: the step that
    would tempt a later PR), ``bf16`` (the stepping wholly in bfloat16),
    ``f32_again`` (the reference twice, which has to read zero)."""
    import jax.numpy as jnp
    from benchmark import reference

    plain = System(config, devices, stepper=False)
    system = LaidOut(plain)
    controls = {"f32_again": {}, "bf16_carry": {"carry_dtype": jnp.bfloat16},
                "bf16": {"dtype": jnp.bfloat16}}
    rows = []
    for seed in seeds:
        driver = scalar_preheat.new_driver(plain, traffic, seed, False)
        background = driver.background()
        nsteps = driver.first_nsteps
        ref, a_ref, hubble_ref = scalar_preheat.reference_state(
            system, seed, background, nsteps)
        row = {"seed": seed}
        for name in kinds:
            got, a, hub = scalar_preheat.reference_state(
                system, seed, background, nsteps, **controls[name])
            row[name] = reference.field_gap(got, ref)
            if background["mode"] == "coupled":
                row[name + "_a_gap"] = abs(a - a_ref) / abs(a_ref - 1.0)
                row[name + "_hubble_gap"] = abs(hub / hubble_ref - 1.0)
            del got
        del ref
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows
