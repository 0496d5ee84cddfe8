"""The family of upstream's ``-gws`` run on a mesh (``zachjweiner/pystella
examples/scalar_preheating.py -gws -proc 2 2 1``): ``gw_preheat``'s
system, loop body and numbers on a lattice that several chips share, so
that the tensors' windows are fed by exchanged slabs and every output's
transforms cross the mesh. What each part has to give the harness is in
``benchmark/README.md``, "What a family gives".

**Why a module of its own** (PR 50, ``preheat-gw-mesh4-f32``).
``gw_preheat``'s comparison cannot run on a mesh: its scalar output check
(``scalar_preheat.compare`` -> ``reference._mode_power``) stops with
``slicing on sharded dims where out dim (385) is not divisible by mesh
axes (2)`` (a (64, 64, 32) rehearsal on four virtual devices shows it
with 33), its ``-gws`` reference starts 24 lattice-sized arrays on the
first chip (21.7 GB at (768, 768, 384)), and both references' transforms
gather a component onto every chip. None of those files is this PR's to
edit, so here:

- the system and the answers kept are ``gw_preheat``'s (``System``,
  ``first_answers``, ``WATCHED`` plus the outputs' ``spectra_plan``),
  the loop body ``drivers/gw_coupled.py``, the traffic
  ``gw-coupled-run``;
- the reference is ``benchmark/gw_mesh_reference.py`` (which only this
  module imports): ``gw_reference``'s steps from zeros laid out like
  the fields, and both references' outputs through a transform that
  never gathers;
- the scalars' state, background and statistics are compared by
  ``scalar_preheat.compare`` as they stand (given a ``first`` without
  its output: those references take sharded operands as they are), the
  outputs and the tensors here;
- the guarantee the mesh adds, that **no output transform replicates a
  field**, is held by ``fallback_events``.

**The numbers compared** are ``gw_preheat``'s, under the same names, each
beside the limit of ``limits/<cell>.json``: ``field_gap``, ``a_gap``,
``hubble_gap``, ``constraint_per_step``, ``stats_gap``, ``hij_gap``,
``dhij_gap``, ``spectra_gap.scalar0/.scalar1/.rho/.gw``, ``hist_gap``,
``hist_edge_gap``, ``spectra_nonfinite``; and

``fallback_events``  a ``kernel_fallback`` or ``diverged`` event, or an
    output transform (the one ``PowerSpectra`` and ``Projector`` hold)
    on a tier under which a chip holds more than its share
    (``replicate``, ``partial``). Exact: limit 0. The tier is read off
    the objects, so a program that emits no ``spectra_plan`` event
    (PR 50's parent) is held to it too; where the event is there it is
    printed.
"""

import json
import os

import numpy as np

from benchmark.families import gw_preheat, scalar_preheat
from benchmark.families.gw_preheat import (  # noqa: F401
    System, TENSORS, first_answers)

#: program events the harness listens for
WATCHED = gw_preheat.WATCHED + ("spectra_plan",)
#: transform schemes under which a chip holds more than its share
REPLICATING = ("replicate", "partial")


def plan_line(d):
    return (f"spectra_plan: {d['consumer']} on {d['tier']} ({d['scheme']}), "
            f"real inverse {d['real_inverse']}, mesh "
            f"{tuple(d['proc_shape'])}: {d['transposes_forward']} + "
            f"{d['transposes_inverse']} transposes a transform pair, "
            f"{d['transpose_bytes']} bytes a component and chip each")


def replicating_transforms(system):
    """How many of the outputs' transforms replicate a field."""
    obs = system.observables()
    return sum(1 for name in ("spectra", "projector")
               if obs[name].fft.scheme in REPLICATING)


def on_mesh(system, array, outer=1):
    """A host array laid out as the program's own state is."""
    import jax
    return jax.device_put(array, system.decomp.sharding(outer))


def reference_state(system, seed, background, nsteps, dtype=None,
                    carry_dtype=None):
    """``gw_preheat.reference_state`` through the mesh reference."""
    from benchmark import gw_mesh_reference
    state, _, _ = scalar_preheat.System.initial_state(system, seed)
    return gw_mesh_reference.run(
        state.pop("f"), state.pop("dfdt"), nsteps, system.dt,
        system.physics(), system.dx, system.h, system.grid_size,
        background, dtype=dtype or system.dtype, carry_dtype=carry_dtype)


def spectrum_bins(system):
    from benchmark import reference
    return reference.SpectrumBins(system.grid_shape,
                                  system.config["box_dim"])


def reference_output(system, ref, a, hubble, bins=None, **kw):
    from benchmark import gw_mesh_reference
    return gw_mesh_reference.output(
        ref["f"], ref["dfdt"], a, hubble, system.physics(), system.dx,
        system.h, system.mpl, bins or spectrum_bins(system),
        system.hist_bins, **kw)


def reference_gw(system, dhijdt, hubble, bins=None, **kw):
    from benchmark import gw_mesh_reference
    return gw_mesh_reference.gw_spectrum(
        dhijdt, hubble, bins or spectrum_bins(system), system.grid_shape,
        system.config["box_dim"], system.dx, system.h, **kw)


def tensor_gaps(system, seed, first, background, nsteps):
    """``hij_gap`` and ``dhij_gap``: the ``-gws`` reference follows the
    first steps from the seed."""
    from benchmark import gw_mesh_reference
    ref, _, _ = reference_state(system, seed, background, nsteps)
    del ref["f"], ref["dfdt"]
    numbers = {}
    for name, key in zip(TENSORS, ("hij_gap", "dhij_gap")):
        got = gw_mesh_reference.at_home(on_mesh(system, first["state"][name]))
        numbers[key] = gw_mesh_reference.tensor_gap(got, ref.pop(name))
        del got
    return numbers


def output_gaps(system, first, keep):
    """The gaps of the output written for the state the first steps
    reached: the references' output of that state (read back from the
    host), the gravitational-wave spectrum's of its ``dhijdt``."""
    from benchmark import gw_mesh_reference, reference
    bins = spectrum_bins(system)
    got = {k: on_mesh(system, first["state"][k]) for k in ("f", "dfdt")}
    keep["output"] = reference_output(system, got, first["a"],
                                      first["hubble"], bins)
    del got
    numbers = {"spectra_gap." + name: gap for name, gap in
               reference.spectra_gaps(first["output"],
                                      keep["output"]).items()}
    numbers["hist_gap"], numbers["hist_edge_gap"] = reference.hist_gaps(
        first["output"]["hist"], keep["output"]["hist"])
    keep["gw"] = reference_gw(
        system, on_mesh(system, first["state"]["dhijdt"]), first["hubble"],
        bins)
    numbers["spectra_gap.gw"] = gw_mesh_reference.gw_gap(
        first["output"]["gw"], keep["gw"])
    return numbers


def compare(system, seed, first, background, nsteps, end, found, events,
            keep=None):
    """The scalar family's numbers for the scalars' state, background
    and statistics; the tensors'; and, where an output was kept, every
    spectrum and the histograms against the mesh reference's."""
    keep = {} if keep is None else keep
    scalars = dict(first, output=None,
                   state={k: v for k, v in first["state"].items()
                          if k not in TENSORS})
    numbers = scalar_preheat.compare(
        system, seed, scalars, background, nsteps, end, found, events, keep)
    numbers.update(tensor_gaps(system, seed, first, background, nsteps))
    if first.get("output"):
        numbers.update(output_gaps(system, first, keep))
    for e in events:
        if e["kind"] == "spectra_plan":
            print("[bench] " + plan_line(e["data"]), flush=True)
    numbers["fallback_events"] += replicating_transforms(system)
    return numbers


# -- the readings ``benchmark/control.py`` takes -----------------------------

def program_readings(cell_name, config, traffic, devices, seeds, outfile,
                     dump=None):
    """Sound runs, as ``gw_preheat.program_readings`` takes them: from
    each seed the program's first steps, statistics row and one output
    through the window's calls, against the mesh references."""
    system = System(config, devices, outfile=outfile)
    rows = []
    for seed in seeds:
        driver = scalar_preheat.new_driver(system, traffic, seed, True)
        background = driver.background()
        first, _ = first_answers(driver, "output" in traffic["schedule"])
        driver.state = driver.energy = None
        row, keep = {"seed": seed}, {}
        row.update(compare(system, seed, first, background,
                           driver.first_nsteps, {}, {}, [], keep))
        if dump:
            os.makedirs(dump, exist_ok=True)
            got = {k: first[k] for k in ("stats", "output") if first.get(k)}
            np.savez(os.path.join(dump, f"{cell_name}.{seed}.npz"),
                     **scalar_preheat.flat("got", got),
                     **scalar_preheat.flat("ref", keep))
        del first
        rows.append(row)
        print(json.dumps(row), flush=True)
    system.close()
    return rows


def control_readings(cell_name, config, traffic, devices, seeds, dump=None):
    """The control of ``gw_preheat.control_readings``, a row per seed,
    through the mesh references: the plain references one step below
    what the configuration states, put in the program's place and
    compared with the float32 references as the program is (``bf16``:
    state and registers in bfloat16; ``bf16_carry``: the registers
    alone; ``f32_again``: the reference twice, which has to read zero);
    for the outputs everything that goes into them rounded to bfloat16
    (``bf16``) and the mode powers alone (``bf16_power``). ``dump`` is
    not used: the rows hold every number."""
    import jax.numpy as jnp
    from benchmark import gw_mesh_reference, reference

    system = System(config, devices, stepper=False)
    with_output = "output" in traffic["schedule"]
    bins = spectrum_bins(system) if with_output else None
    rows = []
    for seed in seeds:
        driver = scalar_preheat.new_driver(system, traffic, seed, False)
        background, nsteps = driver.background(), driver.first_nsteps
        driver.state = driver.energy = None
        ref, a_ref, hubble = reference_state(system, seed, background,
                                             nsteps)
        row = {"seed": seed}
        for name, kw in (("f32_again", {}),
                         ("bf16_carry", {"carry_dtype": jnp.bfloat16}),
                         ("bf16", {"dtype": jnp.bfloat16})):
            got, a, hub = reference_state(system, seed, background, nsteps,
                                          **kw)
            row[name] = reference.field_gap(got, ref)
            for tensor, key in zip(TENSORS, ("_hij_gap", "_dhij_gap")):
                row[name + key] = gw_mesh_reference.tensor_gap(
                    got[tensor], ref[tensor])
            row[name + "_a_gap"] = abs(a - a_ref) / abs(a_ref - 1.0)
            row[name + "_hubble_gap"] = abs(hub / hubble - 1.0)
            del got
        if driver.stats_every:
            row["bf16_stats_gap"] = reference.stats_gap(
                reference.statistics(ref["f"].astype(jnp.bfloat16)),
                reference.statistics(ref["f"]))
        if with_output:
            ref_out = reference_output(system, ref, a_ref, hubble, bins)
            ref_gw = reference_gw(system, ref["dhijdt"], hubble, bins)
            for name, kw in (("bf16", {"dtype": jnp.bfloat16}),
                             ("bf16_power", {"power_dtype": jnp.bfloat16})):
                got = reference_output(system, ref, a_ref, hubble, bins,
                                       **kw)
                for key, gap in reference.spectra_gaps(got,
                                                       ref_out).items():
                    row[f"{name}_spectra_gap.{key}"] = gap
                row[name + "_hist_gap"], row[name + "_hist_edge_gap"] = \
                    reference.hist_gaps(got["hist"], ref_out["hist"])
                row[f"{name}_spectra_gap.gw"] = gw_mesh_reference.gw_gap(
                    reference_gw(system, ref["dhijdt"], hubble, bins, **kw),
                    ref_gw)
        del ref
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows
