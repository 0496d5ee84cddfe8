"""How ``correct`` is decided: the numbers compared, each beside a limit
of its own (``benchmark/limits/``), and nothing else.

``field_gap``  the state the program reached by its first steps — from
    the seeded state, through the window's own call, on the object the
    window then drove — against the plain reference following the same
    steps: the largest difference over each field, relative to that
    field's largest value.
``a_gap``, ``hubble_gap``  the scale factor after those steps against
    the reference's, relative to how far it moved from 1, and the
    conformal Hubble rate against the reference's (cells with a
    self-consistent background).
``constraint_per_step``  the Friedmann constraint of the state the window
    ended on, over the steps taken since the seeded state (same cells): the
    float32 background drifts by a steady amount per step, so the number
    does not depend on how many steps a window holds.
``stats_gap``  the statistics row written for the state those steps
    reached (through the call that writes every row of the window)
    against the reference's moments of that state: mean and second
    moment.
``spectra_gap.<spectrum>``, ``hist_gap``, ``hist_edge_gap``  the output
    written for that same state, through the window's own output call,
    against the reference's output for it: every bin of each spectrum
    (``scalar0``, ``scalar1``, ``rho``); the cumulative distributions of
    the two histograms; their bin edges. The
    reference reads the state, the scale factor and the Hubble rate the
    program reached (``field_gap``, ``a_gap`` and ``hubble_gap`` hold
    those to the reference's own), so these gaps are the output code's
    alone: the float32 background's drift, a thousandth of the
    histogram's range in four steps, is not in them.
``spectra_nonfinite``  non-finite values in every spectrum the window
    wrote. ``fallback_events``  kernel or assembly fallbacks, or a
    divergence, recorded by the program. ``compiled_in_window``
    programs asked of the compiler inside the window. These three are
    exact: limit 0.

A number for which the cell's limits hold no entry is printed and not
compared (``PERF.md`` says which, and why).
"""

import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: program events the harness listens for
WATCHED = ("kernel_tier", "block_choice", "kernel_fallback",
           "assemble_fallback", "diverged")
BAD_EVENTS = ("kernel_fallback", "assemble_fallback", "diverged")


def limits_for(cell_name, rehearse=False):
    """``benchmark/limits/<cell>.json`` where a cell has its own, laid
    over ``benchmark/limits/default.json``; in a rehearsal
    ``limits/rehearsal.json`` is laid over both for the numbers the cell
    has a limit for (a CPU's arithmetic at 32^3 is not the chip's at
    512^3)."""
    def read(name):
        path = os.path.join(HERE, "limits", name + ".json")
        if not os.path.exists(path):
            return {}
        with open(path) as f:
            return json.load(f)["limits"]

    limits = dict(read("default"), **read(cell_name))
    if rehearse:
        limits.update({k: v for k, v in read("rehearsal").items()
                       if k in limits})
    return limits


def first_answers(driver, with_output):
    """Drive the first steps from the seeded state through the window's
    own calls, on the object the window will drive, and keep what they
    answer: the state (on the host), the scale factor, the statistics row
    of that state and, where the window holds outputs, one output on it.
    Returns ``(first, seconds spent copying the state)``."""
    import jax
    driver.first_steps()
    jax.block_until_ready(driver.state)
    if driver.stats_every and driver.last_stats is None:
        driver.write_stats()     # the first steps ended off the cadence
    t0 = time.perf_counter()
    first = {"state": jax.device_get(driver.state),
             "a": float(driver.expand.a),
             "hubble": float(driver.expand.hubble)}
    t_snap = time.perf_counter() - t0
    if with_output:
        driver.output()
    first["stats"], first["output"] = driver.last_stats, driver.last_output
    return first, t_snap


def reference_state(system, seed, background, nsteps, dtype=None,
                    carry_dtype=None):
    """The plain reference's state after ``nsteps`` from the state the
    seed gives (regenerated: the program consumed its copy), with the
    scale factor and the conformal Hubble rate it ended on."""
    from benchmark import reference
    state, _, _ = system.initial_state(seed)
    f, dfdt, a, hubble = reference.run(
        state.pop("f"), state.pop("dfdt"), nsteps, system.dt,
        system.physics(), system.dx, system.h, system.grid_size,
        background, dtype=dtype or system.dtype, carry_dtype=carry_dtype)
    return {"f": f, "dfdt": dfdt}, a, hubble


def reference_output(system, ref, a, hubble, bins=None, **kw):
    from benchmark import reference
    bins = bins or reference.SpectrumBins(system.grid_shape,
                                          system.config["box_dim"])
    return reference.output(
        ref["f"], ref["dfdt"], a, hubble, system.physics(), system.dx,
        system.h, system.mpl, bins, system.hist_bins, **kw)


def compare(system, seed, first, background, nsteps, end, found, events,
            compiled_in_window, keep=None):
    """Every number compared, by name; the limits are applied by the
    caller, which prints each beside its limit. ``keep``, a dict, is
    given the reference's statistics and output."""
    import jax
    from benchmark import reference
    ref, a_ref, hubble_ref = reference_state(system, seed, background,
                                             nsteps)
    sharding = ref["f"].sharding
    got = {k: jax.device_put(v, sharding) for k, v in first["state"].items()}
    numbers = {"field_gap": reference.field_gap(got, ref)}
    del ref
    if background["mode"] == "coupled":
        numbers["a_gap"] = abs(first["a"] - a_ref) / abs(a_ref - 1.0)
        numbers["hubble_gap"] = abs(first["hubble"] / hubble_ref - 1.0)
    if "constraint_per_step" in end:
        numbers["constraint_per_step"] = end["constraint_per_step"]
    keep = {} if keep is None else keep
    if first.get("stats"):
        keep["stats"] = reference.statistics(got["f"])
        numbers["stats_gap"] = reference.stats_gap(first["stats"],
                                                   keep["stats"])
    if first.get("output"):
        keep["output"] = reference_output(system, got, first["a"],
                                          first["hubble"])
        for name, gap in reference.spectra_gaps(first["output"],
                                                keep["output"]).items():
            numbers["spectra_gap." + name] = gap
        numbers["hist_gap"], numbers["hist_edge_gap"] = reference.hist_gaps(
            first["output"]["hist"], keep["output"]["hist"])
    del got
    if "spectra_nonfinite" in found:
        numbers["spectra_nonfinite"] = found["spectra_nonfinite"]
    numbers["fallback_events"] = sum(
        1 for e in events if e["kind"] in BAD_EVENTS)
    numbers["compiled_in_window"] = compiled_in_window
    return numbers
