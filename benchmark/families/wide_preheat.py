"""The family of upstream's ``--halo-shape 4`` run
(``zachjweiner/pystella examples/scalar_preheating.py`` with the
eighth-order rows of ``derivs.py:127-131,160-165``): ``scalar_preheat``'s
two fields under ``FusedScalarStepper`` and ``FiniteDifferencer``, both
at the stencil radius the configuration states, held to a plain
reference that has the rows of every radius the tables hold
(``benchmark/wide_reference.py``, which only this module imports;
``benchmark/reference.py``'s stop at h = 3). What each part has to give
the harness is in ``benchmark/README.md``, "What a family gives".

**New files only** (PR 40, ``preheat-h4-f32``): this module;
``benchmark/wide_reference.py``; ``configs/preheat-h4-f32.json``
(``"family": "wide_preheat"``, ``"halo_shape": 4``);
``limits/preheat-h4-f32.coupled-steps.json``;
``selftest/test_wide_family.py``. The loop body, the traffic file, the
kernel files and the metric files are the ones that were there.

**The system** is ``scalar_preheat.System`` as it is: it passes the
configuration's ``halo_shape`` to the stepper and to the differencer.
This class adds sight only: it stops in set-up where the program's
``block_choice`` events do not say the radius their kernels took (PR 40's
parent is such a program), because a run on a narrower stencil could then
not be told from this one.

**The numbers compared**, each beside a limit of its own
(``limits/preheat-h4-f32.coupled-steps.json``):

``field_gap``, ``a_gap``, ``hubble_gap``, ``constraint_per_step``,
``stats_gap``  as ``scalar_preheat``'s, against ``wide_reference.run``.
``lap_gap``, ``grad_gap``  the program's ``derivs.lap`` and
    ``derivs.grad`` of the state its first steps reached (taken and
    compared in set-up, outside the window and outside ``setup_s``)
    against the reference's of the same state: the
    largest ``max |got - ref| / max |ref|`` over the fields (and the
    three directions). They hold the rows of the configuration's radius
    at the cell's full width; ``grad`` is what an output calls. Each
    field's own reading is printed (``lap_gap.0`` ...), not compared.
``fallback_events``  a ``kernel_fallback`` or ``diverged`` event; a
    ``kernel_tier`` of ``multi_step`` that is not ``pair``; a coupled
    chunk (it builds the ``energy`` kernel for its odd stage) without its
    two ``coupled_pair`` kernels, i.e. one that fell to the single-stage
    kernel; a kernel whose ``block_choice`` names another radius than
    the configuration's. Exact: limit 0. The cell measures the pair
    kernels at its radius or it fails.
"""

import json
import time

from benchmark.families import scalar_preheat

#: program events the harness listens for
WATCHED = ("kernel_tier", "block_choice", "kernel_fallback", "diverged")
BAD_EVENTS = ("kernel_fallback", "diverged")


def kernel_line(d):
    """One line a built kernel, as its ``block_choice`` says."""
    return (f"built {d['kernel']}: (bx, by) = ({d['bx']}, {d['by']}), "
            f"grid {d['grid']}, h {d.get('h')}, taps {d.get('taps')}, "
            f"reread {d['reread']}, {d['source']}")


class System(scalar_preheat.System):
    """``scalar_preheat.System`` at the configuration's radius, built
    under a watch of its ``block_choice`` events."""

    def __init__(self, config, devices, outfile=None, stepper=True):
        from benchmark import wide_reference
        import pystella_tpu as ps
        if int(config["halo_shape"]) not in wide_reference.LAP_COEFS:
            raise ValueError(
                f"halo_shape {config['halo_shape']}: the plain reference "
                f"has the rows of {sorted(wide_reference.LAP_COEFS)}")
        seen = []
        log = ps.obs.get_log()
        tap = log.subscribe(
            lambda rec: seen.append(rec["data"])
            if rec["kind"] == "block_choice" else None)
        try:
            super().__init__(config, devices, outfile=outfile,
                             stepper=stepper)
        finally:
            log.unsubscribe(tap)
        if any("h" not in d or "taps" not in d for d in seen):
            # before anything is compiled: a program whose kernels do not
            # say which radius they took cannot be held to
            # ``fallback_events`` (PR 40's parent is one)
            raise SystemExit(
                "wide_preheat family: this pystella_tpu's block_choice "
                "events carry no stencil radius (ops/fused.py, PR 40), so "
                "a run on a narrower stencil could not be told from this "
                "one; nothing run")


def first_answers(driver, with_output):
    """``scalar_preheat.first_answers``, then the program's ``lap`` and
    ``grad`` of the state the first steps reached against the
    reference's of the same state, here and not after the window: what
    is kept is eight numbers, not the derivatives. (Kept on the host
    for ``compare``, their 4.3 GB beside the state's 2.1 slowed the
    first blocks of every window: ``PERF.md`` section 6, PR 40. The
    seconds, like the state's copy, are the check's and not the
    set-up's; the arrays are gone before the warm-up block.)"""
    first, t_snap = scalar_preheat.first_answers(driver, with_output)
    t0 = time.perf_counter()
    system, f = driver.sys, driver.state["f"]
    lap, grad = system.derivs.lap(f), system.derivs.grad(f)
    first["derivative_gaps"] = derivative_gaps(
        system, f, lambda c: lap[c], lambda c, mu: grad[c, mu])
    del lap, grad
    return first, t_snap + time.perf_counter() - t0


def reference_state(system, seed, background, nsteps, h=None, **kw):
    """The plain reference's state after ``nsteps`` from the state the
    seed gives (regenerated: the program consumed its copy), with the
    scale factor and the conformal Hubble rate it ended on; ``h`` is the
    radius it takes where that is not the configuration's (the
    control)."""
    from benchmark import wide_reference as reference
    state, _, _ = system.initial_state(seed)
    kw.setdefault("dtype", system.dtype)
    f, dfdt, a, hubble = reference.run(
        state.pop("f"), state.pop("dfdt"), nsteps, system.dt,
        system.physics(), system.dx, system.h if h is None else h,
        system.grid_size, background, **kw)
    return {"f": f, "dfdt": dfdt}, a, hubble


def derivative_gaps(system, f, lap, grad):
    """``{"lap_gap.<c>", "grad_gap.<c>"}``: ``lap(c)`` and ``grad(c,
    mu)`` (calls that give one component, and one direction of it, of
    somebody's derivatives of ``f``, on the device) against the
    reference's of the same ``f`` at the configuration's radius: one
    component, and one direction, in memory at a time; a gradient's
    worst direction is its component's reading."""
    from benchmark import wide_reference as reference
    out = {}
    for c in range(f.shape[0]):
        fc = f[c]
        (ref,) = reference.laplacian(fc[None], system.dx, system.h)
        out[f"lap_gap.{c}"] = reference.gap(lap(c), ref)
        del ref
        out[f"grad_gap.{c}"] = max(
            reference.gap(grad(c, mu),
                          reference.partial(fc, system.dx, system.h, mu))
            for mu in range(3))
    return out


def worst(numbers, name):
    return max(v for k, v in numbers.items() if k.startswith(name + "."))


def wrong_paths(system, events):
    """How many of the program's events say that the run did not take
    the kernels the cell is there to measure."""
    choices = [e["data"] for e in events if e["kind"] == "block_choice"]
    kinds = [d["kernel"] for d in choices]
    return (
        sum(1 for e in events if e["kind"] in BAD_EVENTS)
        + sum(1 for e in events if e["kind"] == "kernel_tier"
              and e["data"]["entrypoint"] == "multi_step"
              and e["data"]["tier"] != "pair")
        # a coupled chunk always builds `energy`, for its odd stage
        + int("energy" in kinds and kinds.count("coupled_pair") != 2)
        + sum(1 for d in choices if d.get("h") != system.h))


def compare(system, seed, first, background, nsteps, end, found, events,
            keep=None):
    """Every number compared, by name (the module docstring says what
    each is); the limits are applied by the caller."""
    import jax
    from benchmark import reference as scalar_reference
    for e in events:
        if e["kind"] == "block_choice":
            print("[bench] " + kernel_line(e["data"]), flush=True)
    ref, a_ref, hubble_ref = reference_state(system, seed, background,
                                             nsteps)
    sharding = ref["f"].sharding
    got = {k: jax.device_put(first["state"][k], sharding)
           for k in ("f", "dfdt")}
    numbers = {"field_gap": scalar_reference.field_gap(got, ref)}
    del ref
    if background["mode"] == "coupled":
        numbers["a_gap"] = abs(first["a"] - a_ref) / abs(a_ref - 1.0)
        numbers["hubble_gap"] = abs(first["hubble"] / hubble_ref - 1.0)
    if "constraint_per_step" in end:
        numbers["constraint_per_step"] = end["constraint_per_step"]
    if first.get("stats"):
        numbers["stats_gap"] = scalar_reference.stats_gap(
            first["stats"], scalar_reference.statistics(got["f"]))
    per_field = first["derivative_gaps"]
    del got
    numbers["lap_gap"] = worst(per_field, "lap_gap")
    numbers["grad_gap"] = worst(per_field, "grad_gap")
    numbers.update(per_field)
    numbers["fallback_events"] = wrong_paths(system, events)
    return numbers


# -- the readings ``benchmark/control.py`` takes -----------------------------

def program_readings(cell_name, config, traffic, devices, seeds, outfile,
                     dump=None):
    """Sound runs: from each seed the program's first steps, statistics
    row and its ``lap`` and ``grad`` of the state they reached, through
    the calls the window makes, against the plain reference: the numbers
    ``compare`` gives. The kernels are built once a process, at the
    first seed's first call, so the events of all seeds so far are what
    each seed's ``fallback_events`` is counted from."""
    import pystella_tpu as ps
    events = []
    log = ps.obs.get_log()
    tap = log.subscribe(lambda rec: events.append(rec)
                        if rec["kind"] in WATCHED else None)
    system = System(config, devices, outfile=outfile)
    rows = []
    try:
        for seed in seeds:
            driver = scalar_preheat.new_driver(system, traffic, seed, True)
            background = driver.background()
            first, _ = first_answers(driver, False)
            driver.state = driver.energy = None
            row = {"seed": seed}
            row.update(compare(system, seed, first, background,
                               driver.first_nsteps, {}, {}, events))
            del first
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        log.unsubscribe(tap)
    system.close()
    return rows


def control_readings(cell_name, config, traffic, devices, seeds, dump=None):
    """The control, a row per seed: the plain reference with something
    taken away, put in the program's place and compared with the
    reference as the program is. ``h3``: the rows one radius narrower
    than the configuration states, in the stepping (fields, ``a``,
    Hubble rate) and in ``lap`` and ``grad`` of the reference's own
    state. ``bf16_carry``: the RK registers alone in bfloat16, as the
    scalar family's. ``f32_again``: the reference twice, which has to
    read zero. One reading of each kind has to lie above the limit of
    the number it is made for."""
    import jax.numpy as jnp
    from benchmark import reference as scalar_reference
    from benchmark import wide_reference as reference

    system = System(config, devices, stepper=False)
    narrow = system.h - 1
    rows = []
    for seed in seeds:
        driver = scalar_preheat.new_driver(system, traffic, seed, False)
        background = driver.background()
        nsteps = driver.first_nsteps
        ref, a_ref, hubble_ref = reference_state(system, seed, background,
                                                 nsteps)
        row = {"seed": seed}
        for name, kw in (("f32_again", {}),
                         (f"h{narrow}", {"h": narrow}),
                         ("bf16_carry", {"carry_dtype": jnp.bfloat16})):
            got, a, hub = reference_state(system, seed, background,
                                          nsteps, **kw)
            row[name] = scalar_reference.field_gap(got, ref)
            if background["mode"] == "coupled":
                row[name + "_a_gap"] = abs(a - a_ref) / abs(a_ref - 1.0)
                row[name + "_hubble_gap"] = abs(hub / hubble_ref - 1.0)
            del got
        # the narrower rows' derivatives of the reference's own state
        f = ref["f"]
        for key, v in derivative_gaps(
                system, f,
                lambda c: reference.laplacian(f[c][None], system.dx,
                                              narrow)[0],
                lambda c, mu: reference.partial(f[c], system.dx, narrow,
                                                mu)).items():
            row[f"h{narrow}_{key}"] = v
        del ref, f
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows
