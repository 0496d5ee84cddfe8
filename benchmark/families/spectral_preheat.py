"""The family of upstream's ``--halo-shape 0`` run
(``zachjweiner/pystella examples/scalar_preheating.py:92-96``): the two
scalar fields of ``scalar_preheat`` with every derivative taken by
transforms (``SpectralCollocator`` over ``DFT(real_inverse="matmul")``)
and stepped by the generic ``LowStorageRK54(full_rhs)``, one XLA program
a stage, as ``examples/scalar_preheating.py`` builds them when
``--halo-shape 0`` (``--fused`` is refused there). What each part has to
give the harness is in ``benchmark/README.md``, "What a family gives".

**New files only** (PR 34, ``preheat-spectral-f32``): this module; its
plain reference ``benchmark/spectral_reference.py`` (which only this
module imports); the loop body ``benchmark/drivers/spectral_stage_loop.py``;
``traffic/spectral-stage-loop.json``; ``configs/preheat-spectral-f32.json``
(``"family": "spectral_preheat"``, ``"halo_shape": 0``);
``limits/preheat-spectral-f32.spectral-stage-loop.json``;
``metrics/spectral_lap_ms_per_step.json``;
``selftest/test_spectral_family.py``.

**The system** is ``scalar_preheat.System`` (lattice, sector, energy
reduction, seeded WKB state, observables) with the example's spectral
branch in place of the stencil and the fused stepper: the transform with
the inverse by matrix products, the collocator, ``full_rhs`` and
``LowStorageRK54(full_rhs, dt=dt)``, not donated, exactly as the example
passes them.

**The numbers compared**, each beside a limit of its own
(``limits/preheat-spectral-f32.spectral-stage-loop.json``):

``field_gap``, ``a_gap``, ``constraint_per_step``  as ``scalar_preheat``'s,
    against ``spectral_reference.run``.
``lap_gap``, ``grad_gap``  the program's ``derivs.lap`` and ``derivs.grad``
    of the state its first steps reached (taken in set-up, outside the
    window) against the reference's of the same state: the largest
    ``max |got - ref| / max |ref|`` over the fields (and the three
    directions). They hold ``-k^2``, ``i k_mu`` and the Nyquist rule at
    the cell's full width; ``grad`` is what an output calls. Each field's
    own reading is printed (``lap_gap.0`` ...), not compared.
``reference_roundtrip_gap``  the reference's own transforms on this
    device: ``ifftn(fftn(x))`` against the seeded fields.
``fallback_events``  a ``diverged`` event, or a ``spectral_plan`` event
    whose inverse is not ``matmul``. Exact: limit 0.
``stats_gap``, ``hubble_gap``  printed, not compared (as ``stage-loop``).
"""

import json
import time

import numpy as np

from benchmark.families import scalar_preheat

#: program events the harness listens for
WATCHED = ("spectral_plan", "diverged")


def plan_line(d):
    return (f"spectral_plan: {d['scheme']} transform of "
            f"{tuple(d['grid_shape'])} {d['dtype']}, inverse "
            f"{d['inverse']}, fields a call: {d['fields_a_call']}")


class System(scalar_preheat.System):
    """``scalar_preheat.System`` with the example's ``--halo-shape 0``
    derivatives and generic stepper."""

    def __init__(self, config, devices, outfile=None, stepper=True):
        from pystella_tpu.obs import events
        if "spectral_plan" not in events.registered_event_kinds():
            # before anything is built or compiled: a program that does
            # not say which inverse its collocator got cannot be held to
            # ``fallback_events`` (PR 34's parent is one)
            raise SystemExit(
                "spectral_preheat family: this pystella_tpu emits no "
                "spectral_plan event (fourier/derivs.py, PR 34), so a "
                "collocator on XLA's inverse real transform could not be "
                "told from one on the matrix products; nothing run")
        if int(config["halo_shape"]) != 0:
            raise ValueError("a spectral_preheat configuration sets "
                             "halo_shape 0")
        # the base class builds a stencil of the configuration's radius
        # before anything can replace it, and there is none of radius 0:
        # it gets radius 1, and the stencil is dropped unused below
        super().__init__(dict(config, halo_shape=1), devices,
                         outfile=outfile, stepper=False)
        ps = self.ps
        self.config, self.h = config, 0
        self.fft = ps.DFT(self.decomp, grid_shape=self.grid_shape,
                          dtype=self.dtype, real_inverse="matmul")
        seen = []
        log = ps.obs.get_log()
        tap = log.subscribe(
            lambda rec: seen.append(rec["data"])
            if rec["kind"] == "spectral_plan" else None)
        try:
            self.derivs = ps.SpectralCollocator(self.fft, self.lattice.dk)
        finally:
            log.unsubscribe(tap)
        for d in seen:
            print("[bench] " + plan_line(d), flush=True)
        sector_rhs = ps.compile_rhs_dict(self.sector.rhs_dict)

        def full_rhs(state, t, a, hubble):
            return sector_rhs(state, t, lap_f=self.derivs.lap(state["f"]),
                              a=a, hubble=hubble)

        self.stepper = self.Stepper(full_rhs, dt=self.dt) if stepper \
            else None


def first_answers(driver, with_output):
    """``scalar_preheat.first_answers``, then the program's ``lap`` and
    ``grad`` of the state the first steps reached, fetched to the host
    (their seconds, like the state's copy, are the check's and not the
    set-up's)."""
    import jax
    first, t_snap = scalar_preheat.first_answers(driver, with_output)
    t0 = time.perf_counter()
    derivs, f = driver.sys.derivs, driver.state["f"]
    first["lap"] = jax.device_get(derivs.lap(f))
    first["grad"] = jax.device_get(derivs.grad(f))
    return first, t_snap + time.perf_counter() - t0


def momenta(system):
    from benchmark import spectral_reference as reference
    return reference.momenta(system.grid_shape, system.config["box_dim"],
                             system.dtype)


def reference_state(system, seed, background, nsteps, **kw):
    """The plain reference's state after ``nsteps`` from the state the
    seed gives (regenerated), its scale factor and Hubble rate, and the
    round trip of its own transforms on the seeded fields."""
    from benchmark import spectral_reference as reference
    state, _, _ = system.initial_state(seed)
    roundtrip = reference.roundtrip_gap(state["f"])
    kw.setdefault("dtype", system.dtype)
    f, dfdt, a, hubble = reference.run(
        state.pop("f"), state.pop("dfdt"), nsteps, system.dt,
        system.physics(), momenta(system), system.grid_size, background,
        **kw)
    return {"f": f, "dfdt": dfdt}, a, hubble, roundtrip


def derivative_gaps(system, f, lap, grad):
    """``{"lap_gap.<c>", "grad_gap.<c>"}``: ``lap`` and ``grad`` (host
    arrays, as the program's collocator returned them for ``f``) against
    the reference's of the same ``f``, one component at a time."""
    import jax
    from benchmark import spectral_reference as reference
    ks = momenta(system)
    out = {}
    for c in range(f.shape[0]):
        fc = f[c]
        (ref,) = reference.laplacian(fc[None], ks)
        out[f"lap_gap.{c}"] = reference.gap(
            jax.device_put(lap[c], fc.sharding), ref)
        del ref
        refs = reference.gradient(fc, ks)
        out[f"grad_gap.{c}"] = max(
            reference.gap(jax.device_put(grad[c][mu], fc.sharding), r)
            for mu, r in enumerate(refs))
        del refs
    return out


def worst(numbers, name):
    return max(v for k, v in numbers.items() if k.startswith(name + "."))


def compare(system, seed, first, background, nsteps, end, found, events,
            keep=None):
    """Every number compared, by name (the module docstring says what
    each is); the limits are applied by the caller."""
    import jax
    from benchmark import reference as scalar_reference
    ref, a_ref, hubble_ref, roundtrip = reference_state(
        system, seed, background, nsteps)
    sharding = ref["f"].sharding
    got = {k: jax.device_put(first["state"][k], sharding)
           for k in ("f", "dfdt")}
    numbers = {"field_gap": scalar_reference.field_gap(got, ref)}
    del ref
    numbers["a_gap"] = abs(first["a"] - a_ref) / abs(a_ref - 1.0)
    numbers["hubble_gap"] = abs(first["hubble"] / hubble_ref - 1.0)
    if "constraint_per_step" in end:
        numbers["constraint_per_step"] = end["constraint_per_step"]
    if first.get("stats"):
        numbers["stats_gap"] = scalar_reference.stats_gap(
            first["stats"], scalar_reference.statistics(got["f"]))
    per_field = derivative_gaps(system, got["f"], first["lap"],
                                first["grad"])
    del got
    numbers["lap_gap"] = worst(per_field, "lap_gap")
    numbers["grad_gap"] = worst(per_field, "grad_gap")
    numbers.update(per_field)
    numbers["reference_roundtrip_gap"] = roundtrip
    plans = [e["data"] for e in events if e["kind"] == "spectral_plan"]
    numbers["fallback_events"] = (
        sum(1 for e in events if e["kind"] == "diverged")
        + sum(1 for d in plans if d["inverse"] != "matmul"))
    return numbers


# -- the readings ``benchmark/control.py`` takes -----------------------------

def program_readings(cell_name, config, traffic, devices, seeds, outfile,
                     dump=None):
    """Sound runs: from each seed the program's first steps and its
    ``lap`` and ``grad`` of the state they reached, through the calls the
    window makes, against the plain reference: the numbers ``compare``
    gives."""
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


def control_readings(cell_name, config, traffic, devices, seeds, dump=None):
    """The control, a row per seed: the plain reference one step down,
    put in the program's place and compared with the reference as the
    program is. ``matmul_bf16``: the inverse transform by real matrix
    products in one bfloat16 pass (the TPU's default matmul precision)
    where the configuration states full precision. ``bf16_carry``: the RK
    registers alone in bfloat16, as the scalar family's. ``f32_again``:
    the reference twice, which has to read zero. One reading of each
    kind has to lie above the limit of the number it is made for."""
    import jax.numpy as jnp
    from benchmark import reference as scalar_reference
    from benchmark import spectral_reference as reference

    system = System(config, devices, stepper=False)
    rows = []
    for seed in seeds:
        driver = scalar_preheat.new_driver(system, traffic, seed, False)
        background = driver.background()
        nsteps = driver.first_nsteps
        ref, a_ref, hubble_ref, roundtrip = reference_state(
            system, seed, background, nsteps)
        row = {"seed": seed, "reference_roundtrip_gap": roundtrip}
        for name, kw in (("f32_again", {}),
                         ("matmul_bf16", {"inverse": "matmul_bf16"}),
                         ("bf16_carry", {"carry_dtype": jnp.bfloat16})):
            got, a, hub, _ = reference_state(system, seed, background,
                                             nsteps, **kw)
            row[name] = scalar_reference.field_gap(got, ref)
            row[name + "_a_gap"] = abs(a - a_ref) / abs(a_ref - 1.0)
            row[name + "_hubble_gap"] = abs(hub / hubble_ref - 1.0)
            del got
        # the control's derivatives of the reference's own state, and
        # its round trip of the seeded fields
        ks = momenta(system)
        f = ref["f"]
        lap = np.stack([np.asarray(x) for x in reference.laplacian(
            f, ks, "matmul_bf16")])
        grad = np.stack([
            np.stack([np.asarray(x) for x in reference.gradient(
                f[c], ks, "matmul_bf16")]) for c in range(f.shape[0])])
        for key, v in derivative_gaps(system, f, lap, grad).items():
            row["matmul_bf16_" + key] = v
        del lap, grad
        seeded = system.initial_state(seed)[0]["f"]
        row["matmul_bf16_roundtrip_gap"] = reference.roundtrip_gap(
            seeded, "matmul_bf16")
        del ref, seeded
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows
