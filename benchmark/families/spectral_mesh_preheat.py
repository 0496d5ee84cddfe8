"""The family of upstream's ``--halo-shape 0`` run on a mesh
(``zachjweiner/pystella examples/scalar_preheating.py:92-96 --halo-shape
0 -proc 2 2 1``; upstream's transform there is ``mpi4py-fft``'s pencil
``PFFT``, ``fourier/dft.py:391-417``): ``spectral_preheat``'s system,
loop body and numbers, on a lattice that several chips share, so that
every transform crosses the mesh. What each part has to give the
harness is in ``benchmark/README.md``, "What a family gives".

**Why a module of its own** (PR 46, ``preheat-spectral-mesh4-f32``).
``spectral_preheat``'s plain reference takes ``jnp.fft.fftn`` of a whole
component, which on a sharded component gathers it onto every chip; its
check fetches the program's ``grad`` of both fields to the host whole
(12.9 GB at (1024, 1024, 512)); and its system builds ``ps.DFT`` where
the example, on a mesh, builds what ``make_dft`` picks
(:meth:`System.transform`). None of those files is this PR's to edit,
so here:

- the reference is ``benchmark/spectral_mesh_reference.py`` (which only
  this module imports): the same derivatives and steps with a transform
  that never gathers;
- the derivative check is made in set-up, on the device, a component
  and a direction at a time, and eight numbers are kept
  (``wide_preheat`` does the same): the program's ``lap`` of both
  fields, then ``grad`` a field at a time through the same
  ``derivs.grad`` (both fields at once are 9.7 GB a chip by the
  compiler's account on ``PencilFFT`` and 10.8 on ``ps.DFT``, beside a
  state of 2.1 and a reference at work);
- the guarantee the configuration adds, that **no transform replicates
  a field**, is held by ``fallback_events``.

**New files only**: this module, ``benchmark/spectral_mesh_reference.py``,
``configs/preheat-spectral-mesh4-f32.json`` (``"family":
"spectral_mesh_preheat"``) and
``limits/preheat-spectral-mesh4-f32.spectral-stage-loop.json``. Loop
body (``drivers/spectral_stage_loop.py``), traffic file and metric files
are ``preheat-spectral-f32``'s.

**The numbers compared** are ``spectral_preheat``'s, each beside the
limit of its name: ``field_gap``, ``a_gap``, ``constraint_per_step``,
``lap_gap``, ``grad_gap``, ``reference_roundtrip_gap``; and

``fallback_events``  a ``diverged`` event; a ``spectral_plan`` whose
    inverse is not ``matmul``, whose ``scheme`` replicates (``replicate``:
    every chip holds and transforms the whole field; ``partial``: one
    mesh axis' worth of it), or whose ``proc_shape`` is not the
    configuration's; or no ``spectral_plan`` at all. Exact: limit 0.

A program whose ``spectral_plan`` says nothing of the mesh (PR 46's
parent) cannot be held to that and is stopped in set-up, before
anything is compiled.
"""

import functools
import json
import time

from benchmark.families import scalar_preheat, spectral_preheat

#: program events the harness listens for
WATCHED = spectral_preheat.WATCHED
#: transform schemes under which a chip holds more than its share
REPLICATING = ("replicate", "partial")
#: what a ``spectral_plan`` event says of the mesh since PR 46
MESH_FIELDS = ("proc_shape", "transposes_forward", "transposes_inverse",
               "transpose_bytes")


def plan_line(d):
    return (spectral_preheat.plan_line(d)
            + f"; mesh {tuple(d['proc_shape'])}: "
            f"{d['transposes_forward']} + {d['transposes_inverse']} "
            f"transposes a transform pair, {d['transpose_bytes']} bytes "
            "a field and chip each")


class System(scalar_preheat.System):
    """``scalar_preheat.System`` with the example's ``--halo-shape 0``
    branch as it is built on a mesh: the transform, the collocator and
    the generic stepper, not donated."""

    def __init__(self, config, devices, outfile=None, stepper=True):
        if int(config["halo_shape"]) != 0:
            raise ValueError("a spectral_mesh_preheat configuration sets "
                             "halo_shape 0")
        # there is no stencil of radius 0 for the base class to build:
        # it gets radius 1, and the stencil is dropped unused below
        super().__init__(dict(config, halo_shape=1), devices,
                         outfile=outfile, stepper=False)
        ps = self.ps
        self.config, self.h = config, 0
        self.fft = self.transform()
        seen = []
        log = ps.obs.get_log()
        tap = log.subscribe(
            lambda rec: seen.append(rec["data"])
            if rec["kind"] == "spectral_plan" else None)
        try:
            self.derivs = ps.SpectralCollocator(self.fft, self.lattice.dk)
        finally:
            log.unsubscribe(tap)
        if not seen or any(k not in d for d in seen for k in MESH_FIELDS):
            # before anything is compiled: a program that does not say
            # on which mesh its collocator's transform runs, or what it
            # moves between chips, cannot be held to "no transform
            # replicates a field" (PR 46's parent is one)
            raise SystemExit(
                "spectral_mesh_preheat family: this pystella_tpu's "
                "spectral_plan event says nothing of the mesh ("
                + ", ".join(MESH_FIELDS) + ": fourier/derivs.py, PR 46), "
                "so a transform that replicates a field could not be told "
                "from one that does not; nothing run")
        #: what the collocator said of its transform, for the readings
        #: ``control.py`` takes (a run's come through the harness)
        self.plans = [{"kind": "spectral_plan", "data": d} for d in seen]
        for d in seen:
            print("[bench] " + plan_line(d), flush=True)
        sector_rhs = ps.compile_rhs_dict(self.sector.rhs_dict)

        def full_rhs(state, t, a, hubble):
            return sector_rhs(state, t, lap_f=self.derivs.lap(state["f"]),
                              a=a, hubble=hubble)

        self.stepper = self.Stepper(full_rhs, dt=self.dt) if stepper \
            else None

    def transform(self):
        """The transform ``examples/scalar_preheating.py`` builds for
        ``--halo-shape 0`` on a mesh: the planner's choice
        (``PencilFFT`` where the lattice's x and y divide by the chips,
        else ``ps.DFT``'s tiers), the inverse by matrix products. Both
        tiers' readings at the cell's size are in ``PERF.md`` section 6,
        PR 46."""
        return self.ps.make_dft(self.decomp, grid_shape=self.grid_shape,
                                dtype=self.dtype, real_inverse="matmul")


def momenta(system):
    from benchmark import spectral_mesh_reference as reference
    return reference.momenta(system.grid_shape, system.config["box_dim"],
                             system.dtype)


def derivative_gaps(system, f, lap, grad):
    """``{"lap_gap.<c>", "grad_gap.<c>"}``: ``lap(c)`` and ``grad(c,
    mu)`` (calls that give one component, and one direction of it, of
    somebody's derivatives of ``f``, on the device) against the
    reference's of the same ``f``: one component, and one direction, in
    memory at a time; a gradient's worst direction is its component's
    reading."""
    from benchmark import spectral_mesh_reference as reference
    ks = momenta(system)
    out = {}
    for c in range(f.shape[0]):
        fc = reference.at_home(f[c])
        (ref,) = reference.laplacian(f[c:c + 1], ks)
        out[f"lap_gap.{c}"] = reference.gap(reference.at_home(lap(c)), ref)
        del ref
        out[f"grad_gap.{c}"] = max(
            reference.gap(reference.at_home(grad(c, mu)),
                          reference.partial_derivative(fc, ks, mu))
            for mu in range(3))
    return out


def program_gaps(system, f):
    """:func:`derivative_gaps` of the program's own collocator: ``lap``
    of both fields, then ``grad`` a field at a time through the same
    ``derivs.grad`` (the module docstring says why)."""
    derivs = system.derivs
    lap = derivs.lap(f)

    @functools.lru_cache(maxsize=1)
    def grad_of(c):
        return derivs.grad(f[c:c + 1])[0]

    return derivative_gaps(system, f, lambda c: lap[c],
                           lambda c, mu: grad_of(c)[mu])


def first_answers(driver, with_output):
    """``scalar_preheat.first_answers``, then the program's ``lap`` and
    ``grad`` of the state the first steps reached against the
    reference's of the same state, here and on the device: what is kept
    is eight numbers, not the derivatives. The seconds, like the state's
    copy, are the check's and not the set-up's; the arrays are gone
    before the warm-up block."""
    first, t_snap = scalar_preheat.first_answers(driver, with_output)
    t0 = time.perf_counter()
    first["derivative_gaps"] = program_gaps(driver.sys, driver.state["f"])
    return first, t_snap + time.perf_counter() - t0


def reference_state(system, seed, background, nsteps, **kw):
    """The plain reference's state after ``nsteps`` from the state the
    seed gives (regenerated), its scale factor and Hubble rate, and the
    round trip of its own transforms on the seeded fields."""
    from benchmark import spectral_mesh_reference as reference
    state, _, _ = system.initial_state(seed)
    roundtrip = reference.roundtrip_gap(state["f"])
    kw.setdefault("dtype", system.dtype)
    f, dfdt, a, hubble = reference.run(
        state.pop("f"), state.pop("dfdt"), nsteps, system.dt,
        system.physics(), momenta(system), system.grid_size, background,
        **kw)
    return {"f": f, "dfdt": dfdt}, a, hubble, roundtrip


def wrong_transforms(system, events):
    """How many of the program's events say that the run did not take
    the transforms the configuration states."""
    plans = [e["data"] for e in events if e["kind"] == "spectral_plan"]
    mesh = [int(n) for n in system.proc_shape]
    return (
        sum(1 for e in events if e["kind"] == "diverged")
        + int(not plans)
        + sum(1 for d in plans if d["inverse"] != "matmul")
        + sum(1 for d in plans if d["scheme"] in REPLICATING)
        + sum(1 for d in plans if list(d["proc_shape"]) != mesh))


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
    del got
    per_field = first["derivative_gaps"]
    numbers["lap_gap"] = spectral_preheat.worst(per_field, "lap_gap")
    numbers["grad_gap"] = spectral_preheat.worst(per_field, "grad_gap")
    numbers.update(per_field)
    numbers["reference_roundtrip_gap"] = roundtrip
    numbers["fallback_events"] = wrong_transforms(system, events)
    return numbers


# -- the readings ``benchmark/control.py`` takes -----------------------------

def program_readings(cell_name, config, traffic, devices, seeds, outfile,
                     dump=None):
    """Sound runs: from each seed the program's first steps and its
    ``lap`` and ``grad`` of the state they reached, through the calls the
    window makes, against the plain reference: the numbers ``compare``
    gives, a JSON row per seed."""
    system = System(config, devices, outfile=outfile)
    rows = []
    for seed in seeds:
        driver = scalar_preheat.new_driver(system, traffic, seed, True)
        background = driver.background()
        first, _ = first_answers(driver, False)
        driver.state = driver.energy = None
        row = {"seed": seed}
        row.update(compare(system, seed, first, background,
                           driver.first_nsteps, {}, {}, system.plans))
        del first
        rows.append(row)
        print(json.dumps(row), flush=True)
    system.close()
    return rows


def control_readings(cell_name, config, traffic, devices, seeds, dump=None):
    """The control, a row per seed: the plain reference one step down,
    put in the program's place and compared with the reference as the
    program is (``spectral_preheat``'s three: ``matmul_bf16``, the
    inverse transform by real matrix products in one bfloat16 pass where
    the configuration states full precision; ``bf16_carry``, the RK
    registers alone in bfloat16; ``f32_again``, the reference twice,
    which has to read zero), the derivatives a component and a direction
    at a time. One reading of each kind has to lie above the limit of
    the number it is made for."""
    import jax.numpy as jnp
    from benchmark import reference as scalar_reference
    from benchmark import spectral_mesh_reference as reference

    system = System(config, devices, stepper=False)
    ks = momenta(system)
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
        f = ref["f"]
        gaps = derivative_gaps(
            system, f,
            lambda c: reference.laplacian(f[c:c + 1], ks,
                                          "matmul_bf16")[0],
            lambda c, mu: reference.partial_derivative(
                f[c], ks, mu, "matmul_bf16"))
        for key, v in gaps.items():
            row["matmul_bf16_" + key] = v
        seeded = system.initial_state(seed)[0]["f"]
        row["matmul_bf16_roundtrip_gap"] = reference.roundtrip_gap(
            seeded, "matmul_bf16")
        del ref, f, seeded
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows
