"""The family of upstream's multigrid benchmark
(``zachjweiner/pystella test/test_multigrid.py:42-106``): a
``FullApproximationScheme`` over a ``NewtonIterator`` solving Poisson
``lap f = rho`` and Helmholtz ``lap f2 - f2 = rho2`` side by side on a
periodic box, the default cycle V(25, 50) to depth ``log2(N/8)``. What
each part has to give the harness is in ``benchmark/README.md``, "What a
family gives".

**New files only** (PR 32, ``multigrid-512-f32``): this module; its plain
reference ``benchmark/mg_reference.py`` (which only this module imports);
the loop body ``benchmark/drivers/mg_solve.py``; ``traffic/vcycle.json``;
``configs/multigrid-512-f32.json`` (``"family": "multigrid"``);
``limits/multigrid-512-f32.vcycle.json``;
``kernels/pallas_stencil_mg_smooth.json`` and
``kernels/pallas_stencil_mg_residual.json`` with
``metrics/mg_smooth_roofline.json`` and ``metrics/mg_residual_roofline.json``;
``selftest/test_mg_family.py``.

**The system** is built through ``pystella_tpu``'s public API exactly as
``tests/test_multigrid.py::test_multigrid`` builds it:
``NewtonIterator(decomp, problems, halo_shape, dtype,
fixed_parameters=dict(omega=...))`` and
``FullApproximationScheme(solver=..., halo_shape=...)``, called with no
``cycle`` (the default is upstream's) and with the smoother the code
picks for the backend. The configuration's ``cycle``, ``nu`` and
``depth`` state what that default is (a configuration that names another
cycle or operator is refused; ``tests/test_mg_reference.py`` holds the
file to the program's default). The seeded unknowns and sources are
upstream's: uniform in [0, 1) with the mean taken off, drawn on the host
from the seed.

**The numbers compared**, each beside a limit of its own
(``benchmark/limits/``), all of the set-up's solve (``cycles_per_solve``
cycles from the seeded arrays, through the window's own calls):

``solution_gap.f``, ``solution_gap.f2``  the unknowns it reached against
    the plain reference's after the same cycles: the largest difference
    over the largest value of the reference's. Poisson's with the means
    taken off both: on a periodic box its solution is free up to a
    constant that no sweep pins.
``residual_gap``  the error norms the last call returned for the finest
    level after its last smooth (L-infinity and L2 of ``rho - L f``, both
    unknowns) against the reference's operator on the program's own
    solution: the largest relative difference of the four. It holds the
    returned errors to be those of the returned unknowns.
``residual_drop.f``, ``residual_drop.f2``  the L2 residual the last call
    returned after its last smooth over the one the first call returned
    before its first: what the solve is for, and the configuration's
    guarantee. Fewer cycles or fewer sweeps read higher.
``repeat_gap``  the largest absolute difference between the unknowns of
    the window's last solve and those of the set-up's: every block is
    the same solve of the same arrays. Exact: limit 0.
``fallback_events``  a ``kernel_fallback``, or a level on the XLA path
    though the Pallas smoother was asked for (``mg_level_plan`` with tier
    ``xla`` under ``smoother="pallas"``). Exact: limit 0.
"""

import json
import time

import numpy as np

#: program events the harness listens for
WATCHED = ("mg_level_plan", "kernel_fallback")

#: the problems: unknown, source, and the mass of ``L f = lap f - mass f``
PROBLEMS = (("f", "rho", 0), ("f2", "rho2", 1))


class System:
    """The solver and scheme of a configuration, and its seeded arrays."""

    def __init__(self, config, devices, outfile=None):
        import pystella_tpu as ps
        from pystella_tpu import multigrid
        from pystella_tpu.obs import events

        if "mg_level_plan" not in events.registered_event_kinds():
            # before anything is built or compiled: a program that does
            # not say which tier serves each level cannot be held to
            # ``fallback_events`` (PR 32's parent is one)
            raise SystemExit(
                "multigrid family: this pystella_tpu emits no "
                "mg_level_plan event (multigrid/relax.py, PR 32), so a "
                "level on the XLA path could not be told from one on "
                "the kernels; nothing run")
        self.ps, self.config = ps, config
        self.grid_shape = tuple(config["grid_shape"])
        self.dtype = np.dtype(config["dtype"])
        self.h = int(config["halo_shape"])
        self.devices = list(devices)[:1]
        if tuple(config["proc_shape"]) != (1, 1, 1):
            raise ValueError("the multigrid family is cut to one chip")
        self.local_shape = self.grid_shape
        self.grid_size = float(np.prod(self.grid_shape))
        box = tuple(config["box_dim"])
        if len(set(self.grid_shape)) != 1 or len(set(box)) != 1:
            raise ValueError("the plain reference knows cubes only")
        self.dx = float(box[0]) / self.grid_shape[0]
        self.cycles_per_solve = int(config["cycles_per_solve"])
        #: upstream's default cycle, typed in for the reference: the cell
        #: passes no ``cycle``, so a program whose default is another
        #: misses ``solution_gap``. What the file states of it is held
        #: true by ``tests/test_mg_reference.py`` (its ``depth`` is that
        #: of its own lattice; a rehearsal's smaller lattice has fewer
        #: levels)
        self.depth = max(1, int(np.log2(min(self.grid_shape) / 8)))
        self.nu = (25, 50)
        stated = (config["cycle"], tuple(config["nu"]), config["solver"],
                  config["scheme"], config["restriction"],
                  config["interpolation"])
        if stated != ("V", self.nu, "NewtonIterator",
                      "FullApproximationScheme", "FullWeighting",
                      "LinearInterpolation"):
            raise ValueError(
                f"the configuration states {stated}; this family drives "
                f"the default V{self.nu} cycle of FullApproximationScheme "
                "over NewtonIterator with FullWeighting and "
                "LinearInterpolation, and its reference knows no other")
        self.decomp = ps.DomainDecomposition((1, 1, 1),
                                             devices=self.devices)
        lhs = {"lap f": lambda f: ps.Field("lap_f"),
               "lap f2 - f2": lambda f: ps.Field("lap_f2") - f}
        problems = {}
        for name, rho, _ in PROBLEMS:
            p = config["problems"][name]
            if p["rho"] != rho:
                raise ValueError(f"problem {name}: source {p['rho']!r}")
            f = ps.Field(name)
            problems[f] = (lhs[p["lhs"]](f), ps.Field(rho))
        kw = {}
        if config.get("smoother"):
            # rehearsals only (a CPU's default is "xla"): the cell's
            # configuration does not say, and gets what the code picks
            kw["smoother"] = config["smoother"]
        self.solver = multigrid.NewtonIterator(
            self.decomp, problems, halo_shape=self.h, dtype=self.dtype,
            fixed_parameters=dict(omega=float(config["omega"])), **kw)
        self.mg = multigrid.FullApproximationScheme(
            solver=self.solver, halo_shape=self.h)
        self._drawn = None

    def initial_state(self, seed):
        """What the driver's ``start`` takes: the seeded unknowns and
        sources on the device. One draw a seed is kept, so the check
        starts its reference from the very arrays the program started
        from."""
        if self._drawn is None or self._drawn[0] != seed:
            rng = np.random.default_rng(int(seed))
            arrays = {}
            for name in ("f", "rho", "f2", "rho2"):
                a = rng.random(self.grid_shape, dtype=np.float32)
                a -= np.float32(a.mean(dtype=np.float64))
                arrays[name] = self.decomp.shard(a.astype(self.dtype))
            self._drawn = (seed, arrays)
        arrays = self._drawn[1]
        return ({n: arrays[n] for n, _, _ in PROBLEMS},
                {r: arrays[r] for _, r, _ in PROBLEMS}, None)

    def close(self):
        pass


def first_answers(driver, with_output):
    """The set-up's solve through the window's own call, its unknowns on
    the host (the driver keeps the same copy for ``repeat_gap``) and the
    errors each of its cycles returned."""
    import jax
    driver.first_steps()
    t0 = time.perf_counter()
    driver.first_host = jax.device_get(driver.state)
    return ({"state": driver.first_host, "errors": driver.errors},
            time.perf_counter() - t0)


def returned_norms(errors, name):
    """``(before, after)``: what the first call of a solve returned for
    the finest level before its first smooth, and the last call after
    its last, each ``[L-infinity, L2]``."""
    (lv0, before), (lv1, after) = errors[0][0], errors[-1][-1]
    if lv0 != 0 or lv1 != 0:
        raise ValueError("a cycle starts and ends on the finest level")
    return before[name], after[name]


def gaps(reference, got, errors, ref, rho, dx, mass, name):
    """One problem's numbers: ``got`` and the ``errors`` returned with
    it, against the reference's ``ref``."""
    before, after = returned_norms(errors, name)
    own = [float(v) for v in reference.norms(
        reference.residual(got, rho, dx, mass))]
    return {
        "solution_gap." + name: reference.solution_gap(got, ref, not mass),
        "residual_gap." + name: max(
            abs(a / o - 1.0) for a, o in zip(after, own)),
        "residual_drop." + name: after[1] / before[1]}


def plan_line(d):
    where = ("(bx, by) = (%s, %s), grid %s" % (d["bx"], d["by"], d["grid"])
             if d["tier"] == "streaming" else d["reason"] or "")
    return (f"level {tuple(d['grid_shape'])}: {d['tier']} {where}").rstrip()


def compare(system, seed, first, background, nsteps, end, found, events,
            keep=None):
    """Runs the plain reference from the seeded arrays through the
    solve's cycles, one problem at a time (they do not touch), and
    returns the numbers the module docstring lists."""
    import jax
    from benchmark import mg_reference as reference
    unknowns, sources, _ = system.initial_state(seed)
    cycles = nsteps
    numbers, residual_gap = {}, 0.0
    for name, rho, mass in PROBLEMS:
        ref, _, _ = reference.solve(
            unknowns[name], sources[rho], system.dx, mass, system.depth,
            cycles, system.nu)
        got = jax.device_put(first["state"][name], ref.sharding)
        row = gaps(reference, got, first["errors"], ref, sources[rho],
                   system.dx, mass, name)
        del ref, got
        residual_gap = max(residual_gap, row.pop("residual_gap." + name))
        numbers.update(row)
    numbers["residual_gap"] = residual_gap
    numbers["repeat_gap"] = end["repeat_gap"]
    plans = [e["data"] for e in events if e["kind"] == "mg_level_plan"]
    for d in plans:
        print("[bench] mg_level_plan " + plan_line(d), flush=True)
    numbers["fallback_events"] = (
        sum(1 for e in events if e["kind"] == "kernel_fallback")
        + sum(1 for d in plans
              if d["tier"] == "xla" and d["smoother"] == "pallas"))
    return numbers


# -- the readings ``benchmark/control.py`` takes -----------------------------

def new_driver(system, traffic, seed):
    from benchmark import drivers
    from benchmark.spans import Spans
    driver = drivers.load(traffic["driver"])(system, traffic, Spans(False))
    driver.start(*system.initial_state(seed))
    return driver


def l2_by_cycle(errors, name):
    """The finest level's L2 residual before the first cycle and after
    each: how far each cycle of a solve took it."""
    return ([errors[0][0][1][name][1]]
            + [errs[-1][1][name][1] for errs in errors])


def program_readings(cell_name, config, traffic, devices, seeds, outfile,
                     dump=None):
    """Sound runs: from each seed the program's solve through the calls
    the window makes, against the plain reference: the numbers ``compare``
    gives, and the L2 residual by cycle."""
    system = System(config, devices, outfile=outfile)
    rows = []
    for seed in seeds:
        driver = new_driver(system, traffic, seed)
        first, _ = first_answers(driver, False)
        driver.block()
        end = driver.end_numbers()
        errors = first["errors"]
        driver.state = None
        row = {"seed": seed}
        row.update(compare(system, seed, first, driver.background(),
                           driver.first_nsteps, end, {}, []))
        for name, _, _ in PROBLEMS:
            row["l2_by_cycle." + name] = l2_by_cycle(errors, name)
        del first
        rows.append(row)
        print(json.dumps(row), flush=True)
    system.close()
    return rows


def control_readings(cell_name, config, traffic, devices, seeds, dump=None):
    """The control, a row per seed: the plain reference put in the
    program's place and compared with the float32 reference as the program
    is. ``bf16``: the unknowns, the sources and the arithmetic in bfloat16,
    one step below what the configuration states (the errors it "returns"
    are its own bfloat16 norms): it has to miss a limit. ``f32_again``: the
    reference twice, which has to read zero. ``cycles3`` and ``sweeps``:
    the float32 reference with a cycle fewer, and with V(12, 25): what
    ``residual_drop`` is there to catch."""
    import jax.numpy as jnp
    from benchmark import mg_reference as reference

    system = System(config, devices)
    cycles = int(traffic["check_steps"])
    rows = []
    for seed in seeds:
        unknowns, sources, _ = system.initial_state(seed)
        row = {"seed": seed}
        for name, rho, mass in PROBLEMS:
            args = (unknowns[name], sources[rho], system.dx, mass,
                    system.depth)
            ref, _, _ = reference.solve(*args, cycles, system.nu)
            for label, ncycles, nu, dtype in (
                    ("f32_again", cycles, system.nu, None),
                    ("bf16", cycles, system.nu, jnp.bfloat16),
                    ("cycles3", cycles - 1, system.nu, None),
                    ("sweeps", cycles, (12, 25), None)):
                got, before, after = reference.solve(*args, ncycles, nu,
                                                     dtype=dtype)
                errors = [[(0, {name: [float(v) for v in before]})],
                          [(0, {name: [float(v) for v in after]})]]
                for key, v in gaps(reference, got.astype(ref.dtype), errors,
                                   ref, sources[rho], system.dx, mass,
                                   name).items():
                    row[f"{label}_{key}"] = v
                del got
            del ref
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows
