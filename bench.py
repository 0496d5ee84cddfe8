"""Chip timings of the kernel families, one process, TPU only.

``python bench.py [config ...]`` runs the named configurations (default:
all of :data:`CONFIGS`, in order) in THIS process on the chip JAX finds:
the fused preheating hot loop at 128/256/512**3, the compiled-vs-XLA
parity checks, and the secondary families (wave equation, GW stepping
and spectra, the coupled chunk, multigrid, the depth-4 chunk kernel),
each at the size it was written for. It refuses any platform but a TPU,
names the device on every JSON line it prints, and lets a failing
configuration's exception end the run (non-zero exit) — there is no CPU
fallback, no cached line and no retry. It is not the benchmark
(ROADMAP.md, speed item 1, defines that); it is the per-family
does-it-compile-and-run check.

The persistent compilation cache follows
``pystella_tpu.obs.ensure_compilation_cache``: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``bench_results/xla_cache`` in the checkout.
``BENCH_PROFILE=<logdir>`` wraps an extra (untimed) preheat chunk in a
``jax.profiler`` capture whose per-scope durations land in the event
log as ``trace_summary`` events (doc/observability.md).

``python bench.py --smoke`` is a different animal: a tiny,
deterministic, CPU-safe in-process run that exercises the full perf
EVIDENCE pipeline — per-step ``step_time`` events, a profiler capture
parsed into per-scope durations, and a ``PerfLedger`` written to
``bench_results/perf_report.json`` + ``.md`` — so CI can smoke → gate
(``python -m pystella_tpu.obs.gate``) end to end without hardware.
It includes a supervised elastic-runtime drill (an injected mid-run
device-loss fault survived via restore-from-last-good,
``pystella_tpu.resilience``) whose incident lands in the report's
``resilience`` section.
"""

import json
import os
import sys
import time
import traceback

import numpy as np

T0 = time.time()
#: monotonic process-start anchor for time-to-first-step measurements
PERF_T0 = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))


def hb(msg):
    print(f"[bench +{time.time() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def device_block():
    """The device as JAX reports it — on every metric line, so a number
    can never be read apart from what it ran on."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def emit(metric, value, unit, vs_baseline=None):
    from pystella_tpu import obs
    print(json.dumps({"metric": metric, "value": value, "unit": unit,
                      "vs_baseline": vs_baseline,
                      "device": device_block()}), flush=True)
    obs.emit("bench_metric", metric=metric, value=value, unit=unit,
             vs_baseline=vs_baseline)


# ---------------------------------------------------------------------------
# headline: fused preheating step
# ---------------------------------------------------------------------------


def build_preheat_step(grid_shape, dtype=np.float32, halo_shape=2,
                       fused=False, decomp=None, make_state=True,
                       donate=False):
    """The two-field preheating step: ``fused=True`` is the Pallas
    :class:`~pystella_tpu.FusedScalarStepper` (a lattice it cannot block
    raises — a caller that asked for the fused tier gets it or an
    error), ``fused=False`` the generic ``LowStorageRK54`` over the XLA
    ``FiniteDifferencer``."""
    import jax
    import pystella_tpu as ps

    lattice = ps.Lattice(grid_shape, (5.0, 5.0, 5.0), dtype=dtype)
    dt = dtype(0.1 * min(lattice.dx))
    if decomp is None:
        decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])

    mphi, gsq = 1.20e-6, 2.5e-7

    def potential(f):
        phi, chi = f[0], f[1]
        return (mphi**2 / 2 * phi**2 + gsq / 2 * phi**2 * chi**2) / mphi**2

    sector = ps.ScalarSector(2, potential=potential)

    if fused:
        # fully-fused Pallas stages: stencil + KG rhs + RK update in
        # one pass over HBM per stage
        stepper = ps.FusedScalarStepper(
            sector, decomp, grid_shape, lattice.dx, halo_shape,
            dtype=dtype, donate=donate)
    else:
        derivs = ps.FiniteDifferencer(decomp, halo_shape, lattice.dx)
        sector_rhs = ps.compile_rhs_dict(sector.rhs_dict)

        def full_rhs(state, t, a, hubble):
            return sector_rhs(state, t, lap_f=derivs.lap(state["f"]),
                              a=a, hubble=hubble)

        # donate: the driver loops rebind state = step(state), so the
        # old buffers are dead — aliasing them into the outputs halves
        # the state's HBM footprint (the IR-tier lint audits this)
        stepper = ps.LowStorageRK54(full_rhs, dt=dt, donate=donate)

    if not make_state:  # callers supplying their own initial state
        return stepper, None, dt
    # fluctuation amplitudes small enough that the g^2 phi^2 chi^2
    # coupling (g^2/m_phi^2 ~ 1.7e5) keeps the run FINITE: the original
    # 0.1/0.01 amplitudes blew up to NaN within ~3 steps, which nothing
    # noticed for five rounds because only step TIMES were measured —
    # the numerics sentinel (obs.sentinel) caught it the first time it
    # ran, and now trips the smoke run if this regresses
    rng = np.random.default_rng(7)
    state = {
        "f": decomp.shard(
            1e-3 * rng.standard_normal((2,) + grid_shape).astype(dtype)),
        "dfdt": decomp.shard(
            1e-4 * rng.standard_normal((2,) + grid_shape).astype(dtype)),
    }
    return stepper, state, dt


def run_preheat(n, nsteps=10, dtype=np.float32):
    """The fused hot loop on a fixed background: ``nsteps`` steps as one
    ``multi_step`` chunk (stage pairs across step boundaries — no odd
    single-stage kernel at all for RK54), one warm-up chunk, one timed."""
    import jax
    from pystella_tpu import config, obs

    grid_shape = (n, n, n)
    hb(f"{n}^3: building model")
    stepper, state, dt = build_preheat_step(grid_shape, dtype, fused=True)
    t = dtype(0.0)
    args = {"a": dtype(1.0), "hubble": dtype(0.5)}

    def chunk(st):
        return stepper.multi_step(st, nsteps, t, dt, args)

    hb(f"{n}^3: compiling + warmup (one {nsteps}-step chunk)")
    t_compile = time.perf_counter()
    state = chunk(state)
    jax.block_until_ready(state)
    obs.emit("bench_warmup", config=f"preheat-{n}^3",
             seconds=round(time.perf_counter() - t_compile, 3))

    hb(f"{n}^3: timing one {nsteps}-step chunk")
    start = time.perf_counter()
    state = chunk(state)
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - start

    profile_dir = config.getenv("BENCH_PROFILE")
    if profile_dir:
        # capture a SEPARATE extra chunk (outside the timed window —
        # tracing overhead must not contaminate the reported number);
        # the parsed per-scope durations land in the event log as a
        # trace_summary event (obs.trace)
        hb(f"{n}^3: profiling one extra chunk")
        with obs.trace.capture(
                os.path.join(profile_dir, f"preheat-{n}"),
                label=f"preheat-{n}^3"):
            state = chunk(state)
            jax.block_until_ready(state)

    sites = float(n) ** 3
    ups = sites * nsteps / elapsed
    ms = elapsed / nsteps * 1e3
    # 5*nsteps stages -> ceil(5*nsteps/2) pair kernels x 8 lattice-array
    # transfers x 2 fields
    npairs = -(-stepper.num_stages * nsteps // 2)
    gbps = 8 * npairs * sites * 2 * np.dtype(dtype).itemsize \
        / elapsed / 1e9
    hb(f"{n}^3: {ms:.2f} ms/step, {ups:.3e} site-updates/s, "
       f"~{gbps:.0f} GB/s effective")
    return ups


# ---------------------------------------------------------------------------
# secondary config matrix (BASELINE.md "configs")
# ---------------------------------------------------------------------------

def run_coupled(n=512, nsteps=10, dtype=np.float32):
    """The energy-coupled chunked SCIENCE driver: expansion ODE on
    device with exact per-stage feedback from in-kernel energy sums.
    Since round 5 this rides the deferred-drag stage-PAIR kernels by
    default (driver-loop accuracy at the pair-fused hot loop's HBM
    traffic; ops/fused.py _coupled_pair_impl)."""
    import jax
    import pystella_tpu as ps

    grid_shape = (n, n, n)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    stepper, _, dt = build_preheat_step(grid_shape, dtype, fused=True,
                                        decomp=decomp, make_state=False)
    # physical near-homogeneous preheating ICs (the random-noise state
    # the throughput configs use is violently unstable under the
    # g^2 phi^2 chi^2 coupling and would drive the expansion to nan)
    rng = np.random.default_rng(31)
    f0, df0 = [0.193, 0.0], [-0.142231, 0.0]
    state = {
        "f": decomp.shard(np.stack(
            [np.full(grid_shape, f0[i], dtype)
             + 1e-4 * rng.standard_normal(grid_shape).astype(dtype)
             for i in range(2)])),
        "dfdt": decomp.shard(np.stack(
            [np.full(grid_shape, df0[i], dtype)
             + 1e-4 * rng.standard_normal(grid_shape).astype(dtype)
             for i in range(2)])),
    }
    # rho of the homogeneous background in mphi units:
    # kinetic 0.142231^2/2 + potential 0.193^2/2
    expand = ps.Expansion(0.0287, ps.LowStorageRK54)

    hb(f"coupled-{n}^3: compiling + warmup (one {nsteps}-step chunk)")
    state = stepper.coupled_multi_step(state, nsteps, expand, 0.0, dt)
    jax.block_until_ready(state)
    hb(f"coupled-{n}^3: timing one {nsteps}-step chunk")
    start = time.perf_counter()
    state = stepper.coupled_multi_step(state, nsteps, expand, 0.0, dt)
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - start
    ups = float(n) ** 3 * nsteps / elapsed
    hb(f"coupled-{n}^3: {elapsed / nsteps * 1e3:.2f} ms/step, "
       f"{ups:.3e} site-updates/s (a={float(expand.a):.6f})")
    return ups


def run_wave(n=64, nsteps=50, nwarmup=5):
    """3-D wave equation, classical RK4 + 4th-order FD Laplacian."""
    import jax
    import pystella_tpu as ps

    dtype = np.float32
    grid_shape = (n, n, n)
    lattice = ps.Lattice(grid_shape, (2 * np.pi,) * 3, dtype=dtype)
    dt = dtype(0.1 * min(lattice.dx))
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    derivs = ps.FiniteDifferencer(decomp, 2, lattice.dx)

    def rhs(state, t):
        return {"f": state["dfdt"], "dfdt": derivs.lap(state["f"])}

    stepper = ps.RungeKutta4(rhs, dt=dt)

    rng = np.random.default_rng(3)
    state = {"f": decomp.shard(rng.standard_normal(grid_shape).astype(dtype)),
             "dfdt": decomp.zeros(grid_shape, dtype)}
    for _ in range(nwarmup):
        state = stepper.step(state, 0.0, dt)
    jax.block_until_ready(state)
    start = time.perf_counter()
    for _ in range(nsteps):
        state = stepper.step(state, 0.0, dt)
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - start
    return float(n) ** 3 * nsteps / elapsed


def run_gw_spectra(n=256, nreps=5):
    """GW tensor-sector power spectrum: pencil/local rfftn + binning."""
    import jax
    import pystella_tpu as ps

    dtype = np.float32
    grid_shape = (n, n, n)
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=dtype)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    fft = ps.DFT(decomp, grid_shape=grid_shape, dtype=dtype)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)

    rng = np.random.default_rng(5)
    fx = decomp.shard(rng.standard_normal((2,) + grid_shape).astype(dtype))
    out = spectra(fx)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(nreps):
        out = spectra(fx)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / nreps * 1e3


def build_gw_step(grid_shape, dtype=np.float32, decomp=None,
                  carry_dtype=None):
    """Construct the full scalar+GW preheating system (the one model that
    REQUIRES multi-chip at 512^3: ~17 GB f32 state+carry > one v5e's
    HBM) on ``decomp``'s mesh; returns ``(stepper, state, dt)`` like
    :func:`build_preheat_step` so the weak-scaling harness
    (bench_scaling.py --system gw) and the single-chip bench share it."""
    import jax
    import pystella_tpu as ps

    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=dtype)
    dt = dtype(0.1 * min(lattice.dx))
    if decomp is None:
        decomp = ps.DomainDecomposition((1, 1, 1),
                                        devices=jax.devices()[:1])

    def potential(f):
        return 0.5 * 1.2e-2 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    sector = ps.ScalarSector(2, potential=potential)
    gw = ps.TensorPerturbationSector([sector])
    kw = {} if carry_dtype is None else {"carry_dtype": carry_dtype}
    stepper = ps.FusedPreheatStepper(sector, gw, decomp, grid_shape,
                                     lattice.dx, 2, dtype=dtype, dt=dt,
                                     **kw)
    rng = np.random.default_rng(9)
    state = {
        "f": decomp.shard(
            0.1 * rng.standard_normal((2,) + grid_shape).astype(dtype)),
        "dfdt": decomp.shard(
            0.01 * rng.standard_normal((2,) + grid_shape).astype(dtype)),
        "hij": decomp.zeros(grid_shape, dtype, outer_shape=(6,)),
        "dhijdt": decomp.zeros(grid_shape, dtype, outer_shape=(6,)),
    }
    return stepper, state, dt


def run_gw_step(n=256, nsteps=5, dtype=np.float32, carry_dtype=None):
    """Full scalar+GW preheating step (FusedPreheatStepper, stage-pair
    kernels on TPU): the BASELINE 'GW tensor sector' stepping config, and
    the on-device compile proof for the 24-component pair kernel.
    ``carry_dtype=jnp.bfloat16`` is the 512^3-fits-one-chip memory
    configuration (~12.6 GB vs 17.2 GB f32; doc/performance.md)."""
    import jax

    grid_shape = (n, n, n)
    stepper, state, dt = build_gw_step(grid_shape, dtype,
                                       carry_dtype=carry_dtype)
    args = {"a": dtype(1.0), "hubble": dtype(0.1)}

    def chunk(st):
        def body(carry, _):
            return stepper.step(carry, 0.0, dt, args), None
        st, _ = jax.lax.scan(body, st, xs=None, length=nsteps)
        return st

    chunk = jax.jit(chunk, donate_argnums=0)

    state = chunk(state)
    jax.block_until_ready(state)
    start = time.perf_counter()
    state = chunk(state)
    jax.block_until_ready(state)
    return float(n) ** 3 * nsteps / (time.perf_counter() - start)


def fused_parity(grid_shape, decomp=None, nsteps=1, dtype=np.float32,
                 **stepper_kw):
    """The compiled Pallas path against the plain reference: ``nsteps``
    of :class:`~pystella_tpu.FusedScalarStepper` ``step()`` vs
    ``LowStorageRK54`` over the XLA halo ``FiniteDifferencer`` from one
    seeded state, on ``decomp``'s mesh (default: one device). Returns
    ``(max difference relative to each field's scale, the fused
    stepper)``. One path at a time, results staged on the host: at
    512**3 the two paths' device buffers together would crowd a chip."""
    import jax
    import pystella_tpu as ps

    grid_shape = tuple(grid_shape)
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=dtype)
    dt = dtype(0.1 * min(lattice.dx))
    if decomp is None:
        decomp = ps.DomainDecomposition((1, 1, 1),
                                        devices=jax.devices()[:1])

    def potential(f):
        return 0.5 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    sector = ps.ScalarSector(2, potential=potential)
    rng = np.random.default_rng(21)
    host = {k: 0.1 * rng.standard_normal((2,) + grid_shape).astype(dtype)
            for k in ("f", "dfdt")}
    args = {"a": dtype(1.0), "hubble": dtype(0.1)}

    fused = ps.FusedScalarStepper(sector, decomp, grid_shape, lattice.dx,
                                  2, dtype=dtype, dt=dt, **stepper_kw)
    fd = ps.FiniteDifferencer(decomp, 2, lattice.dx, mode="halo")
    rhs = ps.compile_rhs_dict(sector.rhs_dict)

    def full_rhs(s, t, a, hubble):
        return rhs(s, t, lap_f=fd.lap(s["f"]), a=a, hubble=hubble)

    generic = ps.LowStorageRK54(full_rhs, dt=dt)

    results = []
    for stepper in (fused, generic):
        state = {k: decomp.shard(v) for k, v in host.items()}
        for _ in range(nsteps):
            state = stepper.step(state, 0.0, dt, args)
        results.append({k: np.asarray(v) for k, v in state.items()})
        del state
    got, ref = results
    maxrel = 0.0
    for k in ref:
        if not (np.all(np.isfinite(got[k])) and np.all(np.isfinite(ref[k]))):
            raise FloatingPointError(f"non-finite {k} after {nsteps} step(s)")
        scale = np.max(np.abs(ref[k])) or 1.0
        maxrel = max(maxrel, float(np.max(np.abs(got[k] - ref[k])) / scale))
    return maxrel, fused


def run_pallas_parity(n=128):
    """The Mosaic-compiled streaming kernels against XLA, one step (the
    CPU suite only ever runs these kernels in interpret mode)."""
    return fused_parity((n, n, n))[0]


def run_resident_parity(n=64):
    """The RESIDENT kernel tier (whole-lattice VMEM, all-roll taps — the
    Z < 128 path, incl. pltpu.roll on a sub-tile lane axis) against XLA,
    one step at 64^3."""
    import jax
    from pystella_tpu.ops.pallas_stencil import ResidentStencil

    # on TPU the lane gate auto-selects the resident tier at 64^3; on
    # CPU (interpret smoke runs) force it — same kernels either way
    force = {} if jax.default_backend() == "tpu" else {"resident": True}
    maxrel, fused = fused_parity((n, n, n), **force)
    if not isinstance(fused._scalar_st, ResidentStencil):
        raise RuntimeError(f"{n}^3 took {type(fused._scalar_st).__name__}, "
                           "not the resident tier")
    return maxrel


def run_multigrid(n=512, ncycles=2):
    """FAS V-cycle on the nonlinear problem lap f - f + f**3 = rho."""
    import jax
    import pystella_tpu as ps
    from pystella_tpu.multigrid import (
        FullApproximationScheme, NewtonIterator)

    dtype = np.float32
    grid_shape = (n, n, n)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    dx = 10.0 / n

    f_sym = ps.Field("f")
    problems = {f_sym: (ps.Field("lap_f") - f_sym + f_sym**3,
                        ps.Field("rho"))}
    solver = NewtonIterator(decomp, problems, halo_shape=1, omega=2 / 3,
                            dtype=dtype)
    mg = FullApproximationScheme(solver=solver, halo_shape=1)

    rng = np.random.default_rng(11)
    rho_np = rng.standard_normal(grid_shape).astype(dtype)
    rho = decomp.shard(rho_np - rho_np.mean())
    f = decomp.zeros(grid_shape, dtype)

    t0 = time.perf_counter()
    _, sol = mg(decomp, dx0=dx, f=f, rho=rho)  # warm compile
    f = sol["f"]
    jax.block_until_ready(f)
    hb(f"multigrid-{n}^3: first V-cycle (compile + run) "
       f"{time.perf_counter() - t0:.1f}s")
    start = time.perf_counter()
    for _ in range(ncycles):
        _, sol = mg(decomp, dx0=dx, f=f, rho=rho)
        f = sol["f"]
    jax.block_until_ready(f)
    return (time.perf_counter() - start) / ncycles * 1e3


def run_ensemble(n=16, size=None, nsteps=8, chunk=4, divergent=True,
                 forensics_dir=None, label=None):
    """Batched scenario population through the ensemble engine
    (:mod:`pystella_tpu.ensemble`): ``size`` members of the ``n``^3
    preheating system packed along the ensemble mesh axis, advanced
    chunk-wise by the :class:`~pystella_tpu.EnsembleDriver` with the
    per-member numerics sentinel piggybacked. With ``divergent=True``
    ONE member's IC draw is seeded non-finite, so the run also proves
    evict-and-resample end to end: the batch survives, a
    ``member_evicted`` event (and, with ``forensics_dir``, a
    member-scoped bundle) names the member and its parameter draw, and
    the slot is resampled under a fresh seed. Emits
    ``ensemble_run``/``ensemble_chunk``/``ensemble_done`` events into
    whatever event log is configured — the ledger's ``ensemble``
    report section and the gate's member-throughput verdict ingest
    exactly these. Returns ``(member_steps_per_s, evictions)``."""
    import jax
    import pystella_tpu as ps
    from pystella_tpu import config, obs

    if size is None:
        size = config.get_int("PYSTELLA_ENSEMBLE_SIZE")
    grid_shape = (n, n, n)
    # pack members over as many devices as divide the member count (the
    # member axis must tile the ensemble device extent); the largest
    # such divisor, not just a power of two — 6 members on 8 devices
    # must pack 6, not 2
    edev = max(d for d in range(1, min(size, len(jax.devices())) + 1)
               if size % d == 0)
    mesh = ps.ensemble_mesh(proc_shape=(1, 1, 1), ensemble_devices=edev,
                            devices=jax.devices()[:edev])
    decomp = ps.DomainDecomposition(mesh=mesh,
                                    ensemble_axis=mesh.axis_names[0])
    stepper, _, dt = build_preheat_step(grid_shape, fused=False,
                                        decomp=decomp, make_state=False)
    bad_seed = 1 if divergent else None

    def sample(seed):
        rng = np.random.default_rng(100 + seed)
        state = {
            "f": 1e-3 * rng.standard_normal(
                (2,) + grid_shape).astype(np.float32),
            "dfdt": 1e-4 * rng.standard_normal(
                (2,) + grid_shape).astype(np.float32),
        }
        if seed == bad_seed:
            # the forced-divergent draw: a non-finite IC the per-member
            # sentinel must catch without killing the other members
            state["f"][0, 0, 0, 0] = np.inf
        return state, {"a": 1.0, "hubble": 0.5}

    label = label or f"ensemble-{size}x{n}^3"
    sink = (obs.ForensicSink(forensics_dir, label=label)
            if forensics_dir else None)
    scenario = ps.Scenario(f"preheat-{n}^3", stepper, sample,
                           nsteps=nsteps, dt=dt)
    driver = ps.EnsembleDriver(size=size, chunk=chunk, decomp=decomp,
                               via="vmap", forensics=sink,
                               emit_steps=True, label=label)
    driver.submit(scenario, seeds=range(size))
    out = driver.run()
    st = out["stats"]
    hb(f"{label}: {st['member_steps']} member-steps in "
       f"{st['wall_s']:.2f}s -> {st['member_steps_per_s']:.1f} "
       f"member-steps/s ({edev} ensemble device(s), "
       f"{st['evictions']} eviction(s), occupancy "
       f"{st['occupancy_mean']:.0%})")
    return st["member_steps_per_s"], st["evictions"]


# ---------------------------------------------------------------------------
# smoke: tiny deterministic in-process run of the full evidence pipeline
# ---------------------------------------------------------------------------

def run_smoke(argv=None):
    """``python bench.py --smoke``: exercise the whole perf evidence
    pipeline on a tiny deterministic grid (CPU-safe, ~seconds).

    Produces under ``--out`` (default ``bench_results/``):

    - ``smoke_events.jsonl`` — the structured run record (per-step
      ``step_time`` events, the step executable's ``compile`` report,
      a ``trace_summary`` from a real ``jax.profiler`` capture, and
      per-step ``health`` events from the async numerics sentinel —
      the report's ``numerics`` section derives from them);
    - ``perf_report.json`` + ``perf_report.md`` — the
      :class:`pystella_tpu.obs.ledger.PerfLedger` output the regression
      gate consumes.

    This is pipeline-integrity evidence, not a performance claim: the
    generic XLA path on whatever backend is present, fixed seeds, fixed
    step count. CI runs smoke → ``python -m pystella_tpu.obs.gate``
    end to end (tests/test_gate.py).
    """
    import argparse
    p = argparse.ArgumentParser(prog="bench.py --smoke")
    p.add_argument("--grid", type=int, default=32)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_results"))
    p.add_argument("--no-profile", action="store_true",
                   help="skip the jax.profiler capture (the report's "
                        "scope table is then empty)")
    p.add_argument("--no-warmstart", action="store_true",
                   help="skip the AOT warm-start leg (export the smoke "
                        "step program, reload it, pin bit-exactness)")
    p.add_argument("--no-ensemble", action="store_true",
                   help="skip the batched-population payload (8 members "
                        "x 16^3 through the ensemble driver with one "
                        "forced-divergent member)")
    p.add_argument("--no-supervised", action="store_true",
                   help="skip the supervised (elastic-runtime) payload: "
                        "a 16^3 run under resilience.Supervisor with an "
                        "injected mid-run device-loss fault, completed "
                        "via restore-from-last-good — the report's "
                        "`resilience` section derives from it")
    p.add_argument("--no-remesh", action="store_true",
                   help="skip the re-mesh drill: a 16^3 run on the "
                        "8-device (2,2,2) mesh under "
                        "resilience.Supervisor with a PERSISTENT "
                        "device-subset fault (half the mesh lost "
                        "mid-run) and the RemeshPlanner as the default "
                        "remesh policy — the run completes on the "
                        "degraded 4-device mesh, the checkpoint is "
                        "restored straight onto it, and the report's "
                        "resilience `degraded` block (plus the gate's "
                        "degraded-throughput audit) derives from the "
                        "emitted remesh_plan record")
    p.add_argument("--no-service", action="store_true",
                   help="skip the scenario-service payload: the seeded "
                        "loadgen mix (pystella_tpu.service.loadgen) "
                        "through a live ScenarioService — mixed "
                        "tenants/priorities, warm-pool admissions with "
                        "zero backend compiles on the warm path, one "
                        "forced cold signature, one quota rejection, "
                        "and one forced preemption with a "
                        "bit-consistent resume; the report's `service` "
                        "section and the gate's SLO verdicts derive "
                        "from it")
    p.add_argument("--no-capacity", action="store_true",
                   help="skip the capacity leg riding the service "
                        "payload: the loadgen's pinned HBM budget, "
                        "the seeded CapacityExceeded rejection, the "
                        "per-chunk watermark polls (predicted-only on "
                        "stat-less backends, honestly flagged), and "
                        "the retire-time per-tenant chip-second/"
                        "goodput attribution feeding the report's "
                        "`capacity` section and the gate's goodput "
                        "verdicts")
    p.add_argument("--no-fleet", action="store_true",
                   help="skip the two-replica fleet drill: a pair of "
                        "ScenarioService replicas announced into a "
                        "throwaway replica registry, scraped and "
                        "federated by obs.fleet.FleetAggregator (the "
                        "seeded fleet burn alert fires AND resolves "
                        "from replica-a's deadline story), with "
                        "replica-b's live endpoint wedged and its "
                        "heartbeats killed mid-run — the recorded "
                        "fleet_replica_lost and the lossy scrape "
                        "coverage feed the report's `fleet` section "
                        "and the gate's honest-degraded annotation")
    p.add_argument("--no-autotune", action="store_true",
                   help="skip the fused-tier + autotune payload: a "
                        "tiny (bx, by, chunk-depth) sweep persisting "
                        "its winner to <out>/autotune_<device>.json, "
                        "the pair-vs-whole-RK-chunk steppers dispatched "
                        "back to back (bit-exact pin + the roofline's "
                        "kernel-tier traffic-reduction record), and a "
                        "table-hit rebuild dispatched against the warm "
                        "compilation cache with ZERO extra backend "
                        "compiles (compile-watch proof)")
    p.add_argument("--no-spectra", action="store_true",
                   help="skip the sharded-spectra payload: a 16^3 "
                        "2-field power spectrum on the 8-device "
                        "(2,2,2) mesh with the pencil FFT tier FORCED "
                        "(fourier.pencil: explicit all_to_all "
                        "transposes inside shard_map, one fused "
                        "dispatch), the report's `fft` section and the "
                        "lint collective audit of the spectra program "
                        "derive from it")
    args = p.parse_args(argv)

    import contextlib

    # the overlapped-halo payload below needs a sharded mesh; fake 8
    # host-platform devices before jax initializes (harmless for the
    # main payload, which pins a single-device mesh, and for non-CPU
    # backends, which ignore the host-platform count). Guard on the
    # flag NAME: an explicit user-set count must not get a second,
    # conflicting instance appended
    flags = os.environ.get("XLA_FLAGS", "")
    if ("jax" not in sys.modules
            and "xla_force_host_platform_device_count" not in flags):
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    t_import0 = time.perf_counter()
    import jax
    import pystella_tpu as ps
    from pystella_tpu import obs
    import_s = time.perf_counter() - t_import0

    os.makedirs(args.out, exist_ok=True)
    events_path = os.path.join(args.out, "smoke_events.jsonl")
    # fresh record per smoke run: the ledger must describe THIS run,
    # not an accumulation of prior ones — including any size-rotated
    # family members a rotation-enabled earlier run left behind (the
    # ledger reads the whole family)
    from pystella_tpu.obs.events import rotated_family
    for member in rotated_family(events_path):
        if os.path.exists(member):
            os.remove(member)
    obs.configure(events_path)

    # persistent compilation cache: JAX_COMPILATION_CACHE_DIR when set
    # (two smoke runs against one fresh directory are the cold/warm
    # e2e), else bench_results/xla_cache in the checkout
    cache_dir = obs.ensure_compilation_cache()
    hb(f"smoke: compilation cache {cache_dir}")

    n = args.grid
    grid_shape = (n, n, n)
    hb(f"smoke: {n}^3 generic path, {args.steps} steps, "
       f"backend={jax.default_backend()}")
    obs.emit("bench_run", mode="smoke", grid_shape=list(grid_shape),
             nsteps=args.steps)

    t = np.float32(0.0)
    t_build0 = time.perf_counter()
    stepper, state, dt = build_preheat_step(grid_shape, fused=False,
                                            donate=True)
    build_s = time.perf_counter() - t_build0
    rhs_args = {"a": np.float32(1.0), "hubble": np.float32(0.5)}
    compiled, rec = obs.compile_with_report(
        stepper._jit_step, state, t, dt, rhs_args, label="smoke_step")
    hb(f"smoke: traced in {rec.trace_seconds:.2f}s, compiled in "
       f"{rec.compile_seconds:.2f}s (cache "
       f"{'hit' if rec.cache_hit else 'miss' if rec.cache_hit is False else 'n/a'}"
       f"; arg+out bytes {((rec.argument_bytes or 0) + (rec.output_bytes or 0)):,})")
    # keep a host copy of the warmed input: the warm-start leg below
    # replays the SAME step from it on both the jit and AOT paths (the
    # donated originals are consumed by the timed loop)
    t_first0 = time.perf_counter()
    state = compiled(state, t, dt, rhs_args)
    jax.block_until_ready(state)
    first_dispatch_s = time.perf_counter() - t_first0
    time_to_first_step_s = time.perf_counter() - PERF_T0
    hb(f"smoke: time-to-first-step {time_to_first_step_s:.2f}s "
       f"(import {import_s:.2f} / build {build_s:.2f} / trace "
       f"{rec.trace_seconds:.2f} / compile {rec.compile_seconds:.2f} / "
       f"dispatch {first_dispatch_s:.2f})")
    ws_input = {k: np.asarray(v) for k, v in state.items()}
    ws_shardings = {k: v.sharding for k, v in state.items()}
    state = compiled(state, t, dt, rhs_args)
    jax.block_until_ready(state)

    # numerics sentinel: a per-step health vector (per-field finite/
    # max-abs/rms + a kinetic-energy invariant) observed asynchronously
    # — poll only ever converts vectors >= 4 steps behind — so the
    # smoke report's `numerics` section (invariant drift slope,
    # sentinel overhead) and the `health` event schema are exercised
    # end to end by smoke -> ledger -> gate (tests/test_gate.py)
    import jax.numpy as jnp
    sentinel = obs.Sentinel.for_state(state, invariants={
        "kinetic_mean": lambda st, aux: 0.5 * jnp.mean(
            jnp.sum(jnp.square(st["dfdt"]), axis=0))})
    smon = obs.SentinelMonitor(sentinel, every=4, history=64,
                               emit_steps=True, label="smoke")
    # compile the (tiny) health computation outside the timed loop, like
    # the step warmup above — the `sentinel` metrics timer should
    # measure steady-state overhead, not one jit compile
    jax.block_until_ready(sentinel.compute_jit(state))

    # overlapped-halo payload: a sharded-mesh Laplacian through the
    # interior/shell split (PYSTELLA_HALO_OVERLAP / FiniteDifferencer
    # overlap=True), so the smoke report exercises the halo_overlap
    # scope names and the ledger's exposed-vs-hidden communication line
    # end to end. Built (and compiled) before the capture; runs inside
    # it so its spans land in the trace_summary. Degrades to a note
    # when the backend exposes fewer than 4 devices.
    overlap_seg = None
    if len(jax.devices()) >= 4:
        odec = ps.DomainDecomposition((2, 2, 1),
                                      devices=jax.devices()[:4])
        ofd = ps.FiniteDifferencer(odec, 2, 0.1, mode="halo",
                                   overlap=True)
        ox = odec.shard(np.random.default_rng(13).standard_normal(
            grid_shape).astype(np.float32))
        jax.block_until_ready(ofd.lap(ox))  # compile outside the window
        overlap_seg = (odec, ofd, ox)
    else:
        hb("smoke: <4 devices — skipping the overlapped-halo payload")

    # sharded-spectra payload (pencil tier FORCED): a 2-field 16^3
    # power spectrum on the full 8-device (2,2,2) mesh — the transform
    # runs as per-axis local FFT stages with explicit all_to_all
    # transposes inside shard_map, fused with the |f(k)|^2 weighting
    # and the per-device binning into ONE dispatch. Compiled before the
    # capture; the timed calls run inside it so the fft_stage /
    # fft_transpose scopes land in trace_summary and the ledger's
    # `fft` section can derive its per-stage rows. Degrades to a note
    # below 8 devices (the pencil tier needs 16 % ndev == 0).
    spectra_seg = None
    if not args.no_spectra and len(jax.devices()) >= 8 \
            and 16 % len(jax.devices()[:8]) == 0:
        try:
            sdec = ps.DomainDecomposition((2, 2, 2),
                                          devices=jax.devices()[:8])
            sgrid = (16, 16, 16)
            slat = ps.Lattice(sgrid, (5.0,) * 3, dtype=np.float32)
            sfft = ps.make_dft(sdec, grid_shape=sgrid, dtype=np.float32,
                               scheme="pencil")
            sspec = ps.PowerSpectra(sdec, sfft, slat.dk, slat.volume)
            sfx = sdec.shard(np.random.default_rng(29).standard_normal(
                (2,) + sgrid).astype(np.float32))
            sspec(sfx)  # compile outside the capture window
            spectra_seg = (sdec, sfft, sspec, sfx, sgrid)
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: sharded-spectra payload failed to build: "
               f"{type(e).__name__}: {e}")
            traceback.print_exc()
    elif not args.no_spectra:
        hb("smoke: <8 devices — skipping the sharded-spectra payload")

    steptimer = ps.StepTimer(report_every=float("inf"), emit_steps=True)
    capture = (contextlib.nullcontext() if args.no_profile else
               obs.trace.capture(os.path.join(args.out, "smoke_trace"),
                                 label="smoke"))
    spectra_times = []
    with capture:
        steptimer.tick()  # arm the clock
        for i in range(args.steps):
            with obs.trace_scope("bench_step"):
                state = compiled(state, t, dt, rhs_args)
                jax.block_until_ready(state)
            steptimer.tick()
            smon.observe(i + 1, state)
            smon.poll()
        if overlap_seg is not None:
            odec, ofd, ox = overlap_seg
            for _ in range(6):
                with obs.trace_scope("halo_overlap"):
                    jax.block_until_ready(ofd.lap(ox))
        if spectra_seg is not None:
            _, _, sspec, sfx, _ = spectra_seg
            for _ in range(4):
                t0_spec = time.perf_counter()
                sspec(sfx)  # host histogram: call is synchronous
                spectra_times.append(
                    (time.perf_counter() - t0_spec) * 1e3)

    # drain the sentinel queue: the trailing <4 health vectors land in
    # the event log before the ledger ingests it
    smon.flush()

    if overlap_seg is not None:
        # per-device ICI bytes one overlapped call moves — computed by
        # the decomposition from slab shapes/dtype at trace time; the
        # ledger derives the achieved-ICI-bandwidth line from it
        obs.emit("halo_traffic",
                 bytes_per_step=overlap_seg[0].traced_halo_bytes(),
                 label="smoke-overlap")

    if spectra_seg is not None and spectra_times:
        # the ledger's `fft` section derives from these: per-call
        # spectra_time samples plus one fft_spectra leg record (scheme,
        # grid, field count -> the 5 N log2 N flops model)
        _, sfft, sspec, sfx, sgrid = spectra_seg
        for ms in spectra_times:
            obs.emit("spectra_time", ms=ms, label="smoke-spectra")
        ms_p50 = sorted(spectra_times)[len(spectra_times) // 2]
        obs.emit("fft_spectra", scheme=sfft.scheme,
                 grid_shape=list(sgrid), nfields=2,
                 calls=len(spectra_times), ms_per_call=ms_p50,
                 complex_itemsize=8, label="smoke-spectra")
        hb(f"smoke: sharded spectra ({sfft.scheme}) p50 "
           f"{ms_p50:.2f} ms/call over {len(spectra_times)} call(s)")

    # fused-tier + autotune payload: the temporal-blocking rung of the
    # kernel ladder, end to end on the smoke budget. (a) A tiny
    # (bx, by, chunk-depth) sweep through ops.autotune persists its
    # winner to <out>/autotune_<device-kind>.json — the same candidate
    # model (choose_blocks' VMEM feasibility) and min-over-rounds
    # paired estimator a hardware window uses. (b) The pair-tier and
    # whole-RK-chunk steppers advance the same trajectory back to
    # back: the chunked path is pinned bit-exact against the pair
    # sequence it replaces, and both emit kernel_tier dispatch
    # records, so the report's roofline section carries the measured
    # per-step HBM-traffic reduction. (c) A fresh stepper built over
    # the table (chunk_stages=None -> consult) picks the recorded
    # winner (block_choice source="autotune") and a SECOND table-hit
    # build dispatches against the now-warm compilation cache with
    # ZERO extra backend compiles — the compile-watch proof that a
    # tuned kernel is warm-servable (the scenario service's
    # dispatch-never-compile contract extends to tuned programs).
    if not args.no_autotune:
        try:
            from pystella_tpu.ops import autotune as ps_autotune
            at_store = ps_autotune.AutotuneStore(root=args.out)
            at_grid = (16, 16, 16)
            # max_blocks=1: one pair + one chunk candidate — the table
            # round trip and winner record are what smoke proves; the
            # breadth of the sweep grid is the hardware window's job
            ps_autotune.sweep(at_grid, store=at_store, nsteps=2,
                              rounds=2, max_blocks=1,
                              chunk_depths=(0, 4), log=lambda m: None)
            hb(f"smoke: autotune sweep ({at_store.device_kind}) -> "
               f"{at_store.path}")

            at_dt = np.float32(0.1 * 5.0 / at_grid[0])
            at_args = {"a": np.float32(1.0), "hubble": np.float32(0.5)}
            at_t = np.float32(0.0)
            pair_st, at_state = ps_autotune._build_sweep_stepper(
                at_grid, {"chunk": 0, "bx": 4, "by": 8})
            chunk_st, _ = ps_autotune._build_sweep_stepper(
                at_grid, {"chunk": 4, "bx": 4, "by": 8})
            at_host = {k: np.asarray(v) for k, v in at_state.items()}

            def at_fresh():
                return {k: jax.device_put(v) for k, v in at_host.items()}

            at_ref = pair_st.multi_step(at_fresh(), 4, at_t, at_dt,
                                        at_args)
            at_got = chunk_st.multi_step(at_fresh(), 4, at_t, at_dt,
                                         at_args)
            jax.block_until_ready(at_ref)
            jax.block_until_ready(at_got)
            at_bitexact = all(
                np.array_equal(np.asarray(at_got[k]),
                               np.asarray(at_ref[k])) for k in at_ref)
            tier_pair = pair_st.kernel_tier_report()
            tier_chunk = chunk_st.kernel_tier_report()
            at_red = 1.0 - (tier_chunk["bytes_per_step"]
                            / tier_pair["bytes_per_step"])
            hb(f"smoke: fused tiers {tier_chunk['tier']} "
               f"{tier_chunk['bytes_per_step']:,} B/step vs pair "
               f"{tier_pair['bytes_per_step']:,} B/step "
               f"({at_red:.0%} less lattice traffic), "
               f"bit-exact={at_bitexact}")
            if not (at_bitexact and chunk_st._chunk_call is not None):
                obs.emit("smoke_autotune_failed", bitexact=at_bitexact,
                         chunk_built=chunk_st._chunk_call is not None)

            # table-hit rebuild: consult -> winner blocks -> dispatch.
            # The first tuned build's step program lands in the
            # persistent cache; the second build's dispatch must then
            # be compile-free (the undonated step program is
            # cache-eligible on every backend).
            tuned1, _ = ps_autotune._build_sweep_stepper(
                at_grid, {}, autotune=at_store)
            at_hit = tuned1._autotune_entry is not None
            jax.block_until_ready(
                tuned1.step(at_fresh(), at_t, at_dt, at_args))
            tuned2, _ = ps_autotune._build_sweep_stepper(
                at_grid, {}, autotune=at_store)
            with obs.compile_watch("autotune_warm_build") as at_w:
                jax.block_until_ready(
                    tuned2.step(at_fresh(), at_t, at_dt, at_args))
            at_compiles = at_w.backend_compiles
            if cache_dir:
                obs.emit("autotune_warm_build",
                         table_hit=at_hit and
                         tuned2._autotune_entry is not None,
                         backend_compiles=at_compiles,
                         cache_hits=at_w.cache_hits,
                         cache_misses=at_w.cache_misses,
                         trace_s=round(at_w.trace_seconds, 4),
                         compile_s=round(at_w.compile_seconds, 4),
                         table=at_store.path)
                hb(f"smoke: autotune table-hit rebuild "
                   f"(hit={at_hit}) dispatched with "
                   f"{at_compiles} backend compile(s) "
                   f"({at_w.cache_hits} cache hit(s))")
            else:
                hb("smoke: compilation cache disabled — skipping the "
                   "zero-compile table-hit proof")
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: fused-tier/autotune payload failed: "
               f"{type(e).__name__}: {e}")
            traceback.print_exc()

    # ensemble payload: a batched scenario population (8 members x 16^3
    # packed along the ensemble mesh axis) through the EnsembleDriver
    # with ONE forced-divergent member, so smoke -> ledger -> gate
    # exercises member-steps/s, batch occupancy, and evict-and-resample
    # end to end (the report's `ensemble` section and the gate's
    # member-throughput verdict). The eviction is per-member physics,
    # not a run failure: the batch completes and the report stays valid
    # evidence (exactly one member_evicted event + one member-scoped
    # forensic bundle).
    if not args.no_ensemble:
        try:
            # chunk=2 keeps the unrolled batched-chunk graph (and its
            # one-off XLA compile, the payload's dominant cost on a
            # fresh cache) small — smoke is pipeline integrity, not a
            # throughput claim
            rate, nev = run_ensemble(
                n=16, nsteps=4, chunk=2, divergent=True,
                forensics_dir=os.path.join(args.out, "forensics"),
                label="smoke-ensemble")
            hb(f"smoke: ensemble {rate:.1f} member-steps/s, "
               f"{nev} eviction(s)")
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: ensemble payload failed: "
               f"{type(e).__name__}: {e}")
            traceback.print_exc()

    # supervised (elastic-runtime) payload: a second tiny 16^3 run
    # driven by resilience.Supervisor with a DEVICE-LOSS fault injected
    # mid-run (simulated XlaRuntimeError UNAVAILABLE at step 9 of 12,
    # checkpoints every 4 steps): the run completes by restoring the
    # durable last-good checkpoint and replaying at most one interval,
    # bit-consistent with an uninterrupted run of the same program.
    # Exactly one incident (fault_detected -> recovery_attempt ->
    # run_resumed with a measured MTTR) lands in the event log, the
    # report's `resilience` section, and the gate's degraded-annotation
    # path — the smoke e2e (tests/test_gate.py) pins all three.
    if not args.no_supervised:
        try:
            import shutil
            from pystella_tpu import resilience as rzl
            sup_ck_dir = os.path.join(args.out, "supervised_ckpt")
            shutil.rmtree(sup_ck_dir, ignore_errors=True)
            sstepper, sstate, sdt = build_preheat_step(
                (16, 16, 16), fused=False)
            sargs = {"a": np.float32(1.0), "hubble": np.float32(0.5)}

            def sup_step(st, i):
                return sstepper.step(st, np.float32(0.0), sdt, sargs)

            # clean reference trajectory for the bit-consistency pin
            sref = {k: v for k, v in sstate.items()}
            for i in range(12):
                sref = sup_step(sref, i)
            jax.block_until_ready(sref)
            smon_sup = ps.HealthMonitor(every=2,
                                        metrics_prefix="supervised")
            with ps.Checkpointer(sup_ck_dir, max_to_keep=2) as sup_ck:
                sup = rzl.Supervisor(
                    sup_step, sup_ck, 12, monitor=smon_sup,
                    checkpoint_every=4,
                    faults=rzl.FaultInjector.device_loss(
                        step=9, label="smoke-supervised"),
                    retry=rzl.RetryPolicy(base_s=0.05, max_s=0.2),
                    label="smoke-supervised")
                sup_rep = sup.run(sstate)
            bit_ok = all(
                np.array_equal(np.asarray(sup_rep["state"][k]),
                               np.asarray(sref[k])) for k in sref)
            inc = (sup_rep["incident_records"][0]
                   if sup_rep["incident_records"] else {})
            hb(f"smoke: supervised run "
               f"{'completed' if sup_rep['completed'] else 'FAILED'} "
               f"with {sup_rep['incidents']} incident(s) "
               f"(MTTR {inc.get('mttr_s', float('nan')):.3f}s, "
               f"{sup_rep['steps_replayed']} step(s) replayed, "
               f"bit-consistent={bit_ok})")
            if not (sup_rep["completed"] and bit_ok
                    and sup_rep["incidents"] == 1):
                obs.emit("smoke_supervised_failed",
                         completed=sup_rep["completed"],
                         incidents=sup_rep["incidents"],
                         bitexact=bit_ok)
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: supervised payload failed: "
               f"{type(e).__name__}: {e}")
            traceback.print_exc()

    # re-mesh drill: a second supervised 16^3 run, this one sharded
    # over the full 8-device (2,2,2) mesh, with a PERSISTENT
    # device-subset fault taking half the mesh at step 9 of 12 and NO
    # caller-provided remesh hook: the RemeshPlanner (the supervisor's
    # default policy) solves the best feasible 4-device mesh, restores
    # the durable step-8 checkpoint STRAIGHT onto it (the
    # Checkpointer mesh= template path — never materialized on one
    # device), rebuilds the step program through the same constructors,
    # and the replay sails past the still-armed fault because the
    # degraded program no longer touches the lost devices. The emitted
    # remesh_plan record lands in the report's resilience `degraded`
    # block, flips the throughput per-chip normalization to the
    # SURVIVORS, and the gate's degraded-throughput audit accepts it —
    # the smoke e2e (tests/test_gate.py) pins the whole chain. The
    # final state is pinned bit-consistent with an uninterrupted run
    # computed entirely on the degraded mesh's own trajectory.
    if not args.no_remesh and len(jax.devices()) >= 8:
        try:
            import shutil
            from pystella_tpu import resilience as rzl
            rm_grid = (16, 16, 16)
            rm_ck_dir = os.path.join(args.out, "remesh_ckpt")
            shutil.rmtree(rm_ck_dir, ignore_errors=True)
            rm_dec = ps.DomainDecomposition((2, 2, 2),
                                            devices=jax.devices()[:8])
            rm_args = {"a": np.float32(1.0), "hubble": np.float32(0.5)}

            def rm_build_step(dec):
                stp, _, rdt = build_preheat_step(
                    rm_grid, fused=False, decomp=dec, make_state=False)
                return lambda st, i: stp.step(st, np.float32(0.0), rdt,
                                              rm_args)

            rng = np.random.default_rng(7)
            rm_host = {
                "f": 1e-3 * rng.standard_normal(
                    (2,) + rm_grid).astype(np.float32),
                "dfdt": 1e-3 * rng.standard_normal(
                    (2,) + rm_grid).astype(np.float32)}
            rm_state = {k: rm_dec.shard(v) for k, v in rm_host.items()}
            planner = rzl.RemeshPlanner(rm_dec, rm_grid, rm_build_step,
                                        halo=2, label="smoke-remesh")
            rm_mon = ps.HealthMonitor(every=2,
                                      metrics_prefix="supervised")
            with ps.Checkpointer(rm_ck_dir, max_to_keep=2) as rm_ck:
                rm_sup = rzl.Supervisor(
                    rm_build_step(rm_dec), rm_ck, 12, monitor=rm_mon,
                    checkpoint_every=4, planner=planner,
                    faults=rzl.FaultInjector.device_subset(
                        step=9, count=4, label="smoke-remesh"),
                    retry=rzl.RetryPolicy(base_s=0.05, max_s=0.2),
                    label="smoke-remesh")
                rm_rep = rm_sup.run(rm_state)
            # reference: the degraded mesh's OWN uninterrupted
            # trajectory — built on the very decomposition the planner
            # realized (planner.decomp after the swap), so the pin
            # compares against the mesh the run actually finished on
            rm_ref_step = rm_build_step(planner.decomp)
            rm_ref = {k: planner.decomp.shard(v)
                      for k, v in rm_host.items()}
            for i in range(12):
                rm_ref = rm_ref_step(rm_ref, i)
            jax.block_until_ready(rm_ref)
            rm_bit = all(
                np.array_equal(np.asarray(rm_rep["state"][k]),
                               np.asarray(rm_ref[k])) for k in rm_ref)
            rm_plan = planner.last_plan
            hb(f"smoke: remesh drill "
               f"{'completed' if rm_rep['completed'] else 'FAILED'} "
               f"{list(rm_plan.old_proc_shape) if rm_plan else '?'}"
               f"->{list(rm_plan.new_proc_shape) if rm_plan else '?'} "
               f"({len(rm_plan.devices) if rm_plan else '?'} "
               f"survivor(s)), bit-consistent={rm_bit}")
            if not (rm_rep["completed"] and rm_bit and rm_plan):
                obs.emit("smoke_remesh_failed",
                         completed=rm_rep["completed"], bitexact=rm_bit)
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: remesh drill failed: {type(e).__name__}: {e}")
            traceback.print_exc()
    elif not args.no_remesh:
        hb("smoke: <8 devices — skipping the remesh drill")

    # scenario-service payload: the seeded loadgen mix through a live
    # ScenarioService (pystella_tpu.service) — warm-pool admissions
    # whose leases record ZERO backend compiles (the compile-ledger
    # proof of dispatch-never-compile), one forced cold signature
    # queued behind its build, one quota rejection, and one forced
    # preemption (priority-3 arrival mid-lease -> drain -> durable
    # checkpoint -> requeue) whose resumed members are re-verified
    # bit-consistent against an uninterrupted replay. Every decision
    # lands in the event log; the report's `service` section and the
    # gate's SLO verdicts (queue-p95, warm TTFS, fingerprint refusal)
    # derive from exactly this record — the smoke e2e
    # (tests/test_gate.py) pins the whole chain.
    if not args.no_service:
        try:
            import shutil
            from pystella_tpu.service import loadgen as service_loadgen
            svc_ck = os.path.join(args.out, "service_ckpt")
            shutil.rmtree(svc_ck, ignore_errors=True)
            svc = service_loadgen.run(
                svc_ck, seed=11, label="smoke-service",
                capacity=(False if args.no_capacity else None))
            hb(f"smoke: service {svc['completed']}/{svc['requests']} "
               f"request(s) completed over {svc['leases']} lease(s) "
               f"({svc['warm_admissions']} warm / "
               f"{svc['cold_admissions']} cold admission(s), "
               f"{sum(svc['rejected'].values())} rejected, "
               f"{svc['preemptions']} preemption(s), bit-consistent "
               f"resume={svc['preempt_bitexact']}, "
               f"{svc['deadline_misses']}/{svc['deadlined_requests']} "
               "deadline(s) missed)")
            slo = svc.get("slo") or {}
            if slo:
                # the seeded live burn alert: fires on the guaranteed
                # deadline miss, resolves on the next guaranteed hit —
                # both transitions must be in every smoke record
                hb(f"smoke: service slo {slo['alerts']} alert(s) "
                   f"fired / {slo['resolved']} resolved"
                   + (f", STILL BURNING: {slo['alerting']}"
                      if slo.get("alerting") else "")
                   + f" (monitor overhead {slo['overhead_pct']:.3f}% "
                   "of serve wall)")
            if not (svc["preempt_bitexact"]
                    and svc["preemptions"] >= 1
                    and svc["lease_failures"] == 0):
                obs.emit("smoke_service_failed",
                         preemptions=svc["preemptions"],
                         bitexact=svc["preempt_bitexact"],
                         lease_failures=svc["lease_failures"])
            cap = svc.get("capacity") or {}
            if cap:
                # the capacity leg riding the same loadgen run: the
                # seeded hog MUST have been refused admission, and
                # retire-time attribution MUST have produced a goodput
                # figure (committed member-steps per chip-second) —
                # the closed loop the report's `capacity` section and
                # the gate's goodput verdicts consume
                goodput = svc.get("goodput")
                hb("smoke: capacity budget "
                   f"{cap['budget_bytes'] / 2**20:.1f} MiB, hog "
                   f"rejection={'OK' if cap['hog_rejected'] else 'MISSING'}"
                   f", {cap['watermark_samples']} watermark sample(s)"
                   + (" (predicted-only backend)"
                      if not cap["watermark_samples"] else "")
                   + (f", goodput {goodput:g} steps/chip-s"
                      if isinstance(goodput, (int, float)) else ""))
                if not (cap["hog_rejected"]
                        and isinstance(goodput, (int, float))
                        and goodput > 0):
                    obs.emit("smoke_capacity_failed",
                             hog_rejected=cap["hog_rejected"],
                             goodput=goodput,
                             budget_bytes=cap["budget_bytes"],
                             watermark_samples=cap[
                                 "watermark_samples"])
            # the request-scoped trace layer, closed end to end: every
            # loadgen request's span tree reassembles from the event
            # log and exports as a Perfetto-loadable service timeline
            # (the same vocabulary hardware captures fold through) —
            # the report's `latency` section derives from the same
            # record at ledger time
            from pystella_tpu.obs.spans import SpanAssembler
            asm = SpanAssembler.from_events(events_path)
            lat = asm.summary() or {}
            svc_trace = asm.export_perfetto(
                os.path.join(args.out, "service_trace.json"))
            extra = os.environ.get(
                "PYSTELLA_TRACE_EXPORT")  # env-registry: PYSTELLA_TRACE_EXPORT
            if svc_trace and extra:
                asm.export_perfetto(extra)
            obs.emit("service_trace", path=svc_trace,
                     traced=lat.get("traced"),
                     assembled=lat.get("assembled"),
                     unassembled=lat.get("unassembled_total") or 0,
                     max_rel_err=(lat.get("phase_sum_check")
                                  or {}).get("max_rel_err"),
                     label="smoke-service")
            chk = lat.get("phase_sum_check") or {}
            hb(f"smoke: service spans {lat.get('assembled')}/"
               f"{lat.get('traced')} request tree(s) assembled, "
               f"critical-path partition err "
               f"{(chk.get('max_rel_err') or 0.0):.2e} "
               f"-> {svc_trace}")
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: service payload failed: "
               f"{type(e).__name__}: {e}")
            traceback.print_exc()

    # fleet drill: TWO ScenarioService replicas heartbeating into a
    # throwaway replica registry, scraped over live HTTP and federated
    # by obs.fleet.FleetAggregator. The orchestration is deterministic
    # (blocking event-log subscribers, no sleeps-and-hope): replica-a's
    # seeded deadline story replays through the fleet monitor so the
    # fleet burn alert FIRES and RESOLVES inside the first scrape;
    # replica-b's live endpoint is wedged (one recorded failed scrape
    # against a still-beating record), then its heartbeats are killed —
    # the aggregator records fleet_replica_lost (reason "expired") and
    # the final scrape's lossy coverage is exactly what the report's
    # `fleet` section carries and the gate annotates (honest-degraded)
    # rather than refuses. The smoke e2e (tests/test_gate.py) pins the
    # whole chain, including the exit-2 refusal of a synthetic report
    # that claims complete coverage over this lossy record.
    if not args.no_fleet:
        try:
            from pystella_tpu.service import loadgen as fleet_loadgen
            fl_dir = os.path.join(args.out, "fleet_drill")
            fleet_events = os.path.join(args.out, "fleet_events.jsonl")
            # the drill replicas are a separate logical service: run
            # them against their own event log so their service_*/slo_*
            # records cannot contaminate the single-replica
            # service/latency/alerts sections, then fold ONLY the
            # fleet_* vocabulary back into the run record for the
            # ledger's fleet section and the gate
            obs.configure(fleet_events)
            try:
                fl = fleet_loadgen.run_fleet(fl_dir, label="smoke-fleet")
            finally:
                obs.configure(events_path)
            with open(fleet_events) as src, open(events_path, "a") as dst:
                for line in src:
                    try:
                        kind = json.loads(line).get("kind")
                    except ValueError:
                        continue
                    if isinstance(kind, str) and kind.startswith("fleet_"):
                        dst.write(line)
            hb(f"smoke: fleet {len(fl['replicas'])} replica(s) "
               f"({fl['scrapes']} scrape(s), "
               f"{fl['endpoint_ok']} endpoint pass(es) / "
               f"{fl['endpoint_failed']} failed, "
               f"coverage {fl['scrape_success_rate']:.0%}), "
               f"killed {fl['killed']} -> "
               f"{fl['lost'][0]['reason'] if fl['lost'] else '?'}, "
               f"{fl['alerts']} fleet alert(s) fired / "
               f"{fl['resolved']} resolved"
               + (f", still burning: {fl['alerting']}"
                  if fl.get("alerting") else ""))
            lost_reasons = [e.get("reason") for e in fl["lost"]]
            if not (fl["live_both_pass"] >= 2
                    and len(fl["queue_gauge_replicas"]) == 2
                    and fl["alerts"] >= 2 and fl["resolved"] >= 1
                    and "dead_replicas" in fl["alerting"]
                    and fl["dead"] == 1
                    and lost_reasons == ["expired"]):
                obs.emit("smoke_fleet_failed",
                         live_both_pass=fl["live_both_pass"],
                         queue_gauge_replicas=fl["queue_gauge_replicas"],
                         alerts=fl["alerts"], resolved=fl["resolved"],
                         alerting=fl["alerting"], dead=fl["dead"],
                         lost_reasons=lost_reasons)
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: fleet drill failed: {type(e).__name__}: {e}")
            traceback.print_exc()

    # AOT warm-start leg: export the very step program this run timed,
    # reload the artifact, and pin the loaded program bit-exact against
    # the jit executable from the same input — the round-trip proof the
    # cold_start report's `warmstart` block carries. save(verify=True)
    # also runs the exported module once, so its backend compile lands
    # in the persistent cache for a later warmed process.
    warm_artifacts = []
    if not args.no_warmstart:
        from pystella_tpu.obs import warmstart as obs_warmstart

        def ws_fresh():
            # the compiled AOT executable requires its lowered input
            # shardings; replaying from host copies keeps the donated/
            # consumed originals out of the comparison
            return {k: jax.device_put(v, ws_shardings[k])
                    for k, v in ws_input.items()}
        try:
            from pystella_tpu import config as _pcfg
            store = obs_warmstart.WarmstartStore(
                _pcfg.getenv("PYSTELLA_WARMSTART_DIR")
                or os.path.join(args.out, "warmstart"))
            meta = store.save("smoke_step", stepper._jit_step,
                              (ws_fresh(), t, dt, rhs_args))
            prog = store.load("smoke_step",
                              args=(ws_fresh(), t, dt, rhs_args))
            match = prog is not None
            bitexact = None
            if match:
                # reference = the very executable this run timed (no
                # second step compile on the smoke budget)
                ref = compiled(ws_fresh(), t, dt, rhs_args)
                got = prog(ws_fresh(), t, dt, rhs_args)
                jax.block_until_ready(ref)
                jax.block_until_ready(got)
                bitexact = all(
                    np.array_equal(np.asarray(got[k]), np.asarray(ref[k]))
                    for k in ref)
            warm_artifacts.append({
                "label": "smoke_step",
                "fingerprint": meta["fingerprint"],
                "match": match, "bitexact": bitexact})
            hb(f"smoke: warm-start round trip "
               f"{'bit-exact' if bitexact else 'FAILED' if match else 'MISMATCH'}"
               f" [{meta['fingerprint']}]")
        except Exception as e:  # noqa: BLE001 — record, never kill smoke
            hb(f"smoke: warm-start leg failed: {type(e).__name__}: {e}")
            traceback.print_exc()
            warm_artifacts.append({"label": "smoke_step",
                                   "match": False,
                                   "reason": f"{type(e).__name__}: {e}"})

    # the cold-start record the ledger's `cold_start` section (and the
    # gate's cold-start verdicts) are built from
    totals = obs.compile_totals()
    obs.emit("cold_start",
             time_to_first_step_s=time_to_first_step_s,
             phases={"import_s": import_s, "build_s": build_s,
                     "trace_s": rec.trace_seconds,
                     "compile_s": rec.compile_seconds,
                     "first_dispatch_s": first_dispatch_s},
             cache={"dir": cache_dir,
                    "hits": totals["cache_hits"],
                    "misses": totals["cache_misses"]},
             warmstart={"claimed": bool(warm_artifacts
                                        and warm_artifacts[0]["match"]),
                        "artifacts": warm_artifacts})

    # static analysis, end to end: the SOURCE tier over the package and
    # the IR tier over the very step executable this run just timed —
    # the verdict lands in the event log (kind="lint"), the ledger's
    # `lint` report section, and the gate's refusal trigger, plus
    # lint_report.json next to the perf report
    from pystella_tpu import lint as _lint
    lint_rep = _lint.run_lint(run_graph=False)
    # per-target static comm model blocks (dataflow tier) — joined by
    # the ledger against the measured halo/fft traffic into the
    # report's modeled-vs-measured `comm` section
    static_comm = {}
    try:
        # the audits read the very (donated) program this run timed
        asm = stepper._jit_step.lower(
            state, t, dt, rhs_args).compiler_ir().operation.get_asm(
                enable_debug_info=True)
        graph_violations, graph_stats = _lint.audit_artifacts(
            "smoke_step", asm, compiled.as_text(),
            donatable_bytes=sum(v.nbytes for v in state.values()),
            dtype_policy=_lint.POLICY_F32,
            fused_scopes=("rk_stage",))
        lint_rep.extend(graph_violations)
        df_viol, df_stats = _lint.audit_dataflow_artifacts(
            "smoke_step", asm, compiled.as_text(),
            dtype_policy=_lint.POLICY_F32)
        lint_rep.extend(df_viol)
        graph_stats.update(df_stats)
        static_comm["smoke_step"] = df_stats["static_comm"]
        lint_rep.graph = {"smoke_step": graph_stats}
        lint_rep.donation = graph_stats.get("donation")
        for chk in _lint.GRAPH_CHECKS + _lint.DATAFLOW_CHECKS:
            lint_rep.add_check(chk)
    except Exception as e:  # noqa: BLE001 — record, never kill the run
        lint_rep.extend([_lint.Violation(
            checker="graph-build", where="smoke_step", severity="warning",
            message=f"IR audit of the smoke step failed: "
                    f"{type(e).__name__}: {e}")])
    if spectra_seg is not None:
        # the spectral-tier acceptance pin: the compiled pencil-spectra
        # program may carry ONLY the allowlisted all_to_all transposes
        # — an all-gather of a field-sized operand there means the
        # transform replicated, the cliff the tier exists to remove
        try:
            from pystella_tpu.lint.targets import TRANSPOSE_COLLECTIVES
            _, _, sspec, sfx, _ = spectra_seg
            sfn, sk_args = sspec.spectrum_program(outer_shape=(2,),
                                                  k_power=3)
            s_asm, s_hlo = _lint.lower_and_compile(
                sfn, (sfx,) + sk_args)
            s_viol, s_stats = _lint.audit_artifacts(
                "smoke_spectra", s_asm, s_hlo,
                dtype_policy=_lint.POLICY_SPECTRAL_F32,
                collectives=dict(TRANSPOSE_COLLECTIVES),
                fused_scopes=("fft_stage", "fft_transpose"))
            lint_rep.extend(s_viol)
            sdf_viol, sdf_stats = _lint.audit_dataflow_artifacts(
                "smoke_spectra", s_asm, s_hlo,
                dtype_policy=_lint.POLICY_SPECTRAL_F32)
            lint_rep.extend(sdf_viol)
            s_stats.update(sdf_stats)
            static_comm["smoke_spectra"] = sdf_stats["static_comm"]
            lint_rep.graph = {**(lint_rep.graph or {}),
                              "smoke_spectra": s_stats}
        except Exception as e:  # noqa: BLE001 — record, never kill it
            lint_rep.extend([_lint.Violation(
                checker="graph-build", where="smoke_spectra",
                severity="warning",
                message=f"IR audit of the spectra program failed: "
                        f"{type(e).__name__}: {e}")])
    if overlap_seg is not None:
        # static comm model of the overlapped-halo program — the very
        # program the halo_traffic event measures, so the ledger's comm
        # section can put modeled and measured halo bytes side by side
        try:
            _, ofd_a, ox_a = overlap_seg
            o_asm, o_hlo = _lint.lower_and_compile(
                jax.jit(lambda x: ofd_a.lap(x)), (ox_a,))
            o_viol, o_stats = _lint.audit_dataflow_artifacts(
                "smoke_overlap", o_asm, o_hlo,
                dtype_policy=_lint.POLICY_F32)
            lint_rep.extend(o_viol)
            static_comm["smoke_overlap"] = o_stats["static_comm"]
            lint_rep.graph = {**(lint_rep.graph or {}),
                              "smoke_overlap": o_stats}
        except Exception as e:  # noqa: BLE001 — record, never kill it
            lint_rep.extend([_lint.Violation(
                checker="graph-build", where="smoke_overlap",
                severity="warning",
                message=f"dataflow audit of the overlap program "
                        f"failed: {type(e).__name__}: {e}")])
    lint_path = lint_rep.write(os.path.join(args.out, "lint_report.json"))
    lint_summary = lint_rep.summary()
    hb(f"smoke: lint {'PASS' if lint_rep.ok else 'FAIL'} "
       f"({lint_summary['errors']} error(s), "
       f"{lint_summary['warnings']} warning(s)) -> {lint_path}")
    obs.emit("lint", ok=lint_rep.ok, errors=lint_summary["errors"],
             warnings=lint_summary["warnings"],
             checks=lint_summary["checks"],
             donation=lint_summary.get("donation"),
             static_comm=static_comm or None,
             first_errors=[str(v) for v in lint_rep.errors[:5]],
             report_path=lint_path)

    ledger = obs.PerfLedger.from_events(
        events_path, registry=obs.registry(), label=f"smoke-{n}^3",
        step_label="smoke_step")
    report_path = ledger.write(args.out)
    rep = ledger.report()
    st = rep["steps"]
    hb(f"smoke: p50 {st['p50_ms']:.3f} ms/step (MAD {st['mad_ms']:.3f}), "
       f"{len(rep['scopes'])} scope(s) in breakdown -> {report_path}")
    # stdout metric line + event, via the SMOKE event log (not the
    # orchestrator's long-lived run_events.jsonl — smoke is self-contained)
    metric = (f"smoke p50 ms/step ({n}^3 preheating, generic, "
              f"{jax.default_backend()})")
    print(json.dumps({"metric": metric, "value": st["p50_ms"],
                      "unit": "ms/step", "vs_baseline": None}), flush=True)
    obs.emit("bench_metric", metric=metric, value=st["p50_ms"],
             unit="ms/step")
    return report_path


# ---------------------------------------------------------------------------
# the configurations, each at the size it was written for
# ---------------------------------------------------------------------------

def run_chunk4(n=128, nsteps=4, dtype=np.float32):
    """One ``multi_step`` through the depth-4 whole-RK-chunk kernel (on
    no default path) against the pair tier it is bit-exact with by
    design, from one seeded state; returns the max abs difference."""
    import jax
    import pystella_tpu as ps

    grid_shape = (n, n, n)
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=dtype)
    dt = dtype(0.1 * min(lattice.dx))
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])

    def potential(f):
        return 0.5 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    sector = ps.ScalarSector(2, potential=potential)
    rng = np.random.default_rng(33)
    host = {k: 0.1 * rng.standard_normal((2,) + grid_shape).astype(dtype)
            for k in ("f", "dfdt")}
    args = {"a": dtype(1.0), "hubble": dtype(0.1)}
    outs = []
    for depth in (4, 0):
        stepper = ps.FusedScalarStepper(
            sector, decomp, grid_shape, lattice.dx, 2, dtype=dtype, dt=dt,
            chunk_stages=depth)
        if depth and stepper._chunk_call is None:
            raise RuntimeError("the depth-4 chunk kernel was not built")
        out = stepper.multi_step(
            {k: decomp.shard(v) for k, v in host.items()}, nsteps,
            dtype(0.0), dt, args)
        outs.append({k: np.asarray(v) for k, v in out.items()})
    return max(float(np.max(np.abs(outs[0][k] - outs[1][k])))
               for k in outs[0])


#: name -> (callable, unit, BASELINE.json target or None). Parity rows
#: report a max relative (chunk4: absolute) difference, not a rate.
CONFIGS = {
    "preheat-128^3": (lambda: run_preheat(128), "site-updates/s", 1e9),
    "preheat-256^3": (lambda: run_preheat(256), "site-updates/s", 1e9),
    "preheat-512^3": (lambda: run_preheat(512), "site-updates/s", 1e9),
    "pallas-parity-128^3": (run_pallas_parity, "max rel diff", None),
    "resident-parity-64^3": (run_resident_parity, "max rel diff", None),
    "chunk4-parity-128^3": (run_chunk4, "max abs diff", None),
    "wave-64^3": (lambda: run_wave(64), "site-updates/s", 1e9),
    "gw-spectra-256^3": (lambda: run_gw_spectra(256), "ms/call", None),
    "gw-step-256^3": (lambda: run_gw_step(256), "site-updates/s", 1e9),
    "gw-step-512^3-bf16carry": (
        lambda: run_gw_step(512, carry_dtype="bfloat16"),
        "site-updates/s", 1e9),
    "coupled-science-512^3": (lambda: run_coupled(512),
                              "site-updates/s", 1e9),
    # 512^3 spent 240-365 s compiling in its only chip session
    # (2026-07-31, pre-PR-1 code); 256^3 until that is attacked
    "multigrid-256^3": (lambda: run_multigrid(256), "ms/V-cycle", None),
}


def main(names):
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:
        raise SystemExit(f"unknown configuration(s) {unknown}; "
                         f"have {list(CONFIGS)}")
    import jax
    from pystella_tpu import config, obs

    dev = device_block()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; JAX found {dev} "
            "(`python bench.py --smoke` is the CPU pipeline check)")
    if not config.getenv("PYSTELLA_EVENT_LOG"):
        obs.configure(os.path.join(HERE, "bench_results",
                                   "run_events.jsonl"))
    cache_dir = obs.ensure_compilation_cache()
    hb(f"device {dev}, jax {jax.__version__}, compile cache {cache_dir}")
    obs.device_memory_report(label="bench-start")
    for name in names or list(CONFIGS):
        fn, unit, base = CONFIGS[name]
        hb(f"config: {name}")
        t0 = time.perf_counter()
        val = fn()
        emit(name, val, unit, val / base if base else None)
        hb(f"{name}: {val:.4g} {unit} "
           f"({time.perf_counter() - t0:.1f}s incl. compile)")
    totals = obs.compile_totals()
    obs.device_memory_report(label="bench-end")
    hb(f"done: trace {totals['trace_s']:.1f}s, compile "
       f"{totals['compile_s']:.1f}s, cache hits {totals['cache_hits']} / "
       f"misses {totals['cache_misses']}")


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        run_smoke([a for a in sys.argv[1:] if a != "--smoke"])
    else:
        main(sys.argv[1:])
