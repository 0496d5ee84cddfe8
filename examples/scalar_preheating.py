"""Scalar-field preheating after inflation, with optional gravitational-wave
production — the flagship application.

TPU-native analog of /root/reference/examples/scalar_preheating.py:28-283:
two (or more) coupled scalars in conformal FLRW spacetime with WKB
vacuum-fluctuation initial conditions, self-consistent scale-factor
evolution via the Friedmann equations, energy reductions, power spectra,
histograms, and provenance-rich HDF5 output — over a sharded device mesh.
"""

import os
import time
from argparse import ArgumentParser

#: process-start anchor for the cold_start event's time-to-first-step —
#: set BEFORE the jax/package imports below, which are the largest
#: fixed phase of the breakdown the event reports
_T0 = time.perf_counter()

import numpy as np

import jax.numpy as jnp

import pystella_tpu as ps

parser = ArgumentParser()
parser.add_argument("--grid-shape", "-grid", type=int, nargs=3,
                    metavar=("Nx", "Ny", "Nz"), default=(128, 128, 128))
parser.add_argument("--proc-shape", "-proc", type=int, nargs=3,
                    metavar=("Npx", "Npy", "Npz"), default=(1, 1, 1))
parser.add_argument("--dtype", type=np.dtype, default=np.float64,
                    help="field dtype; float64 needs jax's x64 mode "
                         "(JAX_ENABLE_X64=1) — without it jax truncates "
                         "to float32 with only a warning, so pass "
                         "float32 explicitly for a float32 run")
parser.add_argument("--halo-shape", type=int, default=2, metavar="h",
                    help="stencil radius, 1-4 (centred differences of "
                         "order 2-8; anything else is refused); 0 "
                         "selects spectral derivatives")
parser.add_argument("--box-dim", "-box", type=float, nargs=3,
                    metavar=("Lx", "Ly", "Lz"), default=(5., 5., 5.))
parser.add_argument("--kappa", type=float, default=1 / 10,
                    help="timestep to grid-spacing ratio")
parser.add_argument("--mpl", type=float, default=1.)
parser.add_argument("--mphi", type=float, default=1.20e-6)
parser.add_argument("--mchi", type=float, default=0.)
parser.add_argument("--gsq", type=float, default=2.5e-7)
parser.add_argument("--sigma", type=float, default=0.)
parser.add_argument("--lambda4", type=float, default=0.)
parser.add_argument("--end-time", "-end-t", type=float, default=20)
parser.add_argument("--end-scale-factor", "-end-a", type=float, default=20)
parser.add_argument("--gravitational-waves", "-gws", action="store_true")
parser.add_argument("--outfile", type=str, default=None)
parser.add_argument("--seed", type=int, default=49279)
parser.add_argument("--fused", action="store_true",
                    help="use the fused Pallas RK stages (halo-shape "
                         ">= 1; the mesh may shard x and y, not z: "
                         "-proc px py 1). On an x-only mesh (-proc N 1 "
                         "1) the stage and pair kernels take the "
                         "interior/shell halo-overlap split unless "
                         "PYSTELLA_HALO_OVERLAP=0")
parser.add_argument("--carry-dtype", type=jnp.dtype, default=None,
                    metavar="DTYPE",
                    help="with --fused: storage precision of the RK "
                         "2N-storage carries (default: --dtype, as "
                         "upstream's k registers). bfloat16 stores "
                         "them in half the bytes: a quarter less "
                         "traffic per stage (-gws at 384^3 float32: "
                         "10.9 GB a stage instead of 14.5), and what "
                         "lets a chunk of ONE step fit a 16 GB chip "
                         "there (4-step chunks fit either way). It "
                         "costs accuracy: on the chip the fields end "
                         "17x, the tensors 9x further from the plain "
                         "float32 reference (PERF.md section 6, PR "
                         "28); arithmetic stays in --dtype")
parser.add_argument("--chunk-steps", type=int, default=0, metavar="N",
                    help="with --fused: advance N steps per device "
                         "dispatch (one jitted chunk, no per-stage host "
                         "round-trips). Energy output and checkpoint "
                         "cadence coarsen to chunk boundaries. See "
                         "--chunk-mode for the accuracy tradeoff.")
parser.add_argument("--chunk-mode", choices=("coupled", "frozen"),
                    default="coupled",
                    help="coupled (default): single-stage kernels emit "
                         "in-VMEM energy sums and the Friedmann ODE "
                         "integrates on device with exact per-stage "
                         "feedback — driver-loop accuracy at chunked "
                         "speed. frozen: stage-pair kernels (the bench "
                         "hot path, ~2x less HBM traffic) with the "
                         "background precomputed from the chunk-entry "
                         "energy — first-order background coupling, "
                         "measured constraint drift ~3e-2 at 32^3/t=1/"
                         "N=4 vs 6e-8 exact; benchmark / fixed-"
                         "background use.")
parser.add_argument("--chunk-pair", choices=("auto", "on", "off"),
                    default="auto",
                    help="with --chunk-mode coupled: run the chunk "
                         "through the deferred-drag stage-PAIR kernels "
                         "(exact coupling at pair-fused HBM traffic). "
                         "auto uses them when available; off forces "
                         "single-stage kernels (one global energy "
                         "barrier per stage).")
parser.add_argument("--spectra-cadence", type=float, default=1.05,
                    metavar="RATIO",
                    help="scale-factor growth ratio between spectra "
                         "outputs (spectra/histograms recompute each "
                         "time a grows by this factor; 1.0 outputs "
                         "every driver step). Each output's wall time "
                         "is emitted as a spectra_time run event, so "
                         "spectra cost shows up in run_events.jsonl as "
                         "a per-output-step series the perf ledger's "
                         "`fft` section summarizes — spectra are the "
                         "dominant cost of runs that output them "
                         "(241 ms/call at 256^3 vs a sub-ms step)")
parser.add_argument("--fft-scheme", type=str, default=None,
                    metavar="SCHEME",
                    help="distributed-FFT scheme for the SPECTRA/"
                         "projection transform: 'pencil' forces the "
                         "fully distributed shard_map pencil tier "
                         "(fourier.pencil), default follows "
                         "PYSTELLA_FFT_SCHEME ('auto' keeps the "
                         "DFT tiering). The derivative/initialization "
                         "transform is unaffected (with --halo-shape 0 "
                         "on a mesh it is make_dft's own choice)")
parser.add_argument("--checkpoint-dir", type=str, default=None,
                    help="enable checkpoint/resume under this directory")
parser.add_argument("--checkpoint-interval", type=int, default=100,
                    metavar="STEPS")
parser.add_argument("--health-every", type=int, default=50,
                    metavar="STEPS",
                    help="poll lag of the async numerics sentinel: the "
                    "driver observes a health vector every iteration "
                    "(no sync) and only ever blocks on one at least "
                    "this many steps behind (doc/observability.md "
                    "'Numerics health')")
parser.add_argument("--forensics-dir", type=str, default="forensics",
                    metavar="DIR",
                    help="where a forensic bundle is written when the "
                    "sentinel trips (last-K health vectors, event-log "
                    "tail, config/env fingerprint, last-good-checkpoint"
                    " pointer); only created on divergence")
parser.add_argument("--event-log", type=str, default=None,
                    metavar="PATH", help="structured JSONL run-event log"
                    " (doc/observability.md); PYSTELLA_EVENT_LOG also"
                    " works")
parser.add_argument("--profile", type=str, default=None, metavar="DIR",
                    help="capture a jax.profiler trace of a step window"
                    " under DIR; the parsed per-scope durations are"
                    " emitted as a trace_summary run event")
parser.add_argument("--profile-start", type=int, default=10,
                    metavar="STEP", help="first profiled step (leave"
                    " room for jit compilation to finish)")
parser.add_argument("--profile-steps", type=int, default=20, metavar="N",
                    help="length of the profiled step window")
parser.add_argument("--perf-report", type=str, default=None,
                    metavar="DIR", help="at run end, digest the event"
                    " log + metrics registry into perf_report.json/.md"
                    " under DIR (requires --event-log or"
                    " PYSTELLA_EVENT_LOG)")


def main(argv=None):
    import jax
    p = parser.parse_args(argv)
    if p.event_log is not None:
        # HealthMonitor divergences, checkpoint saves/restores, per-step
        # timings, and StepTimer reports then all land in one greppable
        # record
        ps.obs.configure(p.event_log)
    if p.perf_report is not None and p.event_log is None \
            and not ps.config.getenv("PYSTELLA_EVENT_LOG"):
        raise ValueError("--perf-report digests the event log: pass "
                         "--event-log (or set PYSTELLA_EVENT_LOG)")
    if p.halo_shape not in range(5):
        raise ValueError(
            f"--halo-shape {p.halo_shape}: takes 0-4 (0: spectral "
            "derivatives; 1-4: the stencil radii of the coefficient "
            "tables, order 2-8)")
    cache_dir = ps.obs.ensure_compilation_cache()
    p.grid_shape = tuple(p.grid_shape)
    p.proc_shape = tuple(p.proc_shape)
    p.box_dim = tuple(p.box_dim)
    p.grid_size = float(np.prod(p.grid_shape))

    lattice = ps.Lattice(p.grid_shape, p.box_dim, dtype=p.dtype)
    dt = p.kappa * min(lattice.dx)

    p.nscalars = 2
    f0 = [.193 * p.mpl, 0]
    df0 = [-.142231 * p.mpl, 0]
    Stepper = ps.LowStorageRK54

    ndev = int(np.prod(p.proc_shape))
    decomp = ps.DomainDecomposition(p.proc_shape,
                                    devices=jax.devices()[:ndev])
    # the seeded fluctuations come back from their modes by matrix
    # products: XLA's inverse real transform is wrong on the TPU
    fft_kw = dict(grid_shape=p.grid_shape, dtype=p.dtype,
                  real_inverse="matmul")
    if p.halo_shape == 0 and ndev > 1:
        # derivatives by transforms on a mesh: forty distributed
        # transforms a step, so the planner picks the tier (PencilFFT's
        # explicit all_to_all where the lattice allows it: at 512^3 a
        # chip on (2,2,1) a step is 2.74 s where the declarative
        # reshards of ps.DFT take 3.79: PERF.md section 6, PR 46)
        fft = ps.make_dft(decomp, **fft_kw)
    else:
        fft = ps.DFT(decomp, **fft_kw)
    if p.halo_shape == 0:
        derivs = ps.SpectralCollocator(fft, lattice.dk)
    else:
        derivs = ps.FiniteDifferencer(decomp, p.halo_shape, lattice.dx)

    def potential(f):
        phi, chi = f[0], f[1]
        unscaled = (p.mphi**2 / 2 * phi**2
                    + p.mchi**2 / 2 * chi**2
                    + p.gsq / 2 * phi**2 * chi**2
                    + p.sigma / 2 * phi * chi**2
                    + p.lambda4 / 4 * chi**4)
        return unscaled / p.mphi**2

    scalar_sector = ps.ScalarSector(p.nscalars, potential=potential)
    sectors = [scalar_sector]
    if p.gravitational_waves:
        gw_sector = ps.TensorPerturbationSector([scalar_sector])
        sectors.append(gw_sector)

    merged = {}
    for sector in sectors:
        merged.update(sector.rhs_dict)
    sector_rhs = ps.compile_rhs_dict(merged)

    def full_rhs(state, t, a, hubble):
        aux = {"lap_f": derivs.lap(state["f"]), "a": a, "hubble": hubble}
        if p.gravitational_waves:
            aux["dfdx"] = derivs.grad(state["f"])
            aux["lap_hij"] = derivs.lap(state["hij"])
        return sector_rhs(state, t, **aux)

    if p.fused and p.halo_shape == 0:
        raise ValueError("--fused requires finite differences "
                         "(--halo-shape >= 1), not spectral derivatives")
    if p.chunk_steps and not p.fused:
        raise ValueError("--chunk-steps requires --fused (multi_step is "
                         "a fused-stepper driver)")
    if p.carry_dtype is not None and not p.fused:
        raise ValueError("--carry-dtype requires --fused (the generic "
                         "steppers keep their carries in --dtype)")
    if p.fused:
        # donate=True: the driver loop never reuses a consumed dfdt or
        # carry, so the per-stage kernel writes them in place and each
        # stage program is that kernel alone, at state + carry + one
        # fresh f (doc/performance.md "Memory")
        fused_kw = dict(tableau=Stepper, dtype=p.dtype, dt=dt,
                        donate=True, carry_dtype=p.carry_dtype)
        if p.gravitational_waves:
            stepper = ps.FusedPreheatStepper(
                scalar_sector, gw_sector, decomp, p.grid_shape,
                lattice.dx, p.halo_shape, **fused_kw)
        else:
            stepper = ps.FusedScalarStepper(
                scalar_sector, decomp, p.grid_shape, lattice.dx,
                p.halo_shape, **fused_kw)
    else:
        stepper = Stepper(full_rhs, dt=dt)

    reduce_energy = ps.Reduction(decomp, scalar_sector,
                                 callback=ps.get_rho_and_p,
                                 grid_size=p.grid_size)

    def compute_energy(state, a):
        return reduce_energy(f=state["f"], dfdt=state["dfdt"],
                             lap_f=derivs.lap(state["f"]),
                             a=np.float64(a))

    # observables
    # default output lands in bench_results/ beside the other run
    # artifacts (an explicit --outfile path is honored as given)
    out = ps.OutputFile(
        runfile=__file__, name=p.outfile,
        out_dir=os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench_results")) \
        if decomp.rank == 0 else None
    statistics = ps.FieldStatistics(decomp, grid_size=p.grid_size)
    # the spectra/projection transform may take the distributed pencil
    # tier (--fft-scheme pencil / PYSTELLA_FFT_SCHEME): spectra then
    # run shard-local end to end in one fused dispatch — the
    # derivative/initialization fft above keeps its own tiering
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume,
                              scheme=p.fft_scheme)
    projector = ps.Projector(fft, p.halo_shape, lattice.dk, lattice.dx,
                             scheme=p.fft_scheme)
    hist = ps.FieldHistogrammer(decomp, 1000, p.dtype)

    hubble_var = ps.Var("hubble")
    a_sq_rho = 3 * p.mpl**2 * hubble_var**2 / 8 / np.pi
    compute_rho = ps.ElementWiseMap(
        {ps.Field("rho"): scalar_sector.stress_tensor(0, 0) / a_sq_rho})

    def output(step_count, t, energy, expand, state):
        if step_count % 4 == 0:
            f_stats = statistics(state["f"])
            if out is not None:
                out.output(
                    "energy", t=t, a=expand.a,
                    adot=expand.adot / expand.a,
                    hubble=expand.hubble / expand.a,
                    **{k: np.asarray(v) for k, v in energy.items()},
                    eos=energy["pressure"] / energy["total"],
                    constraint=expand.constraint(energy["total"]))
                out.output("statistics/f", t=t, a=expand.a, **f_stats)

        if expand.a / output.a_last_spec >= p.spectra_cadence:
            output.a_last_spec = expand.a

            dfdx = derivs.grad(state["f"])
            rho = compute_rho(
                a=np.float64(expand.a), hubble=np.float64(expand.hubble),
                f=state["f"], dfdt=state["dfdt"], dfdx=dfdx)["rho"]
            rho_hist = hist(rho)
            # time the spectra block and emit one spectra_time event
            # per output: spectra cost becomes a per-output-step series
            # in the run record (the ledger's `fft` section summarizes
            # it), not a one-off microbenchmark. The calls finalize
            # their histograms on host, so the wall time is honest.
            t_spec0 = time.perf_counter()
            spec_out = {"scalar": spectra(state["f"]), "rho": spectra(rho)}

            if p.gravitational_waves:
                spec_out["gw"] = spectra.gw(state["dhijdt"], projector,
                                            expand.hubble)
            ps.obs.emit(
                "spectra_time", step=step_count,
                ms=(time.perf_counter() - t_spec0) * 1e3,
                a=float(expand.a), gw=bool(p.gravitational_waves),
                label="scalar_preheating")

            if out is not None:
                out.output("rho_histogram", t=t, a=expand.a, **rho_hist)
                out.output("spectra", t=t, a=expand.a, **spec_out)

    output.a_last_spec = .1

    print("Initializing fields")
    state = {
        "f": decomp.shard(np.stack(
            [np.full(p.grid_shape, f0[i], p.dtype)
             for i in range(p.nscalars)])),
        "dfdt": decomp.shard(np.stack(
            [np.full(p.grid_shape, df0[i], p.dtype)
             for i in range(p.nscalars)])),
    }
    if p.gravitational_waves:
        state["hij"] = decomp.zeros(p.grid_shape, p.dtype, outer_shape=(6,))
        state["dhijdt"] = decomp.zeros(p.grid_shape, p.dtype,
                                       outer_shape=(6,))

    # background energy -> initial expansion
    energy = compute_energy(state, 1.)
    expand = ps.Expansion(energy["total"], Stepper, mpl=p.mpl)

    # effective masses (with Hubble correction) for WKB initialization,
    # via symbolic second derivatives of the potential
    addot = expand.addot_friedmann_2(expand.a, energy["total"],
                                     energy["pressure"])
    hubble_correction = - addot / expand.a
    fsym = ps.Field("f0_bg", shape=(p.nscalars,))
    eff_mass = [
        float(ps.evaluate(ps.diff(potential(fsym), fsym[i], fsym[i]),
                          {"f0_bg": np.array(f0)})) + hubble_correction
        for i in range(p.nscalars)]

    modes = ps.RayleighGenerator(fft=fft, dk=lattice.dk,
                                 volume=lattice.volume, seed=p.seed)

    fluct_f, fluct_df = [], []
    for fld in range(p.nscalars):
        fx, dfx = modes.init_WKB_fields(
            norm=p.mphi**2,
            omega_k=lambda k, fld=fld: jnp.sqrt(k**2 + eff_mass[fld]),
            hubble=expand.hubble)
        fluct_f.append(np.asarray(fx))
        fluct_df.append(np.asarray(dfx))

    state["f"] = state["f"] + decomp.shard(np.stack(fluct_f))
    state["dfdt"] = state["dfdt"] + decomp.shard(np.stack(fluct_df))

    # re-initialize energy and expansion with fluctuations included
    energy = compute_energy(state, expand.a)
    expand = ps.Expansion(energy["total"], Stepper, mpl=p.mpl)

    t, step_count = 0., 0

    ckpt = None
    if p.checkpoint_dir is not None:
        ckpt = ps.Checkpointer(p.checkpoint_dir,
                               save_interval_steps=p.checkpoint_interval)
        if ckpt.latest_step is not None:
            step_count, state, meta = ckpt.restore(sharding_fn=decomp.shard)
            t = meta["t"]
            expand = ps.Expansion(meta["energy_total"], Stepper, mpl=p.mpl)
            expand.a = expand.dtype.type(meta["a"])
            expand.adot = expand.dtype.type(meta["adot"])
            expand.hubble = expand.adot / expand.a
            energy = compute_energy(state, expand.a)
            if decomp.rank == 0:
                print(f"Resumed from checkpoint at step {step_count}")

    output(step_count, t, energy, expand, state)

    if decomp.rank == 0:
        print("Time evolution beginning")
        print("time\t", "scale factor", "ms/step\t", "steps/second",
              sep="\t")
    ps.obs.emit("run_start", step=step_count, t=t, a=float(expand.a),
                grid_shape=p.grid_shape, proc_shape=p.proc_shape,
                gravitational_waves=p.gravitational_waves,
                chunk_steps=p.chunk_steps)
    setup_s = time.perf_counter() - _T0
    cold_start_pending = True

    # per-step step_time events cost nothing when no event log is
    # configured, and give the PerfLedger its step-time distribution
    # when one is (--event-log / PYSTELLA_EVENT_LOG)
    steptimer = ps.StepTimer(report_every=30.0, emit_steps=True)
    # async numerics sentinel: a per-iteration health vector (one tiny
    # fused dispatch, no sync) polled with a lag of health_every steps,
    # so the device queue never drains for a health check; a sync
    # check_now still guards every checkpoint save. On a trip the
    # forensic bundle is written before SimulationDiverged propagates.
    monitor = ps.HealthMonitor(every=p.health_every)
    monitor.forensics = ps.obs.ForensicSink(
        p.forensics_dir, events_path=ps.obs.get_log().path,
        checkpoint=ckpt, config={k: v for k, v in vars(p).items()
                                 if isinstance(v, (bool, int, float,
                                                   str, tuple, list,
                                                   type(None)))},
        label="scalar_preheating")

    # --profile: jax.profiler capture of a mid-run step window (entered
    # once compilation has settled), parsed into per-scope durations on
    # exit (obs.trace.capture emits the trace_summary event, with the
    # host-span table of the window, which is printed too)
    profiler = None
    profile_begin = None
    profile_done = p.profile is None

    carry = None
    try:
        while t < p.end_time and expand.a < p.end_scale_factor:
            if not profile_done and profiler is None \
                    and step_count >= p.profile_start:
                jax.block_until_ready(state)
                profiler = ps.obs.trace.capture(
                    p.profile, label="scalar_preheating", step=step_count)
                profiler.__enter__()
                profile_begin = step_count
            with ps.obs.host_span("driver_step"):
                if p.chunk_steps:
                    # chunked hot loop: one device dispatch per N steps
                    n = p.chunk_steps
                    if p.chunk_mode == "coupled":
                        # expansion ODE integrated on device, exact
                        # per-stage energy feedback (in-kernel
                        # reductions)
                        pair = {"auto": None, "on": True,
                                "off": False}[p.chunk_pair]
                        state = stepper.coupled_multi_step(
                            state, n, expand, t, dt,
                            grid_size=p.grid_size, pair=pair)
                    else:
                        # frozen-rho: host-precomputed background (see
                        # --chunk-mode help for the accuracy price)
                        a_seq, hubble_seq = expand.stage_sequence(
                            n, energy["total"], energy["pressure"], dt)
                        state = stepper.multi_step(
                            state, n, t, dt,
                            rhs_seq={"a": a_seq, "hubble": hubble_seq})
                    energy = compute_energy(state, expand.a)
                    t += n * dt
                    step_count += n
                else:
                    for s in range(stepper.num_stages):
                        carry = stepper(s, state if s == 0 else carry, t,
                                        a=np.float64(expand.a),
                                        hubble=np.float64(expand.hubble))
                        expand.step(s, energy["total"],
                                    energy["pressure"], dt)
                        if s == stepper.num_stages - 1:
                            state = carry
                            energy = compute_energy(state, expand.a)
                        else:
                            energy = compute_energy(
                                stepper.current(carry), expand.a)
                    t += dt
                    step_count += 1
            if cold_start_pending:
                # first driver step landed: the whole startup cost —
                # import, model build, tracing, backend compiles (or
                # cache hits) — is now behind us; the ledger's
                # cold_start section derives from this one event plus
                # the per-program compile events
                cold_start_pending = False
                totals = ps.obs.compile_totals()
                ps.obs.emit(
                    "cold_start",
                    time_to_first_step_s=time.perf_counter() - _T0,
                    phases={"setup_s": setup_s,
                            "trace_s": totals["trace_s"],
                            "compile_s": totals["compile_s"]},
                    cache={"dir": cache_dir,
                           "hits": totals["cache_hits"],
                           "misses": totals["cache_misses"]})
            if profiler is not None and not profile_done \
                    and step_count - profile_begin >= p.profile_steps:
                jax.block_until_ready(state)
                # the program's own host spans over the window: where
                # it dispatched, where it waited, how many host syncs
                # a step cost (also on the trace_summary event)
                profiler.steps = step_count - profile_begin
                profiler.__exit__(None, None, None)
                spans = (profiler.summary or {}).get("host_spans")
                if spans and decomp.rank == 0:
                    print("\n".join(
                        ps.obs.trace.format_host_spans(spans)))
                profiler, profile_done = None, True
            output(step_count, t, energy, expand, state)
            # host-side model invariants ride the same health record the
            # sentinel's field stats land in: the ledger's numerics
            # section derives invariant drift slopes from these, and the
            # gate fails CI when the constraint drifts worse than the
            # baseline (doc/observability.md "Numerics health")
            ps.obs.emit("health", step=step_count, invariants={
                "constraint": float(expand.constraint(energy["total"])),
                "energy_total": float(np.sum(energy["total"]))})
            # async numerics sentinel: observe dispatches one tiny fused
            # reduction (no sync); poll only ever converts vectors at
            # least health_every steps behind, so the driver loop stays
            # that far ahead of any device->host transfer
            monitor.observe(step_count, state)
            monitor.poll()
            # a NaN state must never be checkpointed: every save is
            # preceded by a SYNCHRONOUS health check of the exact state
            # being saved (the async poll lags by design); chunked runs
            # step past exact interval multiples, so the checkpoint
            # fires whenever this advance CROSSED a multiple (for
            # stride 1 this is the step_count % interval == 0 cadence)
            prev = step_count - (p.chunk_steps or 1)
            save_due = (ckpt is not None
                        and step_count // p.checkpoint_interval
                        > prev // p.checkpoint_interval)
            if save_due:
                monitor.check_now(state, step=step_count)
                # durability barrier for the PREVIOUS interval's save
                # (it had a whole interval to land in the background),
                # so last_good — the pointer a forensic bundle embeds —
                # only ever names checkpoints confirmed on disk
                ckpt.finalize()
                # force=True: orbax's interval policy would drop saves at
                # non-multiple steps (chunked crossings)
                ckpt.save(step_count, state, metadata={
                    "t": t, "a": float(expand.a),
                    "adot": float(expand.adot),
                    "energy_total": float(np.sum(energy["total"]))},
                    force=True)
            telemetry = steptimer.tick()
            if telemetry is not None and decomp.rank == 0:
                ms_per_step, steps_per_s = telemetry
                print(f"{t:<15.3f}", f"{expand.a:<15.3f}",
                      f"{ms_per_step:<15.3f}", f"{steps_per_s:<15.3f}")

        # normal completion (incl. silent NaN-exit from the while
        # condition): drain the async queue, then verify the FINAL
        # state synchronously before the final checkpoint
        monitor.flush()
        monitor.check_now(state, step=step_count)
        if ckpt is not None and ckpt.latest_step != step_count:
            ckpt.save(step_count, state, metadata={
                "t": t, "a": float(expand.a), "adot": float(expand.adot),
                "energy_total": float(np.sum(energy["total"]))})
        constraint = expand.constraint(energy["total"])
        if out is not None:
            out.file.attrs["final_constraint"] = constraint
    except BaseException as e:
        # the forensic tail of the run record: what killed the loop and
        # exactly when (HealthMonitor's diverged event, if any, directly
        # precedes this one)
        ps.obs.emit("run_aborted", step=step_count, t=t,
                    error=f"{type(e).__name__}: {e}")
        if p.fused and "RESOURCE_EXHAUSTED" in str(e):
            # the device's memory, at compile time or at an allocation:
            # say which flag buys room (the carries are half of the
            # stepper's arrays)
            held = jnp.dtype(p.carry_dtype or p.dtype)
            raise RuntimeError(
                "the step program does not fit the device's memory at "
                f"-grid {' '.join(map(str, p.grid_shape))} with {held} "
                "RK carries: " + (
                    "pass --carry-dtype bfloat16 (the carries stored in "
                    "fewer bytes, arithmetic unchanged) or "
                    if held.itemsize > 2 else "") + "a smaller -grid"
            ) from e
        raise
    finally:
        # finalize persistence even on divergence/interrupt so the last
        # good checkpoint and the HDF5 series survive
        if profiler is not None:
            profiler.__exit__(None, None, None)
        if ckpt is not None:
            ckpt.wait()
            ckpt.close()
        if out is not None:
            out.close()

    if decomp.rank == 0:
        print("Simulation complete")
        print(f"final constraint: {constraint:.16e}")
    # where the final state lived, beside how the run ended: a mesh run
    # whose state sits on one device is a failure this record shows
    ps.obs.emit("run_complete", step=step_count, t=t,
                a=float(expand.a), constraint=float(constraint),
                devices=len(state["f"].sharding.device_set),
                shard_shape=list(
                    state["f"].addressable_shards[0].data.shape),
                halo_bytes=decomp.traced_halo_bytes())
    if p.perf_report is not None:
        # digest this run's record into the evidence artifact the
        # regression gate consumes (python -m pystella_tpu.obs.gate)
        ledger = ps.obs.PerfLedger.from_events(
            ps.obs.get_log().path, registry=ps.obs.registry(),
            label="scalar_preheating", sites=int(p.grid_size))
        if decomp.rank == 0:
            print(f"perf report: {ledger.write(p.perf_report)}")
    return constraint


if __name__ == "__main__":
    main()
