"""The audited step functions: small, CPU-safe builds of the real
production computations.

Every builder constructs the SAME ``jax.jit`` objects the drivers
dispatch (``Stepper._jit_step``, ``Stepper._health_jit``,
``FusedScalarStepper._multi_jit`` / ``_coupled_jit``, the multigrid
smoother) on a tiny lattice, so the audited jaxpr/HLO is the real step
program — only the shapes are small. Builders run lazily inside
:func:`~pystella_tpu.lint.graph.audit_target`; a build failure is
itself a lint finding.

The sharded targets want >= 4 devices (the lint CLI forces an 8-device
host-platform mesh, like the test suite); with fewer they degrade to a
single-device mesh and the collective audit trivially passes.
"""

from __future__ import annotations

import numpy as np

from pystella_tpu.lint.graph import (POLICY_BF16_ACC32, POLICY_F32,
                                     POLICY_SPECTRAL_F32, GraphTarget)

__all__ = ["default_targets", "targets_by_name", "GRID"]

#: audited lattice (tiny: the hazards are shape-independent)
GRID = (16, 16, 16)

#: the ppermutes of a halo exchange are the one collective a sharded
#: stencil step is allowed to carry
HALO_COLLECTIVES = {
    "collective-permute": "halo exchange ppermutes "
                          "(parallel.decomp / parallel.overlap)",
}

#: sentinel / energy reductions over a sharded mesh land as all-reduce
REDUCTION_COLLECTIVES = {
    "all-reduce": "registered in-graph reductions (obs.sentinel health "
                  "vector, fused energy sums)",
}

#: the pencil-FFT stage redistributions are explicit all_to_alls — the
#: ONLY collective a sharded spectral program is allowed to carry: an
#: all-gather there means the transform replicated a field-sized
#: operand, exactly the cliff the pencil tier exists to remove
TRANSPOSE_COLLECTIVES = {
    "all-to-all": "pencil-FFT transposes (fourier.pencil per-stage "
                  "redistributions inside shard_map)",
}


def _mesh_decomp(want_sharded):
    import jax
    import pystella_tpu as ps
    if want_sharded and len(jax.devices()) >= 4:
        return ps.DomainDecomposition((2, 2, 1),
                                      devices=jax.devices()[:4])
    return ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])


def _preheat_parts(decomp, dtype=np.float32):
    """The two-field preheating system on ``GRID``:
    ``(stepper_rhs, state, t, dt, rhs_args)`` ingredients shared by the
    generic-step targets."""
    import pystella_tpu as ps
    lattice = ps.Lattice(GRID, (5.0, 5.0, 5.0), dtype=dtype)
    dt = dtype(0.1 * min(lattice.dx))
    mphi, gsq = 1.20e-6, 2.5e-7

    def potential(f):
        phi, chi = f[0], f[1]
        return (mphi**2 / 2 * phi**2 + gsq / 2 * phi**2 * chi**2) / mphi**2

    sector = ps.ScalarSector(2, potential=potential)
    derivs = ps.FiniteDifferencer(decomp, 2, lattice.dx, mode="halo")
    sector_rhs = ps.compile_rhs_dict(sector.rhs_dict)

    def full_rhs(state, t, a, hubble):
        return sector_rhs(state, t, lap_f=derivs.lap(state["f"]),
                          a=a, hubble=hubble)

    rng = np.random.default_rng(7)
    state = {
        "f": decomp.shard(
            1e-3 * rng.standard_normal((2,) + GRID).astype(dtype)),
        "dfdt": decomp.shard(
            1e-4 * rng.standard_normal((2,) + GRID).astype(dtype)),
    }
    rhs_args = {"a": dtype(1.0), "hubble": dtype(0.5)}
    return full_rhs, state, dtype(0.0), dt, rhs_args


def build_step_generic():
    """The generic (XLA-tier) LowStorageRK54 step on a sharded mesh."""
    import pystella_tpu as ps
    decomp = _mesh_decomp(want_sharded=True)
    full_rhs, state, t, dt, rhs_args = _preheat_parts(decomp)
    stepper = ps.LowStorageRK54(full_rhs, dt=dt, donate=True)
    return stepper._jit_step, (state, t, dt, rhs_args), {}, state


def build_step_sentinel():
    """The sentinel-piggybacked step (``Stepper.step_with_health``) on
    a sharded mesh: health reductions must fuse INTO the step module."""
    import jax.numpy as jnp
    import pystella_tpu as ps
    from pystella_tpu import obs
    decomp = _mesh_decomp(want_sharded=True)
    full_rhs, state, t, dt, rhs_args = _preheat_parts(decomp)
    stepper = ps.LowStorageRK54(full_rhs, dt=dt, donate=True)
    sentinel = obs.Sentinel.for_state(state, invariants={
        "kinetic_mean": lambda st, aux: 0.5 * jnp.mean(
            jnp.sum(jnp.square(st["dfdt"]), axis=0))})
    fn = stepper._health_jit(sentinel)
    return fn, (state, t, dt, rhs_args, {}), {}, state


def _fused_stepper():
    import jax.numpy as jnp
    import pystella_tpu as ps
    decomp = _mesh_decomp(want_sharded=False)
    lattice = ps.Lattice(GRID, (5.0, 5.0, 5.0), dtype=np.float32)

    def potential(f):
        return 0.5 * 1.2e-2 * f[0] ** 2 + 0.125 * f[0] ** 2 * f[1] ** 2

    sector = ps.ScalarSector(2, potential=potential)
    stepper = ps.FusedScalarStepper(
        sector, decomp, GRID, lattice.dx, 2, dtype=jnp.float32,
        bx=4, by=8)
    rng = np.random.default_rng(11)
    state = {
        "f": decomp.shard(
            1e-3 * rng.standard_normal((2,) + GRID).astype(np.float32)),
        "dfdt": decomp.shard(
            1e-4 * rng.standard_normal((2,) + GRID).astype(np.float32)),
    }
    dt = np.float32(0.01)
    return stepper, state, dt


def build_fused_multi_step():
    """``FusedScalarStepper.multi_step`` (2-step chunk with the
    sentinel piggyback) — the flagship hot-loop program."""
    import jax.numpy as jnp
    from pystella_tpu import obs
    stepper, state, dt = _fused_stepper()
    sentinel = obs.Sentinel.for_state(state, invariants={
        "kinetic_mean": lambda st, aux: 0.5 * jnp.mean(
            jnp.sum(jnp.square(st["dfdt"]), axis=0))})
    fn = stepper._multi_jit(2, sentinel=sentinel)
    args = (state,)
    kwargs = {"t": np.float32(0.0), "dt": dt,
              "rhs_args": {"a": np.float32(1.0),
                           "hubble": np.float32(0.5)},
              "rhs_seq": {}}
    return fn, args, kwargs, state


def build_chunk_multi_step():
    """``FusedScalarStepper.multi_step`` with the whole-RK-chunk
    (temporal blocking) kernel dispatched — the depth-4 resident-chunk
    program the roofline's tier record names, audited for donation /
    dtype / collectives exactly like the pair-tier chunk program."""
    import jax.numpy as jnp
    import pystella_tpu as ps
    decomp = _mesh_decomp(want_sharded=False)
    lattice = ps.Lattice(GRID, (5.0, 5.0, 5.0), dtype=np.float32)

    def potential(f):
        return 0.5 * 1.2e-2 * f[0] ** 2 + 0.125 * f[0] ** 2 * f[1] ** 2

    sector = ps.ScalarSector(2, potential=potential)
    stepper = ps.FusedScalarStepper(
        sector, decomp, GRID, lattice.dx, 2, dtype=jnp.float32,
        chunk_stages=4, chunk_bx=4, chunk_by=8)
    if stepper._chunk_call is None:
        raise RuntimeError("chunk kernel failed to build at the audit "
                           "shape — the fallback warning says why")
    rng = np.random.default_rng(11)
    state = {
        "f": decomp.shard(
            1e-3 * rng.standard_normal((2,) + GRID).astype(np.float32)),
        "dfdt": decomp.shard(
            1e-4 * rng.standard_normal((2,) + GRID).astype(np.float32)),
    }
    fn = stepper._multi_jit(2)
    args = (state,)
    kwargs = {"t": np.float32(0.0), "dt": np.float32(0.01),
              "rhs_args": {"a": np.float32(1.0),
                           "hubble": np.float32(0.5)},
              "rhs_seq": {}}
    return fn, args, kwargs, state


def build_bf16_chunk_multi_step():
    """The ROADMAP mixed-precision production tier's chunk program:
    ``carry_dtype=bf16`` keeps the RK carries (``kf``/``kdfdt``) in
    bf16 between stages while state and every accumulation stay f32.
    Audited under ``POLICY_BF16_ACC32`` — the dataflow tier must see
    every f32->bf16 narrowing under the registered ``carry_quantize``
    scope (ops/fused.py ``CARRY_SCOPE``) and no bf16 on any
    accumulation chain; this is the flow property the set-based dtype
    check cannot express (bf16 AND f32 are both in the allow-set)."""
    import jax.numpy as jnp
    import pystella_tpu as ps
    decomp = _mesh_decomp(want_sharded=False)
    lattice = ps.Lattice(GRID, (5.0, 5.0, 5.0), dtype=np.float32)

    def potential(f):
        return 0.5 * 1.2e-2 * f[0] ** 2 + 0.125 * f[0] ** 2 * f[1] ** 2

    sector = ps.ScalarSector(2, potential=potential)
    stepper = ps.FusedScalarStepper(
        sector, decomp, GRID, lattice.dx, 2, dtype=jnp.float32,
        carry_dtype=jnp.bfloat16, chunk_stages=4, chunk_bx=4,
        chunk_by=8)
    if stepper._chunk_call is None:
        raise RuntimeError("bf16-carry chunk kernel failed to build at "
                           "the audit shape — the fallback warning "
                           "says why")
    rng = np.random.default_rng(11)
    state = {
        "f": decomp.shard(
            1e-3 * rng.standard_normal((2,) + GRID).astype(np.float32)),
        "dfdt": decomp.shard(
            1e-4 * rng.standard_normal((2,) + GRID).astype(np.float32)),
    }
    fn = stepper._multi_jit(2)
    args = (state,)
    kwargs = {"t": np.float32(0.0), "dt": np.float32(0.01),
              "rhs_args": {"a": np.float32(1.0),
                           "hubble": np.float32(0.5)},
              "rhs_seq": {}}
    return fn, args, kwargs, state


def build_coupled_multi_step():
    """``FusedScalarStepper.coupled_multi_step`` (on-device Friedmann
    background) — the expanding-universe chunk program."""
    import jax.numpy as jnp
    stepper, state, dt = _fused_stepper()
    pair = stepper._ensure_coupled_pair_calls() is not None
    stepper._ensure_energy_call()
    grid_size = float(np.prod(GRID))
    fn = stepper._coupled_jit(2, grid_size, 1.0, pair)
    args = (state,)
    kwargs = {"t": np.float32(0.0), "dt": dt,
              "a": jnp.float32(1.0), "adot": jnp.float32(0.1)}
    return fn, args, kwargs, state


def build_ensemble_step(size=4):
    """The vmapped ensemble step+health program
    (:meth:`pystella_tpu.ensemble.EnsembleStepper.health_jit`) on an
    ``(ensemble, x, y, z)`` mesh packing ``size`` members along the
    ensemble axis — the batched-population program the ensemble driver
    dispatches. Auditing it proves the batching preserved the
    single-run program's properties: state donation survives the vmap,
    per-member stencils/reductions stay shard-local on the member axis
    (no all-gather of the whole population), dtypes hold, and the
    member-axis sentinel reductions fuse into the one batched step
    module."""
    import jax
    import numpy as np
    import pystella_tpu as ps
    from pystella_tpu import obs

    ndev = min(size, max(1, len(jax.devices())))
    mesh = ps.ensemble_mesh(proc_shape=(1, 1, 1), ensemble_devices=ndev,
                            devices=jax.devices()[:ndev])
    decomp = ps.DomainDecomposition(mesh=mesh, ensemble_axis=
                                    mesh.axis_names[0])
    full_rhs, _, t, dt, rhs_args = _preheat_parts(decomp)
    # donate=True: the driver loop rebinds batch = step(batch), so the
    # input population buffers are dead — the audit pins that the
    # aliasing survives the vmap (a donation miss here doubles the
    # WHOLE population's HBM footprint, `size` times the single-run
    # cost)
    stepper = ps.LowStorageRK54(full_rhs, dt=dt, donate=True)
    ens = ps.EnsembleStepper(stepper, size, decomp=decomp, via="vmap",
                             donate=True)

    rng = np.random.default_rng(23)
    members = []
    for _ in range(size):
        members.append({
            "f": 1e-3 * rng.standard_normal(
                (2,) + GRID).astype(np.float32),
            "dfdt": 1e-4 * rng.standard_normal(
                (2,) + GRID).astype(np.float32),
        })
    batch = ens.stack(members)
    import jax.numpy as jnp
    sentinel = obs.Sentinel.for_state(members[0], invariants={
        "kinetic_mean": lambda st, aux: 0.5 * jnp.mean(
            jnp.sum(jnp.square(st["dfdt"]), axis=0))})
    fn = ens.health_jit(sentinel)
    t_vec = ens.batch_args(np.float32(0.0))
    dt_vec = ens.batch_args(dt)
    bargs = ens.batch_args(rhs_args)
    return fn, (batch, t_vec, dt_vec, bargs, {}), {}, batch


def build_sharded_spectra():
    """The pencil-tier spectra program on a sharded mesh: ONE jitted
    module from the position-space fields to per-device partial bin
    sums — the distributed r2c transform (explicit all_to_all
    transposes), the ``counts·|k|³·|f(k)|²`` weighting, and the
    shard-local binning kernel. Auditing it pins the acceptance
    contract of the spectral tier: the compiled module's only
    collectives are the allowlisted transposes — no all-gather of a
    field-sized operand anywhere in the spectra program — and no f64
    leaked into the f32 pipeline (complex64 is the transform's working
    type, POLICY_SPECTRAL_F32)."""
    import jax
    import pystella_tpu as ps
    decomp = _mesh_decomp(want_sharded=True)
    lattice = ps.Lattice(GRID, (5.0, 5.0, 5.0), dtype=np.float32)
    # force the pencil tier on the sharded mesh (GRID divides the
    # 4-device count); the <4-device fallback audits the local path
    nproc = int(np.prod(decomp.proc_shape))
    fft = ps.make_dft(decomp, grid_shape=GRID, dtype=np.float32,
                      scheme="pencil" if nproc > 1 else "auto")
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)
    fn, k_args = spectra.spectrum_program(outer_shape=(2,), k_power=3)
    rng = np.random.default_rng(17)
    fx = decomp.shard(
        1e-3 * rng.standard_normal((2,) + GRID).astype(np.float32))
    return fn, (fx,) + k_args, {}, None


def build_mg_smooth():
    """The multigrid V-cycle's hot kernel: a level-0 Jacobi smooth on a
    sharded mesh (the compiled body every cycle dispatches most)."""
    import jax
    import pystella_tpu as ps
    from pystella_tpu.multigrid import JacobiIterator
    from pystella_tpu.multigrid.relax import LevelSpec
    decomp = _mesh_decomp(want_sharded=True)
    solver = JacobiIterator(
        decomp, {ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho"))},
        halo_shape=1, dtype=np.float32,
        fixed_parameters=dict(omega=1 / 2))
    dx = 10.0 / GRID[0]
    sharded = any(p > 1 for p in decomp.proc_shape)
    level = LevelSpec(GRID, (dx,) * 3, sharded)
    rng = np.random.default_rng(5521)
    f = decomp.shard(rng.standard_normal(GRID).astype(np.float32))
    rho = decomp.shard(rng.standard_normal(GRID).astype(np.float32))

    def smooth(fs, rhos):
        return solver.smooth(level, fs, rhos, {}, 4, decomp)

    fn = jax.jit(smooth)
    return fn, ({"f": f}, {"rho": rho}), {}, None


def targets_by_name(names=None):
    """The audited targets as a name -> :class:`GraphTarget` dict,
    optionally restricted to ``names`` (unknown names raise). The
    registry is shared infrastructure now: the IR audit lowers these
    programs, and ``python -m pystella_tpu.obs.warmstart export``
    AOT-serializes the very same builds — one definition of "the
    dispatched step programs" for both."""
    table = {t.name: t for t in default_targets()}
    if names is None:
        return table
    missing = sorted(set(names) - set(table))
    if missing:
        raise KeyError(f"unknown lint target(s) {missing}; "
                       f"known: {sorted(table)}")
    return {n: table[n] for n in names}


def default_targets():
    """The audited target list (build callables stay lazy)."""
    return [
        GraphTarget(
            name="step_generic",
            build=build_step_generic,
            dtype_policy=POLICY_F32,
            collectives=dict(HALO_COLLECTIVES),
            fused_scopes=("rk_stage",),
        ),
        GraphTarget(
            name="step_sentinel",
            build=build_step_sentinel,
            dtype_policy=POLICY_F32,
            collectives={**HALO_COLLECTIVES, **REDUCTION_COLLECTIVES},
            fused_scopes=("rk_stage", "sentinel"),
        ),
        GraphTarget(
            name="fused_multi_step",
            build=build_fused_multi_step,
            dtype_policy=POLICY_F32,
            collectives=dict(REDUCTION_COLLECTIVES),
            fused_scopes=("fused_rk_stage", "sentinel"),
        ),
        GraphTarget(
            name="chunk_multi_step",
            build=build_chunk_multi_step,
            dtype_policy=POLICY_F32,
            collectives={},
            fused_scopes=("chunk_stage",),
        ),
        GraphTarget(
            name="bf16_chunk_multi_step",
            build=build_bf16_chunk_multi_step,
            dtype_policy=POLICY_BF16_ACC32,
            collectives={},
            # carry_quantize itself is NOT listed: interpret-mode
            # lowering erases in-kernel name stacks, so the carry casts
            # carry the chunk_stage/pallas_stencil dispatch path — the
            # dataflow tier's kernel_converts stat pins them instead
            fused_scopes=("chunk_stage",),
        ),
        GraphTarget(
            name="coupled_multi_step",
            build=build_coupled_multi_step,
            dtype_policy=POLICY_F32,
            collectives=dict(REDUCTION_COLLECTIVES),
            fused_scopes=("fused_",),
        ),
        GraphTarget(
            name="ensemble_step",
            build=build_ensemble_step,
            dtype_policy=POLICY_F32,
            # per-member lattices are unsharded on the ensemble mesh
            # (members pack the device axis), so the only collectives a
            # correct batched program may carry are the tiny sentinel
            # reductions — an all-gather here would mean the
            # partitioner is replicating the population
            collectives=dict(REDUCTION_COLLECTIVES),
            fused_scopes=("ensemble_step", "rk_stage", "sentinel"),
        ),
        GraphTarget(
            name="mg_smooth",
            build=build_mg_smooth,
            dtype_policy=POLICY_F32,
            collectives=dict(HALO_COLLECTIVES),
            fused_scopes=("mg_smooth",),
        ),
        GraphTarget(
            name="sharded_spectra",
            build=build_sharded_spectra,
            dtype_policy=POLICY_SPECTRAL_F32,
            # ONLY the pencil transposes: an all-gather of a
            # field-sized operand in the spectra program is exactly
            # the replication hazard the distributed tier removes
            collectives=dict(TRANSPOSE_COLLECTIVES),
            fused_scopes=("fft_stage",),
        ),
    ]
