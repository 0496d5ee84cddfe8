"""Fused Pallas RK stages must agree with the generic (unfused) path
bit-for-bit up to fp roundoff (reference semantics:
scalar_preheating.py:258-266 stage loop = stencil + RK-stage kernels)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pystella_tpu as ps
from pystella_tpu.ops.fused import FusedPreheatStepper, FusedScalarStepper

# Small-grid bodies run the Pallas stages in interpret mode (f64,
# bit-exact vs the generic stepper); compiled Mosaic kernels require
# Z % 128 == 0 and f32 — the on-device check is chip_smoke.py's
# fused_parity (fused vs XLA at 512^3 f32). Under a TPU-backed session these
# logic tests still run (ADVICE r3): arrays are placed on the host CPU
# device and the kernels forced to interpret mode, so the f64 bit-
# exactness pins hold without a Mosaic lowering.
_TPU_SESSION = jax.default_backend() == "tpu"
_XKW = {"interpret": True} if _TPU_SESSION else {}


def _arr(x):
    x = jnp.asarray(x)
    if _TPU_SESSION:
        return jax.device_put(x, jax.devices("cpu")[0])
    return x


@pytest.fixture
def decomp():
    devs = (jax.devices("cpu") if _TPU_SESSION else jax.devices())[:1]
    return ps.DomainDecomposition((1, 1, 1), devices=devs)


def _potential(f):
    return 0.5 * 1.2e-2 * f[0] ** 2 + 0.125 * f[0] ** 2 * f[1] ** 2


def _generic_step(decomp, grid_shape, dx, h, state, dt, a, hubble,
                  gravitational_waves=False):
    derivs = ps.FiniteDifferencer(decomp, h, dx, mode="halo")
    sector = ps.ScalarSector(2, potential=_potential)
    sectors = [sector]
    if gravitational_waves:
        sectors.append(ps.TensorPerturbationSector([sector]))
    merged = {}
    for s in sectors:
        merged.update(s.rhs_dict)
    rhs = ps.compile_rhs_dict(merged)

    def full_rhs(st, t, a, hubble):
        aux = {"lap_f": derivs.lap(st["f"]), "a": a, "hubble": hubble}
        if gravitational_waves:
            aux["dfdx"] = derivs.grad(st["f"])
            aux["lap_hij"] = derivs.lap(st["hij"])
        return rhs(st, t, **aux)

    stepper = ps.LowStorageRK54(full_rhs, dt=dt)
    return stepper.step(state, 0.0, dt, {"a": a, "hubble": hubble})


def test_pair_stages_match_single_stages(decomp):
    """The stage-pair kernel keeps the exact arithmetic sequence of two
    single-stage kernels (the intermediate field's Laplacian composes
    through the pointwise axpy), so pairing must be bit-level equivalent
    in f64 interpret mode."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = 0.01
    rng = np.random.default_rng(11)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
    }
    args = {"a": 1.3, "hubble": 0.21}

    sector = ps.ScalarSector(2, potential=_potential)
    kw = dict(dtype=jnp.float64, bx=4, by=8, **_XKW)
    paired = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                                pair_stages=True, **kw)
    single = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                                pair_stages=False, **kw)
    assert paired._pair_call is not None and single._pair_call is None

    got = paired.step(state, 0.0, dt, args)
    ref = single.step(state, 0.0, dt, args)
    for name in ("f", "dfdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = np.max(np.abs(np.asarray(ref[name])))
        assert err / scale < 1e-14, f"{name}: pair/single diverge ({err})"


@pytest.mark.slow
def test_multi_step_matches_sequential_steps(decomp):
    """multi_step pairs stages across step boundaries (A[0] == 0 makes
    the skipped k-carry reset a no-op) and must be bit-exact against
    sequential step() calls — for an even number of steps RK54's odd
    5th stage pairs with the next step's stage 0."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = 0.01
    rng = np.random.default_rng(13)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
    }
    args = {"a": 1.3, "hubble": 0.21}

    sector = ps.ScalarSector(2, potential=_potential)
    fused = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               dtype=jnp.float64, bx=4, by=8, **_XKW)
    for nsteps in (2, 3):
        ref = dict(state)
        for _ in range(nsteps):
            ref = fused.step(ref, 0.0, dt, args)
        # multi_step donates its input buffers — pass a fresh copy
        fresh = {k: _arr(np.asarray(v)) for k, v in state.items()}
        got = fused.multi_step(fresh, nsteps, 0.0, dt, args)
        for name in ("f", "dfdt"):
            err = np.max(np.abs(np.asarray(got[name])
                                - np.asarray(ref[name])))
            scale = np.max(np.abs(np.asarray(ref[name])))
            assert err / scale < 1e-14, \
                f"{name}@{nsteps}: multi_step diverges ({err})"


def test_multi_step_rhs_seq_matches_per_stage_loop(decomp):
    """Per-stage expansion scalars threaded through multi_step(rhs_seq=)
    must reproduce the driver's per-stage stage() loop bit-for-bit: the
    pairing only regroups kernels, the (a, hubble) entering each stage
    update is identical."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = 0.01
    rng = np.random.default_rng(17)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
    }

    sector = ps.ScalarSector(2, potential=_potential)
    fused = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               dtype=jnp.float64, bx=4, by=8, **_XKW)
    nsteps = 2
    nflat = nsteps * fused.num_stages
    a_seq = 1.0 + 0.01 * np.arange(nflat)
    h_seq = 0.2 - 0.003 * np.arange(nflat)

    # reference: the per-stage driver loop with evolving scalars
    ref = dict(state)
    i = 0
    for _ in range(nsteps):
        carry = fused.init_carry(ref)
        for s in range(fused.num_stages):
            carry = fused.stage(s, carry, 0.0, dt,
                                {"a": a_seq[i], "hubble": h_seq[i]})
            i += 1
        ref = fused.extract(carry)

    fresh = {k: _arr(np.asarray(v)) for k, v in state.items()}
    got = fused.multi_step(fresh, nsteps, 0.0, dt,
                           rhs_seq={"a": a_seq, "hubble": h_seq})
    for name in ("f", "dfdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = np.max(np.abs(np.asarray(ref[name])))
        assert err / scale < 1e-14, f"{name}: rhs_seq diverges ({err})"

    # malformed sequence lengths are rejected
    with pytest.raises(ValueError, match="rhs_seq"):
        fused.multi_step(dict(got), nsteps, 0.0, dt,
                         rhs_seq={"a": a_seq[:-1]})


@pytest.mark.slow
def test_coupled_multi_step_matches_driver_loop(decomp):
    """coupled_multi_step integrates the Friedmann ODE on device with
    per-stage energy feedback from in-kernel reductions; it must
    reproduce the reference-style driver loop (field stage -> Expansion
    stage with the entering state's energy) to fp-roundoff — the only
    difference is the summation order of the energy reduction."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = 0.01
    grid_size = float(np.prod(grid_shape))
    rng = np.random.default_rng(23)
    state = {
        "f": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.01 * rng.standard_normal((2,) + grid_shape)),
    }

    sector = ps.ScalarSector(2, potential=_potential)
    fused = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               dtype=jnp.float64, bx=4, by=8, **_XKW)
    derivs = ps.FiniteDifferencer(decomp, h, dx, mode="halo")
    reduce_energy = ps.Reduction(decomp, sector, callback=ps.get_rho_and_p,
                                 grid_size=grid_size)

    def energy_of(st, a):
        return reduce_energy(f=st["f"], dfdt=st["dfdt"],
                             lap_f=derivs.lap(st["f"]), a=np.float64(a))

    nsteps = 2

    # reference: the example's per-stage loop (field stage, expansion
    # stage on the entering energy, re-reduce)
    ref = dict(state)
    energy = energy_of(ref, 1.0)
    expand_ref = ps.Expansion(energy["total"], ps.LowStorageRK54)
    for _ in range(nsteps):
        carry = fused.init_carry(ref)
        for s in range(fused.num_stages):
            carry = fused.stage(s, carry, 0.0, dt,
                                {"a": np.float64(expand_ref.a),
                                 "hubble": np.float64(expand_ref.hubble)})
            expand_ref.step(s, energy["total"], energy["pressure"], dt)
            energy = energy_of(fused.current(carry), expand_ref.a)
        ref = fused.extract(carry)

    # coupled chunk (pair=False: the single-stage path is the one that
    # matches the driver loop to summation order; the pair path's
    # accuracy is quantified by test_coupled_pair_accuracy_vs_driver)
    energy0 = energy_of(state, 1.0)
    expand = ps.Expansion(energy0["total"], ps.LowStorageRK54)
    fresh = {k: _arr(np.asarray(v)) for k, v in state.items()}
    got = fused.coupled_multi_step(fresh, nsteps, expand, 0.0, dt,
                                   grid_size=grid_size, pair=False)

    for name in ("f", "dfdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = np.max(np.abs(np.asarray(ref[name])))
        assert err / scale < 1e-12, f"{name}: coupled diverges ({err})"
    assert abs(expand.a - expand_ref.a) / expand_ref.a < 1e-12
    assert abs(expand.adot - expand_ref.adot) / expand_ref.adot < 1e-12

    # the deferred-drag pair-fused coupled path (default) is EXACT: it
    # must match the driver loop to float roundoff too (the deferral
    # only re-associates one dt distribution)
    energy0 = energy_of(state, 1.0)
    expand_p = ps.Expansion(energy0["total"], ps.LowStorageRK54)
    fresh = {k: _arr(np.asarray(v)) for k, v in state.items()}
    assert fused._ensure_coupled_pair_calls() is not None
    got_p = fused.coupled_multi_step(fresh, nsteps, expand_p, 0.0, dt,
                                     grid_size=grid_size, pair=True)
    for name in ("f", "dfdt"):
        err = np.max(np.abs(np.asarray(got_p[name])
                            - np.asarray(ref[name])))
        scale = np.max(np.abs(np.asarray(ref[name])))
        assert err / scale < 1e-12, \
            f"{name}: pair-coupled diverges ({err})"
    assert abs(expand_p.a - expand_ref.a) / expand_ref.a < 1e-12
    assert abs(expand_p.adot - expand_ref.adot) / abs(expand_ref.adot) \
        < 1e-12


@pytest.mark.slow
def test_coupled_multi_step_gw(decomp):
    """The scalar+GW coupled chunk matches the per-stage driver loop
    (expansion couples to the scalar-sector energy only)."""
    grid_shape = (16, 16, 16)
    h, dx = 2, 0.3
    dt = 0.01
    grid_size = float(np.prod(grid_shape))
    rng = np.random.default_rng(29)
    state = {
        "f": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.01 * rng.standard_normal((2,) + grid_shape)),
        "hij": _arr(1e-3 * rng.standard_normal((6,) + grid_shape)),
        "dhijdt": _arr(1e-4 * rng.standard_normal((6,) + grid_shape)),
    }

    sector = ps.ScalarSector(2, potential=_potential)
    gw = ps.TensorPerturbationSector([sector])
    fused = FusedPreheatStepper(sector, gw, decomp, grid_shape, dx, h,
                                dtype=jnp.float64, bx=4, by=8, **_XKW)
    derivs = ps.FiniteDifferencer(decomp, h, (dx,) * 3, mode="halo")
    reduce_energy = ps.Reduction(decomp, sector, callback=ps.get_rho_and_p,
                                 grid_size=grid_size)

    def energy_of(st, a):
        return reduce_energy(f=st["f"], dfdt=st["dfdt"],
                             lap_f=derivs.lap(st["f"]), a=np.float64(a))

    nsteps = 2
    ref = dict(state)
    energy = energy_of(ref, 1.0)
    expand_ref = ps.Expansion(energy["total"], ps.LowStorageRK54)
    for _ in range(nsteps):
        carry = fused.init_carry(ref)
        for s in range(fused.num_stages):
            carry = fused.stage(s, carry, 0.0, dt,
                                {"a": np.float64(expand_ref.a),
                                 "hubble": np.float64(expand_ref.hubble)})
            expand_ref.step(s, energy["total"], energy["pressure"], dt)
            energy = energy_of(fused.current(carry), expand_ref.a)
        ref = fused.extract(carry)

    energy0 = energy_of(state, 1.0)
    expand = ps.Expansion(energy0["total"], ps.LowStorageRK54)
    fresh = {k: _arr(np.asarray(v)) for k, v in state.items()}
    got = fused.coupled_multi_step(fresh, nsteps, expand, 0.0, dt,
                                   grid_size=grid_size, pair=False)

    for name in ("f", "dfdt", "hij", "dhijdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = max(np.max(np.abs(np.asarray(ref[name]))), 1e-30)
        assert err / scale < 1e-12, f"{name}: coupled diverges ({err})"
    assert abs(expand.a - expand_ref.a) / expand_ref.a < 1e-12

    # deferred-drag pair-fused coupled chunk for the full scalar+GW
    # system: exact, so driver-loop parity to roundoff here too.
    # nsteps=1 (5 flat stages) exercises the preheat odd-tail path —
    # mid-chunk finalize of the deferred tensor drag + the single-stage
    # energy kernel; nsteps=2 ends on a deferred pair, exercising the
    # chunk-end finalize
    for n_pair in (1, 2):
        ref_p = fused.coupled_multi_step(
            {k: _arr(np.asarray(v)) for k, v in state.items()},
            n_pair, ps.Expansion(energy0["total"], ps.LowStorageRK54),
            0.0, dt, grid_size=grid_size, pair=False)
        expand_p = ps.Expansion(energy0["total"], ps.LowStorageRK54)
        fresh = {k: _arr(np.asarray(v)) for k, v in state.items()}
        got_p = fused.coupled_multi_step(fresh, n_pair, expand_p, 0.0,
                                         dt, grid_size=grid_size,
                                         pair=True)
        for name in ("f", "dfdt", "hij", "dhijdt"):
            err = np.max(np.abs(np.asarray(got_p[name])
                                - np.asarray(ref_p[name])))
            scale = max(np.max(np.abs(np.asarray(ref_p[name]))), 1e-30)
            assert err / scale < 1e-12, \
                f"{name}@{n_pair}: pair-coupled diverges ({err})"
    assert abs(expand_p.a - expand_ref.a) / expand_ref.a < 1e-12


def test_coupled_multi_step_sharded_x_matches_single():
    """Energy-coupled chunks on an x-sharded mesh (per-shard esums
    psum'ed inside the shard_map) match the single-device result."""
    if len(jax.devices()) < 2 or _TPU_SESSION:
        pytest.skip("needs 2 CPU devices")
    grid_shape = (16, 16, 16)
    h, dx, dt = 2, 0.3, 0.01
    grid_size = float(np.prod(grid_shape))
    rng = np.random.default_rng(31)
    state_h = {
        "f": 0.1 * rng.standard_normal((2,) + grid_shape),
        "dfdt": 0.01 * rng.standard_normal((2,) + grid_shape),
    }
    sector = ps.ScalarSector(2, potential=_potential)

    results = []
    for px in (1, 2):
        dp = ps.DomainDecomposition((px, 1, 1), devices=jax.devices()[:px])
        fp = FusedScalarStepper(sector, dp, grid_shape, dx, h,
                                dtype=jnp.float64, bx=4, by=8)
        expand = ps.Expansion(1e-3, ps.LowStorageRK54)
        st = {k: dp.shard(jnp.asarray(v)) for k, v in state_h.items()}
        got = fp.coupled_multi_step(st, 2, expand, 0.0, dt,
                                    grid_size=grid_size)
        results.append((got, expand.a, expand.adot))

    (ref, a1, adot1), (got, a2, adot2) = results
    for name in ("f", "dfdt"):
        assert np.allclose(np.asarray(got[name]), np.asarray(ref[name]),
                           rtol=1e-12, atol=1e-13), name
    assert abs(a2 - a1) / a1 < 1e-13
    assert abs(adot2 - adot1) / abs(adot1) < 1e-13


@pytest.mark.slow
def test_coupled_pair_accuracy_vs_driver(decomp):
    """The deferred-drag pair-coupled path is EXACT: against the
    per-stage coupled path (itself driver-loop-parity to summation
    order) it may differ only by the re-association of one ``dt``
    distribution in the deferred Hubble-drag completion — float
    roundoff, even in a violently-expanding O(1)-energy regime and for
    odd flat stage counts (the finalize-then-single trailing path)."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    grid_size = float(np.prod(grid_shape))
    rng = np.random.default_rng(41)
    # O(1) energies: hubble ~ 3, the harshest coupling regime — any
    # stale-background approximation would show up at ~1e-3 here
    # (measured for the rejected extrapolation predictor)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.3 * rng.standard_normal((2,) + grid_shape)),
    }
    sector = ps.ScalarSector(2, potential=_potential)
    fused = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               dtype=jnp.float64, bx=4, by=8, **_XKW)
    assert fused._ensure_coupled_pair_calls() is not None

    dt = 0.01
    # nsteps=1: 5 flat stages = 2 pairs + odd tail; nsteps=2: 5 pairs
    for nsteps in (1, 2):
        outs = {}
        for pair in (False, True):
            expand = ps.Expansion(1.0, ps.LowStorageRK54)
            fresh = {k: _arr(np.asarray(v)) for k, v in state.items()}
            res = fused.coupled_multi_step(fresh, nsteps, expand, 0.0,
                                           dt, grid_size=grid_size,
                                           pair=pair)
            outs[pair] = (res, float(expand.a), float(expand.adot))
        (ref, a_ref, adot_ref), (got, a_got, adot_got) = \
            outs[False], outs[True]
        for n in ("f", "dfdt"):
            err = (np.max(np.abs(np.asarray(got[n]) - np.asarray(ref[n])))
                   / np.max(np.abs(np.asarray(ref[n]))))
            assert err < 1e-12, f"{n}@{nsteps}: deferred pair ({err})"
        assert abs(a_got - a_ref) / a_ref < 1e-13
        assert abs(adot_got - adot_ref) / abs(adot_ref) < 1e-12


@pytest.mark.slow
def test_bf16_carry_accuracy(decomp):
    """``carry_dtype=bfloat16`` stores the 2N RK carries at half width
    (the 512^3-GW-on-one-chip memory flag, VERDICT r4 #6) while all
    in-kernel arithmetic stays f32. The error vs the f32-carry path
    must be bounded by carry quantization (~2^-8 relative per stage,
    here over 2 steps), and the carries must actually be bf16."""
    grid_shape = (16, 16, 16)
    h, dx, dt = 2, (0.3, 0.25, 0.2), 0.01
    rng = np.random.default_rng(47)
    state_h = {
        "f": 0.1 * rng.standard_normal((2,) + grid_shape),
        "dfdt": 0.01 * rng.standard_normal((2,) + grid_shape),
        "hij": 1e-3 * rng.standard_normal((6,) + grid_shape),
        "dhijdt": 1e-4 * rng.standard_normal((6,) + grid_shape),
    }
    sector = ps.ScalarSector(2, potential=_potential)
    gw = ps.TensorPerturbationSector([sector])

    results = {}
    for cd in (None, jnp.bfloat16):
        fused = FusedPreheatStepper(sector, gw, decomp, grid_shape, dx,
                                    h, dtype=jnp.float32, bx=4, by=8,
                                    carry_dtype=cd, **_XKW)
        carry = fused.init_carry(
            {k: _arr(jnp.asarray(v, jnp.float32))
             for k, v in state_h.items()})
        if cd is not None:
            assert carry[1]["f"].dtype == jnp.bfloat16
            assert carry[1]["dhijdt"].dtype == jnp.bfloat16
        st = fused.extract(carry)
        for _ in range(2):
            st = fused.step(st, 0.0, dt, {"a": 1.1, "hubble": 0.2})
        results[cd] = st

    for name in ("f", "dfdt", "hij", "dhijdt"):
        a = np.asarray(results[None][name], np.float64)
        b = np.asarray(results[jnp.bfloat16][name], np.float64)
        scale = max(np.max(np.abs(a)), 1e-30)
        err = np.max(np.abs(a - b)) / scale
        # carry quantization: ~2^-8 relative on the k increments, which
        # enter the state scaled by B*dt — well under 1% here, and far
        # above zero (the flag must actually change the storage)
        assert err < 1e-2, f"{name}: bf16-carry error too large ({err})"
    assert any(
        np.max(np.abs(np.asarray(results[None][n], np.float64)
                      - np.asarray(results[jnp.bfloat16][n], np.float64)))
        > 0 for n in ("f", "dfdt"))


def test_stage_pair_guards(decomp):
    """stage_pair raises clearly when pairing is disabled, and rejects a
    wrapped pairing whose tableau carry scale is nonzero (ADVICE r3)."""
    grid_shape = (16, 16, 16)
    sector = ps.ScalarSector(1, potential=lambda f: 0.5 * f[0] ** 2)
    single = FusedScalarStepper(sector, decomp, grid_shape, 0.3, 2,
                                pair_stages=False, dtype=jnp.float64,
                                bx=4, by=8, **_XKW)
    state = {"f": _arr(np.zeros((1,) + grid_shape)),
             "dfdt": _arr(np.zeros((1,) + grid_shape))}
    carry = single.init_carry(state)
    with pytest.raises(RuntimeError, match="stage-pair"):
        single.stage_pair(0, carry, 0.0, 0.01, {})

    paired = FusedScalarStepper(sector, decomp, grid_shape, 0.3, 2,
                                dtype=jnp.float64, bx=4, by=8, **_XKW)
    # RK54 has A[1] != 0: pairing stage 4 with next-step stage 1 would
    # need the skipped k-carry reset to matter -> must be rejected
    with pytest.raises(ValueError, match="A\\[1\\]"):
        paired.stage_pair(4, paired.init_carry(state), 0.0, 0.01, {}, s2=1)


@pytest.mark.slow
def test_preheat_pair_stages_match_single_stages(decomp):
    """Same bit-level pair/single equivalence for the scalar+GW system
    (lap(h1) and S_ij(grad f1) compose through the axpy taps)."""
    grid_shape = (16, 16, 16)
    h, dx = 2, 0.3
    dt = 0.01
    rng = np.random.default_rng(12)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
        "hij": _arr(1e-3 * rng.standard_normal((6,) + grid_shape)),
        "dhijdt": _arr(
            1e-4 * rng.standard_normal((6,) + grid_shape)),
    }
    args = {"a": 1.3, "hubble": 0.21}

    sector = ps.ScalarSector(2, potential=_potential)
    gw = ps.TensorPerturbationSector([sector])
    kw = dict(dtype=jnp.float64, bx=4, by=8, **_XKW)
    paired = FusedPreheatStepper(sector, gw, decomp, grid_shape, dx, h,
                                 pair_stages=True, **kw)
    single = FusedPreheatStepper(sector, gw, decomp, grid_shape, dx, h,
                                 pair_stages=False, **kw)
    assert paired._pair_call is not None and single._pair_call is None

    got = paired.step(state, 0.0, dt, args)
    ref = single.step(state, 0.0, dt, args)
    for name in ("f", "dfdt", "hij", "dhijdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = np.max(np.abs(np.asarray(ref[name])))
        assert err / scale < 1e-14, f"{name}: pair/single diverge ({err})"


def test_preheat_pair_degrades_at_production_size(decomp):
    """At 512**3 the 24-window-component preheat pair kernel has no
    VMEM-feasible blocking (ADVICE r3, medium): construction must warn
    and degrade to single-stage kernels instead of handing Mosaic an
    over-budget config, while the scalar-only pair (6 components) stays
    paired at the same size."""
    import warnings
    from pystella_tpu.ops.pallas_stencil import choose_blocks

    with pytest.raises(ValueError, match="VMEM budget"):
        choose_blocks(24, (512, 512, 512), 2, 4, n_extra=8, n_out=32)

    sector = ps.ScalarSector(2, potential=_potential)
    gw = ps.TensorPerturbationSector([sector])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        stepper = FusedPreheatStepper(sector, gw, decomp, (512, 512, 512),
                                      0.01, 2, dtype=jnp.float32, **_XKW)
    assert stepper._pair_call is None and not stepper._pair_stages
    assert any("stage-pair fusion disabled" in str(w.message)
               for w in caught)
    # the single-stage kernel remains available at this size
    assert stepper._both_st.bx >= 2

    # ... and the coupled chunk follows the same split: GW degrades to
    # single-stage coupled kernels (pairing is already off), while the
    # scalar system's 8-window deferred coupled pair has a valid
    # blocking — coupled-science-512^3 benches the PAIR path
    assert stepper._ensure_coupled_pair_calls() is None

    scalar = FusedScalarStepper(sector, decomp, (512, 512, 512), 0.01, 2,
                                dtype=jnp.float32, **_XKW)
    assert scalar._pair_call is not None
    assert scalar._ensure_coupled_pair_calls() is not None

    # explicitly pinned pair blocking is honored verbatim (no degrade)
    pinned = FusedPreheatStepper(sector, gw, decomp, (512, 512, 512),
                                 0.01, 2, dtype=jnp.float32,
                                 pair_bx=2, pair_by=8, **_XKW)
    assert pinned._pair_call is not None


def test_fused_scalar_matches_generic(decomp):
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = 0.01
    rng = np.random.default_rng(5)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
    }
    a, hubble = 1.3, 0.21

    ref = _generic_step(decomp, grid_shape, dx, h, state, dt, a, hubble)

    sector = ps.ScalarSector(2, potential=_potential)
    fused = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               dtype=jnp.float64, bx=4, by=8, **_XKW)
    got = fused.step(state, 0.0, dt, {"a": a, "hubble": hubble})

    for name in ("f", "dfdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = np.max(np.abs(np.asarray(ref[name])))
        assert err / scale < 1e-12, (name, err, scale)


def test_fused_scalar_per_stage_interface(decomp):
    """The per-stage __call__ protocol matches step()."""
    grid_shape = (16, 16, 16)
    h, dx, dt = 1, 0.3, 0.02
    rng = np.random.default_rng(6)
    state = {
        "f": _arr(rng.standard_normal((1,) + grid_shape)),
        "dfdt": _arr(rng.standard_normal((1,) + grid_shape)),
    }
    sector = ps.ScalarSector(1, potential=lambda f: 0.5 * f[0] ** 2)
    fused = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               dtype=jnp.float64, bx=4, by=8, **_XKW)

    whole = fused.step(state, 0.0, dt, {"a": 1.0, "hubble": 0.0})
    carry = state
    for s in range(fused.num_stages):
        carry = fused(s, carry, 0.0, dt, a=1.0, hubble=0.0)
    for name in ("f", "dfdt"):
        assert np.allclose(np.asarray(whole[name]), np.asarray(carry[name]),
                           rtol=1e-13, atol=1e-13)


@pytest.mark.parametrize("system, proc, carry_dtype", [
    ("scalar", (1, 1, 1), None),
    ("scalar", (1, 1, 1), jnp.bfloat16),
    ("gw", (1, 1, 1), None),
    ("scalar", (2, 2, 1), None),
], ids=["scalar", "scalar-bf16-carry", "gw", "scalar-2x2x1"])
def test_stage_loop_in_place_equals_undonated(system, proc, carry_dtype):
    """The per-stage protocol of a ``donate=True`` stepper (its stage
    kernel writes ``dfdt`` and the carry in place, its programs donate
    those and not the windowed ``f`` / ``hij``) gives the bits of a
    ``donate=False`` one over two steps, on one chip and through the
    sharded wrapper; ``current(carry)`` between stages too. After stage 0
    the caller's ``state["f"]`` is still readable: it was not donated;
    its ``dfdt`` was."""
    ndev = int(np.prod(proc))
    if len(jax.devices()) < ndev or (ndev > 1 and _TPU_SESSION):
        pytest.skip(f"needs {ndev} CPU devices")
    devs = (jax.devices("cpu") if _TPU_SESSION else jax.devices())[:ndev]
    decomp = ps.DomainDecomposition(proc, devices=devs)
    grid_shape = (8, 16, 16)
    h, dx, dt = 2, 0.3, np.float32(0.01)
    rng = np.random.default_rng(37)
    host = {"f": rng.standard_normal((2,) + grid_shape),
            "dfdt": 0.1 * rng.standard_normal((2,) + grid_shape)}
    sector = ps.ScalarSector(2, potential=_potential)
    kw = dict(dtype=jnp.float32, bx=4, by=8, carry_dtype=carry_dtype,
              **_XKW)
    if system == "gw":
        host["hij"] = 1e-3 * rng.standard_normal((6,) + grid_shape)
        host["dhijdt"] = 1e-4 * rng.standard_normal((6,) + grid_shape)
        gw = ps.TensorPerturbationSector([sector])

        def build(donate):
            return FusedPreheatStepper(sector, gw, decomp, grid_shape, dx,
                                       h, donate=donate, **kw)
    else:
        def build(donate):
            return FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                                      donate=donate, **kw)

    runs = {}
    for donate in (True, False):
        stepper = build(donate)
        extras = tuple(stepper._stage_st.extra_defs)
        assert stepper._stage_st.in_place == (extras if donate else ())
        state = {n: decomp.shard(v.astype(np.float32))
                 for n, v in host.items()}
        seen = []
        for step in range(2):
            carry = state
            for stage in range(stepper.num_stages):
                carry = stepper(stage, carry, 0.0, dt, a=np.float64(1.2),
                                hubble=np.float64(0.3))
                if stage == 0 and step == 0:
                    # what a driver that kept its state may still read
                    for n in stepper._stage_st.win_defs:
                        assert np.array_equal(
                            np.asarray(state[n]),
                            host[n].astype(np.float32)), n
                    # and what went to the kernel for good
                    for n, v in state.items():
                        assert v.is_deleted() == (
                            donate and n not in stepper._stage_st.win_defs)
                if stage < stepper.num_stages - 1:
                    seen.append({n: np.asarray(v) for n, v in
                                 stepper.current(carry).items()})
            state = carry
        seen.append({n: np.asarray(v) for n, v in state.items()})
        runs[donate] = seen
    assert len(runs[True]) == len(runs[False]) == 9
    for got, ref in zip(runs[True], runs[False]):
        assert set(got) == set(host)
        for n in got:
            assert np.array_equal(got[n], ref[n]), n


def test_fused_preheat_matches_generic(decomp):
    grid_shape = (16, 16, 16)
    h, dx = 2, 0.3
    dt = 0.01
    rng = np.random.default_rng(7)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
        "hij": _arr(1e-3 * rng.standard_normal((6,) + grid_shape)),
        "dhijdt": _arr(1e-4 * rng.standard_normal((6,) + grid_shape)),
    }
    a, hubble = 1.1, 0.13

    ref = _generic_step(decomp, grid_shape, (dx,) * 3, h, state, dt, a,
                        hubble, gravitational_waves=True)

    sector = ps.ScalarSector(2, potential=_potential)
    gw = ps.TensorPerturbationSector([sector])
    fused = FusedPreheatStepper(sector, gw, decomp, grid_shape, dx, h,
                                dtype=jnp.float64, bx=4, by=8, **_XKW)
    got = fused.step(state, 0.0, dt, {"a": a, "hubble": hubble})

    for name in ("f", "dfdt", "hij", "dhijdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = max(np.max(np.abs(np.asarray(ref[name]))), 1e-30)
        assert err / scale < 1e-11, (name, err, scale)


@pytest.mark.parametrize("px", [2, 4])
def test_fused_scalar_sharded_x_matches_single(px):
    """x-sharded fused stages agree with the single-device fused path."""
    if len(jax.devices()) < px:
        pytest.skip(f"needs {px} devices")
    grid_shape = (16, 16, 16)
    h, dx, dt = 2, 0.3, 0.01
    rng = np.random.default_rng(8)
    state_h = {
        "f": rng.standard_normal((2,) + grid_shape),
        "dfdt": 0.1 * rng.standard_normal((2,) + grid_shape),
    }
    sector = ps.ScalarSector(2, potential=_potential)

    d1 = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    f1 = FusedScalarStepper(sector, d1, grid_shape, dx, h,
                            dtype=jnp.float64, bx=4, by=8, **_XKW)
    ref = f1.step({k: jnp.asarray(v) for k, v in state_h.items()},
                  0.0, dt, {"a": 1.2, "hubble": 0.3})

    dp = ps.DomainDecomposition((px, 1, 1), devices=jax.devices()[:px])
    fp = FusedScalarStepper(sector, dp, grid_shape, dx, h,
                            dtype=jnp.float64, bx=4, by=8, **_XKW)
    got = fp.step({k: dp.shard(v) for k, v in state_h.items()},
                  0.0, dt, {"a": 1.2, "hubble": 0.3})

    for name in ("f", "dfdt"):
        assert np.allclose(np.asarray(got[name]), np.asarray(ref[name]),
                           rtol=1e-13, atol=1e-13), name


@pytest.mark.parametrize("proc", [
    (1, 2, 1), (2, 2, 1),
    # the wide-px xy mesh re-checks (2,2,1)'s geometry at px=4 (px
    # width alone is covered tier-1 by sharded_x[4]), and the py=4
    # mesh re-checks y-halo DMA pieces the (1,2,1)/(2,2,1) meshes
    # already exercise at two y-blocks per shard: unfiltered only,
    # for the tier-1 wall budget
    pytest.param((4, 2, 1), marks=pytest.mark.slow),
    pytest.param((2, 4, 1), marks=pytest.mark.slow)])
def test_fused_scalar_sharded_2d_matches_single(proc):
    """Fused stages on y- and xy-sharded meshes (HY-padded ppermute
    window halos, VERDICT r3 #3) agree with the single-device path.
    The py=2 meshes use local Y = 16 with by=8, so each shard runs TWO
    y-blocks — covering the y_halo j>0 DMA-piece offsets."""
    ndev = int(np.prod(proc))
    if len(jax.devices()) < ndev or _TPU_SESSION:
        pytest.skip(f"needs {ndev} CPU devices")
    # local y must be a multiple of 8 and >= HY: py=2 -> Y=32 gives two
    # 8-row y-blocks per shard; py=4 -> Y=32 gives one
    grid_shape = (16, 32, 16)
    h, dx, dt = 2, 0.3, 0.01
    rng = np.random.default_rng(8)
    state_h = {
        "f": rng.standard_normal((2,) + grid_shape),
        "dfdt": 0.1 * rng.standard_normal((2,) + grid_shape),
    }
    sector = ps.ScalarSector(2, potential=_potential)

    d1 = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    f1 = FusedScalarStepper(sector, d1, grid_shape, dx, h,
                            dtype=jnp.float64, bx=4, by=8)
    ref = f1.step({k: jnp.asarray(v) for k, v in state_h.items()},
                  0.0, dt, {"a": 1.2, "hubble": 0.3})

    dp = ps.DomainDecomposition(proc, devices=jax.devices()[:ndev])
    fp = FusedScalarStepper(sector, dp, grid_shape, dx, h,
                            dtype=jnp.float64, bx=4, by=8)
    got = fp.step({k: dp.shard(v) for k, v in state_h.items()},
                  0.0, dt, {"a": 1.2, "hubble": 0.3})

    for name in ("f", "dfdt"):
        assert np.allclose(np.asarray(got[name]), np.asarray(ref[name]),
                           rtol=1e-13, atol=1e-13), name


#: the kernels a sharded scalar stepper builds, per (mesh, local x
#: extent): kind -> (stencil, windows, extras), caught at ``_make_call``
_SLAB_KERNELS = {}


def _slab_kernels(proc, local_x, carry_dtype=None):
    """Every kernel kind the coupled and the fixed-background drivers
    run (``stage``, ``pair``, ``coupled_pair`` with its two blocks of
    sums, ``energy``) as a stepper on mesh ``proc`` builds it, at local
    shape ``(local_x, 16, 8)``."""
    key = (proc, local_x, carry_dtype)
    if key not in _SLAB_KERNELS:
        ndev = int(np.prod(proc))
        grid_shape = (local_x * proc[0], 16 * proc[1], 8)
        dp = ps.DomainDecomposition(proc, devices=jax.devices()[:ndev])
        built = {}
        make_call = FusedScalarStepper._make_call

        def record(self, st, windows, extra_names):
            built.setdefault(st.kind, (st, windows, extra_names))
            return make_call(self, st, windows, extra_names)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(FusedScalarStepper, "_make_call", record)
            stepper = FusedScalarStepper(
                ps.ScalarSector(2, potential=_potential), dp, grid_shape,
                0.3, 2, dtype=jnp.float64, carry_dtype=carry_dtype)
            assert stepper._ensure_coupled_pair_calls() is not None
            stepper._ensure_energy_call()
        _SLAB_KERNELS[key] = (dp, grid_shape, built)
    return _SLAB_KERNELS[key]


@pytest.mark.parametrize("kind, proc, local_x, carry_dtype", [
    (kind, proc, local_x, None)
    for kind in ("stage", "pair", "coupled_pair", "energy")
    for proc in ((2, 1, 1), (1, 2, 1), (2, 2, 1))
    for local_x in (8, 16)] + [("pair", (2, 2, 1), 16, jnp.bfloat16)],
    ids=lambda v: ("x".join(map(str, v)) if isinstance(v, tuple)
                   else f"nbx{v // 4}" if isinstance(v, int)
                   else v if isinstance(v, str)
                   else "f64" if v is None else "bf16-carries"))
def test_fused_sharded_slab_kernel_matches_padded(kind, proc, local_x,
                                                  carry_dtype):
    """How a sharded kernel gets its edges: the kernel a stepper builds
    on a mesh streams its own shard and reads its neighbours' rows from
    thin slabs (``halo_slabs``); the variant it replaced read a padded
    copy of every window (``pad_with_halos`` + ``x_halo`` /
    ``y_halo``). Same body, same blocks, same grid: outputs and lattice
    sums are bit-equal on ``(2,1,1)``, ``(1,2,1)`` and ``(2,2,1)``, with
    two y-blocks a shard (``by = 8`` of 16 rows) and with two and four
    x-blocks (``bx = 4``: both ways of priming the ring). On the xy
    mesh the padded reference has NaN planted in its corners (x halo
    rows at y halo rows), which the slab-fed kernel never fetches: a
    tap that read one would poison the reference. With bfloat16 carries
    the pair kernel's ``kf`` window travels in a slab operand of its
    own beside the float64 windows' shared one."""
    from pystella_tpu.ops.pallas_stencil import HY, sharded_halo
    if len(jax.devices()) < int(np.prod(proc)) or _TPU_SESSION:
        pytest.skip(f"needs {int(np.prod(proc))} CPU devices")
    dp, grid_shape, built = _slab_kernels(proc, local_x, carry_dtype)
    st, windows, extra_names = built[kind]
    assert len(st._slab_groups) == (1 if carry_dtype is None else 2)
    px, py = proc[:2]
    assert st.halo == ("slab" if px > 1 else "wrap",
                       "slab" if py > 1 else "wrap")
    slab = st.with_lattice(st.lattice_shape, bx=4, by=8)
    padded = st.with_lattice(st.lattice_shape, bx=4, by=8, padded=True)
    assert slab.grid == padded.grid == (2, local_x // 4)
    assert (padded.x_halo, padded.y_halo) == (px > 1, py > 1)
    h = st.h
    halo = sharded_halo(h, px, py)
    names = list(st.out_defs) + list(st.sum_defs)

    def corners_nan(a):
        if px == 1 or py == 1:
            return a
        nan = jnp.full((a.shape[0], h, HY, a.shape[3]), jnp.nan, a.dtype)
        for x0 in (0, a.shape[1] - h):
            for y0 in (0, a.shape[2] - HY):
                a = jax.lax.dynamic_update_slice(a, nan, (0, x0, y0, 0))
        return a

    def body(*flat):
        nw, ns = len(windows), len(st.scalar_names)
        raw = dict(zip(windows, flat[:nw]))
        scalars = dict(zip(st.scalar_names, flat[nw:nw + ns]))
        extras = dict(zip(extra_names, flat[nw + ns:]))
        new = slab(raw if nw > 1 else raw[windows[0]], scalars=scalars,
                   extras=extras, slabs=slab.halo_slabs(dp, raw))
        wins = {n: corners_nan(dp.pad_with_halos(a, halo,
                                                 exchange=(h,) * 3))
                for n, a in raw.items()}
        old = padded(wins if nw > 1 else wins[windows[0]],
                     scalars=scalars, extras=extras)
        # per-shard sums, before any psum: the kernel's own numbers
        return tuple(o[n] for o in (new, old) for n in names)

    from jax.sharding import PartitionSpec as P
    lat, rep = dp.spec(1), P()
    nsum = len(st.sum_defs)
    out_specs = ((lat,) * len(st.out_defs)
                 + (P(dp.axis_names[:2]),) * nsum) * 2
    fn = jax.jit(dp.shard_map(
        body, (lat,) * len(windows) + (rep,) * len(st.scalar_names)
        + (lat,) * len(extra_names), out_specs, check_vma=False))
    rng = np.random.default_rng(17)
    def draw(n, lead):
        return dp.shard(jnp.asarray(rng.standard_normal(lead + grid_shape),
                                    st.dtypes.get(n, st.dtype)))

    args = [draw(n, (st.win_defs[n],)) for n in windows]
    args += [jnp.asarray(0.3 + 0.1 * k)
             for k in range(len(st.scalar_names))]
    args += [draw(n, st.extra_defs[n]) for n in extra_names]
    res = fn(*args)
    for n, new, old in zip(names, res[:len(names)], res[len(names):]):
        new, old = (np.asarray(a, np.float64) for a in (new, old))
        assert np.isfinite(old).all(), f"{n}: a tap read a corner"
        assert np.array_equal(new, old), n


@pytest.mark.slow
def test_fused_preheat_sharded_2d_matches_single():
    """Scalar+GW fused stages (pair kernels in step()) on a (2, 2, 1)
    mesh match the single-device path, and the energy-coupled chunk
    driver agrees across the same meshes."""
    if len(jax.devices()) < 4 or _TPU_SESSION:
        pytest.skip("needs 4 CPU devices")
    grid_shape = (16, 16, 16)
    h, dx, dt = 2, 0.3, 0.01
    rng = np.random.default_rng(10)
    state_h = {
        "f": rng.standard_normal((2,) + grid_shape),
        "dfdt": 0.1 * rng.standard_normal((2,) + grid_shape),
        "hij": 1e-3 * rng.standard_normal((6,) + grid_shape),
        "dhijdt": 1e-4 * rng.standard_normal((6,) + grid_shape),
    }
    sector = ps.ScalarSector(2, potential=_potential)
    gw = ps.TensorPerturbationSector([sector])

    results = {}
    for proc in ((1, 1, 1), (2, 2, 1)):
        ndev = int(np.prod(proc))
        dp = ps.DomainDecomposition(proc, devices=jax.devices()[:ndev])
        fp = FusedPreheatStepper(sector, gw, dp, grid_shape, dx, h,
                                 dtype=jnp.float64, bx=4, by=8)
        st = {k: dp.shard(jnp.asarray(v)) for k, v in state_h.items()}
        stepped = fp.step(st, 0.0, dt, {"a": 1.1, "hubble": 0.2})
        expand = ps.Expansion(1e-3, ps.LowStorageRK54)
        st2 = {k: dp.shard(jnp.asarray(v)) for k, v in state_h.items()}
        coupled = fp.coupled_multi_step(st2, 2, expand, 0.0, dt)
        results[proc] = (stepped, coupled, expand.a)

    (ref_s, ref_c, ref_a) = results[(1, 1, 1)]
    (got_s, got_c, got_a) = results[(2, 2, 1)]
    for name in state_h:
        assert np.allclose(np.asarray(got_s[name]), np.asarray(ref_s[name]),
                           rtol=1e-12, atol=1e-13), f"step:{name}"
        assert np.allclose(np.asarray(got_c[name]), np.asarray(ref_c[name]),
                           rtol=1e-12, atol=1e-13), f"coupled:{name}"
    assert abs(got_a - ref_a) / ref_a < 1e-13


@pytest.mark.slow  # ~33 s interpret-mode: the preheat (scalar+GW)
# x-sharded parity rides with its already-slow (2,2,1) sibling; tier-1
# keeps preheat-fused coverage (test_fused_preheat_matches_generic)
# and sharded-fused coverage (test_fused_scalar_sharded_x/_2d) — only
# their product moves to the unfiltered run
def test_fused_preheat_sharded_x_matches_single():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    grid_shape = (16, 16, 16)
    h, dx, dt = 2, 0.3, 0.01
    rng = np.random.default_rng(9)
    state_h = {
        "f": rng.standard_normal((2,) + grid_shape),
        "dfdt": 0.1 * rng.standard_normal((2,) + grid_shape),
        "hij": 1e-3 * rng.standard_normal((6,) + grid_shape),
        "dhijdt": 1e-4 * rng.standard_normal((6,) + grid_shape),
    }
    sector = ps.ScalarSector(2, potential=_potential)
    gw = ps.TensorPerturbationSector([sector])

    d1 = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    f1 = FusedPreheatStepper(sector, gw, d1, grid_shape, dx, h,
                             dtype=jnp.float64, bx=4, by=8, **_XKW)
    ref = f1.step({k: jnp.asarray(v) for k, v in state_h.items()},
                  0.0, dt, {"a": 1.1, "hubble": 0.2})

    dp = ps.DomainDecomposition((2, 1, 1), devices=jax.devices()[:2])
    fp = FusedPreheatStepper(sector, gw, dp, grid_shape, dx, h,
                             dtype=jnp.float64, bx=4, by=8, **_XKW)
    got = fp.step({k: dp.shard(v) for k, v in state_h.items()},
                  0.0, dt, {"a": 1.1, "hubble": 0.2})

    for name in state_h:
        assert np.allclose(np.asarray(got[name]), np.asarray(ref[name]),
                           rtol=1e-12, atol=1e-13), name


if __name__ == "__main__":
    # fused-stage microbenchmark (reference test/common.py:41-56 pattern):
    #   python tests/test_fused.py -grid 128 128 128
    import common

    args = common.parse_args()
    decomp = common.script_decomp(args.proc_shape)
    dx = tuple(5.0 / n for n in args.grid_shape)
    dt = 0.1 * min(dx)

    sector = ps.ScalarSector(2, potential=_potential)
    fused = FusedScalarStepper(sector, decomp, args.grid_shape, dx,
                               args.h, dtype=args.dtype, dt=dt)
    rng = np.random.default_rng(5)
    state = {k: decomp.shard(
        0.1 * rng.standard_normal((2,) + args.grid_shape).astype(args.dtype))
        for k in ("f", "dfdt")}  # noqa: E501
    rhs_args = {"a": np.dtype(args.dtype).type(1.0),
                "hubble": np.dtype(args.dtype).type(0.1)}

    nsites = float(np.prod(args.grid_shape))
    isize = np.dtype(args.dtype).itemsize
    ms = ps.timer(lambda: fused.step(state, 0.0, dt, rhs_args),
                  ntime=args.ntime)
    # step() pairs stages: 2 pair kernels (8 arrays each) + 1 single
    # (8 arrays), x 2 fields
    common.report("fused RK54 step", ms,
                  nbytes=(8 * 2 + 8) * 2 * nsites * isize, nsites=nsites)


@pytest.mark.slow
def test_fused_scalar_resident_matches_streaming(decomp):
    """resident=True forces the whole-lattice-resident stage kernels
    (the compiled Z < 128 tier); same arithmetic, same results as the
    streaming-window kernels, including pairing and the energy-coupled
    chunk."""
    grid_shape = (16, 16, 16)
    h, dx, dt = 2, (0.3, 0.25, 0.2), 0.01
    rng = np.random.default_rng(33)
    state = {
        "f": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.01 * rng.standard_normal((2,) + grid_shape)),
    }
    args = {"a": 1.3, "hubble": 0.21}
    sector = ps.ScalarSector(2, potential=_potential)

    stream = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                                dtype=jnp.float64, bx=4, by=8, **_XKW)
    res = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                             dtype=jnp.float64, resident=True, **_XKW)
    from pystella_tpu.ops.pallas_stencil import ResidentStencil
    assert isinstance(res._scalar_st, ResidentStencil)
    assert isinstance(res._pair_st, ResidentStencil)

    got = res.step(state, 0.0, dt, args)
    ref = stream.step(state, 0.0, dt, args)
    for name in ("f", "dfdt"):
        err = np.max(np.abs(np.asarray(got[name]) - np.asarray(ref[name])))
        scale = np.max(np.abs(np.asarray(ref[name])))
        assert err / scale < 1e-13, f"{name}: resident diverges ({err})"

    # energy-coupled chunk through the resident es kernel
    expand_r = ps.Expansion(1e-3, ps.LowStorageRK54)
    expand_s = ps.Expansion(1e-3, ps.LowStorageRK54)
    got_c = res.coupled_multi_step(
        {k: _arr(np.asarray(v)) for k, v in state.items()}, 2, expand_r,
        0.0, dt)
    ref_c = stream.coupled_multi_step(
        {k: _arr(np.asarray(v)) for k, v in state.items()}, 2, expand_s,
        0.0, dt)
    for name in ("f", "dfdt"):
        err = np.max(np.abs(np.asarray(got_c[name])
                            - np.asarray(ref_c[name])))
        scale = np.max(np.abs(np.asarray(ref_c[name])))
        assert err / scale < 1e-12, f"{name}: resident coupled ({err})"
    assert abs(expand_r.a - expand_s.a) / expand_s.a < 1e-13


def test_fused_resident_auto_small_y(decomp):
    """Lattices with no feasible streaming blocking (y not a multiple of
    8) now auto-select the resident tier instead of failing."""
    from pystella_tpu.ops.pallas_stencil import ResidentStencil

    grid_shape = (12, 12, 12)
    sector = ps.ScalarSector(1, potential=lambda f: 0.5 * f[0] ** 2)
    st = FusedScalarStepper(sector, decomp, grid_shape, 0.3, 2,
                            dtype=jnp.float64, **_XKW)
    assert isinstance(st._scalar_st, ResidentStencil)
    state = {"f": _arr(0.1 * np.random.default_rng(3).standard_normal(
        (1,) + grid_shape)), "dfdt": _arr(np.zeros((1,) + grid_shape))}
    out = st.step(state, 0.0, 0.01, {"a": 1.0, "hubble": 0.0})
    assert np.all(np.isfinite(np.asarray(out["f"])))


# -- whole-RK-chunk (temporal blocking) tier --------------------------------

def test_chunk_stages_match_pair_stages(decomp):
    """THE chunk-tier pin: a depth-4 whole-RK-chunk kernel advances four
    stages in one HBM pass by composing the intermediate arrays' taps
    in-register; its arithmetic sequence per element is IDENTICAL to
    the pair-kernel sequence it replaces, so multi_step must be
    bit-exact (not merely close) against the pair tier — across step
    boundaries included (nsteps=2 consumes 10 flat RK54 stages as
    chunk+chunk+pair; nsteps=3 exercises the odd tail)."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = 0.01
    rng = np.random.default_rng(17)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
    }
    args = {"a": 1.3, "hubble": 0.21}

    sector = ps.ScalarSector(2, potential=_potential)
    kw = dict(dtype=jnp.float64, **_XKW)
    pair = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                              bx=4, by=8, **kw)
    chunk = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               chunk_stages=4, chunk_bx=4, chunk_by=8,
                               **kw)
    assert chunk._chunk_call is not None and chunk._chunk_depth == 4
    assert pair._chunk_call is None
    # the chunk window reaches ceil(4/2)*h = 2h into the halo
    assert chunk._chunk_st.wh == 2 * h

    # nsteps=2 consumes all 10 flat stages as chunk+chunk+pair, with
    # the second chunk CROSSING the step boundary (its stage list is
    # [4, 0, 1, 2] — the A[0] == 0 no-op k-carry reset)
    ref = pair.multi_step(
        {k: _arr(np.asarray(v)) for k, v in state.items()},
        2, 0.0, dt, args)
    got = chunk.multi_step(
        {k: _arr(np.asarray(v)) for k, v in state.items()},
        2, 0.0, dt, args)
    for name in ("f", "dfdt"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(ref[name])), \
            f"{name}: chunk diverges from pair sequence"

    # the within-step consumption (chunk + trailing single, the step()
    # shape) pinned EAGERLY at one f64 ulp: each eager dispatch is its
    # own compiled program, and the backend contracts FMAs differently
    # in the one-kernel chunk body than in the two pair bodies (the
    # jitted multi_step comparison above, where both tiers sit in one
    # program context, stays exactly bitwise)
    cp = pair.init_carry(state)
    cp = pair.stage_pair(0, cp, 0.0, dt, args)
    cp = pair.stage_pair(2, cp, 0.0, dt, args)
    cp = pair.stage(4, cp, 0.0, dt, args)
    cc = chunk.init_carry(state)
    cc = chunk.stage_chunk([0, 1, 2, 3], cc, 0.0, dt, [args] * 4)
    cc = chunk.stage(4, cc, 0.0, dt, args)
    for part in (0, 1):
        for name in ("f", "dfdt"):
            a = np.asarray(cp[part][name])
            b = np.asarray(cc[part][name])
            scale = np.max(np.abs(a)) or 1.0
            assert np.max(np.abs(a - b)) / scale < 1e-14, \
                f"{name}: within-step chunk diverges"

    # the dispatch record the roofline section ingests: chunked tier,
    # and strictly less modeled lattice traffic than the pair tier
    trep_c = chunk.kernel_tier_report()
    trep_p = pair.kernel_tier_report()
    assert trep_c["tier"].endswith("-chunk")
    assert trep_p["tier"] == "pair"
    assert trep_c["bytes_per_step"] < trep_p["bytes_per_step"]


@pytest.mark.slow
def test_chunk_multi_step_odd_and_jit_step(decomp):
    """The heavier chunk-tier parity variants: an odd step count (the
    chunk/pair/single tail interleaving differs from nsteps=2) and the
    jitted whole-step path — each compiles its own big composed
    program, so they ride the unfiltered run (the nsteps=2 cross-
    boundary pin and the eager within-step pin stay tier-1)."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = 0.01
    rng = np.random.default_rng(17)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
    }
    args = {"a": 1.3, "hubble": 0.21}
    sector = ps.ScalarSector(2, potential=_potential)
    kw = dict(dtype=jnp.float64, **_XKW)
    pair = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                              bx=4, by=8, **kw)
    chunk = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               chunk_stages=4, chunk_bx=4, chunk_by=8,
                               **kw)
    ref = pair.multi_step({k: _arr(np.asarray(v))
                           for k, v in state.items()}, 3, 0.0, dt, args)
    got = chunk.multi_step({k: _arr(np.asarray(v))
                            for k, v in state.items()}, 3, 0.0, dt,
                           args)
    for name in ("f", "dfdt"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(ref[name]))
    got1 = chunk.step({k: _arr(np.asarray(v))
                       for k, v in state.items()}, 0.0, dt, args)
    ref1 = pair.step({k: _arr(np.asarray(v))
                      for k, v in state.items()}, 0.0, dt, args)
    for name in ("f", "dfdt"):
        assert np.array_equal(np.asarray(got1[name]),
                              np.asarray(ref1[name]))


def test_chunk_bf16_carry_matches_pair(decomp):
    """Reduced-precision carries: the chunk body quantizes its composed
    carry views at interior PAIR boundaries — exactly where the pair
    sequence materializes (and rounds) them — so the CARRY outputs are
    bit-identical. The f32 state outputs are pinned at one f32 ulp:
    the mixed bf16/f32 convert+multiply chains give the backend
    re-contraction freedom across the one-kernel-vs-two boundary (the
    measured ~1-ulp effect doc/performance.md already records for
    composed jits; the pure-f32/f64 chunk pin above stays exactly
    bitwise)."""
    grid_shape = (16, 16, 16)
    h, dx = 2, (0.3, 0.25, 0.2)
    dt = np.float32(0.01)
    rng = np.random.default_rng(23)
    state = {
        "f": _arr(rng.standard_normal((2,) + grid_shape)
                  .astype(np.float32)),
        "dfdt": _arr(0.1 * rng.standard_normal((2,) + grid_shape)
                     .astype(np.float32)),
    }
    args = {"a": np.float32(1.3), "hubble": np.float32(0.21)}
    sector = ps.ScalarSector(2, potential=_potential)
    kw = dict(dtype=jnp.float32, carry_dtype=jnp.bfloat16, **_XKW)
    pair = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                              bx=4, by=8, **kw)
    chunk = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               chunk_stages=4, chunk_bx=4, chunk_by=8,
                               **kw)
    assert chunk._chunk_call is not None
    # carry round trip at stage granularity: the quantization points
    # coincide with the pair sequence's materializations, so the bf16
    # CARRIES come out bit-identical
    cp = pair.init_carry(state)
    cp = pair.stage_pair(0, cp, 0.0, dt, args)
    cp = pair.stage_pair(2, cp, 0.0, dt, args)
    cc = chunk.init_carry(state)
    cc = chunk.stage_chunk([0, 1, 2, 3], cc, 0.0, dt, [args] * 4)
    for name in ("f", "dfdt"):
        assert np.array_equal(np.asarray(cp[1][name]),
                              np.asarray(cc[1][name])), \
            f"k[{name}]: bf16 carry quantization diverges"
        a = np.asarray(cp[0][name], np.float64)
        b = np.asarray(cc[0][name], np.float64)
        scale = np.max(np.abs(a)) or 1.0
        assert np.max(np.abs(a - b)) / scale < 1e-6, \
            f"{name}: bf16-carry chunk beyond the ulp bound"


def test_chunk_fallback_ladder(decomp):
    """Every degradation of the chunk tier is LOUD: bad depths raise,
    sharded meshes / over-wide window halos warn and fall back to the
    pair tier (kernel_fallback), and stage_chunk guards misuse."""
    grid_shape = (16, 16, 16)
    sector = ps.ScalarSector(2, potential=_potential)
    kw = dict(dtype=jnp.float64, bx=4, by=8, **_XKW)

    # odd / too-shallow depths are a usage error, not a fallback
    with pytest.raises(ValueError, match="even number >= 4"):
        FusedScalarStepper(sector, decomp, grid_shape, (0.3,) * 3, 2,
                           chunk_stages=3, **kw)
    with pytest.raises(ValueError, match="even number >= 4"):
        FusedScalarStepper(sector, decomp, grid_shape, (0.3,) * 3, 2,
                           chunk_stages=2, **kw)

    # window halo beyond the 8-aligned y pad: ceil(10/2)*2 = 10 > 8
    # (resident=False pins the streaming tier — on this tiny lattice
    # the whole-lattice-resident kernel, whose rolls have no window to
    # outgrow, would otherwise legitimately serve the deep chunk)
    with pytest.warns(UserWarning, match="chunk fusion disabled"):
        wide = FusedScalarStepper(sector, decomp, grid_shape,
                                  (0.3,) * 3, 2, chunk_stages=10,
                                  resident=False, **kw)
    assert wide._chunk_call is None and wide._pair_call is not None

    # stage_chunk without a chunk kernel
    st = FusedScalarStepper(sector, decomp, grid_shape, (0.3,) * 3, 2,
                            **kw)
    with pytest.raises(RuntimeError, match="chunk fusion is not"):
        st.stage_chunk([0, 1, 2, 3], st.init_carry(
            {"f": _arr(np.zeros((2,) + grid_shape)),
             "dfdt": _arr(np.zeros((2,) + grid_shape))}), 0.0, 0.01,
            [{}] * 4)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_chunk_sharded_falls_back_to_pair():
    """Sharded meshes keep the pair tier (the chunk exchange would need
    ceil(D/2)*h-wide halo slabs): the build warns, logs the fallback,
    and the stepper still works via pair kernels."""
    devs = (jax.devices("cpu") if _TPU_SESSION else jax.devices())[:2]
    decomp = ps.DomainDecomposition((2, 1, 1), devices=devs)
    sector = ps.ScalarSector(2, potential=_potential)
    with pytest.warns(UserWarning, match="sharded mesh"):
        st = FusedScalarStepper(sector, decomp, (16, 16, 16),
                                (0.3,) * 3, 2, chunk_stages=4,
                                dtype=jnp.float64, bx=4, by=8, **_XKW)
    assert st._chunk_call is None and st._pair_call is not None
    assert st.kernel_tier_report()["tier"] == "pair"


@pytest.mark.slow
def test_chunk_resident_matches_pair(decomp):
    """The whole-lattice-resident tier's multi-stage variant: lattices
    with no feasible streaming blocking (y % 8 != 0) chunk via
    RollTaps composition. Pinned at one f64 ulp rather than bitwise:
    the whole-lattice one-program body gives the backend FMA
    re-contraction freedom vs the two-program pair sequence (the
    measured ~1-ulp effect doc/performance.md records for composed
    jits; the streaming chunk pin above is exactly bitwise). Slow: the
    composed whole-lattice trace is the suite's biggest single
    compile, and tier-1 already pins the shared composition logic
    (streaming chunk) and the resident single/pair tiers."""
    from pystella_tpu.ops.pallas_stencil import ResidentStencil

    grid_shape = (12, 12, 12)
    h, dx = 2, (0.3,) * 3
    dt = 0.01
    rng = np.random.default_rng(29)
    state = {
        "f": _arr(0.1 * rng.standard_normal((2,) + grid_shape)),
        "dfdt": _arr(0.01 * rng.standard_normal((2,) + grid_shape)),
    }
    args = {"a": 1.1, "hubble": 0.1}
    sector = ps.ScalarSector(2, potential=_potential)
    kw = dict(dtype=jnp.float64, **_XKW)
    pair = FusedScalarStepper(sector, decomp, grid_shape, dx, h, **kw)
    chunk = FusedScalarStepper(sector, decomp, grid_shape, dx, h,
                               chunk_stages=4, **kw)
    assert isinstance(chunk._chunk_st, ResidentStencil)
    assert chunk.kernel_tier_report()["tier"] == "resident-chunk"
    ref = pair.multi_step({k: _arr(np.asarray(v))
                           for k, v in state.items()}, 2, 0.0, dt, args)
    got = chunk.multi_step({k: _arr(np.asarray(v))
                            for k, v in state.items()}, 2, 0.0, dt,
                           args)
    for name in ("f", "dfdt"):
        a, b = np.asarray(ref[name]), np.asarray(got[name])
        scale = np.max(np.abs(a)) or 1.0
        assert np.max(np.abs(a - b)) / scale < 1e-14, \
            f"{name}: resident chunk diverges from pair sequence"
