"""Multigrid tests (analog of /root/reference/test/test_multigrid.py:
V-cycles on Poisson + Helmholtz must converge the residual to machine
precision, plus transfer-operator identities and a nonlinear FAS solve)."""

import os

import numpy as np
import pytest

import pystella_tpu as ps
from pystella_tpu.multigrid import (
    CubicInterpolation, FullApproximationScheme, FullWeighting, Injection,
    JacobiIterator, LinearInterpolation, MultiGridSolver, NewtonIterator,
    RestrictionBase, f_cycle, v_cycle, w_cycle)


def make_problems():
    """The reference's two test problems (test_multigrid.py:63-72):
    Poisson ``lap f = rho`` and Helmholtz ``lap f2 - f2 = rho2``."""
    return {
        ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho")),
        ps.Field("f2"): (ps.Field("lap_f2") - ps.Field("f2"),
                         ps.Field("rho2")),
    }


def zero_mean_arrays(rng, decomp, grid_shape, n, dtype=np.float64):
    out = []
    for _ in range(n):
        a = rng.random(grid_shape)
        out.append(decomp.shard((a - a.mean()).astype(dtype)))
    return out


#: (dtype, tol): the L2 residual the last of ten cycles has to be under
#: (the one before it: ten times that). float64: the reference's FAS
#: check (test_multigrid.py:103-106); the linear solver matches it here
#: because the coarse correction is zero-initialized. float32, the
#: precision of the benchmark's cell ``multigrid-512-f32.vcycle``: five
#: times the floor float32 reaches at this spacing and stays on from the
#: fourth cycle (L2 3.9e-8 Poisson, 2.3e-8 Helmholtz: one rounding of
#: the Laplacian, eps * |f| * 6/dx**2)
_F64, _F32 = (np.float64, 5e-14), (np.float32, 2e-7)
_SLOW = pytest.mark.slow


@pytest.mark.parametrize("h", [1])
@pytest.mark.parametrize("proc_shape, Solver, MG, precision", [
    ((1, 1, 1), NewtonIterator, FullApproximationScheme, _F64),
    ((1, 1, 1), JacobiIterator, FullApproximationScheme, _F64),
    ((1, 1, 1), NewtonIterator, MultiGridSolver, _F64),
    ((1, 1, 1), JacobiIterator, MultiGridSolver, _F64),
    ((2, 2, 1), NewtonIterator, FullApproximationScheme, _F64),
    ((2, 2, 1), JacobiIterator, FullApproximationScheme, _F64),
    ((2, 2, 1), NewtonIterator, MultiGridSolver, _F64),
    ((2, 2, 1), JacobiIterator, MultiGridSolver, _F64),
    # `slow`: the (2,2,2) quartet costs ~87 s against the tier-1
    # budget; every Solver x MG combo stays covered on the two meshes
    # above, and the z-sharded (2,2,2) mesh itself stays covered by
    # test_multigrid_cycles_and_replicated_levels and
    # test_transfer_identities (unfiltered runs still execute these)
    pytest.param((2, 2, 2), NewtonIterator, FullApproximationScheme, _F64,
                 marks=_SLOW),
    pytest.param((2, 2, 2), JacobiIterator, FullApproximationScheme, _F64,
                 marks=_SLOW),
    pytest.param((2, 2, 2), NewtonIterator, MultiGridSolver, _F64,
                 marks=_SLOW),
    pytest.param((2, 2, 2), JacobiIterator, MultiGridSolver, _F64,
                 marks=_SLOW),
    # the cell's own solver, scheme and precision
    ((1, 1, 1), NewtonIterator, FullApproximationScheme, _F32),
], indirect=["proc_shape"])
@pytest.mark.parametrize("grid_shape", [(32, 32, 32)], indirect=True)
def test_multigrid(make_decomp, grid_shape, proc_shape, h, Solver, MG,
                   precision):
    dtype, tol = precision
    decomp = make_decomp(proc_shape)
    dx = 10.0 / grid_shape[0]

    solver = Solver(decomp, make_problems(), halo_shape=h, dtype=dtype,
                    fixed_parameters=dict(omega=1 / 2))
    mg = MG(solver=solver, halo_shape=h)

    rng = np.random.default_rng(5521)
    f, rho, f2, rho2 = zero_mean_arrays(rng, decomp, grid_shape, 4, dtype)

    poisson_errs, helmholtz_errs = [], []
    for _ in range(10):
        errs, sol = mg(decomp, dx0=dx, f=f, rho=rho, f2=f2, rho2=rho2)
        f, f2 = sol["f"], sol["f2"]
        poisson_errs.append(errs[-1][-1]["f"])
        helmholtz_errs.append(errs[-1][-1]["f2"])
    assert f.dtype == dtype and f2.dtype == dtype

    for name, cycle_errs in zip(["poisson", "helmholtz"],
                                [poisson_errs, helmholtz_errs]):
        assert cycle_errs[-1][1] < tol and cycle_errs[-2][1] < 10 * tol, \
            f"multigrid solution to {name} eqn inaccurate for " \
            f"{grid_shape=}, {h=}, {proc_shape=}\n{cycle_errs=}"


@pytest.mark.parametrize("proc_shape", [(2, 2, 2)], indirect=True)
@pytest.mark.parametrize("grid_shape", [(16, 16, 16)], indirect=True)
@pytest.mark.parametrize("cycle", [
    v_cycle(25, 50, 3), w_cycle(10, 20, 2),
    # the F-cycle recursion shape rides unfiltered: V (deep, the
    # replicated-level path) and W keep the cycle-spec interpreter and
    # the z-sharded (2,2,2) mesh tier-1-covered within the wall budget
    pytest.param(f_cycle(10, 20, 2), marks=pytest.mark.slow)])
def test_multigrid_cycles_and_replicated_levels(make_decomp, grid_shape,
                                                proc_shape, cycle):
    """Deep cycles force coarse levels onto the replicated path (local
    block of 2**3 at depth 3 on a 2x2x2 mesh is below the sharding
    threshold)."""
    decomp = make_decomp(proc_shape)
    dx = 10.0 / grid_shape[0]
    solver = NewtonIterator(decomp, make_problems(), halo_shape=1,
                            omega=1 / 2)
    mg = FullApproximationScheme(solver=solver, halo_shape=1)

    rng = np.random.default_rng(77)
    f, rho, f2, rho2 = zero_mean_arrays(rng, decomp, grid_shape, 4)
    for _ in range(10):
        errs, sol = mg(decomp, dx0=dx, cycle=cycle,
                       f=f, rho=rho, f2=f2, rho2=rho2)
        f, f2 = sol["f"], sol["f2"]
    assert errs[-1][-1]["f"][1] < 5e-14
    assert errs[-1][-1]["f2"][1] < 5e-14


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
@pytest.mark.parametrize("grid_shape", [(32, 32, 32)], indirect=True)
def test_fas_nonlinear(make_decomp, grid_shape, proc_shape):
    """FAS on a genuinely nonlinear problem: lap f - f + f**3 = rho. (The
    mass term keeps the periodic constant mode well-conditioned; without
    it the constant mode is only nonlinearly determined and relaxation
    stalls — the situation the reference's unfinished constraint machinery,
    relax.py:268-320, was aimed at.)"""
    decomp = make_decomp(proc_shape)
    dx = 10.0 / grid_shape[0]
    f_sym = ps.Field("f")
    problems = {f_sym: (ps.Field("lap_f") - f_sym + f_sym**3,
                        ps.Field("rho"))}
    solver = NewtonIterator(decomp, problems, halo_shape=1, omega=2 / 3)
    mg = FullApproximationScheme(solver=solver, halo_shape=1)

    rng = np.random.default_rng(11)
    f, rho = zero_mean_arrays(rng, decomp, grid_shape, 2)
    for _ in range(12):
        errs, sol = mg(decomp, dx0=dx, f=f, rho=rho)
        f = sol["f"]
    assert errs[-1][-1]["f"][1] < 1e-13, errs[-1][-1]["f"]


@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 2)], indirect=True)
def test_transfer_identities(make_decomp, grid_shape, proc_shape):
    """Restriction and interpolation preserve constants; injection picks
    even-index points; interpolation of a coarse field reproduces it at
    coinciding points."""
    decomp = make_decomp(proc_shape)
    rng = np.random.default_rng(3)

    const = decomp.shard(np.full(grid_shape, 2.5))
    for op in (FullWeighting(), Injection()):
        out = np.asarray(op(const, decomp=decomp))
        assert out.shape == tuple(n // 2 for n in grid_shape)
        assert np.allclose(out, 2.5, atol=1e-13)

    for op in (LinearInterpolation(), CubicInterpolation(halo_shape=2)):
        coarse_np = rng.random(tuple(n // 2 for n in grid_shape))
        coarse = decomp.shard(coarse_np)
        fine = np.asarray(op(coarse, decomp=decomp))
        assert fine.shape == tuple(grid_shape)
        assert np.allclose(fine[::2, ::2, ::2], coarse_np, atol=1e-13)

    # injection exactly picks f[2i, 2j, 2k]
    fine_np = rng.random(grid_shape)
    picked = np.asarray(Injection()(decomp.shard(fine_np), decomp=decomp))
    assert np.array_equal(picked, fine_np[::2, ::2, ::2])

    # full weighting of a fine field equals the explicit 27-point average
    fw = np.asarray(FullWeighting()(decomp.shard(fine_np), decomp=decomp))
    expect = np.zeros_like(fw)
    w1 = {-1: 0.25, 0: 0.5, 1: 0.25}
    for a, ca in w1.items():
        for b, cb in w1.items():
            for c, cc in w1.items():
                expect += (ca * cb * cc
                           * np.roll(fine_np, (-a, -b, -c),
                                     (0, 1, 2))[::2, ::2, ::2])
    assert np.allclose(fw, expect, atol=1e-13)


class FivePoint(RestrictionBase):
    """A user's wider restriction: offsets to +-2, so ``pad`` is 2."""

    coefs = {-2: -1 / 16, -1: 4 / 16, 0: 10 / 16, 1: 4 / 16, 2: -1 / 16}


_ALL_MESHES = [(1, 1, 1), (2, 1, 1), (2, 2, 1), (2, 2, 2)]


@pytest.mark.parametrize("proc_shape", _ALL_MESHES, indirect=True)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("Restrictor", [FullWeighting, Injection, FivePoint])
def test_restriction_is_roll_and_pick(make_decomp, proc_shape, dtype,
                                      Restrictor):
    """The split-and-contract restriction against numpy's own reading of
    the same ``coefs``: per axis ``sum_o c_o * roll(f, -o)[::2]``, on an
    uneven lattice with a leading component axis, on every mesh (a
    sharded axis takes its halo rows from the neighbour, an unsharded one
    has the wrap in its weights)."""
    decomp = make_decomp(proc_shape)
    op = Restrictor()
    fine = np.random.default_rng(41).random((2, 16, 32, 64)).astype(dtype)
    got = op(decomp.shard(fine), decomp=decomp)
    assert got.dtype == dtype and got.shape == (2, 8, 16, 32)

    expect = fine.astype(np.float64)
    for ax in (1, 2, 3):
        expect = sum(c * np.roll(expect, -o, ax) for o, c in op.coefs.items())
    expect = expect[:, ::2, ::2, ::2]
    if len(op.coefs) == 1:
        assert np.array_equal(np.asarray(got), expect.astype(dtype))
    else:
        # nine sums a point in another order than numpy's
        assert np.allclose(got, expect, rtol=0, atol=16 * np.finfo(dtype).eps)


@pytest.mark.parametrize("proc_shape", _ALL_MESHES, indirect=True)
@pytest.mark.parametrize("route", ["operator", "scheme"])
def test_transfer_plan_says_each_axis(make_decomp, grid_shape, proc_shape,
                                      route):
    """A built restriction program says once what it made of each axis
    (``mg_transfer_plan``): the halo form exactly on the axes the mesh
    shards. Through the operator's own call and through the scheme's
    cached sharded transfer (unsharded there: ``_run_local``'s)."""
    from test_kernel_choice import _watch_events
    decomp = make_decomp(proc_shape)
    fine = decomp.shard(np.random.default_rng(7).random(grid_shape))
    op = FullWeighting()
    if route == "operator":
        def call():
            return op(fine, decomp=decomp)
    else:
        solver = NewtonIterator(decomp, make_problems(), halo_shape=1,
                                omega=1 / 2)
        mg = FullApproximationScheme(solver=solver, halo_shape=1)
        levels = mg._make_levels(decomp, grid_shape, 1.0, 1)
        op = mg.restrictor

        def call():
            return mg._restrict(decomp, levels[0], levels[1], fine)

    with _watch_events() as seen:
        first = call()
        again = call()
    assert np.array_equal(first, again)
    plan, = seen.of("mg_transfer_plan")
    sharded = [p > 1 for p in proc_shape]
    assert plan["axes"] == [
        "contract_halo" if s else "contract" if d else "split"
        for d, s in enumerate(sharded)]
    assert plan["operator"] == "FullWeighting"
    assert plan["grid_shape"] == list(grid_shape)
    assert plan["local_shape"] == [n // p
                                   for n, p in zip(grid_shape, proc_shape)]
    assert plan["precision"] == "highest" and plan["dtype"] == "float64"
    # each contraction is a (rows, cols) matrix on every line of its axis
    shape, flops = list(plan["local_shape"]), 0
    for d, form in enumerate(plan["axes"]):
        rows, cols = shape[d] // 2, shape[d] + 2 * sharded[d]
        shape[d] = rows
        if form != "split":
            flops += 2 * rows * cols * (int(np.prod(shape)) // rows)
    assert plan["flops"] == flops > 0


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_standalone_relaxation(make_decomp, grid_shape, proc_shape):
    """Plain damped relaxation reduces the Poisson residual (reference
    RelaxationBase.__call__, relax.py:164-200)."""
    decomp = make_decomp(proc_shape)
    dx = 10.0 / grid_shape[0]
    solver = JacobiIterator(decomp, {
        ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho"))},
        halo_shape=1, omega=1 / 2)

    rng = np.random.default_rng(8)
    f, rho = zero_mean_arrays(rng, decomp, grid_shape, 2)
    from pystella_tpu.multigrid.relax import LevelSpec
    level = LevelSpec(tuple(grid_shape), (dx,) * 3, True)

    e0 = solver.get_error(level, {"f": f}, {"rho": rho}, {})["f"][1]
    out = solver(decomp, iterations=200, dx=dx, f=f, rho=rho)
    e1 = solver.get_error(level, out, {"rho": rho}, {})["f"][1]
    assert e1 < e0 / 3, (e0, e1)


if __name__ == "__main__":
    # V-cycle microbenchmark (reference test/common.py:41-56):
    #   python tests/test_multigrid.py -grid 128 128 128
    import common

    args = common.parse_args()
    decomp = common.script_decomp(args.proc_shape)
    dx = 10.0 / args.grid_shape[0]

    f_sym = ps.Field("f")
    problems = {f_sym: (ps.Field("lap_f") - f_sym + f_sym**3,
                        ps.Field("rho"))}
    solver = NewtonIterator(decomp, problems, halo_shape=args.h,
                            omega=2 / 3, dtype=args.dtype)
    mg = FullApproximationScheme(solver=solver, halo_shape=args.h)

    rng = np.random.default_rng(23)
    rho_np = rng.standard_normal(args.grid_shape).astype(args.dtype)
    rho = decomp.shard(rho_np - rho_np.mean())
    f0 = decomp.zeros(args.grid_shape, args.dtype)

    def cycle():
        _, sol = mg(decomp, dx0=dx, f=f0, rho=rho)
        return sol["f"]

    common.report("FAS V-cycle", ps.timer(cycle, ntime=max(2, args.ntime // 10)),
                  nsites=float(np.prod(args.grid_shape)))


@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1)],
                         indirect=True)
def test_pallas_smoother_matches_xla(make_decomp, grid_shape, proc_shape):
    """The Pallas sweep-kernel smoother tier (smoother='pallas',
    VERDICT r3 #5) performs the identical Jacobi update as the XLA
    halo-pad path: same sweeps, fp-roundoff agreement, and the residual
    pass agrees too. Runs in interpret mode on CPU."""
    from pystella_tpu.multigrid.relax import LevelSpec

    decomp = make_decomp(proc_shape)
    dx = 10.0 / grid_shape[0]
    sharded = any(p > 1 for p in proc_shape)
    level = LevelSpec(tuple(grid_shape), (dx,) * 3, sharded)

    rng = np.random.default_rng(77)
    f, rho = zero_mean_arrays(rng, decomp, grid_shape, 2)
    problems = {ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho"))}

    kw = dict(halo_shape=1, dtype=np.float64,
              fixed_parameters=dict(omega=1 / 2))
    s_xla = JacobiIterator(decomp, problems, smoother="xla", **kw)
    s_pal = JacobiIterator(decomp, problems, smoother="pallas", **kw)

    ref = s_xla.smooth(level, {"f": f}, {"rho": rho}, {}, 3, decomp)["f"]
    got = s_pal.smooth(level, {"f": f}, {"rho": rho}, {}, 3, decomp)["f"]
    err = np.max(np.abs(np.asarray(got) - np.asarray(ref)))
    assert err < 1e-13 * np.max(np.abs(np.asarray(ref))), err

    r_ref = s_xla.residual(level, {"f": f}, {"rho": rho}, {}, decomp)["f"]
    r_got = s_pal.residual(level, {"f": f}, {"rho": rho}, {}, decomp)["f"]
    assert np.max(np.abs(np.asarray(r_got) - np.asarray(r_ref))) < 1e-12

    # the FAS tau-correction right-hand side takes the same tier
    # (VERDICT r4 #4: residual + tau_rhs on the kernel path)
    t_ref = s_xla.tau_rhs(level, {"f": f}, {"f": rho}, {}, decomp)["rho"]
    t_got = s_pal.tau_rhs(level, {"f": f}, {"f": rho}, {}, decomp)["rho"]
    assert np.max(np.abs(np.asarray(t_got) - np.asarray(t_ref))) < 1e-12


def test_pallas_smoother_full_cycle(make_decomp, grid_shape):
    """A full FAS solve with the Pallas smoother converges to the same
    machine-precision residual as the XLA path (small-z lattices take
    the resident kernel)."""
    decomp = make_decomp((1, 1, 1))
    dx = 10.0 / grid_shape[0]
    solver = NewtonIterator(
        decomp, {ps.Field("f"): (ps.Field("lap_f") - ps.Field("f")
                                 + ps.Field("f") ** 3, ps.Field("rho"))},
        halo_shape=1, dtype=np.float64, smoother="pallas",
        fixed_parameters=dict(omega=2 / 3))
    mg = FullApproximationScheme(solver=solver, halo_shape=1)

    rng = np.random.default_rng(91)
    rho, = zero_mean_arrays(rng, decomp, grid_shape, 1)
    f = decomp.zeros(grid_shape, np.float64)
    err = None
    for _ in range(8):
        errs, sol = mg(decomp, dx0=dx, f=f, rho=rho)
        f = sol["f"]
        err = errs[-1][-1]["f"][1]
    assert err < 5e-13, err


#: per (mesh, precision): the solver, its level and arrays, and the
#: unknowns after 0..25 single-sweep calls — built once, by the first
#: case that needs them (the file runs in one worker)
_SWEEP_CHAINS = {}


def _sweep_chain(make_decomp, grid_shape, proc_shape, dtype):
    from pystella_tpu.multigrid.relax import LevelSpec
    key = (proc_shape, grid_shape, np.dtype(dtype).name)
    if key not in _SWEEP_CHAINS:
        decomp = make_decomp(proc_shape)
        level = LevelSpec(tuple(grid_shape), (10.0 / grid_shape[0],) * 3,
                          any(p > 1 for p in proc_shape))
        solver = NewtonIterator(
            decomp, make_problems(), halo_shape=1, dtype=dtype,
            smoother="pallas", fixed_parameters=dict(omega=1 / 2))
        rng = np.random.default_rng(33)
        f, f2, rho, rho2 = zero_mean_arrays(rng, decomp, grid_shape, 4,
                                            dtype=dtype)
        rhos = {"rho": rho, "rho2": rho2}
        fs, chain = {"f": f, "f2": f2}, []
        for _ in range(26):
            chain.append({n: np.asarray(v) for n, v in fs.items()})
            fs = solver.smooth(level, fs, rhos, {}, 1, decomp)
        _SWEEP_CHAINS[key] = (decomp, level, solver, rhos, chain)
    return _SWEEP_CHAINS[key]


@pytest.mark.parametrize("nu", [0, 1, 2, 3, 25])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1), (2, 1, 1)],
                         indirect=True)
def test_pallas_smooth_is_nu_single_sweeps(make_decomp, grid_shape,
                                           proc_shape, dtype, nu):
    """``smooth(level, ..., nu)`` on the kernel tier is ``nu`` single-sweep
    calls, bit for bit, whatever ``nu``'s parity: the sweep loop runs two
    sweeps an iteration and the odd one after it (so that XLA need not
    copy the loop's carry before every kernel call on the chip), which
    changes which buffer a sweep writes and no arithmetic. 0 is the empty
    loop, 1 the odd sweep alone, 2 a pair alone, 3 and 25 both; one
    compiled program serves them all. Interpret mode on the CPU."""
    decomp, level, solver, rhos, chain = _sweep_chain(
        make_decomp, grid_shape, proc_shape, dtype)
    fs = {n: decomp.shard(v) for n, v in chain[0].items()}
    got = solver.smooth(level, fs, rhos, {}, nu, decomp)
    for n, want in chain[nu].items():
        assert got[n].dtype == want.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(got[n]), want, err_msg=n)
    # every sweep count went through the level's one smooth program
    smooths = [k for k in solver._compiled
               if k[0] == "pallas" and k[1] == "smooth"]
    assert len(smooths) == 1 and solver._compiled[smooths[0]] is not None


#: the two-sweep kernel's cases: name -> (problems, auxiliary arrays by
#: name from (rng, decomp, grid_shape)); ``None`` marks a lattice-shaped
#: auxiliary, a float a scalar one
_PAIR_CASES = {
    # the benchmark cell's Poisson + Helmholtz pair
    "cell": (make_problems, {}),
    # a nonlinear operator: the diagonal depends on the unknown
    "nonlinear": (lambda: {ps.Field("f"): (
        ps.Field("lap_f") - ps.Field("f") + ps.Field("f") ** 3,
        ps.Field("rho"))}, {}),
    # a lattice-shaped coefficient (a window of the pair kernel, an
    # extra of the single one) beside a scalar one
    "aux": (lambda: {ps.Field("f"): (
        ps.Field("lap_f") - (ps.Field("m2") + ps.Var("c")) * ps.Field("f"),
        ps.Field("rho"))}, {"m2": None, "c": 0.25}),
}
_PAIR_CHAINS = {}
#: the XLA flag under which the CPU's compiler contracts nothing
_UNCONTRACTED = "--xla_backend_optimization_level=0"


def _pair_chain(make_decomp, grid_shape, case):
    """The case's solvers, level and arrays, and the unknowns after 0..9
    calls of ONE sweep each (the single-sweep kernel alone: a call of
    one sweep takes the `nu mod 4 = 1` branch): built by the first case
    that needs them."""
    from pystella_tpu.multigrid.relax import LevelSpec
    from pystella_tpu.obs import events
    if case not in _PAIR_CHAINS:
        problems, aux_spec = _PAIR_CASES[case]
        decomp = make_decomp((1, 1, 1))
        level = LevelSpec(tuple(grid_shape), (10.0 / grid_shape[0],) * 3,
                          False)
        kw = dict(halo_shape=1, dtype=np.float32,
                  fixed_parameters=dict(omega=1 / 2))
        solver = NewtonIterator(decomp, problems(), smoother="pallas", **kw)
        xla = NewtonIterator(decomp, problems(), smoother="xla", **kw)
        rng = np.random.default_rng(52)
        nf = len(solver.f_to_rho_dict)
        arrays = zero_mean_arrays(rng, decomp, grid_shape, 2 * nf,
                                  dtype=np.float32)
        fs = dict(zip(solver.f_to_rho_dict, arrays))
        rhos = dict(zip(solver.f_to_rho_dict.values(), arrays[nf:]))
        aux = {k: (decomp.shard((1 + rng.random(grid_shape)).astype(
            np.float32)) if v is None else v) for k, v in aux_spec.items()}
        records = []
        events.get_log().subscribe(records.append)
        try:
            chain = [fs]
            for _ in range(9):
                chain.append(solver.smooth(level, chain[-1], rhos, aux, 1,
                                           decomp))
        finally:
            events.get_log().unsubscribe(records.append)
        plan, = [r["data"] for r in records if r["kind"] == "mg_level_plan"]
        _PAIR_CHAINS[case] = (decomp, level, solver, xla, rhos, aux, chain,
                              plan)
    return _PAIR_CHAINS[case]


@pytest.mark.parametrize("nu", range(1, 10))
@pytest.mark.parametrize("case", list(_PAIR_CASES))
def test_pallas_pair_kernel_is_two_sweeps(make_decomp, grid_shape, case, nu):
    """On a streaming, unsharded level a smooth runs two sweeps a kernel
    pass (PR 52: sweep 1 once over the block grown by ``h`` rows, sweep
    2 from it, the single kernel's body twice), four sweeps a loop
    iteration and ``nu mod 4`` taken out in front by a four-way switch:
    ``smooth(..., nu)`` is ``nu`` calls of the single-sweep kernel bit
    for bit, for every remainder with and without loop iterations
    (``nu`` 1...9), for the benchmark cell's two linear problems and
    with a lattice-shaped auxiliary (a window of the pair kernel) beside
    a scalar one, and for an operator whose diagonal depends on the
    unknown as far as the CPU's compiler lets two programs agree; and
    the XLA path's ``nu`` sweeps to float32 rounding. Interpret mode on
    the CPU."""
    decomp, level, solver, xla, rhos, aux, chain, plan = _pair_chain(
        make_decomp, grid_shape, case)
    assert (plan["tier"], plan["sweeps_per_pass"]) == ("streaming", 2), plan
    assert plan["pair_bx"] >= 2 and plan["pair_reason"] is None, plan
    got = solver.smooth(level, chain[0], rhos, aux, nu, decomp)
    ref = xla.smooth(level, chain[0], rhos, aux, nu, decomp)
    for n, want in chain[nu].items():
        if case == "cell" or _UNCONTRACTED in os.environ.get("XLA_FLAGS", ""):
            np.testing.assert_array_equal(np.asarray(got[n]),
                                          np.asarray(want), err_msg=n)
        else:
            # XLA:CPU contracts a product into the sum after it in one
            # of the two programs and not in the other: one rounding of
            # the largest value a sweep (bit for bit where it does not
            # contract: the test below, and the chip)
            want = np.asarray(want)
            assert np.max(np.abs(np.asarray(got[n]) - want)) \
                <= nu * np.finfo(np.float32).eps * np.max(np.abs(want)), n
        r = np.asarray(ref[n])
        assert np.max(np.abs(np.asarray(got[n]) - r)) \
            < 2e-6 * np.max(np.abs(r)), n


def test_pallas_pair_kernel_is_two_sweeps_bit_for_bit_uncontracted():
    """The cases of ``test_pallas_pair_kernel_is_two_sweeps`` whose
    operator holds a product before a sum, bit for bit: in a child whose
    XLA:CPU runs no LLVM optimisation, so that no product is contracted
    into a sum in one program and left alone in the other. The pair
    kernel's arithmetic is the single kernel's twice, operation for
    operation."""
    import subprocess
    import sys
    env = dict(os.environ, XLA_FLAGS=_UNCONTRACTED, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", __file__, "-q", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-k",
         "test_pallas_pair_kernel_is_two_sweeps and not cell"
         " and not uncontracted"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0 and "18 passed" in out.stdout, \
        out.stdout[-3000:] + out.stderr[-1000:]


# -- the walk's layout: stacks, against the operations by name ---------------

def _cycle_by_name(mg, decomp, dx, cycle, unknowns, rhos):
    """One cycle of ``mg``'s scheme by the dict interfaces alone, an
    unknown at a time where the scheme allows it: every smooth, norm,
    residual, tau right-hand side and transfer is a call of its own on
    arrays by name (each stacks at its own boundary), as the walk made
    them before it carried stacks. Returns ``(errors, unknowns)``."""
    solver = mg.solver
    f_to_rho = solver.f_to_rho_dict
    depth = max(i for i, _ in cycle)
    levels = mg._make_levels(decomp, next(iter(unknowns.values())).shape,
                             dx, depth)
    fs, rs, errors = {0: dict(unknowns)}, {0: dict(rhos)}, []

    def restrict(i, x):
        return mg._restrict(decomp, levels[i], levels[i + 1], x)

    def smooth(i, nu):
        errors.append((i, solver.get_error(levels[i], fs[i], rs[i], {},
                                           decomp)))
        fs[i] = solver.smooth(levels[i], fs[i], rs[i], {}, nu, decomp)
        errors.append((i, solver.get_error(levels[i], fs[i], rs[i], {},
                                           decomp)))

    smooth(0, cycle[0][1])
    previous = 0
    for i, nu in cycle[1:]:
        if i == previous + 1:
            resid = solver.residual(levels[i - 1], fs[i - 1], rs[i - 1], {},
                                    decomp)
            rr = {n: restrict(i - 1, r) for n, r in resid.items()}
            if isinstance(mg, MultiGridSolver):
                rs[i] = {f_to_rho[n]: r for n, r in rr.items()}
                fs[i] = {n: 0 * r for n, r in rr.items()}
            else:
                fs[i] = {n: restrict(i - 1, f) for n, f in fs[i - 1].items()}
                rs[i] = solver.tau_rhs(levels[i], fs[i], rr, {}, decomp)
        else:
            for n, f in fs[i].items():
                corr = fs[i + 1][n]
                if not isinstance(mg, MultiGridSolver):
                    corr = corr - restrict(i, f)
                fs[i][n] = f + mg._interpolate(
                    decomp, levels[i + 1], levels[i], corr)
        smooth(i, nu)
        previous = i
    return errors, fs[0]


@pytest.mark.parametrize("MG", [FullApproximationScheme, MultiGridSolver])
@pytest.mark.parametrize("nf", [1, 2])
@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1), (2, 1, 1)],
                         indirect=True)
@pytest.mark.parametrize("smoother", ["xla", "pallas"])
def test_stacked_walk_is_the_operations_by_name(make_decomp, grid_shape,
                                                proc_shape, smoother, nf,
                                                MG):
    """A cycle carries each level's unknowns, sources, residuals and tau
    right-hand sides as one ``(nf, X, Y, Z)`` stack, stacked once on
    entry and unstacked once on return (``mg_cycle.layout_copies`` 3,
    whatever ``nf``), and a transfer, a norm or an add is one program a
    level: its unknowns and errors are those of the same operations made
    by name, to float32 rounding; the caller's arrays are neither changed
    nor donated away, so the same call twice gives the same answer bit
    for bit (the benchmark's ``repeat_gap``)."""
    from pystella_tpu.obs import events
    decomp = make_decomp(proc_shape)
    dx = 10.0 / grid_shape[0]
    problems = dict(list(make_problems().items())[-nf:])
    solver = NewtonIterator(decomp, problems, halo_shape=1, dtype=np.float32,
                            smoother=smoother,
                            fixed_parameters=dict(omega=1 / 2))
    mg = MG(solver=solver, halo_shape=1)
    cycle = v_cycle(2, 3, 1)
    names = list(solver.f_to_rho_dict)
    rng = np.random.default_rng(404)
    host = dict(zip(names + list(solver.f_to_rho_dict.values()),
                    (np.asarray(a) for a in zero_mean_arrays(
                        rng, decomp, grid_shape, 2 * nf, np.float32))))
    arrays = {k: decomp.shard(v) for k, v in host.items()}

    records = []
    events.get_log().subscribe(records.append)
    try:
        runs = [mg(decomp, dx0=dx, cycle=cycle, **arrays) for _ in range(2)]
    finally:
        events.get_log().unsubscribe(records.append)
    for k, v in arrays.items():  # still there, and still what they were
        np.testing.assert_array_equal(np.asarray(v), host[k], err_msg=k)
    (errs, sol), (errs_again, sol_again) = runs
    assert errs == errs_again
    for n in names:
        assert sol[n].dtype == np.float32 and sol[n].shape == grid_shape
        np.testing.assert_array_equal(np.asarray(sol[n]),
                                      np.asarray(sol_again[n]), err_msg=n)

    want_errs, want = _cycle_by_name(
        mg, decomp, dx, cycle, {n: arrays[n] for n in names},
        {r: arrays[r] for r in solver.f_to_rho_dict.values()})
    for n in names:
        got, ref = np.asarray(sol[n]), np.asarray(want[n])
        assert np.max(np.abs(got - ref)) < 2e-6 * np.max(np.abs(ref)), n
    assert [i for i, _ in errs] == [i for i, _ in want_errs] == [
        0, 0, 1, 1, 0, 0]
    for (_, got), (_, ref) in zip(errs, want_errs):
        assert list(got) == names
        for n in names:
            np.testing.assert_allclose(got[n], ref[n], rtol=2e-4, err_msg=n)

    done = [r["data"] for r in records if r["kind"] == "mg_cycle"]
    assert [d["layout_copies"] for d in done] == [3, 3]
    # three smooths of a kernel program and two norms of two programs
    # each; down, two restrictions, a residual and a tau (the linear
    # scheme: a restriction, a residual and the zeroed correction); up, a
    # restriction and a subtraction (the full scheme's alone), an
    # interpolation and an add; and the three layout copies
    down_up = 3 + 2 if MG is MultiGridSolver else 4 + 4
    assert [d["dispatches"] for d in done] == [3 * 5 + down_up + 3] * 2
    plans = [r["data"] for r in records if r["kind"] == "mg_level_plan"]
    assert plans and all(d["layout"] == "stacked" for d in plans)
    # a streaming level's smooth takes two sweeps a kernel pass unless the
    # level is sharded, and then says why it does not
    for d in plans:
        if d["tier"] == "streaming":
            sharded = d["local_shape"] != d["grid_shape"]
            assert d["sweeps_per_pass"] == (1 if sharded else 2), d
            assert (d["pair_reason"] or "").startswith("sharded") == sharded
