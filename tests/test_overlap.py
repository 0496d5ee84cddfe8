"""Overlapped halo-exchange equivalence tests.

The contract under test: the overlapped path (ppermutes issued first,
interior computed while the collectives fly, boundary shells stitched
once halos land) is BIT-IDENTICAL to the padded path — same tap
offsets, same per-element reduction order — for every stencil consumer
(FiniteDifferencer halo/pallas modes, the fused RK stages, the
multigrid smoother), on 1- and 2-axis-sharded CPU meshes, including
the degenerate configurations that must fall back (3-axis/z-sharded
meshes, blocks thinner than ``MIN_INTERIOR_FACTOR * h``, halo width
equal to the local block size). Plus the policy plumbing: the
``PYSTELLA_HALO_OVERLAP`` env gate, the scheduler-flag fingerprint, the
``halo_exchanges``/``halo_bytes_exchanged`` counters, and the ledger's
exposed-vs-hidden derivation.
"""

import os

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.parallel import overlap as overlap_mod
from pystella_tpu.parallel.decomp import HaloShells


def _field(grid_shape, seed=3, dtype=np.float32, outer=()):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(tuple(outer) + tuple(grid_shape)) \
        .astype(dtype)


# -- the decomp-level contract ---------------------------------------------

@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_pad_with_halos_overlap_contract(decomp, grid_shape, proc_shape):
    """``pad_with_halos(overlap=True)`` returns ``(interior, shells)``;
    the shell regions tile the boundary exactly once and stitch with
    the interior back to the full block."""
    import jax
    h = 2
    halo = (h, h, h)
    host = _field(grid_shape)
    arr = decomp.shard(host)
    spec = decomp.spec(0)

    def body(x):
        interior, shells = decomp.pad_with_halos(x, halo, overlap=True)
        assert isinstance(shells, HaloShells)
        # regions tile the boundary once: interior + shells == block
        vol = np.prod([b - a for a, b in shells.interior_region()])
        for region in shells.regions():
            vol += np.prod([b - a for a, b in region])
        assert vol == np.prod(x.shape)
        # identity stencil: stitching center slices reproduces x
        def center(p):
            return p[tuple(slice(halo[d], p.shape[d] - halo[d])
                           for d in range(3))]
        return shells.stitch(
            center(interior), [center(i) for i in shells.inputs()])

    out = jax.jit(decomp.shard_map(body, spec, spec))(arr)
    assert np.array_equal(np.asarray(out), host)


def test_pad_with_halos_overlap_rejects_infeasible(make_decomp,
                                                   grid_shape):
    """No split exists on an unsharded mesh, under a z exchange, or for
    blocks thinner than MIN_INTERIOR_FACTOR*h — pad_with_halos raises;
    overlap_stencil silently takes the padded path instead."""
    import jax
    decomp = make_decomp((1, 1, 1))
    x = decomp.shard(_field(grid_shape))
    with pytest.raises(ValueError, match="no overlappable axis"):
        jax.eval_shape(
            lambda a: decomp.pad_with_halos(a, (1, 1, 1), overlap=True),
            x)
    sharded_z = make_decomp((1, 1, 2))
    xz = sharded_z.shard(_field(grid_shape))

    def split_z(a):
        return sharded_z.pad_with_halos(a, (1, 1, 1), overlap=True)

    with pytest.raises(ValueError, match="no overlappable axis"):
        jax.eval_shape(
            lambda a: sharded_z.shard_map(
                split_z, sharded_z.spec(0),
                (sharded_z.spec(0), sharded_z.spec(0)))(a), xz)


# -- FiniteDifferencer: halo mode ------------------------------------------

@pytest.mark.parametrize("proc_shape", [(2, 1, 1), (2, 2, 1), (2, 2, 2)],
                         indirect=True)
@pytest.mark.parametrize("h", [1, 2])
def test_derivs_overlap_bitexact(decomp, grid_shape, proc_shape, h):
    """Laplacian, gradient, fused gradient+Laplacian, per-axis
    derivatives and divergence: overlapped == padded, bit for bit, on
    1-, 2- and 3-axis-sharded meshes (the 3-axis mesh exercises the
    z-communication fallback, which must still be exact)."""
    f = decomp.shard(_field(grid_shape))
    v = decomp.shard(_field(grid_shape, seed=5, outer=(3,)))
    fd_ov = ps.FiniteDifferencer(decomp, h, 0.1, mode="halo",
                                 overlap=True)
    fd_pd = ps.FiniteDifferencer(decomp, h, 0.1, mode="halo",
                                 overlap=False)
    for op in ("lap", "grad", "pdx", "pdy", "pdz"):
        a = np.asarray(getattr(fd_ov, op)(f))
        b = np.asarray(getattr(fd_pd, op)(f))
        assert np.array_equal(a, b), op
    ga, la = fd_ov.grad_lap(f)
    gb, lb = fd_pd.grad_lap(f)
    assert np.array_equal(np.asarray(ga), np.asarray(gb))
    assert np.array_equal(np.asarray(la), np.asarray(lb))
    assert np.array_equal(np.asarray(fd_ov.divergence(v)),
                          np.asarray(fd_pd.divergence(v)))


@pytest.mark.parametrize("proc_shape", [(2, 1, 1)], indirect=True)
def test_derivs_overlap_lowering_has_scopes(decomp, grid_shape,
                                            proc_shape):
    """The overlapped lowering really takes the split (halo_overlap /
    interior / shells scopes present); the padded lowering does not."""
    import jax
    f = decomp.shard(_field(grid_shape))
    fd_ov = ps.FiniteDifferencer(decomp, 2, 0.1, mode="halo",
                                 overlap=True)
    lowered = fd_ov._sharded("lap", 0, False, False).lower(f)
    for scope in ("halo_overlap", "halo_overlap_interior",
                  "halo_overlap_shells", "halo_exchange"):
        assert obs.has_scope(lowered, scope), scope
    fd_pd = ps.FiniteDifferencer(decomp, 2, 0.1, mode="halo",
                                 overlap=False)
    lowered = fd_pd._sharded("lap", 0, False, False).lower(f)
    assert not obs.has_scope(lowered, "halo_overlap")
    assert obs.has_scope(lowered, "halo_exchange")


def test_overlap_degenerate_all_shell(make_decomp):
    """Halo width equal to the local block size: every site is shell,
    there is no interior — the overlapped call must take the padded
    path and stay bit-identical (the all-shell case from the issue)."""
    decomp = make_decomp((2, 1, 1))
    grid = (8, 8, 8)   # local block 4 wide, h = 4
    h = 4
    f = decomp.shard(_field(grid))
    fd_ov = ps.FiniteDifferencer(decomp, h, 0.1, mode="halo",
                                 overlap=True)
    fd_pd = ps.FiniteDifferencer(decomp, h, 0.1, mode="halo",
                                 overlap=False)
    assert np.array_equal(np.asarray(fd_ov.lap(f)),
                          np.asarray(fd_pd.lap(f)))
    lowered = fd_ov._sharded("lap", 0, False, False).lower(f)
    assert not obs.has_scope(lowered, "halo_overlap")  # fell back


# -- fused RK stages (interpret-mode Pallas) -------------------------------

def _fused_pair(decomp, grid, overlap, dt):
    def potential(f):
        return 0.5 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    sector = ps.ScalarSector(2, potential=potential)
    return ps.FusedScalarStepper(sector, decomp, grid, 0.3, 2,
                                 dtype=np.float32, dt=dt,
                                 overlap=overlap)


@pytest.mark.parametrize("proc_shape", [
    (2, 1, 1),
    # the xy-mesh repeat of the same interior/shell split rides
    # unfiltered for the tier-1 wall budget; the x-sharded case keeps
    # the fused overlapped-stage path (and its bit-exactness) tier-1
    pytest.param((2, 2, 1), marks=pytest.mark.slow)],
    indirect=True)
def test_fused_stage_overlap_bitexact(make_decomp, proc_shape):
    """A fused scalar RK stage and a full (pair-kernel) step:
    overlapped == padded bit for bit. On the x-sharded mesh the
    interior/shell Pallas launch split really engages; the x/y-sharded
    mesh exercises its feasibility fallback (y shells have no legal
    sublane blocking), which must be exact trivially."""
    decomp = make_decomp(proc_shape)
    grid = (16, 16, 16)
    dt = np.float32(0.01)
    state = {k: decomp.shard(
        0.1 * _field(grid, seed=21, outer=(2,)))
        for k in ("f", "dfdt")}
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    s_ov = _fused_pair(decomp, grid, True, dt)
    s_pd = _fused_pair(decomp, grid, False, dt)

    c_ov = s_ov.stage(0, s_ov.init_carry(dict(state)), 0.0, dt, args)
    c_pd = s_pd.stage(0, s_pd.init_carry(dict(state)), 0.0, dt, args)
    for tree_a, tree_b in zip(c_ov, c_pd):
        for k in tree_a:
            assert np.array_equal(np.asarray(tree_a[k]),
                                  np.asarray(tree_b[k])), ("stage", k)

    st_ov = s_ov.step(dict(state), 0.0, dt, args)
    st_pd = s_pd.step(dict(state), 0.0, dt, args)
    for k in st_ov:
        assert np.array_equal(np.asarray(st_ov[k]),
                              np.asarray(st_pd[k])), ("step", k)

    lowered = s_ov._jit_step.lower(dict(state), 0.0, dt, args)
    if proc_shape == (2, 1, 1):  # the split engages on x-sharded meshes
        assert obs.has_scope(lowered, "halo_overlap_interior")
    else:                        # ...and falls back under y sharding
        assert not obs.has_scope(lowered, "halo_overlap")


# -- multigrid smoother ----------------------------------------------------

@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
@pytest.mark.parametrize("smoother", ["xla", "pallas"])
def test_multigrid_smooth_overlap_bitexact(make_decomp, grid_shape,
                                           proc_shape, smoother):
    """Jacobi sweeps and residuals on a sharded level: overlapped ==
    padded, on both the XLA tier and the (interpret-mode) Pallas sweep
    tier."""
    from pystella_tpu.multigrid.relax import JacobiIterator, LevelSpec
    decomp = make_decomp(proc_shape)
    f_sym = ps.Field("f")
    problems = {f_sym: (ps.Field("lap_f") - f_sym, ps.Field("rho"))}
    f0 = decomp.shard(_field(grid_shape, seed=11))
    rho = decomp.shard(_field(grid_shape, seed=12))
    level = LevelSpec(grid_shape, (0.1,) * 3, True)
    outs = {}
    for ov in (True, False):
        solver = JacobiIterator(decomp, problems, halo_shape=1,
                                omega=2 / 3, dtype=np.float32,
                                smoother=smoother, overlap=ov)
        outs[ov] = np.asarray(
            solver.smooth(level, {"f": f0}, {"rho": rho}, {}, 3)["f"])
        outs[(ov, "r")] = np.asarray(
            solver.residual(level, {"f": f0}, {"rho": rho}, {})["f"])
    assert np.array_equal(outs[True], outs[False])
    assert np.array_equal(outs[(True, "r")], outs[(False, "r")])


# -- policy, counters, fingerprint -----------------------------------------

def test_overlap_env_gate(make_decomp, monkeypatch):
    sharded = make_decomp((2, 1, 1))
    single = make_decomp((1, 1, 1))
    monkeypatch.delenv("PYSTELLA_HALO_OVERLAP", raising=False)
    assert overlap_mod.enabled(sharded)          # auto: on when sharded
    assert not overlap_mod.enabled(single)
    assert not overlap_mod.enabled(sharded, override=False)
    monkeypatch.setenv("PYSTELLA_HALO_OVERLAP", "0")
    assert not overlap_mod.enabled(sharded)
    monkeypatch.setenv("PYSTELLA_HALO_OVERLAP", "1")
    assert overlap_mod.enabled(single)           # env wins over auto
    assert not overlap_mod.enabled(single, override=False)


def test_scheduler_flags_fingerprint():
    """The package sets no libtpu flag itself (the set it used to append
    named one libtpu 0.0.34 aborts on); what the environment carries is
    still fingerprinted."""
    env = {"LIBTPU_INIT_ARGS":
           "--xla_tpu_enable_latency_hiding_scheduler=true "
           "--xla_enable_async_all_gather=true"}
    fp = overlap_mod.flags_fingerprint(env)
    assert fp.get("xla_tpu_enable_latency_hiding_scheduler") == "true"
    assert fp.get("xla_enable_async_all_gather") == "true"
    # the ledger's stdlib twin parses the same environment shape
    from pystella_tpu.obs import ledger
    os.environ["LIBTPU_INIT_ARGS"] = env["LIBTPU_INIT_ARGS"]
    try:
        led_fp = ledger.xla_flag_fingerprint()
    finally:
        del os.environ["LIBTPU_INIT_ARGS"]
    assert led_fp.get("xla_tpu_enable_latency_hiding_scheduler") == "true"


def test_share_halos_counters(make_decomp, grid_shape):
    """``halo_exchanges`` counts per-axis exchanges actually issued —
    not wrapped-locally axes, not unsharded-mesh calls; the bytes
    counter records a distinct traced program once."""
    from pystella_tpu.obs import metrics
    decomp = make_decomp((2, 2, 1))
    arr = decomp.shard(_field(grid_shape))
    ex = metrics.counter("halo_exchanges")
    by = metrics.counter("halo_bytes_exchanged")

    v0, b0 = ex.value, by.value
    decomp.share_halos(arr, (2, 0, 3))   # x ppermutes, y none, z local
    assert ex.value - v0 == 1
    assert by.value > b0                 # the traced program's bytes
    b1 = by.value
    decomp.share_halos(arr, (2, 0, 3))   # cached program: no new bytes
    assert ex.value - v0 == 2
    assert by.value == b1

    v1 = ex.value
    decomp.share_halos(arr, (1, 1, 1))   # x and y exchange
    assert ex.value - v1 == 2

    single = make_decomp((1, 1, 1))
    sarr = single.shard(_field(grid_shape))
    v2, b2 = ex.value, by.value
    single.share_halos(sarr, 2)          # local wraps only
    assert ex.value == v2 and by.value == b2

    assert decomp.traced_halo_bytes() > 0


def test_ledger_overlap_section():
    """Synthetic ledger: halo scopes + a halo_traffic figure derive the
    exposed-vs-hidden split and the achieved-ICI line; the markdown
    carries them."""
    from pystella_tpu.obs import ledger
    led = ledger.PerfLedger(label="unit", sites=1000)
    for ms in (1.0, 1.1, 0.9):
        led.add_step_ms(ms)
    # device rows appear once per device, so the raw scope totals are
    # fleet sums — overlap_summary must normalize them to per-device
    # wall time (host-side halo_overlap spans stay unscaled)
    led.env["num_devices"] = 2
    led.scopes = {
        "collective-permute": {"count": 8, "total_ms": 8.0,
                               "mean_ms": 1.0},
        "halo_overlap_interior": {"count": 4, "total_ms": 6.0,
                                  "mean_ms": 1.5},
        "halo_overlap": {"count": 4, "total_ms": 6.0, "mean_ms": 1.5},
    }
    led.halo_bytes_per_step = 1e6
    ov = led.overlap_summary()
    assert ov["comm_scope"] == "collective-permute"
    assert ov["comm_ms"] == pytest.approx(4.0)       # 8.0 / 2 devices
    assert ov["interior_ms"] == pytest.approx(3.0)   # 6.0 / 2 devices
    assert ov["hidden_ms"] == pytest.approx(3.0)
    assert ov["exposed_ms"] == pytest.approx(1.0)
    assert ov["achieved_ici_gbps"] == pytest.approx(
        1e6 * 4 / (4.0e-3) / 1e9)
    md = ledger.render_markdown(led.report())
    assert "Communication overlap" in md
    assert "exposed" in md and "GB/s ICI" in md
    # no halo activity at all -> no section
    led.scopes = {}
    assert led.overlap_summary() is None


def test_gate_warns_on_flag_mismatch():
    from pystella_tpu.obs import gate, ledger
    led = ledger.PerfLedger(label="unit", sites=1000)
    led.samples_ms = [10.0 + 0.01 * i for i in range(20)]
    base = led.report()
    cur = led.report()
    base["env"] = dict(base["env"],
                       xla_flags={"xla_tpu_enable_latency_hiding"
                                  "_scheduler": "true"})
    cur["env"] = dict(cur["env"], xla_flags={})
    verdict = gate.compare_reports(base, cur)
    assert verdict["ok"]  # warning, not refusal
    assert any("flags differ" in w for w in verdict["warnings"])


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
