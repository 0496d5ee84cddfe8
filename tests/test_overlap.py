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


@pytest.mark.parametrize("donate", [False, True],
                         ids=["fresh", "in-place"])
@pytest.mark.parametrize("h", [1, 2, 4])
def test_split_stage_loop_equals_single_launch(make_decomp, h, donate):
    """The split as PR 43 builds it (the interior the ring kernel over
    an inset grid, the shells' rows put into its full-lattice outputs)
    against the slab-fed single launch, bit for bit, through a whole
    step of the stage-by-stage protocol (five ``stage`` kernels, three
    extras each) at radius 1, 2 and 4, and a pair-kernel ``step`` at 2
    and 4 (at radius 1 the CPU's compilation of the one-row shell
    contracts one product of the pair body another way: one value in
    8,192 is an ulp off, on the parent of PR 43 as here; Mosaic has no
    such freedom). With ``donate=True`` the ``stage`` kernel writes its
    extras in place: the interior aliases the donated buffers, and the
    shells' rows of them are taken before it runs."""
    decomp = make_decomp((2, 1, 1))
    grid = (max(16, 8 * h), 16, 16)     # four x-blocks a shard at least
    dt = np.float32(0.01)
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}

    def potential(f):
        return 0.5 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    def run(overlap):
        stepper = ps.FusedScalarStepper(
            ps.ScalarSector(2, potential=potential), decomp, grid, 0.3,
            h, dtype=np.float32, dt=dt, overlap=overlap, donate=donate)
        state = {k: decomp.shard(0.1 * _field(grid, seed=21, outer=(2,)))
                 for k in ("f", "dfdt")}
        carry = stepper.init_carry(state)
        for s in range(stepper.num_stages):
            carry = stepper.stage(s, carry, 0.0, dt, args)
        staged = {k: np.asarray(v) for k, v in carry[0].items()}
        state = stepper.step(dict(carry[0]), 0.0, dt, args)
        return stepper, staged, {k: np.asarray(v) for k, v in state.items()}

    split, staged, stepped = run(True)
    _, staged1, stepped1 = run(False)
    assert split._scalar_st.in_place == (
        ("dfdt", "kf", "kdfdt") if donate else ())
    pairs = [(staged, staged1)] + [(stepped, stepped1)] * (h > 1)
    for got, ref in pairs:
        for k in ref:
            assert np.array_equal(got[k], ref[k]), k


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
    from pystella_tpu.obs import memory
    fp = memory.flags_fingerprint(env)
    assert fp.get("xla_tpu_enable_latency_hiding_scheduler") == "true"
    assert fp.get("xla_enable_async_all_gather") == "true"
    # and the report's environment fingerprint reads the process's own
    os.environ["LIBTPU_INIT_ARGS"] = env["LIBTPU_INIT_ARGS"]
    try:
        led_fp = memory.environment_fingerprint()["xla_flags"]
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


# -- the Pallas split says what it built (PR 42) ---------------------------

def _plan_case(make_decomp, case):
    """Build one sharded kernel consumer; returns the events seen."""
    from test_kernel_choice import _watch_events
    proc, grid, overlap = {
        "split": ((2, 1, 1), (16, 16, 16), True),
        "sums": ((2, 1, 1), (16, 16, 16), True),
        "y_sharded": ((2, 2, 1), (16, 16, 16), True),
        "thin": ((2, 1, 1), (8, 16, 16), True),
        "off": ((2, 1, 1), (16, 16, 16), False),
        "derivs": ((2, 1, 1), (16, 16, 16), True),
        "multigrid": ((2, 1, 1), (16, 16, 16), True),
    }[case]
    decomp = make_decomp(proc)
    with _watch_events() as seen:
        if case == "derivs":
            fd = ps.FiniteDifferencer(decomp, 2, 0.3, mode="pallas",
                                      overlap=overlap)
            fd._pallas_op("lap", 2, np.dtype("float32"), False, grid)
        elif case == "multigrid":
            from pystella_tpu.multigrid.relax import (
                LevelSpec, NewtonIterator)
            solver = NewtonIterator(
                decomp, {ps.Field("f"): (ps.Field("f"), ps.Field("rho"))},
                halo_shape=1, dtype=np.float32, smoother="pallas",
                overlap=overlap, fixed_parameters=dict(omega=1 / 2))
            assert solver._pallas_level(
                "smooth", LevelSpec(grid, (0.3,) * 3, True), decomp,
                np.dtype("float32"), ())
        else:
            stepper = _fused_pair(decomp, grid, overlap, np.float32(0.01))
            if case == "sums":
                stepper._ensure_energy_call()
    return seen


@pytest.mark.parametrize("case, kernel, reason", [
    ("split", "pair", None), ("derivs", "lap", None),
    ("multigrid", "mg_smooth", None), ("sums", "energy", "sums"),
    ("y_sharded", "pair", "y_sharded"), ("thin", "pair", "thin"),
    ("off", "pair", "off")])
def test_overlap_plan_event(make_decomp, case, kernel, reason):
    """Every sharded kernel build says which launch it takes: one
    ``overlap_plan`` event a kernel, ``path: split`` with the interior's
    and the shell's lattice, blocking, grid, x-edge source (``halo``),
    modelled re-read, how the pieces meet (``stitch``) and the ideal
    bytes of the copies still round them, or ``path: single`` with the
    reason (what used to be a ``logging.info`` line, or nothing)."""
    seen = _plan_case(make_decomp, case)
    plans = {d["kernel"]: d for d in seen.of("overlap_plan")}
    d = plans[kernel]
    built = {b["kernel"]: b for b in seen.of("block_choice")}
    if reason is not None:
        assert (d["path"], d["reason"]) == ("single", reason)
        assert "interior" not in d and "stitch_bytes" not in d
        assert not any(k.endswith(("_interior", "_shell")) for k in built
                       if k.startswith(kernel))
        return
    assert d["path"] == "split" and "reason" not in d
    X, Y, Z = d["local_shape"]
    h = 1 if case == "multigrid" else 2
    inner, shell = d["interior"], d["shell"]
    assert (inner["kernel"], shell["kernel"]) == (kernel + "_interior",
                                                  kernel + "_shell")
    assert inner["lattice"] == [X - 2 * h, Y, Z]
    assert shell["lattice"] == [h, Y, Z] and shell["bx"] == h
    assert inner["by"] == shell["by"]
    assert inner["grid"] == [Y // inner["by"], (X - 2 * h) // inner["bx"]]
    assert shell["grid"] == [Y // shell["by"], 1]
    # the interior is the ring kernel over the shard, inset by one
    # x-block of h rows at either end (PR 43): every window row is read
    # once, so its re-read is the y windows' alone; the pre-padded
    # shell has no ring and reads every row (bx + 2h) / bx = 3 times
    assert (inner["halo"], shell["halo"]) == ("inset", "padded")
    assert inner["bx"] == h
    from pystella_tpu.ops.pallas_stencil import HY
    assert 1 < inner["reread"] <= 1 + 2 * HY / inner["by"]
    assert shell["reread"] > 1.3 * inner["reread"]
    assert d["stitch"] == "in_place" and d["stitch_bytes"] > 0
    if case != "split":
        return
    # the fused steppers add a block_choice a kernel of the split
    for part, plan in ((kernel + "_interior", inner),
                       (kernel + "_shell", shell)):
        b = built[part]
        assert (b["bx"], b["by"], list(b["grid"])) == (
            plan["bx"], plan["by"], plan["grid"])
        assert b["halo"] == [plan["halo"], "wrap"]
        assert b["source"] == "split"
        assert b["taps"] == 26 and b["reread"] == plan["reread"]
    # the interior moves what the single launch of its kind moves
    assert inner["reread"] == built[kernel]["reread"]
    # the pair call's copies by hand: per window two shell inputs of 3h
    # rows, and 2h rows of the extra kdfdt and of each of four outputs
    # (two fields each); read and written. Nothing of X rows is left
    rows = 3 * (2 * 2 * 3 * h) + (1 + 4) * (2 * 2 * h)
    assert d["stitch_bytes"] == 2 * rows * Y * Z * 4


@pytest.mark.parametrize("proc_shape", [(2, 1, 1)], indirect=True)
def test_split_kernels_are_named_in_the_lowering(decomp, proc_shape):
    """What a device trace will tell apart: under the split a step's
    pair and stage kernels lower under ``pallas_stencil_<kind>_interior``
    and ``..._shell`` and the slab ``ppermute``s under
    ``halo_overlap_exchange``; the single launch keeps the bare kind."""
    grid = (16, 16, 16)
    dt = np.float32(0.01)
    state = {k: decomp.shard(0.1 * _field(grid, seed=21, outer=(2,)))
             for k in ("f", "dfdt")}
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    split = [f"pallas_stencil_{kind}_{part}" for kind in ("pair", "stage")
             for part in ("interior", "shell")] + ["halo_overlap_exchange"]
    lowered = _fused_pair(decomp, grid, True, dt)._jit_step.lower(
        dict(state), 0.0, dt, args)
    for scope in split + ["halo_overlap_interior", "halo_overlap_shells"]:
        assert obs.has_scope(lowered, scope), scope
    lowered = _fused_pair(decomp, grid, False, dt)._jit_step.lower(
        dict(state), 0.0, dt, args)
    for scope in split:
        assert not obs.has_scope(lowered, scope), scope
    assert obs.has_scope(lowered, "pallas_stencil_pair")


# -- the split against a plain RK54 on the gathered lattice ----------------

#: Carpenter & Kennedy's 2N-storage RK54 and the centred second
#: differences of radius 2 and 4, typed in: nothing of the program
_RK54_A = (0.0, -567301805773 / 1357537059087,
           -2404267990393 / 2016746695238,
           -3550918686646 / 2091501179385,
           -1275806237668 / 842570457699)
_RK54_B = (1432997174477 / 9575080441755, 5161836677717 / 13612068292357,
           1720146321549 / 2090206949498, 3134564353537 / 4481467310338,
           2277821191437 / 14882151754819)
_LAP_ROWS = {2: (-5 / 2, 4 / 3, -1 / 12),
             4: (-205 / 72, 8 / 5, -1 / 5, 8 / 315, -1 / 560)}


def _plain_rk54(f, dfdt, nsteps, dt, dx, h, a, hubble, carry_dtype=None):
    """``jax.numpy`` on whole arrays: periodic shifts by ``jnp.roll``,
    float32 throughout, the RK registers stored in ``carry_dtype``."""
    import jax.numpy as jnp
    f32 = jnp.float32
    carry = carry_dtype or f32
    rows = [f32(c / dx**2) for c in _LAP_ROWS[h]]
    dt, a, hubble = f32(dt), f32(a), f32(hubble)
    f, dfdt = jnp.asarray(f, f32), jnp.asarray(dfdt, f32)
    kf = kdf = jnp.zeros_like(f).astype(carry)
    for _ in range(nsteps):
        for A, B in zip(_RK54_A, _RK54_B):
            lap = 3 * rows[0] * f
            for s in range(1, h + 1):
                for axis in (1, 2, 3):
                    lap = lap + rows[s] * (jnp.roll(f, s, axis)
                                           + jnp.roll(f, -s, axis))
            dV = jnp.stack([f[0] + f32(0.25) * f[0] * f[1]**2,
                            f32(0.25) * f[0]**2 * f[1]])
            kf = (f32(A) * kf.astype(f32) + dt * dfdt).astype(carry)
            kdf = (f32(A) * kdf.astype(f32) + dt * (
                lap - 2 * hubble * dfdt - a * a * dV)).astype(carry)
            f = f + f32(B) * kf.astype(f32)
            dfdt = dfdt + f32(B) * kdf.astype(f32)
    return {"f": np.asarray(f), "dfdt": np.asarray(dfdt)}


def _gap(got, ref):
    """The benchmark's ``field_gap``: per array the largest difference
    over the array's largest value; the worst array."""
    return max(float(np.max(np.abs(np.asarray(got[k]) - ref[k]))
                     / np.max(np.abs(ref[k]))) for k in ref)


@pytest.mark.parametrize("h", [2, 4])
def test_multi_step_split_matches_plain_rk54(make_decomp, h):
    """``multi_step`` on the slab decomposition ``(4, 1, 1)`` with the
    split ON (the suite's default is off; a run's is on), from seeded
    random fields: against a plain ``jax.numpy`` RK54 on the gathered
    lattice, and against the single launch.

    The tolerance, 1e-5 of an array's largest value: both sides are
    float32 and differ by the order of their sums (measured here 1.7e-7
    at h = 2 and 1.5e-7 at h = 4 after two steps); the same reference
    with its RK registers in bfloat16 reads 6.6e-4 and 6.3e-4, which
    the last assertion holds well over the tolerance, so a run that
    narrowed them fails.

    The two launches' every output element sees the same taps and the
    same arithmetic; the stage and one-step comparisons above hold them
    bit for bit. Over ten pair calls on this lattice a dozen values of
    a quarter of a million differ by one float32 ulp: in interpret mode
    XLA:CPU compiles each kernel's body for its own block shapes and
    contracts multiply-adds differently. Held here to one ulp of the
    array's largest value at under a thousandth of the sites; on the
    chip ``chip_smoke.py``'s x-only leg holds Mosaic's two paths bit
    for bit at 512**3 a chip."""
    import jax.numpy as jnp
    decomp = make_decomp((4, 1, 1))
    grid, dx, dt, nsteps = (64, 16, 128), 0.3, np.float32(0.01), 2
    host = {k: 0.1 * _field(grid, seed=s, outer=(2,))
            for k, s in (("f", 42), ("dfdt", 43))}
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}

    def potential(f):
        return 0.5 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    def run(overlap):
        stepper = ps.FusedScalarStepper(
            ps.ScalarSector(2, potential=potential), decomp, grid, dx, h,
            dtype=np.float32, dt=dt, overlap=overlap)
        state = {k: decomp.shard(v) for k, v in host.items()}
        out = stepper.multi_step(state, nsteps, 0.0, dt, args)
        return {k: np.asarray(v) for k, v in out.items()}

    from test_kernel_choice import _watch_events
    with _watch_events() as seen:
        split = run(True)
    plans = {d["kernel"]: d for d in seen.of("overlap_plan")}
    assert plans["pair"]["path"] == "split"
    assert plans["pair"]["interior"]["lattice"] == [16 - 2 * h, 16, 128]
    single = run(False)
    for k in split:
        off = np.abs(split[k] - single[k])
        assert off.max() <= 2.0**-23 * np.abs(single[k]).max(), k
        assert np.count_nonzero(off) < 1e-3 * off.size, k
    ref = _plain_rk54(host["f"], host["dfdt"], nsteps, dt, dx, h, 1.0, 0.1)
    assert _gap(split, ref) < 1e-5, _gap(split, ref)
    narrowed = _plain_rk54(host["f"], host["dfdt"], nsteps, dt, dx, h,
                           1.0, 0.1, carry_dtype=jnp.bfloat16)
    assert _gap(narrowed, ref) > 1e-4, _gap(narrowed, ref)
