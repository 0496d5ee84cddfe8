"""Ensemble-engine tests (pystella_tpu.ensemble): batched-vs-sequential
agreement pins (bit-exact for the fused/`lax.map` tier, few-ulp for the
vmapped XLA tier), the evict-and-resample round trip (one NaN member ->
the batch survives, forensics names the member and its draw, the slot
is resampled), ensemble-mesh packing on the 8-device CPU mesh
(including the (2,2,1)+ensemble layout), and the obs generalization
(ledger `ensemble` section, gate member-throughput verdict)."""

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.ensemble import EnsembleMonitor, EnsembleStepper
from pystella_tpu.obs import events, gate, ledger
from pystella_tpu.obs.forensics import ForensicSink, load_bundle
from pystella_tpu.obs.sentinel import SimulationDiverged

GRID = (8, 8, 8)


def _rhs(state, t, m2):
    f, dfdt = state["f"], state["dfdt"]
    lap = sum(jnp.roll(f, 1, i) + jnp.roll(f, -1, i) - 2 * f
              for i in (-3, -2, -1))
    return {"f": dfdt, "dfdt": lap - m2 * f}


def _member(seed, shape=GRID, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return {
        "f": (1e-3 * rng.standard_normal((1,) + shape)).astype(dtype),
        "dfdt": (1e-4 * rng.standard_normal(
            (1,) + shape)).astype(dtype),
    }


def _edecomp(ensemble_devices, proc_shape=(1, 1, 1), halo_shape=0):
    need = ensemble_devices * int(np.prod(proc_shape))
    mesh = ps.ensemble_mesh(proc_shape=proc_shape,
                            ensemble_devices=ensemble_devices,
                            devices=jax.devices()[:need])
    return ps.DomainDecomposition(mesh=mesh, halo_shape=halo_shape,
                                  ensemble_axis=mesh.axis_names[0])


# -- mesh / decomposition ---------------------------------------------------

def test_ensemble_mesh_layout():
    """(ensemble, x, y, z) mesh shapes: pure member packing uses every
    device along the leading axis; a spatial proc_shape splits them."""
    mesh = ps.ensemble_mesh()
    assert mesh.axis_names == ("ensemble", "x", "y", "z")
    assert mesh.devices.shape == (len(jax.devices()), 1, 1, 1)
    mesh = ps.ensemble_mesh(proc_shape=(2, 2, 1), ensemble_devices=2)
    assert mesh.devices.shape == (2, 2, 2, 1)
    with pytest.raises(ValueError, match="devices"):
        ps.ensemble_mesh(proc_shape=(2, 2, 1),
                         ensemble_devices=len(jax.devices()))


def test_ensemble_decomp_describes_member_lattice():
    """The decomposition hides the ensemble axis from the single-member
    verbs (spec/proc_shape see only x/y/z) and exposes it through the
    member_* placement API."""
    decomp = _edecomp(4, proc_shape=(2, 1, 1))
    assert decomp.proc_shape == (2, 1, 1)
    assert decomp.axis_names == ("x", "y", "z")
    assert decomp.ensemble_devices == 4
    # single-member spec: no ensemble axis anywhere
    assert "ensemble" not in str(decomp.spec())
    # batched spec: member axis leads, lattice sharding kept
    assert decomp.member_spec(outer_axes=1) == \
        ps.parallel.decomp.P("ensemble", None, "x", None, None)
    batch = np.zeros((8, 1) + GRID, np.float32)
    placed = decomp.shard_members(batch)
    assert placed.sharding.spec == decomp.member_spec(outer_axes=1)
    with pytest.raises(ValueError, match="divisible"):
        decomp.shard_members(np.zeros((3, 1) + GRID, np.float32))


def test_ensemble_decomp_requires_leading_axis():
    mesh = ps.make_mesh((2, 2, 1), devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="leading"):
        ps.DomainDecomposition(mesh=mesh, ensemble_axis="ensemble")
    with pytest.raises(ValueError, match="explicit mesh"):
        ps.DomainDecomposition((2, 2, 1), ensemble_axis="ensemble")


# -- batched stepping vs sequential ----------------------------------------

@pytest.mark.slow
def test_vmap_tier_agrees_with_sequential():
    """The vmapped XLA tier advances each member exactly as a
    sequential single-member run does (few-ulp: vmap moves XLA fusion
    boundaries, not the math). Per-member dt and parameters enter as
    batched leaves. (`slow`: the tier-1 agreement verdict comes from
    test_spatial_plus_ensemble_mesh_packing, which pins the same
    vmap-vs-sequential contract on the harder sharded mesh.)"""
    size = 4
    stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
    ens = EnsembleStepper(stepper, size, decomp=_edecomp(size),
                          via="vmap")
    members = [_member(s) for s in range(size)]
    batch = ens.stack(members)
    m2 = np.linspace(0.1, 0.7, size)
    dt = np.linspace(1e-3, 2e-3, size)
    out = ens.multi_step(batch, 3, t=0.0, dt=dt, rhs_args={"m2": m2})
    body = stepper.multi_step_fn(3)
    for i in range(size):
        ref = body(jax.tree_util.tree_map(jnp.asarray, members[i]),
                   jnp.float32(0.0), jnp.asarray(dt[i]),
                   {"m2": jnp.asarray(m2[i])})
        for k in ref:
            got = np.asarray(out[k][i])
            want = np.asarray(ref[k])
            assert np.allclose(got, want, rtol=1e-6, atol=1e-12), \
                f"member {i} field {k}"


def test_vmap_tier_traces_once():
    """One batched program, not one per member: a second dispatch at
    the same (nsteps, sentinel) key reuses the cached jit — per-member
    parameters are data, not trace constants."""
    size = 3
    stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
    ens = EnsembleStepper(stepper, size, via="vmap")
    batch = ens.stack([_member(s) for s in range(size)])
    ens.step(batch, t=0.0, dt=1e-3, rhs_args={"m2": np.ones(size)})
    assert len(ens._jits) == 1
    ens.step(batch, t=0.5, dt=1e-3,
             rhs_args={"m2": np.linspace(0.2, 0.9, size)})
    assert len(ens._jits) == 1  # same compiled program, new data


@pytest.mark.slow
def test_map_tier_bitexact_with_fused_sequential():
    """The `lax.map` tier keeps the fused Pallas chunk body at
    single-member shapes, so a mapped member is BIT-EXACT with the same
    member run through the stepper's own multi_step."""
    grid_shape = (16, 16, 16)
    decomp = ps.DomainDecomposition((1, 1, 1),
                                    devices=jax.devices()[:1])
    lattice = ps.Lattice(grid_shape, (5.0, 5.0, 5.0), dtype=np.float32)

    def potential(f):
        return 0.5 * 1.2e-2 * f[0] ** 2 + 0.125 * f[0] ** 2 * f[1] ** 2

    sector = ps.ScalarSector(2, potential=potential)
    fused = ps.FusedScalarStepper(sector, decomp, grid_shape,
                                  lattice.dx, 2, dtype=jnp.float32,
                                  bx=4, by=8)
    size, nsteps = 2, 2
    ens = EnsembleStepper(fused, size)
    assert ens.via == "map"  # auto-detected fused tier
    rng = np.random.default_rng(17)
    members = [
        {"f": jnp.asarray(1e-1 * rng.standard_normal(
            (2,) + grid_shape), jnp.float32),
         "dfdt": jnp.asarray(1e-2 * rng.standard_normal(
             (2,) + grid_shape), jnp.float32)}
        for _ in range(size)]
    args = {"a": 1.1, "hubble": 0.3}
    dt = np.float32(1e-3)
    out = ens.multi_step(ens.stack(members), nsteps, t=0.0, dt=dt,
                         rhs_args=args)
    for i in range(size):
        ref = fused.multi_step(members[i], nsteps, t=0.0, dt=dt,
                               rhs_args=args)
        for k in ref:
            assert np.array_equal(np.asarray(out[k][i]),
                                  np.asarray(ref[k])), \
                f"member {i} field {k} not bit-exact"


def test_spatial_plus_ensemble_mesh_packing():
    """The (2,2,1)+ensemble packing: members shard over the leading
    ensemble devices while each member's lattice keeps its spatial
    sharding (real shard_map halo exchanges inside the vmapped body),
    and members still agree with a sequential spatially-sharded run."""
    grid_shape = (16, 16, 16)
    decomp = _edecomp(2, proc_shape=(2, 2, 1), halo_shape=2)
    lattice = ps.Lattice(grid_shape, (5.0, 5.0, 5.0), dtype=np.float32)
    derivs = ps.FiniteDifferencer(decomp, 2, lattice.dx, mode="halo")

    def rhs(state, t, m2):
        return {"f": state["dfdt"],
                "dfdt": derivs.lap(state["f"]) - m2 * state["f"]}

    stepper = ps.LowStorageRK54(rhs, dt=1e-3)
    size = 4
    ens = EnsembleStepper(stepper, size, decomp=decomp, via="vmap")
    members = [_member(s, shape=grid_shape) for s in range(size)]
    batch = ens.stack(members)
    spec = batch["f"].sharding.spec
    assert spec[0] == "ensemble" and "x" in spec and "y" in spec
    m2 = np.linspace(0.1, 0.4, size)
    out = ens.multi_step(batch, 2, t=0.0, dt=1e-3,
                         rhs_args={"m2": m2})

    sdec = ps.DomainDecomposition((2, 2, 1), halo_shape=2,
                                  devices=jax.devices()[:4])
    sderivs = ps.FiniteDifferencer(sdec, 2, lattice.dx, mode="halo")

    def srhs(state, t, m2):
        return {"f": state["dfdt"],
                "dfdt": sderivs.lap(state["f"]) - m2 * state["f"]}

    body = ps.LowStorageRK54(srhs, dt=1e-3).multi_step_fn(2)
    i = 1
    ref = body({k: sdec.shard(v, outer_axes=1)
                for k, v in members[i].items()},
               jnp.float32(0.0), jnp.float32(1e-3),
               {"m2": jnp.asarray(m2[i])})
    for k in ref:
        # few-ulp agreement at f32 working precision: the vmapped
        # program and the single-member shard_map compile to different
        # fusion/contraction orders across shard boundaries (the PR-3
        # ~1-ulp FMA effect), so exactness is not the contract here
        assert np.allclose(np.asarray(out[k][i]), np.asarray(ref[k]),
                           rtol=1e-5, atol=1e-10)


def test_write_member_touches_one_slot():
    size = 3
    stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
    ens = EnsembleStepper(stepper, size, via="vmap")
    batch = ens.stack([_member(s) for s in range(size)])
    fresh = _member(99)
    out = ens.write_member(batch, 1, fresh)
    assert np.array_equal(np.asarray(out["f"][1]), fresh["f"])
    for i in (0, 2):  # untouched slots stay bit-identical
        assert np.array_equal(np.asarray(out["f"][i]),
                              np.asarray(batch["f"][i]))


# -- per-member health ------------------------------------------------------

def test_health_matrix_rows_match_single_vectors():
    """compute_members row i == compute of member i (the member axis is
    a pure vmap of the single-run reductions)."""
    size = 3
    members = [_member(s) for s in range(size)]
    members[1]["f"][0, 1, 2, 3] = np.nan
    sen = obs.Sentinel.for_state(members[0])
    batched = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *members)
    matrix = np.asarray(jax.jit(sen.compute_members)(batched))
    assert matrix.shape == (size, sen.size)
    decs = sen.decode_members(matrix)
    for i, m in enumerate(members):
        single = sen.decode(np.asarray(sen.compute_jit(m)))
        for name in single["fields"]:
            got, want = decs[i]["fields"][name], single["fields"][name]
            # the finite verdict is exact; the statistics agree to a
            # few ulp (the vmapped reductions compile to a different
            # accumulation order than the single-member pass)
            assert got["finite"] == want["finite"]
            assert got["max_abs"] == pytest.approx(
                want["max_abs"], rel=1e-6, nan_ok=True)
            assert got["rms"] == pytest.approx(
                want["rms"], rel=1e-6, nan_ok=True)
    assert not decs[1]["fields"]["f"]["finite"]
    assert decs[0]["fields"]["f"]["finite"]


def test_monitor_evicts_without_killing_batch(tmp_path):
    """An unhealthy row becomes an Eviction naming the member and its
    parameter draw (no raise); masked members never trip; a resampled
    slot skips its stale pending matrices."""
    events.configure(str(tmp_path / "ev.jsonl"))
    try:
        size = 3
        members = [_member(s) for s in range(size)]
        sen = obs.Sentinel.for_state(members[0])
        sink = ForensicSink(str(tmp_path), label="ens")
        mon = EnsembleMonitor(sen, size, every=1, forensics=sink)
        mon.set_member(1, params={"g2": 0.25, "seed": 7},
                       scenario="preheat")
        bad = [_member(s) for s in range(size)]
        bad[1]["f"][0, 0, 0, 0] = np.inf

        def matrix(mems):
            b = jax.tree_util.tree_map(
                lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                *mems)
            return sen.compute_members(b)

        mon.push(1, matrix(bad))
        assert mon.poll() == []  # maturity lag: nothing converted yet
        mon.push(2, matrix(bad))
        evs = mon.poll()
        assert len(evs) == 1
        ev = evs[0]
        assert ev.member == 1 and ev.scenario == "preheat"
        assert ev.params["g2"] == 0.25
        assert "f" in ev.fields
        # the member-scoped bundle names the member and its draw
        bundle = load_bundle(ev.bundle)
        assert bundle["trip"]["member"] == 1
        assert bundle["trip"]["member_params"]["g2"] == 0.25
        assert "member1" in ev.bundle
        # still bad in the queue, but suspended: no second eviction
        mon.push(3, matrix(bad))
        mon.push(4, matrix(bad))
        assert mon.poll() == []
        # resample: stale matrices (<= at_step) skipped, fresh ones
        # checked again
        mon.reset_member(1, at_step=4, params={"g2": 0.5})
        mon.push(5, matrix(bad))
        mon.push(6, matrix(members))
        mon.push(7, matrix(members))
        evs = mon.flush()
        assert [e.step for e in evs] == [5]
        assert evs[0].params["g2"] == 0.5
        kinds = [e["kind"] for e in events.read_events(
            str(tmp_path / "ev.jsonl"))]
        assert kinds.count("member_evicted") == 2
    finally:
        events.configure(None)


def test_monitor_retire_time_check():
    """check_member_now converts a member's still-immature pending rows
    synchronously (the driver's retire-time check): an unhealthy tail
    becomes an Eviction, a healthy member returns None, and the
    matrices stay queued for the asynchronous path."""
    size = 2
    members = [_member(s) for s in range(size)]
    sen = obs.Sentinel.for_state(members[0])
    mon = EnsembleMonitor(sen, size, every=1)
    mon.set_member(1, params={"seed": 3}, scenario="wave")
    bad = [_member(s) for s in range(size)]
    bad[1]["f"][0, 0, 0, 0] = np.nan
    b = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *bad)
    mon.push(1, sen.compute_members(b))
    assert mon.poll() == []  # inside the maturity lag
    assert mon.check_member_now(0, through_step=1) is None
    # a healthy retire commits nothing to the history ring (a drain
    # wave of healthy retires must not flush other members' series)
    assert len(mon.history) == 0
    ev = mon.check_member_now(1, through_step=1)
    assert ev is not None and ev.member == 1
    assert ev.params["seed"] == 3 and "f" in ev.fields
    assert mon.pending_steps == [1]  # stays queued for the async path
    # the tripping row entered the history BEFORE the evict, so a
    # forensic bundle for this retire-time path carries the member's
    # final-chunk series (the rows that actually diverged)
    hist = mon._member_history(1)
    assert [h["step"] for h in hist] == [1]
    assert not hist[0]["fields"]["f"]["finite"]
    # suspended after the trip: the same rows cannot evict twice
    assert mon.check_member_now(1, through_step=1) is None
    assert mon.flush() == []


def test_monitor_eviction_budget_exhaustion():
    size = 2
    members = [_member(s) for s in range(size)]
    sen = obs.Sentinel.for_state(members[0])
    mon = EnsembleMonitor(sen, size, every=0, max_evictions=1)
    bad = [_member(s) for s in range(size)]
    for m in bad:
        m["f"][0, 0, 0, 0] = np.nan
    b = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *bad)
    mx = sen.compute_members(b)
    mon.push(1, mx)
    with pytest.raises(SimulationDiverged, match="budget"):
        mon.poll()


# -- driver: queue, refill, evict-and-resample ------------------------------

def _scenario(stepper, nsteps=6, bad_seed=None, name="wave"):
    def sample(seed):
        state = _member(100 + seed)
        if seed == bad_seed:
            state["f"][0, 0, 0, 0] = np.nan
        return state, {"m2": float(0.1 + 0.02 * seed)}
    return ps.Scenario(name, stepper, sample, nsteps=nsteps, dt=1e-3)


def test_driver_eviction_round_trip(tmp_path):
    """The acceptance round trip: one seeded-NaN member in a full
    batch -> the batch completes every job, forensics names the bad
    member and its parameter draw, the slot is resampled under a fresh
    seed, and the throughput totals land in ensemble_done."""
    ev_path = str(tmp_path / "ev.jsonl")
    events.configure(ev_path)
    try:
        stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
        sink = ForensicSink(str(tmp_path), events_path=ev_path,
                            label="ens")
        drv = ps.EnsembleDriver(size=4, chunk=2,
                                decomp=_edecomp(4), forensics=sink,
                                emit_steps=True, label="test")
        drv.submit(_scenario(stepper, nsteps=4, bad_seed=2), range(6))
        out = drv.run()
        st = out["stats"]
        assert st["members_completed"] == 6  # every job finished
        assert st["evictions"] == 1
        assert st["member_steps"] > 0 and st["member_steps_per_s"] > 0
        ev = out["evictions"][0]
        assert ev.scenario == "wave"
        assert ev.params["seed"] == 2
        bundle = load_bundle(ev.bundle)
        assert bundle["trip"]["member"] == ev.member
        assert bundle["trip"]["member_params"]["seed"] == 2
        # the resampled job used a fresh seed, not the poisoned one
        recs = events.read_events(ev_path)
        started = [e for e in recs if e["kind"] == "member_started"]
        reseeds = [e["data"]["seed"] for e in started
                   if e["data"]["member"] == ev.member]
        assert reseeds[0] == 2 and all(s != 2 for s in reseeds[1:])
        done = [e for e in recs if e["kind"] == "ensemble_done"]
        assert len(done) == 1
        assert done[0]["data"]["evictions"] == 1
    finally:
        events.configure(None)


def test_driver_preempt_drain_and_requeue_bitexact(tmp_path):
    """A preempted run drains its active members as requeue records,
    and ``requeue`` re-enters a member with its restored state: the
    resumed trajectory is bit-consistent with the uninterrupted run."""
    ev_path = str(tmp_path / "ev.jsonl")
    events.configure(ev_path)
    try:
        sc = _scenario(ps.LowStorageRK54(_rhs, dt=1e-3), nsteps=8)

        finals = {}
        d0 = ps.EnsembleDriver(size=2, chunk=2, via="vmap")
        d0.submit(sc, seeds=[0, 1])
        out0 = d0.run(on_finish=lambda rec, st:
                      finals.setdefault(rec["seed"], st))
        assert out0["stats"]["preempted"] == 0 and out0["pending"] == []

        d1 = ps.EnsembleDriver(size=2, chunk=2, via="vmap",
                               preempt=lambda ci: ci >= 2)
        d1.submit(sc, seeds=[0, 1])
        out1 = d1.run()
        assert len(out1["preempted"]) == 2
        assert all(r["step"] == 4 for r in out1["preempted"])
        assert not out1["results"]

        d2 = ps.EnsembleDriver(size=2, chunk=2, via="vmap")
        for rec in out1["preempted"]:
            d2.requeue(rec["scenario"], rec["state"], rec["step"],
                       seed=rec["seed"], params=rec["params"],
                       t=rec["t"])
        finals2 = {}
        out2 = d2.run(on_finish=lambda rec, st:
                      finals2.setdefault(rec["seed"], st))
        assert [r["steps"] for r in out2["results"]] == [8, 8]
        for seed in (0, 1):
            for k in finals[seed]:
                assert np.array_equal(np.asarray(finals[seed][k]),
                                      np.asarray(finals2[seed][k])), \
                    (seed, k)
        recs = events.read_events(ev_path)
        kinds = [e["kind"] for e in recs]
        assert kinds.count("member_preempted") == 2
        resumed = [e["data"]["resumed_from"] for e in recs
                   if e["kind"] == "member_started"]
        assert resumed == [None, None, None, None, 4, 4]
    finally:
        events.configure(None)


def test_driver_preempt_leaves_pending_jobs():
    """A drain hands back what never started: plain jobs as
    ``submit`` records, and a requeued job that was drained again
    before it got a slot with its resume payload intact."""
    sc = _scenario(ps.LowStorageRK54(_rhs, dt=1e-3), nsteps=8)
    d = ps.EnsembleDriver(size=2, chunk=2, via="vmap",
                          preempt=lambda ci: True)
    d.submit(sc, seeds=[0, 1, 2, 3])
    state, params = sc.sample(7)
    d.requeue(sc, state, 4, seed=7, params=params, t=4e-3)
    out = d.run()
    assert len(out["preempted"]) == 2
    assert [j["seed"] for j in out["pending"]] == [2, 3, 7]
    assert set(out["pending"][0]) == {"scenario", "seed"}
    held = out["pending"][2]
    assert held["step"] == 4 and held["t"] == 4e-3
    assert held["params"] == params
    assert held["state"] is state


@pytest.mark.slow
def test_driver_catches_divergence_in_final_chunk(tmp_path):
    """A member that diverges inside its FINAL chunk — whose health
    matrix is still inside the maturity lag at retire time — must be
    evicted at retire, not reported member_finished with a NaN state.
    chunk == nsteps makes every matrix immature when the member hits
    its budget, so only the retire-time check can catch it. (`slow`:
    compiles its own batched chunk program; the monitor-level verdict
    is test_monitor_retire_time_check.)"""
    events.configure(str(tmp_path / "ev.jsonl"))
    try:
        stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
        drv = ps.EnsembleDriver(size=2, chunk=4, every=1,
                                label="retire")
        drv.submit(_scenario(stepper, nsteps=4, bad_seed=1), range(2))
        out = drv.run()
        assert out["stats"]["evictions"] == 1
        assert out["evictions"][0].params["seed"] == 1
        # the poisoned draw never lands in results; its resampled
        # replacement (fresh seed) completes instead
        seeds = [r["seed"] for r in out["results"]]
        assert 1 not in seeds and len(seeds) == 2
    finally:
        events.configure(None)


@pytest.mark.slow
def test_driver_mask_policy_retires_slot(tmp_path):
    """resample=False: the evicted slot is masked out instead of
    refilled — its job is not completed and no fresh seed is drawn.
    (`slow`: each driver test compiles its own batched chunk programs
    against the tier-1 budget; the tier-1 driver verdict is
    test_driver_eviction_round_trip.)"""
    events.configure(str(tmp_path / "ev.jsonl"))
    try:
        stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
        drv = ps.EnsembleDriver(size=3, chunk=2, resample=False,
                                label="mask")
        drv.submit(_scenario(stepper, nsteps=4, bad_seed=1), range(3))
        out = drv.run()
        assert out["stats"]["evictions"] == 1
        assert out["stats"]["members_completed"] == 2
    finally:
        events.configure(None)


@pytest.mark.slow
def test_driver_groups_shape_incompatible_scenarios(tmp_path):
    """Scenarios with different state shapes cannot share a trace:
    they run as separate sequential batches, all jobs still complete.
    (`slow`: compiles TWO batched programs.)"""
    events.configure(str(tmp_path / "ev.jsonl"))
    try:
        stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
        small = _scenario(stepper, nsteps=4, name="small")

        def sample_big(seed):
            return _member(seed, shape=(12, 8, 8)), {"m2": 0.2}
        big = ps.Scenario("big", stepper, sample_big, nsteps=4,
                          dt=1e-3)
        drv = ps.EnsembleDriver(size=2, chunk=2, label="groups")
        drv.submit(small, range(2)).submit(big, range(2))
        out = drv.run()
        assert out["stats"]["members_completed"] == 4
        assert out["stats"]["batches"] == 2
        recs = events.read_events(str(tmp_path / "ev.jsonl"))
        run_ev = [e for e in recs if e["kind"] == "ensemble_run"][0]
        assert len(run_ev["data"]["groups"]) == 2
    finally:
        events.configure(None)


@pytest.mark.slow
def test_driver_refills_from_queue(tmp_path):
    """More jobs than slots: retired members' slots are refilled from
    the queue (dynamic_update writes, one compiled program) until the
    queue drains. (`slow`: the tier-1 eviction round trip already
    exercises queue refill — 6 jobs through 4 slots.)"""
    events.configure(str(tmp_path / "ev.jsonl"))
    try:
        stepper = ps.LowStorageRK54(_rhs, dt=1e-3)
        drv = ps.EnsembleDriver(size=2, chunk=2, label="refill")
        drv.submit(_scenario(stepper, nsteps=4), range(5))
        out = drv.run()
        assert out["stats"]["members_completed"] == 5
        assert out["stats"]["evictions"] == 0
    finally:
        events.configure(None)


# -- obs generalization: ledger section + gate verdict ----------------------

def _ensemble_report(rate, evictions=0, samples=None):
    led = ledger.PerfLedger(label="synthetic", sites=8**3)
    led.samples_ms = (samples if samples is not None else
                      np.linspace(9.9, 10.1, 40).tolist())
    led.ensemble_runs = [{
        "size": 8, "member_steps": 640, "wall_s": 640.0 / rate,
        "member_steps_per_s": rate, "occupancy_mean": 0.9,
        "members_completed": 8, "evictions": evictions,
    }]
    led.ensemble_chunks_ms = [5.0, 5.5, 6.0]
    return led.report()


def test_ledger_ensemble_section(tmp_path):
    """ensemble_done / ensemble_chunk / member_evicted events become
    the report's `ensemble` section (member-steps/s, per-device rate,
    occupancy, eviction records)."""
    ev = tmp_path / "ev.jsonl"
    events.configure(str(ev))
    try:
        events.emit("ensemble_chunk", step=1, ms=5.0, active=8, size=8,
                    member_steps=32)
        events.emit("member_evicted", step=1, member=3,
                    scenario="preheat", fields=["f"],
                    problems=["non-finite"], params={"seed": 3})
        events.emit("ensemble_done", size=8, member_steps=320,
                    wall_s=4.0, member_steps_per_s=80.0,
                    occupancy_mean=0.83, members_completed=8,
                    evictions=1, batches=1, chunks=10)
    finally:
        events.configure(None)
    led = ledger.PerfLedger.from_events(str(ev))
    en = led.report()["ensemble"]
    assert en["member_steps_per_s"] == pytest.approx(80.0)
    ndev = led.env.get("num_devices")
    if ndev:
        assert en["member_steps_per_s_per_device"] == \
            pytest.approx(80.0 / ndev)
    assert en["evictions"] == 1
    assert en["eviction_records"][0]["member"] == 3
    assert en["chunks"]["count"] == 1
    md = ledger.render_markdown(led.report())
    assert "## Ensemble" in md and "member-steps/s" in md


def test_gate_ensemble_throughput_verdict():
    """Member-throughput is gated like step time: a >20% drop fails
    (exit 1), jitter passes, lost coverage and eviction growth warn."""
    base = _ensemble_report(100.0)
    ok = gate.compare_reports(base, _ensemble_report(95.0))
    assert ok["ok"]
    bad = gate.compare_reports(base, _ensemble_report(70.0))
    assert not bad["ok"] and bad["exit_code"] == 1
    assert any("member throughput" in r for r in bad["reasons"])
    # opt-out restores pass
    assert gate.compare_reports(base, _ensemble_report(70.0),
                                check_ensemble=False)["ok"]
    # coverage loss: warning, not failure
    led = ledger.PerfLedger(label="synthetic", sites=8**3)
    led.samples_ms = np.linspace(9.9, 10.1, 40).tolist()
    lost = gate.compare_reports(base, led.report())
    assert lost["ok"]
    assert any("coverage" in w for w in lost["warnings"])
    # eviction growth: warning
    evw = gate.compare_reports(base,
                               _ensemble_report(98.0, evictions=3))
    assert evw["ok"]
    assert any("eviction" in w for w in evw["warnings"])
    # section present but the throughput metric gone (driver died
    # mid-run: chunk events landed, no ensemble_done): warning too —
    # a baseline-gated metric must not vanish silently
    broken = _ensemble_report(98.0)
    broken["ensemble"]["member_steps_per_s"] = None
    nometric = gate.compare_reports(base, broken)
    assert nometric["ok"]
    assert any("coverage" in w for w in nometric["warnings"])


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"] + sys.argv[1:]))
