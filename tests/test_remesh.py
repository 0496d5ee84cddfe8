"""Re-mesh library tests (pystella_tpu.resilience.remesh): the
feasibility solver's rules and rejection records, restore of a
checkpoint onto a DIFFERENT mesh (bit-exact, shard-direct), the
ensemble member-axis shrink/repack, the persistent device-subset
fault, the supervisor's default-planner degraded continuation (the
8->4 acceptance drill, bit-consistent with the degraded mesh's own
trajectory), the monitor-refresh swap semantics, the ledger's
degraded block + per-surviving-chip throughput normalization, the
gate's degraded-mode verdicts, and the two-process drill (dry-run in
tier-1, the real cluster slow-marked like tests/test_multihost.py)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax

import pystella_tpu as ps
from pystella_tpu import ensemble as ens_mod
from pystella_tpu import resilience
from pystella_tpu.obs import events, gate, ledger
from pystella_tpu.resilience import remesh as rz_remesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "remesh_drill_worker.py")

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs the 8-device CPU mesh")


# -- the solver -------------------------------------------------------------

def test_proc_shape_candidates():
    cands = rz_remesh.proc_shape_candidates(8)
    assert (2, 2, 2) in cands and (8, 1, 1) in cands
    assert all(int(np.prod(c)) == 8 for c in cands)
    assert len(set(cands)) == len(cands)
    assert rz_remesh.proc_shape_candidates(1) == [(1, 1, 1)]


def test_feasible_proc_shapes_rules():
    # grid divisibility: 16^3 over 3 devices is infeasible on every axis
    feasible, rejected = rz_remesh.feasible_proc_shapes((16, 16, 16), 3)
    assert feasible == []
    assert all("not divisible" in r["reason"] for r in rejected)
    # halo feasibility: halo 5 over 8 devices kills blocks thinner
    # than 5 but (2,2,2) (blocks of 8) survives
    feasible, rejected = rz_remesh.feasible_proc_shapes(
        (16, 16, 16), 8, halo=(5, 5, 5))
    assert (2, 2, 2) in feasible
    assert any("halo 5 exceeds" in r["reason"] for r in rejected)
    assert (8, 1, 1) not in feasible
    # pencil divisibility: grid x/y must divide the TOTAL device count
    feasible, rejected = rz_remesh.feasible_proc_shapes(
        (12, 12, 12), 8, pencil=True)
    assert feasible == []
    assert any("pencil" in r["reason"] for r in rejected)
    feasible, _ = rz_remesh.feasible_proc_shapes((16, 16, 16), 8,
                                                 pencil=True)
    assert feasible  # 16 % 8 == 0: pencil-compatible meshes exist
    # best-first: with a real halo the solver prefers an unsharded z
    assert all(p[2] == 1 for p in feasible[:1])


@needs8
def test_planner_solves_spatial_degradation():
    dec = ps.DomainDecomposition((2, 2, 2), devices=jax.devices()[:8])
    planner = resilience.RemeshPlanner(dec, (16, 16, 16),
                                       lambda d: (lambda s, i: s),
                                       halo=2)
    # nothing lost -> no change
    plan = planner.plan(jax.devices()[:8])
    assert plan.changed is False and plan.feasible
    # half the mesh lost -> a 4-device mesh, survivors recorded
    plan = planner.plan(jax.devices()[:4])
    assert plan.changed and plan.feasible
    assert int(np.prod(plan.new_proc_shape)) == 4
    assert len(plan.devices) == 4 and len(plan.lost) == 4
    desc = plan.describe()
    assert desc["old_proc_shape"] == [2, 2, 2]
    assert desc["survivors"] == [0, 1, 2, 3]
    assert desc["lost"] == [4, 5, 6, 7]
    # 5 survivors: no 5-device mesh divides 16^3, so the solver drops
    # to 4 and the rejected list names the 5-device failures
    plan5 = planner.plan(jax.devices()[:5])
    assert int(np.prod(plan5.new_proc_shape)) == 4
    assert any("not divisible" in r["reason"] for r in plan5.rejected)


@needs8
def test_planner_infeasible_raises_deterministic():
    """A halo too wide for ANY degraded block: the planner refuses
    (deterministic — never an optimistic retry loop)."""
    dec = ps.DomainDecomposition((2, 2, 2), devices=jax.devices()[:8])
    planner = resilience.RemeshPlanner(
        dec, (16, 16, 16), lambda d: (lambda s, i: s), halo=17,
        devices_fn=lambda: jax.devices()[:4])
    with pytest.raises(RuntimeError, match="remesh infeasible"):
        planner(RuntimeError("UNAVAILABLE: boom"), 1)
    assert resilience.classify_exception(
        RuntimeError("remesh infeasible: ...")) == "deterministic"


@needs8
def test_planner_ensemble_member_axis_shrink():
    mesh = ps.ensemble_mesh((1, 1, 1), ensemble_devices=8,
                            devices=jax.devices()[:8])
    dec = ps.DomainDecomposition(mesh=mesh, ensemble_axis="ensemble")
    planner = resilience.RemeshPlanner(dec, (8, 8, 8),
                                       lambda d: (lambda s, i: s),
                                       members=8)
    plan = planner.plan(jax.devices()[:6])
    # 6 survivors but 8 members: extent 6 and 5 rejected (divisibility),
    # extent 4 wins — E/D' = 2 members per slice
    assert plan.new_ensemble == 4 and plan.changed
    assert len(plan.devices) == 4
    assert any("does not divide" in r["reason"] for r in plan.rejected)
    desc = plan.describe()
    assert desc["ensemble"] == {"old": 8, "new": 4, "members": 8}


# -- restore onto a different mesh ------------------------------------------

@needs8
def test_checkpoint_restore_onto_different_mesh(tmp_path):
    """The resharding half of the tentpole: a checkpoint written on
    (2,2,1) restores bit-exactly onto (2,1,1) and (1,1,1) through the
    mesh= template path — and lands SHARD-DIRECT (each target device
    holds only its block; the state is never materialized whole on
    one device)."""
    grid = (16, 16, 16)
    rng = np.random.default_rng(3)
    host = {"f": rng.standard_normal((2,) + grid).astype(np.float32),
            "dfdt": rng.standard_normal((2,) + grid).astype(np.float32)}
    dec221 = ps.DomainDecomposition((2, 2, 1), devices=jax.devices()[:4])
    state = {k: dec221.shard(v) for k, v in host.items()}
    with ps.Checkpointer(tmp_path / "ck") as ck:
        ck.save(4, state, metadata={"t": 1.5})
        ck.finalize()
        for proc, ndev in (((2, 1, 1), 2), ((1, 1, 1), 1)):
            target = ps.DomainDecomposition(proc,
                                            devices=jax.devices()[:ndev])
            step, restored, meta = ck.restore(mesh=target)
            assert step == 4 and meta["t"] == 1.5
            for k, v in host.items():
                arr = restored[k]
                assert np.array_equal(np.asarray(arr), v)
                assert arr.sharding.mesh.devices.shape == proc
                # shard-direct: each device holds exactly its block
                for s in arr.addressable_shards:
                    assert s.data.shape == (2, grid[0] // proc[0],
                                            grid[1] // proc[1],
                                            grid[2] // proc[2])


@needs8
def test_checkpoint_restore_ensemble_member_shrink(tmp_path):
    """The ensemble analogue: a batch written member-axis-over-4
    devices restores bit-exactly onto a 2-device ensemble mesh (E/D'
    goes 2 -> 4 members per slice) via the same mesh= path."""
    grid = (8, 8, 8)
    members = 8
    rng = np.random.default_rng(5)
    host = {"f": rng.standard_normal(
        (members,) + grid).astype(np.float32),
        "coupling": rng.standard_normal(members).astype(np.float32)}
    mesh4 = ps.ensemble_mesh((1, 1, 1), ensemble_devices=4,
                             devices=jax.devices()[:4])
    dec4 = ps.DomainDecomposition(mesh=mesh4, ensemble_axis="ensemble")
    batch = {k: dec4.shard_members(v) for k, v in host.items()}
    with ps.Checkpointer(tmp_path / "ck") as ck:
        ck.save(2, batch)
        ck.finalize()
        mesh2 = ps.ensemble_mesh((1, 1, 1), ensemble_devices=2,
                                 devices=jax.devices()[:2])
        dec2 = ps.DomainDecomposition(mesh=mesh2,
                                      ensemble_axis="ensemble")
        _, restored, _ = ck.restore(mesh=dec2)
    for k, v in host.items():
        arr = restored[k]
        assert np.array_equal(np.asarray(arr), v)
        assert len(arr.sharding.device_set) == 2
        for s in arr.addressable_shards:
            assert s.data.shape[0] == members // 2  # 4 members/slice


@needs8
def test_repack_members_across_extents():
    """The in-memory member-axis repack (a batch that survived in
    device buffers, no checkpoint round trip)."""
    grid = (8, 8, 8)
    rng = np.random.default_rng(7)
    host = rng.standard_normal((8,) + grid).astype(np.float32)
    mesh4 = ps.ensemble_mesh((1, 1, 1), ensemble_devices=4,
                             devices=jax.devices()[:4])
    dec4 = ps.DomainDecomposition(mesh=mesh4, ensemble_axis="ensemble")
    batch = {"f": dec4.shard_members(host)}
    mesh2 = ps.ensemble_mesh((1, 1, 1), ensemble_devices=2,
                             devices=jax.devices()[:2])
    dec2 = ps.DomainDecomposition(mesh=mesh2, ensemble_axis="ensemble")
    repacked = ens_mod.repack_members(batch, dec2)
    assert np.array_equal(np.asarray(repacked["f"]), host)
    assert len(repacked["f"].sharding.device_set) == 2


# -- the device-subset fault ------------------------------------------------

@needs8
def test_device_subset_fault_semantics():
    dec8 = ps.DomainDecomposition((2, 2, 2), devices=jax.devices()[:8])
    dec4 = ps.DomainDecomposition((2, 2, 1), devices=jax.devices()[:4])
    grid = (16, 16, 16)
    full = {"f": dec8.shard(np.ones((2,) + grid, np.float32))}
    half = {"f": dec4.shard(np.ones((2,) + grid, np.float32))}
    inj = resilience.FaultInjector.device_subset(step=3, count=4)
    fault = inj.faults[0]
    # persistent by default; silent before its step
    assert fault.once is False
    assert inj.apply(2, full) is full
    # fires at its step, naming the lost devices
    with pytest.raises(Exception, match="UNAVAILABLE.*device-subset"):
        inj.apply(3, full)
    assert [d.id for d in inj.lost_devices()] == [4, 5, 6, 7]
    # STILL fires later while the program touches lost hardware
    with pytest.raises(Exception, match="UNAVAILABLE"):
        inj.apply(5, full)
    # ... and goes quiet once the state lives on survivors only
    assert inj.apply(5, half) is half
    # a mesh-axis slice resolves its ids at construction
    axis_fault = resilience.DeviceSubsetFault(
        1, mesh=dec8.mesh, axis="x", index=1)
    assert axis_fault.device_ids == [4, 5, 6, 7]
    # the env-knob spelling
    f = resilience.DeviceSubsetFault.from_spec("9:4")
    assert f.step == 9 and f.count == 4 and f.once is False
    with pytest.raises(ValueError, match="device_ids"):
        resilience.DeviceSubsetFault(3)


def test_fault_injector_from_env(monkeypatch):
    monkeypatch.delenv("PYSTELLA_FAULT_DEVICE_SUBSET", raising=False)
    assert resilience.FaultInjector.from_env() is None
    monkeypatch.setenv("PYSTELLA_FAULT_DEVICE_SUBSET", "9:4")
    inj = resilience.FaultInjector.from_env(label="env")
    assert inj.faults[0].step == 9 and inj.faults[0].count == 4
    assert inj.faults[0].once is False
    monkeypatch.setenv("PYSTELLA_FAULT_DEVICE_SUBSET_PERSIST", "0")
    inj = resilience.FaultInjector.from_env()
    assert inj.faults[0].once is True


# -- the acceptance drill ---------------------------------------------------

def _drill_host_state(grid):
    rng = np.random.default_rng(7)
    return {"f": 1e-3 * rng.standard_normal(
        (2,) + grid).astype(np.float32),
        "dfdt": 1e-3 * rng.standard_normal(
            (2,) + grid).astype(np.float32)}


def _drill_build_step(grid, emit_times=False):
    def build_step(dec):
        import chip_smoke
        stepper, dt = chip_smoke.build_preheat_step(grid, dec)
        args = {"a": np.float32(1.0), "hubble": np.float32(0.5)}

        def step_fn(st, i):
            import time as _time
            t0 = _time.perf_counter()
            out = stepper.step(st, np.float32(0.0), dt, args)
            if emit_times:
                jax.block_until_ready(out)
                events.emit("step_time",
                            ms=(_time.perf_counter() - t0) * 1e3)
            return out
        return step_fn
    return build_step


@needs8
def test_supervisor_default_planner_degraded_continuation(tmp_path):
    """THE acceptance round trip: a supervised run on the 8-device
    (2,2,2) mesh loses half its devices mid-run (persistent
    device-subset fault at step 9 of 12) with NO caller-provided
    remesh hook — the planner (the supervisor's default policy)
    solves a 4-device mesh, the step-8 checkpoint restores straight
    onto it, the replay sails past the still-armed fault, and the run
    finishes bit-consistent with an uninterrupted run at the degraded
    mesh's own trajectory; remesh_plan + run_degraded land in the
    event record and the resulting report earns a gate-accepted
    degraded verdict."""
    sys.path.insert(0, REPO)
    grid = (16, 16, 16)
    log_path = str(tmp_path / "ev.jsonl")
    events.configure(log_path)
    try:
        host = _drill_host_state(grid)
        build_step = _drill_build_step(grid, emit_times=True)
        dec = ps.DomainDecomposition((2, 2, 2),
                                     devices=jax.devices()[:8])
        state = {k: dec.shard(v) for k, v in host.items()}
        events.emit("run_start", grid_shape=list(grid), nsteps=12)

        planner = resilience.RemeshPlanner(dec, grid, build_step,
                                           halo=2, label="t-remesh")
        mon = ps.HealthMonitor(every=2, metrics_prefix="supervised")
        with ps.Checkpointer(tmp_path / "ck", max_to_keep=2) as ck:
            sup = resilience.Supervisor(
                build_step(dec), ck, 12, monitor=mon,
                checkpoint_every=4, planner=planner,
                faults=resilience.FaultInjector.device_subset(
                    step=9, count=4, label="t-remesh"),
                retry=resilience.RetryPolicy(base_s=0.01, max_s=0.05,
                                             jitter=0.0),
                label="t-remesh")
            rep = sup.run(state)
    finally:
        events.configure(None)

    assert rep["completed"] and rep["incidents"] == 1
    inc = rep["incident_records"][0]
    assert inc["kind"] == "device_loss"
    assert inc["restored_step"] == 8 and inc["steps_replayed"] == 1
    # finished on the survivors only
    assert sorted(d.id for d in
                  rep["state"]["f"].sharding.device_set) == [0, 1, 2, 3]
    plan = planner.last_plan
    assert plan is not None and int(np.prod(plan.new_proc_shape)) == 4

    # bit-consistent with the DEGRADED mesh's own uninterrupted run
    deg_dec = planner.decomp
    ref_step = _drill_build_step(grid)(deg_dec)
    ref = {k: deg_dec.shard(v) for k, v in host.items()}
    for i in range(12):
        ref = ref_step(ref, i)
    for k in ref:
        assert np.array_equal(np.asarray(rep["state"][k]),
                              np.asarray(ref[k]))

    evs = events.read_events(log_path)
    kinds = [e["kind"] for e in evs]
    assert kinds.count("remesh_plan") == 1
    rp = [e for e in evs if e["kind"] == "remesh_plan"][0]["data"]
    assert rp["old_proc_shape"] == [2, 2, 2]
    assert rp["survivors"] == [0, 1, 2, 3]
    assert rp["lost"] == [4, 5, 6, 7]
    assert rp["feasible"] and rp["changed"]
    assert "run_degraded" in kinds

    # ledger: the degraded block, post-remesh samples, and the
    # per-SURVIVING-chip throughput normalization
    led = ledger.PerfLedger.from_events(log_path, label="t-remesh")
    rz = led.resilience()
    deg = rz["degraded"]
    assert deg["new_mesh"] is not None
    assert deg["devices_used"] == 4 and deg["lost_devices"] == 4
    assert deg["post_remesh"]["samples"] == 4  # steps 8..11 replayed
    assert deg["post_remesh"][
        "site_updates_per_s_per_surviving_chip"] > 0
    report = led.report()
    pc = report["throughput"]["per_chip"]
    assert pc["basis"] == "surviving" and pc["chips"] == 4

    # gate: degraded verdict ACCEPTED (annotated), and the
    # full-mesh-throughput lie refused
    verdict = gate.compare_reports(None, report)
    assert verdict["exit_code"] == 0 and verdict["degraded"] is True
    lying = json.loads(json.dumps(report))
    lying["throughput"]["per_chip"] = {
        "chips": 8, "basis": "all",
        "site_updates_per_s_per_chip": 1.0}
    refused = gate.compare_reports(None, lying)
    assert refused["exit_code"] == 2
    assert any("full-mesh" in r for r in refused["reasons"])


@needs8
def test_swap_refreshes_monitor_and_restore_path(tmp_path):
    """Satellite: a remesh swap must refresh the monitor's
    decomp-derived state (HealthMonitor.reset) and point later
    restores at the new mesh — and a swap dict carrying `monitor`
    replaces it outright."""
    calls = []

    class SpyMonitor:
        def observe(self, step, state):
            pass

        def poll(self):
            pass

        def flush(self):
            pass

        def discard(self):
            calls.append("discard")

        def check_now(self, state, step=None):
            pass

        def reset(self):
            calls.append("reset")

    grid = (16, 16, 16)
    host = _drill_host_state(grid)
    build_step = _drill_build_step(grid)
    dec = ps.DomainDecomposition((2, 2, 2), devices=jax.devices()[:8])
    state = {k: dec.shard(v) for k, v in host.items()}
    planner = resilience.RemeshPlanner(dec, grid, build_step, halo=2)
    with ps.Checkpointer(tmp_path / "ck", max_to_keep=2) as ck:
        sup = resilience.Supervisor(
            build_step(dec), ck, 12, monitor=SpyMonitor(),
            checkpoint_every=4, planner=planner,
            faults=resilience.FaultInjector.device_subset(
                step=9, count=4),
            retry=resilience.RetryPolicy(base_s=0.01, max_s=0.05,
                                         jitter=0.0),
            label="t-swap")
        rep = sup.run(state)
    assert rep["completed"]
    assert "reset" in calls
    # the swap pointed restores at the degraded mesh
    assert sup.restore_decomp is planner.decomp
    assert sup.restore_decomp.proc_shape != (2, 2, 2)

    # a hook returning a replacement monitor swaps it in
    sup2 = resilience.Supervisor(
        lambda s, i: s, ck, 1,
        remesh=lambda e, a: {"monitor": "NEW"})
    sup2._apply_swap(sup2.remesh(None, 1), at_step=0)
    assert sup2.monitor == "NEW"


# -- ledger / gate on synthetic degraded telemetry --------------------------

def test_ledger_degraded_block_from_events(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        for ms in (2.0, 2.1, 2.05):
            log.emit("step_time", ms=ms)
        log.emit("fault_detected", step=9, fault_kind="device_loss",
                 error="UNAVAILABLE: lost")
        log.emit("remesh_plan", step=9, old_proc_shape=[2, 2, 2],
                 new_proc_shape=[2, 2, 1],
                 devices=[0, 1, 2, 3], survivors=[0, 1, 2, 3],
                 lost=[4, 5, 6, 7], n_rejected=2,
                 rejected=[{"proc_shape": [5, 1, 1],
                            "reason": "grid axis 0 (8) not divisible "
                                      "by mesh axis 5"}],
                 changed=True, feasible=True)
        log.emit("run_degraded", step=9, note="re-meshed")
        log.emit("run_resumed", step=8, source="recovery",
                 incident=True, fault_kind="device_loss", from_step=9,
                 mttr_s=0.2, steps_replayed=1, attempts=1)
        for ms in (4.0, 4.2, 4.1, 4.3):
            log.emit("step_time", ms=ms)
        log.emit("supervisor_done", step=12, completed=True,
                 preempted=False, incidents=1, steps_replayed=1,
                 wall_s=1.0)
    led = ledger.PerfLedger.from_events(path, label="deg")
    rz = led.resilience()
    deg = rz["degraded"]
    assert deg["old_mesh"] == [2, 2, 2]
    assert deg["new_mesh"] == [2, 2, 1]
    assert deg["surviving_devices"] == 4 and deg["lost_devices"] == 4
    post = deg["post_remesh"]
    assert post["samples"] == 4
    assert post["p50_ms"] == pytest.approx(4.15)
    # sites = 8^3, per SURVIVING chip
    assert post["site_updates_per_s_per_surviving_chip"] == \
        pytest.approx(512 * 1e3 / 4.15 / 4)
    rep = led.report()
    assert rep["throughput"]["per_chip"]["basis"] == "surviving"
    assert rep["throughput"]["per_chip"]["chips"] == 4
    md = ledger.render_markdown(rep)
    assert "re-mesh: [2, 2, 2] -> [2, 2, 1]" in md
    assert "SURVIVING chip" in md


def test_ledger_blip_plan_is_not_degradation(tmp_path):
    """A transport-blip recovery (remesh_plan with changed=False —
    every old device survived, nothing was swapped) must NOT make the
    window read as degraded: no degraded block, per-chip basis stays
    'all'."""
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        for ms in (2.0, 2.1, 2.05):
            log.emit("step_time", ms=ms)
        log.emit("remesh_plan", step=9, old_proc_shape=[2, 2, 2],
                 new_proc_shape=[2, 2, 2],
                 devices=[0, 1, 2, 3, 4, 5, 6, 7],
                 survivors=[0, 1, 2, 3, 4, 5, 6, 7], lost=[],
                 n_rejected=0, rejected=[], changed=False,
                 feasible=True)
        log.emit("run_resumed", step=8, source="recovery",
                 incident=True, fault_kind="device_loss", from_step=9,
                 mttr_s=0.2, steps_replayed=1, attempts=1)
        log.emit("supervisor_done", step=12, completed=True,
                 preempted=False, incidents=1, steps_replayed=1,
                 wall_s=1.0)
    led = ledger.PerfLedger.from_events(path, label="blip")
    rz = led.resilience()
    assert rz is not None and rz["degraded"] is None
    pc = led.report()["throughput"]["per_chip"]
    assert pc is None or pc["basis"] == "all"
    assert "re-mesh:" not in ledger.render_markdown(led.report())


def test_ledger_per_chip_uses_post_remesh_samples(tmp_path):
    """The headline per-chip figure of a degraded window must come
    from the POST-remesh step times — dividing the full-mesh-dominated
    whole-window median by the survivors would overstate degraded
    throughput ~2x in the smoke drill shape."""
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        for _ in range(9):
            log.emit("step_time", ms=2.0)   # full mesh, fast
        log.emit("remesh_plan", step=9, old_proc_shape=[2, 2, 2],
                 new_proc_shape=[2, 2, 1], devices=[0, 1, 2, 3],
                 survivors=[0, 1, 2, 3], lost=[4, 5, 6, 7],
                 n_rejected=0, rejected=[], changed=True,
                 feasible=True)
        for _ in range(4):
            log.emit("step_time", ms=4.0)   # degraded, slower
        log.emit("supervisor_done", step=12, completed=True,
                 preempted=False, incidents=1, steps_replayed=1,
                 wall_s=1.0)
    led = ledger.PerfLedger.from_events(path, label="post")
    pc = led.report()["throughput"]["per_chip"]
    assert pc["basis"] == "surviving" and pc["chips"] == 4
    # 8^3 sites / 4.0 ms / 4 chips — NOT / 2.0 ms (the mixed median)
    assert pc["site_updates_per_s_per_chip"] == \
        pytest.approx(512 * 1e3 / 4.0 / 4)


def _steady(n=60, base=10.0, jitter=0.05, seed=0):
    rng = np.random.default_rng(seed)
    return (base + jitter * rng.standard_normal(n)).tolist()


def _degraded_report(per_chip=None, remesh_plans=True, events_only=False):
    led = ledger.PerfLedger(label="synthetic", sites=32**3)
    led.samples_ms = _steady()
    rep = led.report()
    deg = {"events": [{"step": 9, "note": "re-meshed"}],
           "remesh_plans": ([{"old_proc_shape": [2, 2, 2]}]
                            if remesh_plans else [])}
    if not events_only:
        deg.update({"old_mesh": [2, 2, 2], "new_mesh": [2, 2, 1],
                    "surviving_devices": 4, "devices_used": 4,
                    "lost_devices": 4, "post_remesh": None})
    rep["resilience"] = {
        "n_incidents": 1, "resolved": 1, "unresolved": 0,
        "completed": True, "consistent": True, "claimed_incidents": 1,
        "faults_injected": 0, "incidents": [
            {"kind": "device_loss", "mttr_s": 0.5,
             "steps_replayed": 1, "attempts": 1}],
        "checkpoints": {"saved": 3, "durable": 3, "fallbacks": 0},
        "degraded": deg, "preempted": False,
    }
    if per_chip is not None:
        rep["throughput"]["per_chip"] = per_chip
    return rep


def test_gate_refuses_full_mesh_claim_from_degraded_run():
    honest = _degraded_report(per_chip={
        "chips": 4, "basis": "surviving",
        "site_updates_per_s_per_chip": 1.0})
    v = gate.compare_reports(None, honest)
    assert v["exit_code"] == 0 and v["degraded"] is True
    # full-mesh normalization -> refused
    lying = _degraded_report(per_chip={
        "chips": 8, "basis": "all",
        "site_updates_per_s_per_chip": 1.0})
    v = gate.compare_reports(None, lying)
    assert v["exit_code"] == 2
    assert any("full-mesh" in r for r in v["reasons"])
    # no per-chip claim at all while degraded -> refused too (the
    # per-chip interpretation of the headline number is unauditable)
    missing = _degraded_report(per_chip=None)
    missing["throughput"].pop("per_chip", None)
    v = gate.compare_reports(None, missing)
    assert v["exit_code"] == 2
    # --no-resilience restores plain gating
    v = gate.compare_reports(None, lying, check_resilience=False)
    assert v["exit_code"] == 0


def test_gate_warns_degraded_without_remesh_plan():
    rep = _degraded_report(remesh_plans=False, events_only=True)
    v = gate.compare_reports(None, rep)
    assert v["exit_code"] == 0
    assert any("without a matching remesh_plan" in w
               for w in v["warnings"])


# -- the drill worker -------------------------------------------------------

def test_remesh_drill_dry_run(tmp_path):
    """Tier-1 rehearsal of the drill harness: the worker runs the
    whole degraded continuation single-process, armed purely through
    the env knobs."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("PYSTELLA_FAULT_DEVICE_SUBSET", None)
    res = subprocess.run(
        [sys.executable, WORKER, "--dry-run",
         "--ckdir", str(tmp_path / "ck")],
        capture_output=True, text=True, timeout=240, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["completed"] and out["bit_consistent"]
    assert out["old_mesh"] == [2, 2, 2]
    assert out["survivors"] == 4
    assert out["final_device_ids"] == [0, 1, 2, 3]
    assert out["steps_replayed"] <= 4


@pytest.mark.slow
def test_remesh_drill_two_process(tmp_path):
    """The REAL >=2-process drill: two jax.distributed workers share
    one (2,2,2) mesh; the victim SIGKILLs itself mid-run; the
    survivor's supervisor re-dials down, re-meshes onto its own local
    devices, restores the shared checkpoint, and finishes."""
    coordinator = f"localhost:{_free_port()}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    ck = str(tmp_path / "ck")
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, "--coordinator", coordinator,
             "--process-id", str(i), "--nproc", "2", "--ckdir", ck],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        for i in range(2)]
    outs = [p.communicate(timeout=540)[0] for p in procs]
    # the victim died by SIGKILL; the survivor completed degraded
    assert procs[1].returncode != 0
    assert procs[0].returncode == 0, outs[0][-2000:]
    out = json.loads(outs[0].strip().splitlines()[-1])
    assert out["completed"] and out["bit_consistent"]
    assert out["survivors"] == 4


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


if __name__ == "__main__":
    import pytest as _pytest
    _pytest.main([__file__, "-v"])
