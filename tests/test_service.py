"""Scenario-service tests (pystella_tpu.service): scheduler
fair-share/priority/deadline/quota unit pins, warm-vs-cold admission
including the fingerprint-mismatch demotion, the preempt -> durable
checkpoint -> requeue round trip (bit-consistent resume) under an
injected high-priority arrival, device-loss recovery inside a lease,
the EnsembleDriver preempt/requeue satellite, event-log rotation, and
the loadgen smoke e2e through ledger + gate (SLO accept and
seeded-regression exit-1 legs)."""

import copy
import json
import os
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.obs import events, gate
from pystella_tpu.obs.events import EventLog, rotated_family
from pystella_tpu.obs.ledger import PerfLedger
from pystella_tpu.service import (
    AdmissionController, ColdSignature, FairShareScheduler,
    QuotaExceeded, ScenarioRequest, ScenarioService, WarmPool, loadgen,
    parse_signature, request_signature)

GRID = (8, 8, 8)
SIG = request_signature("toy", GRID)


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure(path)
    yield path
    obs.configure(None)


def _toy_builder(grid_shape, decomp=None):
    """A tiny roll-based Klein-Gordon system: fast to trace/compile,
    deterministic sampler, one scalar parameter (m2)."""
    dt = 0.05

    def rhs(state, t, m2):
        f = state["f"]
        lap = sum(jnp.roll(f, 1, i) + jnp.roll(f, -1, i) - 2 * f
                  for i in (-3, -2, -1))
        # parameters arrive as f64 batch columns; a dtype-stable model
        # casts them to the field dtype (a step that PROMOTES its
        # state would re-trace every chunk on any driver)
        return {"f": state["dfdt"],
                "dfdt": lap - jnp.asarray(m2, f.dtype) * f}

    stepper = ps.LowStorageRK54(rhs, dt=np.float32(dt))

    def sample(seed):
        rng = np.random.default_rng(500 + seed)
        state = {
            "f": rng.standard_normal(grid_shape).astype(np.float32),
            "dfdt": 0.1 * rng.standard_normal(
                grid_shape).astype(np.float32),
        }
        return state, {"m2": 0.25}

    return stepper, sample, dt


def _make_service(tmp_path, **kwargs):
    kwargs.setdefault("slots", 2)
    kwargs.setdefault("chunk", 2)
    svc = ScenarioService(str(tmp_path / "svc_ckpt"), **kwargs)
    svc.register_model("toy", _toy_builder)
    return svc


# -- signature / scheduler units -------------------------------------------

def test_signature_roundtrip():
    sig = request_signature("preheat", (16, 16, 16), (2, 2, 1),
                            "float32")
    assert sig == "preheat/16x16x16/2x2x1/float32"
    assert parse_signature(sig) == ("preheat", (16, 16, 16), (2, 2, 1),
                                    "float32")
    with pytest.raises(ValueError):
        parse_signature("nope")


def test_scheduler_priority_classes_dominate():
    s = FairShareScheduler(quota=16)
    low = [s.submit(ScenarioRequest("a", SIG, 4, seed=i, priority=1))
           for i in range(3)]
    high = s.submit(ScenarioRequest("b", SIG, 4, seed=9, priority=5))
    assert s.has_priority_above(1)
    assert not s.has_priority_above(5)
    picked = s.dispatch(4)
    # the higher class is served alone, never padded with lower-class
    # work (one lease = one priority class)
    assert picked == [high]
    assert {r.id for r in s.dispatch(4)} == {r.id for r in low}


def test_scheduler_weighted_fair_share():
    s = FairShareScheduler(quota=64, weights={"a": 2.0, "b": 1.0})
    for i in range(30):
        s.submit(ScenarioRequest("a", SIG, 4, seed=i))
        s.submit(ScenarioRequest("b", SIG, 4, seed=100 + i))
    served = [s.dispatch(1)[0].tenant for _ in range(30)]
    # weight 2 tenant gets ~2x the slots over any sustained window
    assert 19 <= served.count("a") <= 21, served


def test_scheduler_deadline_ordering():
    s = FairShareScheduler(quota=16)
    loose = s.submit(ScenarioRequest("a", SIG, 4, seed=1,
                                     deadline_s=1000.0))
    none = s.submit(ScenarioRequest("a", SIG, 4, seed=2))
    tight = s.submit(ScenarioRequest("a", SIG, 4, seed=3,
                                     deadline_s=1.0))
    order = [s.dispatch(1)[0].id for _ in range(3)]
    # EDF within the tenant: tightest deadline first, no-deadline last
    assert order == [tight.id, loose.id, none.id]


def test_scheduler_quota_rejects():
    s = FairShareScheduler(quota=2)
    s.submit(ScenarioRequest("a", SIG, 4, seed=1))
    s.submit(ScenarioRequest("a", SIG, 4, seed=2))
    with pytest.raises(QuotaExceeded):
        s.submit(ScenarioRequest("a", SIG, 4, seed=3))
    # other tenants are unaffected, and a preemption requeue is exempt
    s.submit(ScenarioRequest("b", SIG, 4, seed=4))
    r = ScenarioRequest("a", SIG, 4, seed=5)
    r.submit_ts = 0.0
    s.requeue(r)
    assert s.pending == 4


def test_scheduler_leases_are_shape_compatible():
    s = FairShareScheduler(quota=16)
    other = request_signature("toy", (12, 12, 12))
    a = s.submit(ScenarioRequest("a", SIG, 4, seed=1))
    b = s.submit(ScenarioRequest("b", other, 4, seed=2))
    c = s.submit(ScenarioRequest("c", SIG, 4, seed=3))
    picked = s.dispatch(4)
    # one lease = one batched program = one signature
    assert {r.id for r in picked} <= {a.id, c.id} \
        or {r.id for r in picked} == {b.id}
    sigs = {r.signature for r in picked}
    assert len(sigs) == 1


# -- admission --------------------------------------------------------------

def test_admission_warm_vs_cold_and_policy(tmp_path, event_log):
    svc = _make_service(tmp_path)
    svc.arm(SIG)
    warm = svc.admission.admit(ScenarioRequest("a", SIG, 4, seed=1))
    assert warm.admitted and warm.warm
    assert warm.fingerprint_ok is True and warm.fingerprint

    cold_sig = request_signature("toy", (12, 12, 12))
    cold = svc.admission.admit(
        ScenarioRequest("a", cold_sig, 4, seed=1))
    assert isinstance(cold, ColdSignature)
    assert cold.admitted and not cold.warm  # policy "compile"

    reject = AdmissionController(svc.pool, cold_policy="reject")
    verdict = reject.admit(ScenarioRequest("a", cold_sig, 4, seed=1))
    assert isinstance(verdict, ColdSignature) and not verdict.admitted
    with pytest.raises(ValueError):
        AdmissionController(svc.pool, cold_policy="bogus")


def test_admission_fingerprint_mismatch_demotes(tmp_path, event_log):
    """A warm-pool entry whose fingerprint components no longer match
    the live process — or whose AOT store artifact is stale — must NOT
    be admitted warm (the gate refuses reports that claim otherwise)."""
    from pystella_tpu.obs import warmstart

    svc = _make_service(tmp_path)
    entry = svc.arm(SIG)
    # stale pool entry: pretend it was armed under another jax
    entry.components = {**entry.components,
                        "versions": {"jax": "0.0.1", "jaxlib": "0.0.1",
                                     "libtpu": None}}
    v = svc.admission.admit(ScenarioRequest("a", SIG, 4, seed=1))
    assert isinstance(v, ColdSignature)
    assert v.fingerprint_ok is False and not v.warm

    # stale STORE artifact under the signature label demotes too
    svc2 = _make_service(tmp_path, label="svc2")
    store = warmstart.WarmstartStore(str(tmp_path / "store"))
    entry2 = svc2.arm(SIG)
    meta = {"label": SIG, "fingerprint": "feedface",
            "artifact": "x.jaxexport", "created_ts": 1.0,
            "components": {"versions": {"jax": "0.0.1",
                                        "jaxlib": "0.0.1",
                                        "libtpu": None},
                           "flags": {}}}
    with open(os.path.join(store.root, "x.meta.json"), "w") as f:
        json.dump(meta, f)
    ctl = AdmissionController(svc2.pool, store=store)
    v2 = ctl.admit(ScenarioRequest("a", SIG, 4, seed=1))
    assert v2.fingerprint_ok is False and not v2.warm
    assert "stale AOT artifact" in v2.reason
    assert entry2.fingerprint_ok()  # the entry itself was fine


def test_pool_entry_stack_enforces_armed_avals(tmp_path, event_log):
    """A lease batch is canonicalized to the ARMED template's leaf
    dtypes (an f64 host copy of an f32 state — a checkpoint artifact,
    a careless sampler — must not re-trace the warm program)."""
    svc = _make_service(tmp_path)
    entry = svc.arm(SIG)
    state, _ = entry.sample(0)
    off_spec = {k: np.asarray(v, np.float64) for k, v in state.items()}
    batch = entry.stack([off_spec, off_spec])
    assert all(np.asarray(v).dtype == np.float32
               for v in jax.tree_util.tree_leaves(batch))


# -- the preemption round trip ---------------------------------------------

def test_service_preempt_checkpoint_requeue_bitexact(tmp_path,
                                                     event_log):
    """THE tentpole pin: a priority-3 arrival one chunk into a
    priority-1 lease drains it (durable checkpoint, run_preempted),
    the high class is served next, the preempted requests resume with
    their restored states, and each resumed trajectory is bit-equal to
    an uninterrupted replay through the same warm chunk program."""
    from pystella_tpu.service.loadgen import (
        _CapturingEmitter, _uninterrupted_reference)

    results = _CapturingEmitter(label="svc")
    svc = _make_service(tmp_path, results=results)
    svc.arm(SIG)
    r1 = ScenarioRequest("a", SIG, 8, seed=1)
    r2 = ScenarioRequest("b", SIG, 8, seed=2)
    svc.submit(r1)
    svc.submit(r2)
    high = ScenarioRequest("c", SIG, 4, seed=3, priority=3)
    svc.schedule_arrival(1, high)
    summary = svc.serve()

    assert summary["preemptions"] == 1
    assert summary["completed"] == 3
    assert summary["diverged"] == 0 and summary["lease_failures"] == 0
    assert r1.resume_step > 0 and r2.resume_step > 0  # both drained
    assert high.status == "completed"

    entry = svc.pool.get(SIG)
    for req in (r1, r2):
        got = results.states[req.id]
        ref = _uninterrupted_reference(entry, req, svc.slots, svc.chunk)
        for k in ref:
            assert np.array_equal(np.asarray(got[k]),
                                  np.asarray(ref[k])), (req.id, k)

    # the drain was durable and auditable: run_preempted + a durable
    # checkpoint + one service_requeue per drained request
    evs = events.read_events(event_log)
    kinds = [e["kind"] for e in evs]
    assert "run_preempted" in kinds and "service_preempted" in kinds
    assert kinds.count("service_requeue") == 2
    assert "checkpoint_durable" in kinds
    pre = next(e for e in evs if e["kind"] == "service_preempted")
    assert sorted(pre["data"]["requeued"]) == sorted([r1.id, r2.id])
    # the resumed dispatches say so
    resumed = [e["data"] for e in evs
               if e["kind"] == "service_dispatch"
               and e["data"].get("resumed")]
    assert {d["id"] for d in resumed} == {r1.id, r2.id}
    # warm leases recorded zero backend compiles (dispatch, never
    # compile — the compile-ledger proof)
    leases = [e["data"] for e in evs if e["kind"] == "service_lease"]
    warm_leases = [d for d in leases if d["warm"]]
    assert warm_leases and all(d["backend_compiles"] == 0
                               and d["trace_s"] == 0.0
                               for d in warm_leases)


def test_service_device_loss_recovery_in_lease(tmp_path, event_log):
    """A transient device loss mid-lease recovers through the
    supervisor (restore from the durable chunk checkpoint, bounded
    replay), the lease completes, and the replay cost is accounted in
    member-steps."""
    from pystella_tpu import resilience as rzl
    from pystella_tpu.service.loadgen import (
        _CapturingEmitter, _uninterrupted_reference)

    results = _CapturingEmitter(label="svc")
    svc = _make_service(
        tmp_path, results=results, preempt=False,
        faults=rzl.FaultInjector.device_loss(step=3, label="svc-drill"),
        retry=rzl.RetryPolicy(base_s=0.05, max_s=0.2))
    svc.arm(SIG)
    r1 = ScenarioRequest("a", SIG, 8, seed=4)
    svc.submit(r1)
    summary = svc.serve()
    assert summary["completed"] == 1
    assert summary["lease_failures"] == 0
    assert summary["replayed_member_steps"] > 0

    evs = events.read_events(event_log)
    kinds = {e["kind"] for e in evs}
    assert {"fault_injected", "fault_detected", "run_resumed"} <= kinds
    lease = [e["data"] for e in evs
             if e["kind"] == "service_lease"][-1]
    assert lease["incidents"] == 1

    # ... and the recovered trajectory is still the right one
    entry = svc.pool.get(SIG)
    got = results.states[r1.id]
    ref = _uninterrupted_reference(entry, r1, svc.slots, svc.chunk)
    for k in ref:
        assert np.array_equal(np.asarray(got[k]), np.asarray(ref[k]))


def test_service_lease_failure_is_contained(tmp_path, event_log):
    """A lease whose recovery gives up (persistent fault exhausting
    the same-step recurrence rule) requeues its requests and the
    service keeps serving; the per-request failure budget then reports
    the request FAILED instead of spinning forever — a broken lease
    must neither kill nor wedge the server."""
    from pystella_tpu import resilience as rzl

    svc = _make_service(
        tmp_path, preempt=False,
        faults=rzl.FaultInjector(
            [rzl.RaiseFault(step=1, error=rzl.device_loss_error,
                            once=False)], label="persistent"),
        retry=rzl.RetryPolicy(base_s=0.01, max_s=0.02))
    svc.arm(SIG)
    r1 = ScenarioRequest("a", SIG, 4, seed=5)
    svc.submit(r1)
    summary = svc.serve()  # un-capped: the failure budget bounds it
    assert summary["lease_failures"] == 2
    assert r1.status == "failed"
    evs = events.read_events(event_log, kind="service_lease_failed")
    assert len(evs) == 2
    res = events.read_events(event_log, kind="member_result")
    assert res[-1]["data"]["status"] == "failed"


# -- the EnsembleDriver satellite ------------------------------------------

def test_driver_preempt_drain_and_requeue_bitexact(event_log):
    """The queue-hygiene satellite: a preempted EnsembleDriver run
    drains active members as requeue records, and requeue() re-enters
    a member with its restored state — the resumed trajectory is
    bit-consistent with the uninterrupted run (the only prior re-entry
    was a fresh draw)."""
    stepper, sample, dt = _toy_builder(GRID)
    sc = ps.Scenario("toy", stepper, sample, nsteps=8, dt=dt)

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
                   seed=rec["seed"], params=rec["params"], t=rec["t"])
    finals2 = {}
    out2 = d2.run(on_finish=lambda rec, st:
                  finals2.setdefault(rec["seed"], st))
    assert [r["steps"] for r in out2["results"]] == [8, 8]
    for seed in (0, 1):
        for k in finals[seed]:
            assert np.array_equal(np.asarray(finals[seed][k]),
                                  np.asarray(finals2[seed][k])), \
                (seed, k)
    kinds = [e["kind"] for e in events.read_events(event_log)]
    assert kinds.count("member_preempted") == 2


def test_driver_preempt_leaves_pending_jobs(event_log):
    stepper, sample, dt = _toy_builder(GRID)
    sc = ps.Scenario("toy", stepper, sample, nsteps=8, dt=dt)
    d = ps.EnsembleDriver(size=2, chunk=2, via="vmap",
                          preempt=lambda ci: True)
    d.submit(sc, seeds=[0, 1, 2, 3])
    out = d.run()
    assert len(out["preempted"]) == 2
    assert [j["seed"] for j in out["pending"]] == [2, 3]


# -- event-log rotation -----------------------------------------------------

def test_event_log_rotation_and_family_read(tmp_path):
    path = str(tmp_path / "run_events.jsonl")
    log = EventLog(path, rotate_bytes=600)
    log.emit("run_start", mode="svc")
    for i in range(40):
        log.emit("step_time", step=i, ms=1.0 + 0.01 * i)
    log.close()
    family = rotated_family(path)
    assert len(family) > 2, "600-byte threshold must have rotated"
    assert family[-1] == os.path.abspath(path)
    # plain read sees only the live tail; the family read sees all
    tail = events.read_events(path)
    full = events.read_events(path, include_rotated=True)
    assert len(full) == 41 and len(tail) < len(full)
    steps = [e["step"] for e in full if e["kind"] == "step_time"]
    assert steps == list(range(40))  # oldest-first, in order
    # the ledger ingests the whole family (run_start sits in the
    # OLDEST member; the latest-run scoping works across the rotation)
    led = PerfLedger.from_events(path)
    assert led.stats()["count"] == 40


def test_event_rotate_env_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("PYSTELLA_EVENT_ROTATE_MB", "0.0005")  # ~524 B
    path = str(tmp_path / "ev.jsonl")
    log = EventLog(path)
    assert log.rotate_bytes == int(0.0005 * 2**20)
    for i in range(30):
        log.emit("step_time", step=i, ms=1.0)
    log.close()
    assert len(rotated_family(path)) > 1


# -- loadgen e2e through ledger + gate --------------------------------------

@pytest.fixture(scope="module")
def loadgen_report(tmp_path_factory):
    """One loadgen run -> perf-report service section (module-scoped:
    the e2e legs below all read it)."""
    tmp = tmp_path_factory.mktemp("svc_loadgen")
    path = str(tmp / "events.jsonl")
    obs.configure(path)
    try:
        stats = loadgen.run(str(tmp / "ckpt"), seed=7, grid=8,
                            cold_grid=10, nsteps=8, label="t1-loadgen")
    finally:
        obs.configure(None)
    led = PerfLedger.from_events(path, label="t1-loadgen")
    rep = led.report()
    # the gate needs step samples to engage its comparisons at all;
    # the loadgen log has none (no step_time events), so a minimal
    # clean distribution stands in — the SERVICE verdicts are what
    # these legs exercise
    rep["samples_ms"] = [1.0] * 16
    rep["steps"] = {"count": 16, "p50_ms": 1.0, "mad_ms": 0.0}
    return stats, rep


def test_loadgen_mix_and_service_section(loadgen_report):
    stats, rep = loadgen_report
    assert stats["preempt_bitexact"] is True
    assert stats["preemptions"] == 1
    # the quota rejection plus the PR-19 seeded capacity hog
    assert stats["rejected"] == {"quota": 1, "capacity_exceeded": 1}
    assert stats["capacity"]["hog_rejected"] is True
    assert stats["warm_admissions"] == 6
    assert stats["cold_admissions"] == 1
    assert stats["completed"] == 8
    # the seeded deadline pair: bravo's 20 ms deadline cannot survive
    # a lease (the one MISS), charlie's 60 s cannot be missed (the
    # one HIT with margin) — both polarities recorded every run
    assert stats["deadlined_requests"] == 2
    assert stats["deadline_misses"] == 1
    assert len(stats["traces"]) == stats["requests"]

    sv = rep["service"]
    assert sv["completed"] == 8 and sv["diverged"] == 0
    assert sv["rejected"] == {"quota": 1, "capacity_exceeded": 1}
    assert sv["preemptions"] == 1
    assert sv["warm_claimed"] is True
    assert all(a["fingerprint_ok"] for a in sv["warm_admissions"])
    assert sv["warm_lease_backend_compiles"] == 0
    # queue latencies per priority class, including the p3 arrival
    ql = sv["queue_latency_s"]
    assert ql["overall"]["count"] >= 9
    assert "1" in ql["by_priority"] and "3" in ql["by_priority"]
    # the warm/cold TTFS split: cold paid a real build
    assert sv["ttfs_s"]["warm"]["count"] >= 3
    assert sv["ttfs_s"]["cold"]["count"] == 1
    assert sv["ttfs_s"]["cold"]["p50_s"] > sv["ttfs_s"]["warm"]["p50_s"]
    # fair share realized: every tenant got served
    assert set(sv["tenant_share"]) == {"alpha", "bravo", "charlie"}
    assert abs(sum(sv["tenant_share"].values()) - 1.0) < 1e-9
    assert sv["loadgen"]["preempt_bitexact"] is True

    # the latency section: every traced request's span tree assembled,
    # the critical-path partition audited within tolerance, and the
    # deadline ledger carrying the seeded miss
    lat = rep["latency"]
    assert lat["traced"] == lat["assembled"] == stats["requests"]
    assert lat["unassembled"] == []
    assert lat["phase_sum_check"]["ok"] is True
    assert lat["phase_sum_check"]["max_rel_err"] < 0.05
    assert {"service_queue_wait", "service_chunk_compute",
            "service_compile"} <= set(lat["phases_s"])
    assert lat["deadline"]["deadlined"] == 2
    assert lat["deadline"]["missed"] == 1
    assert lat["deadline"]["miss_rate"] == 0.5
    assert lat["deadline"]["miss_events"] == 1
    assert lat["deadline"]["by_priority"]["1"]["missed"] == 1
    # hit AND miss margins both recorded
    margins = [r["margin_s"] for r in lat["requests"]
               if r["margin_s"] is not None]
    assert any(m < 0 for m in margins) and any(m > 0 for m in margins)

    # the seeded live burn alert (obs.slo): the guaranteed deadline
    # miss FIRES it, the next guaranteed hit RESOLVES it — both
    # transitions in the event record, the ledger's alerts section
    # populated, nothing left burning at exit
    assert stats["slo"]["alerts"] >= 1
    assert stats["slo"]["resolved"] == stats["slo"]["alerts"]
    assert stats["slo"]["alerting"] == []
    al = rep["alerts"]
    assert al["by_leg"]["deadline_miss"]["alerts"] >= 1
    assert al["by_leg"]["deadline_miss"]["resolved"] >= 1
    assert al["unresolved"] == []
    # the emit-path subscriber overhead pin: the monitor's whole
    # ingest cost stays under 2% of the serve wall
    assert stats["slo"]["overhead_pct"] < 2.0, stats["slo"]


def test_loadgen_gate_slo_legs(loadgen_report):
    _stats, rep = loadgen_report
    # clean self-comparison accepts
    v = gate.compare_reports(rep, rep)
    assert v["exit_code"] == 0, v
    assert "service" in v and "queue_p95" in v["service"]

    # seeded queue-latency regression -> exit 1
    slow = copy.deepcopy(rep)
    q = slow["service"]["queue_latency_s"]["overall"]
    q["p95_s"] = q["p95_s"] * 50 + 30.0
    v = gate.compare_reports(rep, slow)
    assert v["exit_code"] == 1
    assert any("queue-latency p95" in r for r in v["reasons"])

    # seeded warm-TTFS regression -> exit 1
    slow2 = copy.deepcopy(rep)
    w = slow2["service"]["ttfs_s"]["warm"]
    w["p50_s"] = w["p50_s"] * 50 + 30.0
    v = gate.compare_reports(rep, slow2)
    assert v["exit_code"] == 1
    assert any("warm time-to-first-step" in r for r in v["reasons"])

    # warm admission over a mismatched fingerprint -> refusal (exit 2),
    # --no-service opts out
    bad = copy.deepcopy(rep)
    bad["service"]["warm_admissions"][0]["fingerprint_ok"] = False
    v = gate.compare_reports(rep, bad)
    assert v["exit_code"] == 2
    assert any("mismatched fingerprint" in r for r in v["reasons"])
    assert gate.compare_reports(rep, bad,
                                check_service=False)["exit_code"] == 0

    # compiles inside warm leases warn (the SLO leg is what fails CI)
    warm_broke = copy.deepcopy(rep)
    warm_broke["service"]["warm_lease_backend_compiles"] = 3
    v = gate.compare_reports(rep, warm_broke)
    assert v["exit_code"] == 0
    assert any("backend compile(s) recorded inside warm" in w_
               for w_ in v["warnings"])

    # coverage loss warns
    nosvc = {k: v2 for k, v2 in rep.items() if k != "service"}
    v = gate.compare_reports(rep, nosvc)
    assert v["exit_code"] == 0
    assert any("SLO coverage was lost" in w_ or
               "service section but the current run has none" in w_
               for w_ in v["warnings"])

    # seeded deadline-miss regression -> exit 1 (a clean baseline — no
    # misses — against the current run's seeded miss clears both the
    # factor and the floor); --no-latency / check_latency=False opt out
    clean = copy.deepcopy(rep)
    clean["latency"]["deadline"].update(missed=0, miss_rate=0.0)
    v = gate.compare_reports(clean, rep)
    assert v["exit_code"] == 1
    assert any("deadline-miss SLO regression" in r for r in v["reasons"])
    assert gate.compare_reports(clean, rep,
                                check_latency=False)["exit_code"] == 0
    # ... and the improvement direction merely warns
    v = gate.compare_reports(rep, clean)
    assert v["exit_code"] == 0
    assert any("deadline-miss improvement" in w_ for w_ in v["warnings"])

    # an unresolved live burn alert beside a GREEN post-hoc SLO section
    # is a live/post-hoc contradiction -> refusal (exit 2); --no-alerts
    # opts out. The loadgen's own record passes (its seeded alert
    # resolved — asserted above), so the self-comparison staying exit 0
    # doubles as the resolved-alert acceptance leg.
    stuck = copy.deepcopy(rep)
    stuck["alerts"]["unresolved"] = [
        {"leg": "deadline_miss", "since_ts": 1.0, "value": 1.0,
         "bar": 0.1}]
    v = gate.compare_reports(rep, stuck)
    assert v["exit_code"] == 2
    assert any("live burn alert" in r and "claims green" in r
               for r in v["reasons"])
    assert gate.compare_reports(rep, stuck,
                                check_alerts=False)["exit_code"] == 0

    # an unassembled span tree is a coverage-loss warning, never a
    # refusal (the request may legitimately still be in flight)
    partial = copy.deepcopy(rep)
    partial["latency"]["unassembled"] = [
        {"trace": "dead", "id": 99, "problems": ["no terminal event"]}]
    partial["latency"]["unassembled_total"] = 1
    v = gate.compare_reports(rep, partial)
    assert v["exit_code"] == 0
    assert any("failed to assemble" in w_ for w_ in v["warnings"])

    # losing the whole latency section relative to the baseline warns
    nolat = {k: v2 for k, v2 in rep.items() if k != "latency"}
    v = gate.compare_reports(rep, nolat)
    assert v["exit_code"] == 0
    assert any("deadline-miss SLO coverage was lost" in w_
               for w_ in v["warnings"])


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
