"""Elastic-runtime tests (pystella_tpu.resilience): the retry/backoff
classifier, checkpoint durability semantics, and the Supervisor's
recovery round trips —
injected device loss and NaN faults survived end to end on the CPU
mesh, bit-consistent with an uninterrupted run; SIGTERM preemption
drained to a durable checkpoint in a subprocess and resumed; the
ledger's `resilience` report section and the gate's degraded-evidence
triage on synthetic reports."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import resilience
from pystella_tpu.obs import events, gate, ledger
from pystella_tpu.parallel import multihost
from pystella_tpu.resilience import retry as rz_retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- classification (the "deterministic => no retry" policy) ---------------

def test_classify_exception():
    c = rz_retry.classify_exception
    # transport/availability failures retry
    assert c(TimeoutError("dial timed out")) == "transient"
    assert c(ConnectionResetError("peer reset")) == "transient"
    assert c(RuntimeError("UNAVAILABLE: failed to connect to all "
                          "addresses")) == "transient"
    assert c(OSError("socket closed")) == "transient"
    assert c(resilience.device_loss_error()) == "transient"
    # program bugs must not retry, whatever the message says
    assert c(ValueError("UNAVAILABLE")) == "deterministic"
    assert c(TypeError("bad arg")) == "deterministic"
    assert c(KeyError("f")) == "deterministic"
    # runtime errors carrying a deterministic status stay deterministic
    # even with an incidental transient-looking word in the dump
    assert c(RuntimeError("INVALID_ARGUMENT: timeout=3 is not a "
                          "tensor")) == "deterministic"
    # unknown failure modes default to deterministic (no optimistic
    # retries — the round-5 lesson)
    assert c(RuntimeError("something odd")) == "deterministic"


def test_backoff_sequence_and_jitter():
    p = rz_retry.RetryPolicy(base_s=1.0, factor=2.0, max_s=5.0,
                             jitter=0.0)
    r = rz_retry.Retrier(p, sleep=lambda s: None)
    seq = []
    for _ in range(5):
        assert r.note_failure()[0] == "retry"
        seq.append(r.backoff_s())
    assert seq == [1.0, 2.0, 4.0, 5.0, 5.0]  # clipped at max_s
    # jitter stays within the declared fraction
    import random
    rj = rz_retry.Retrier(
        rz_retry.RetryPolicy(base_s=1.0, factor=1.0, jitter=0.25),
        rng=random.Random(7))
    rj.note_failure()
    for _ in range(50):
        assert 0.75 <= rj.backoff_s() <= 1.25


def test_retrier_deterministic_stops():
    r = rz_retry.Retrier(rz_retry.RetryPolicy())
    decision, reason = r.note_failure(kind="deterministic",
                                      error=ValueError("rc=3"))
    assert decision == "stop" and "deterministic" in reason


def test_retrier_fast_failure_streak():
    """The orchestrator's dial policy: 3 consecutive fast failures
    (a tight crash loop) give up; a slow failure resets the streak."""
    p = rz_retry.RetryPolicy(base_s=0.0, jitter=0.0,
                             fast_failure_s=120.0, max_fast_failures=3)
    r = rz_retry.Retrier(p, sleep=lambda s: None)
    assert r.note_failure(duration_s=5)[0] == "retry"
    assert r.note_failure(duration_s=5)[0] == "retry"
    decision, reason = r.note_failure(duration_s=5)
    assert decision == "stop" and "fast failures" in reason
    # a slow attempt in between resets the counter
    r2 = rz_retry.Retrier(p, sleep=lambda s: None)
    r2.note_failure(duration_s=5)
    r2.note_failure(duration_s=5)
    assert r2.note_failure(duration_s=500)[0] == "retry"
    assert r2.note_failure(duration_s=5)[0] == "retry"
    assert r2.consecutive_fast == 1


def test_retrier_budgets():
    # attempt ceiling
    p = rz_retry.RetryPolicy(base_s=0.0, jitter=0.0, max_attempts=3)
    r = rz_retry.Retrier(p, sleep=lambda s: None)
    assert r.note_failure()[0] == "retry"
    assert r.note_failure()[0] == "retry"
    assert r.note_failure()[0] == "stop"
    # wall budget with an injected clock: stop when the NEXT backoff
    # would land beyond it
    now = [0.0]
    p2 = rz_retry.RetryPolicy(base_s=10.0, factor=1.0, jitter=0.0,
                              budget_s=25.0)
    r2 = rz_retry.Retrier(p2, clock=lambda: now[0],
                          sleep=lambda s: None)
    assert r2.note_failure()[0] == "retry"
    now[0] = 20.0
    decision, reason = r2.note_failure()
    assert decision == "stop" and "budget" in reason


def test_retry_call_transient_then_success():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TimeoutError("dial")
        return 7

    out = rz_retry.retry_call(
        flaky, policy=rz_retry.RetryPolicy(base_s=0.0, jitter=0.0),
        sleep=lambda s: None)
    assert out == 7 and len(calls) == 3


def test_retry_call_deterministic_raises_once():
    calls = []

    def buggy():
        calls.append(1)
        raise ValueError("bug")

    with pytest.raises(ValueError):
        rz_retry.retry_call(buggy, sleep=lambda s: None)
    assert len(calls) == 1


def test_retry_call_budget_exhaustion_reraises_last():
    calls = []

    def down():
        calls.append(1)
        raise TimeoutError(f"attempt {len(calls)}")

    with pytest.raises(TimeoutError, match="attempt 3"):
        rz_retry.retry_call(
            down, policy=rz_retry.RetryPolicy(base_s=0.0, jitter=0.0,
                                              max_attempts=3),
            sleep=lambda s: None)
    assert len(calls) == 3


# -- multihost re-dial -----------------------------------------------------

def test_multihost_latch_is_two_way():
    multihost.init_multihost()
    assert multihost.is_initialized()
    multihost.shutdown()
    assert not multihost.is_initialized()
    multihost.reinit()          # the supervisor's re-dial path
    assert multihost.is_initialized()


# -- checkpoint durability (scheduled != durable; walk-back) ---------------

@pytest.fixture
def decomp():
    if len(jax.devices()) >= 4:
        return ps.DomainDecomposition((2, 2, 1),
                                      devices=jax.devices()[:4])
    return ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])


def _sharded_state(decomp, seed=0):
    rng = np.random.default_rng(seed)
    grid = (16, 16, 16)
    return {"f": decomp.shard(rng.standard_normal((2,) + grid)),
            "dfdt": decomp.shard(rng.standard_normal((2,) + grid))}


def test_checkpoint_durable_semantics(tmp_path, decomp):
    """save() schedules; only finalize() makes last_good advance —
    with checkpoint_save (durable=False) then checkpoint_durable in
    the event record."""
    log_path = str(tmp_path / "ev.jsonl")
    old = events.configure(log_path)
    try:
        state = _sharded_state(decomp)
        with ps.Checkpointer(tmp_path / "ck") as ck:
            assert ck.save(4, state)
            assert ck.last_good is None          # scheduled, not durable
            assert ck.finalize() == [4]
            assert ck.last_good["step"] == 4
            assert ck.finalize() == []           # idempotent barrier
    finally:
        events.configure(None)
        del old
    kinds = [e["kind"] for e in events.read_events(log_path)]
    assert kinds == ["checkpoint_save", "checkpoint_durable"]
    evs = events.read_events(log_path)
    assert evs[0]["data"]["durable"] is False
    assert evs[1]["data"]["wait_s"] >= 0


def test_checkpoint_restore_walks_back_over_corrupt(tmp_path, decomp):
    """A corrupt newest checkpoint falls back to the next-older step
    (checkpoint_fallback event) instead of failing the resume; an
    EXPLICITLY requested corrupt step still raises."""
    log_path = str(tmp_path / "ev.jsonl")
    events.configure(log_path)
    try:
        state = _sharded_state(decomp, seed=3)
        with ps.Checkpointer(tmp_path / "ck") as ck:
            ck.save(2, state, metadata={"t": 0.5})
            ck.save(4, state)
            ck.finalize()
            # corrupt every file of the newest step's payload
            stepdir = os.path.join(str(tmp_path / "ck"), "4")
            for dirpath, _dirs, files in os.walk(stepdir):
                for fname in files:
                    with open(os.path.join(dirpath, fname), "wb") as f:
                        f.write(b"garbage")
            step, restored, meta = ck.restore(
                sharding_fn=decomp.shard)
            assert step == 2 and meta["t"] == 0.5
            for k in state:
                assert np.array_equal(np.asarray(restored[k]),
                                      np.asarray(state[k]))
            with pytest.raises(Exception):
                ck.restore(step=4)
    finally:
        events.configure(None)
    kinds = [e["kind"] for e in events.read_events(log_path)]
    assert "checkpoint_fallback" in kinds
    assert kinds.count("checkpoint_restore") == 1


# -- the supervisor round trips --------------------------------------------

_toy_jit = jax.jit(
    lambda s: {"f": s["f"] * np.float32(0.9)
               + np.float32(0.01) * jnp.roll(s["f"], 1)})


def _toy_step(state, step):
    return _toy_jit(state)


def _toy_state(seed=3):
    rng = np.random.default_rng(seed)
    return {"f": jnp.asarray(
        rng.standard_normal((4, 8)).astype(np.float32))}


def _toy_reference(nsteps, seed=3):
    s = _toy_state(seed)
    for i in range(nsteps):
        s = _toy_step(s, i)
    return s


def _fast_retry():
    return resilience.RetryPolicy(base_s=0.01, max_s=0.05, jitter=0.0)


def test_supervisor_survives_device_loss(tmp_path):
    """The acceptance round trip: an injected mid-run device-loss
    fault (XlaRuntimeError UNAVAILABLE at step 9 of 12, checkpoints
    every 4) is survived end to end — restore from the durable
    last-good checkpoint at 8, replay <= one interval, final state
    bit-identical to an uninterrupted run, one incident with a
    measured MTTR in the record."""
    log_path = str(tmp_path / "ev.jsonl")
    events.configure(log_path)
    try:
        with ps.Checkpointer(tmp_path / "ck", max_to_keep=3) as ck:
            sup = resilience.Supervisor(
                _toy_step, ck, 12, checkpoint_every=4,
                faults=resilience.FaultInjector.device_loss(step=9),
                retry=_fast_retry(), label="t-devloss")
            rep = sup.run(_toy_state())
    finally:
        events.configure(None)
    assert rep["completed"] and rep["final_step"] == 12
    assert rep["incidents"] == 1
    inc = rep["incident_records"][0]
    assert inc["kind"] == "device_loss"
    assert inc["restored_step"] == 8
    assert inc["steps_replayed"] == 1 <= 4      # bounded by the interval
    assert inc["mttr_s"] > 0
    ref = _toy_reference(12)
    assert np.array_equal(np.asarray(rep["state"]["f"]),
                          np.asarray(ref["f"]))
    kinds = [e["kind"] for e in events.read_events(log_path)]
    for k in ("fault_injected", "fault_detected", "recovery_attempt",
              "run_resumed", "supervisor_done"):
        assert k in kinds, (k, kinds)
    # the incident resume names its source
    resumed = events.read_events(log_path, kind="run_resumed")[0]
    assert resumed["data"]["incident"] is True
    assert resumed["data"]["mttr_s"] > 0


def test_supervisor_nan_fault_trips_and_restores(tmp_path):
    """The numerics round trip: a NaN injected at step 6 propagates;
    the async monitor trips at the checkpoint boundary BEFORE the
    corrupt state is saved; the supervisor restores last_good (step 4)
    and the replayed (clean) trajectory completes bit-identical to an
    uninterrupted run."""
    log_path = str(tmp_path / "ev.jsonl")
    events.configure(log_path)
    try:
        mon = ps.HealthMonitor(every=2, metrics_prefix="supervised")
        with ps.Checkpointer(tmp_path / "ck", max_to_keep=3) as ck:
            sup = resilience.Supervisor(
                _toy_step, ck, 12, monitor=mon, checkpoint_every=4,
                faults=resilience.FaultInjector.nan(step=6, field="f"),
                retry=_fast_retry(), label="t-nan")
            rep = sup.run(_toy_state())
    finally:
        events.configure(None)
    assert rep["completed"] and rep["incidents"] == 1
    inc = rep["incident_records"][0]
    assert inc["kind"] == "numerics"
    assert inc["restored_step"] == 4
    assert inc["steps_replayed"] <= 4
    ref = _toy_reference(12)
    assert np.array_equal(np.asarray(rep["state"]["f"]),
                          np.asarray(ref["f"]))
    # a durable checkpoint of the corrupt state was never taken: every
    # durable step is <= the trip step's last good boundary or from
    # the clean replay
    evs = events.read_events(log_path)
    diverged = [e for e in evs if e["kind"] == "diverged"]
    assert diverged and diverged[0]["step"] == 7  # NaN entering step 6
    # pending corrupt-trajectory vectors were discarded, not checked
    assert not any(e["kind"] == "diverged" and e["step"] > 7
                   for e in evs)


def test_supervisor_deterministic_fault_reraises(tmp_path):
    """A ValueError at step 5 re-raises immediately — no recovery, no
    incident; the event record carries the reraise verdict."""
    log_path = str(tmp_path / "ev.jsonl")
    events.configure(log_path)
    try:
        with ps.Checkpointer(tmp_path / "ck") as ck:
            sup = resilience.Supervisor(
                _toy_step, ck, 12, checkpoint_every=4,
                faults=resilience.FaultInjector.raise_at(
                    5, ValueError("program bug")),
                retry=_fast_retry(), label="t-det")
            with pytest.raises(ValueError, match="program bug"):
                sup.run(_toy_state())
    finally:
        events.configure(None)
    assert sup.incidents == []
    evs = events.read_events(log_path)
    det = [e for e in evs if e["kind"] == "fault_detected"]
    assert det and det[0]["data"]["action"] == "reraise"
    assert not any(e["kind"] == "run_resumed" for e in evs)


def test_supervisor_persistent_fault_gives_up(tmp_path):
    """A NaN fault that re-fires on every pass (once=False) recurs at
    the same step after the restore — RecoveryFailed, not an infinite
    replay loop."""
    mon = ps.HealthMonitor(every=2, metrics_prefix="supervised")
    with ps.Checkpointer(tmp_path / "ck") as ck:
        sup = resilience.Supervisor(
            _toy_step, ck, 12, monitor=mon, checkpoint_every=4,
            faults=resilience.FaultInjector(
                [resilience.NaNFault(6, "f", once=False)]),
            retry=_fast_retry(), label="t-persist")
        with pytest.raises(resilience.RecoveryFailed,
                           match="recurred"):
            sup.run(_toy_state())
    assert len(sup.incidents) == 1  # recovered once, gave up on repeat


def test_supervisor_incident_budget(tmp_path):
    """max_recoveries bounds the whole run's incident count."""
    faults = resilience.FaultInjector(
        [resilience.RaiseFault(5, resilience.device_loss_error),
         resilience.RaiseFault(6, resilience.device_loss_error),
         resilience.RaiseFault(7, resilience.device_loss_error)])
    with ps.Checkpointer(tmp_path / "ck") as ck:
        sup = resilience.Supervisor(
            _toy_step, ck, 12, checkpoint_every=4, faults=faults,
            retry=_fast_retry(), max_recoveries=2, label="t-budget")
        with pytest.raises(resilience.RecoveryFailed,
                           match="incident budget"):
            sup.run(_toy_state())
    assert len(sup.incidents) == 2


def test_supervisor_fault_before_first_checkpoint(tmp_path):
    """A device loss before any checkpoint restarts from the
    initial-state snapshot instead of failing the run."""
    with ps.Checkpointer(tmp_path / "ck") as ck:
        sup = resilience.Supervisor(
            _toy_step, ck, 8, checkpoint_every=4,
            faults=resilience.FaultInjector.device_loss(step=2),
            retry=_fast_retry(), label="t-early")
        rep = sup.run(_toy_state())
    assert rep["completed"] and rep["incidents"] == 1
    assert rep["incident_records"][0]["restored_step"] == 0
    ref = _toy_reference(8)
    assert np.array_equal(np.asarray(rep["state"]["f"]),
                          np.asarray(ref["f"]))


def test_supervisor_recovers_over_torn_checkpoint(tmp_path):
    """The crash-mid-write composition: the newest checkpoint is torn
    when the device-loss fault hits — recovery walks back to the older
    durable step, replays THROUGH the torn boundary (re-writing it
    clean), and still completes bit-identical."""
    log_path = str(tmp_path / "ev.jsonl")
    events.configure(log_path)
    try:
        with ps.Checkpointer(tmp_path / "ck", max_to_keep=3) as ck:
            def tearing_step(state, step):
                out = _toy_step(state, step)
                if step == 8:
                    # after the boundary-8 save lands, corrupt it on
                    # disk — the torn artifact of a crash mid-write
                    ck.finalize()
                    stepdir = os.path.join(str(tmp_path / "ck"), "8")
                    for dirpath, _dirs, files in os.walk(stepdir):
                        for fname in files:
                            with open(os.path.join(dirpath, fname),
                                      "wb") as f:
                                f.write(b"torn")
                return out

            sup = resilience.Supervisor(
                tearing_step, ck, 12, checkpoint_every=4,
                faults=resilience.FaultInjector.device_loss(step=9),
                retry=_fast_retry(), label="t-torn")
            rep = sup.run(_toy_state())
    finally:
        events.configure(None)
    assert rep["completed"] and rep["incidents"] == 1
    # walked back past the torn 8 to the durable 4
    assert rep["incident_records"][0]["restored_step"] == 4
    ref = _toy_reference(12)
    assert np.array_equal(np.asarray(rep["state"]["f"]),
                          np.asarray(ref["f"]))
    kinds = [e["kind"] for e in events.read_events(log_path)]
    assert "checkpoint_fallback" in kinds


def test_supervisor_remesh_hook_degrades(tmp_path):
    """The re-mesh hook swaps in a replacement program during
    device-loss recovery and the run records a run_degraded event."""
    log_path = str(tmp_path / "ev.jsonl")
    events.configure(log_path)
    hook_calls = []

    def remesh(error, attempt):
        hook_calls.append((type(error).__name__, attempt))
        return {"step_fn": _toy_step,
                "note": "re-meshed to 1 surviving device"}

    try:
        with ps.Checkpointer(tmp_path / "ck") as ck:
            sup = resilience.Supervisor(
                _toy_step, ck, 12, checkpoint_every=4,
                faults=resilience.FaultInjector.device_loss(step=9),
                retry=_fast_retry(), remesh=remesh, label="t-remesh")
            rep = sup.run(_toy_state())
    finally:
        events.configure(None)
    assert rep["completed"] and hook_calls == [("JaxRuntimeError", 1)]
    degraded = events.read_events(log_path, kind="run_degraded")
    assert degraded and "surviving" in degraded[0]["data"]["note"]


def test_supervisor_sigterm_preemption_subprocess(tmp_path):
    """Preemption end to end, in a real process: SIGTERM mid-run =>
    drain + durable checkpoint + clean exit; a fresh process resumes
    at that step and completes bit-identical to an uninterrupted
    run."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYSTELLA_EVENT_LOG", None)
    ck_dir = str(tmp_path / "ck")
    worker = os.path.join(REPO, "tests", "resilience_worker.py")

    res = subprocess.run(
        [sys.executable, worker, "preempt", ck_dir],
        capture_output=True, text=True, timeout=240, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    first = json.loads(res.stdout.strip().splitlines()[-1])
    assert first["preempted"] is True and first["completed"] is False
    # the drain checkpointed the CURRENT step durably
    assert first["last_good"]["step"] == first["checkpoint_step"]

    res2 = subprocess.run(
        [sys.executable, worker, "resume", ck_dir],
        capture_output=True, text=True, timeout=240, env=env)
    assert res2.returncode == 0, res2.stderr[-2000:]
    second = json.loads(res2.stdout.strip().splitlines()[-1])
    assert second["completed"] is True
    assert second["final_step"] == 12
    # resumed exactly at the preemption checkpoint
    assert second["resumed_from"] == first["checkpoint_step"]
    assert second["bit_consistent"] is True


def test_preemption_drain_health_checks_before_saving(tmp_path):
    """A NaN inside the sentinel's maturity lag when SIGTERM arrives:
    the drain's own pre-save health check trips, recovery restores the
    clean last-good state, and the still-set preemption flag drains
    THAT — the corrupt state is never durably checkpointed and the
    preemption still completes cleanly."""
    mon = ps.HealthMonitor(every=2, metrics_prefix="supervised")
    with ps.Checkpointer(tmp_path / "ck", max_to_keep=3) as ck:
        sup = resilience.Supervisor(
            _toy_step, ck, 12, monitor=mon, checkpoint_every=4,
            faults=resilience.FaultInjector(
                [resilience.NaNFault(5, "f"),
                 resilience.SigtermFault(6)]),
            retry=_fast_retry(), label="t-preempt-nan")
        rep = sup.run(_toy_state())
        assert rep["preempted"] and not rep["completed"]
        assert rep["incidents"] == 1
        assert rep["incident_records"][0]["kind"] == "numerics"
        # drained at the RESTORED clean step, not the corrupt one
        assert rep["final_step"] == 4
        assert rep["last_good"]["step"] == 4
        assert ck.all_steps() == [4]   # no corrupt checkpoint on disk


# -- ledger + gate on resilience telemetry ---------------------------------

def test_ledger_resilience_ingestion(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        log.emit("checkpoint_save", step=4, durable=False)
        log.emit("checkpoint_durable", step=4, wait_s=0.02)
        log.emit("checkpoint_save", step=8, durable=False)
        log.emit("checkpoint_durable", step=8, wait_s=0.01)
        log.emit("fault_injected", step=9, fault_kind="raise")
        log.emit("fault_detected", step=9, fault_kind="device_loss",
                 error="XlaRuntimeError: UNAVAILABLE: link died")
        log.emit("recovery_attempt", step=9, fault_kind="device_loss",
                 attempt=1)
        log.emit("checkpoint_restore", step=8)
        log.emit("run_resumed", step=8, source="recovery",
                 incident=True, fault_kind="device_loss", from_step=9,
                 mttr_s=0.4, steps_replayed=1, attempts=1)
        for ms in (2.0, 2.1, 2.05, 2.2):
            log.emit("step_time", ms=ms)
        log.emit("supervisor_done", step=12, completed=True,
                 preempted=False, incidents=1, steps_replayed=1,
                 wall_s=3.0)
    led = ledger.PerfLedger.from_events(path, label="rz")
    rz = led.resilience()
    assert rz["n_incidents"] == 1 and rz["resolved"] == 1
    assert rz["unresolved"] == 0 and rz["consistent"] is True
    inc = rz["incidents"][0]
    assert inc["kind"] == "device_loss" and inc["mttr_s"] == 0.4
    assert inc["detected_at_step"] == 9 and inc["restored_step"] == 8
    assert rz["checkpoints"]["saved"] == 2
    assert rz["checkpoints"]["durable"] == 2
    assert rz["checkpoints"]["cadence_steps"] == 4.0
    assert rz["checkpoints"]["barrier_s"] == pytest.approx(0.03)
    assert rz["faults_injected"] == 1
    md = ledger.render_markdown(led.report())
    assert "## Resilience" in md and "device_loss" in md
    # a run with no resilience telemetry has no section
    assert ledger.PerfLedger(label="bare").resilience() is None
    # several supervised runs in one window (a preempted run + its
    # resumed successor): the claim the gate audits is their SUM — a
    # clean resume run's incidents=0 must not make the window read as
    # claiming fewer incidents than its record (found by the verify
    # drive: the last-run-wins claim flagged an honest two-leg log)
    with events.EventLog(path) as log:
        log.emit("supervisor_done", step=12, completed=False,
                 preempted=True, incidents=0, steps_replayed=0,
                 wall_s=1.0)
        log.emit("run_preempted", step=12, checkpoint_step=12)
    led2 = ledger.PerfLedger.from_events(path, label="rz2")
    rz2 = led2.resilience()
    assert rz2["claimed_incidents"] == 1 and rz2["consistent"] is True
    assert rz2["preempted"] is True
    # a preemption drain is a clean hand-off, not a death mid-recovery
    assert rz2["completed"] is True


def _report(samples_ms, **env_overrides):
    led = ledger.PerfLedger(label="synthetic", sites=32**3)
    led.samples_ms = list(samples_ms)
    rep = led.report()
    rep["env"].update(env_overrides)
    return rep


def _steady(n=60, base=10.0, jitter=0.05, seed=0):
    rng = np.random.default_rng(seed)
    return (base + jitter * rng.standard_normal(n)).tolist()


def _with_resilience(rep, n_incidents=1, completed=True,
                     consistent=True, unresolved=0, claimed=None,
                     injected=0):
    rep = dict(rep)
    rep["resilience"] = {
        "n_incidents": n_incidents, "resolved": n_incidents - unresolved,
        "unresolved": unresolved, "completed": completed,
        "consistent": consistent,
        "claimed_incidents": (n_incidents if claimed is None
                              else claimed),
        "faults_injected": injected,
        "incidents": [{"kind": "device_loss", "mttr_s": 0.5,
                       "steps_replayed": 3, "attempts": 1}
                      ] * n_incidents,
        "checkpoints": {"saved": 3, "durable": 3, "fallbacks": 0},
    }
    return rep


def test_gate_regression_across_incident_is_annotated():
    """The acceptance case: a step-time regression measured across a
    recorded (and recovered) incident is annotated as degraded — exit
    0 with a warning — not failed; without the incident record the
    same delta gates exit 1, and --no-resilience restores that."""
    base = _report(_steady(seed=1))
    slow = _report([x * 1.3 for x in _steady(seed=1)])
    assert gate.compare_reports(base, slow)["exit_code"] == 1
    degraded = gate.compare_reports(base, _with_resilience(slow))
    assert degraded["exit_code"] == 0 and degraded["ok"]
    assert degraded["degraded"] is True
    assert any("degraded fleet" in w for w in degraded["warnings"])
    forced = gate.compare_reports(base, _with_resilience(slow),
                                  check_resilience=False)
    assert forced["exit_code"] == 1


def test_gate_drill_incidents_do_not_soften_verdicts():
    """A harness-injected drill (faults_injected covers the incident
    count — every smoke run carries one) annotates the verdict
    degraded but leaves the regression and contamination verdicts
    fully armed: otherwise the ever-present smoke drill would
    permanently disarm CI."""
    base = _report(_steady(seed=1))
    slow = _report([x * 1.3 for x in _steady(seed=1)])
    drill = gate.compare_reports(
        base, _with_resilience(slow, injected=1))
    assert drill["exit_code"] == 1          # regression still fails
    assert drill["degraded"] is True        # ... but is annotated
    assert any("drill" in w for w in drill["warnings"])
    # one REAL incident on top of a drill re-earns the softening
    mixed = gate.compare_reports(
        base, _with_resilience(slow, n_incidents=2, injected=1))
    assert mixed["exit_code"] == 0 and mixed["degraded"] is True
    # drill-only contamination on an accelerator still refuses
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite"}
    samples = _steady(n=50, seed=3)
    for i in range(20, 27):
        samples[i] *= 5.0
    cont = gate.compare_reports(
        _report(_steady(seed=4), **tpu),
        _with_resilience(_report(samples, **tpu), injected=1))
    assert cont["exit_code"] == 2


def test_gate_contamination_across_incident_is_annotated():
    """On an accelerator report, a recovery stall looks exactly like
    the round-5 contamination burst — with a recorded incident it is
    annotated (degraded), not refused; without one it stays exit 2."""
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite"}
    samples = _steady(n=50, seed=3)
    for i in range(20, 27):
        samples[i] *= 5.0
    base = _report(_steady(seed=4), **tpu)
    cont = _report(samples, **tpu)
    assert gate.compare_reports(base, cont)["exit_code"] == 2
    verdict = gate.compare_reports(base, _with_resilience(cont))
    assert verdict["exit_code"] == 0 and verdict["degraded"] is True
    assert any("annotated, not refused" in w
               for w in verdict["warnings"])


def test_gate_claims_clean_with_incidents_refused(tmp_path):
    """A supervisor claiming fewer incidents than the event record
    carries is hiding a degraded fleet: invalid evidence, exit 2 —
    pinned through the CLI too."""
    base = _report(_steady(seed=1))
    lying = _with_resilience(_report(_steady(seed=5)), n_incidents=2,
                             consistent=False, claimed=0)
    verdict = gate.compare_reports(base, lying)
    assert verdict["exit_code"] == 2
    assert any("claims" in r for r in verdict["reasons"])
    bp, cp = tmp_path / "b.json", tmp_path / "c.json"
    bp.write_text(json.dumps(base))
    cp.write_text(json.dumps(lying))
    assert gate.main(["--baseline", str(bp), "--current", str(cp)]) == 2
    assert gate.main(["--baseline", str(bp), "--current", str(cp),
                      "--no-resilience"]) == 0


def test_gate_resilience_warnings():
    base_rz = _with_resilience(_report(_steady()))
    # coverage loss: baseline had the section, current does not
    lost = gate.compare_reports(base_rz, _report(_steady(seed=9)))
    assert lost["exit_code"] == 0
    assert any("resilience" in w and "coverage was lost" in w
               for w in lost["warnings"])
    # unresolved incidents warn (and do NOT earn the degraded shield:
    # the regression still gates)
    slow = _report([x * 1.3 for x in _steady(seed=1)])
    half = _with_resilience(slow, n_incidents=2, unresolved=1)
    verdict = gate.compare_reports(_report(_steady(seed=1)), half)
    assert any("never resumed" in w for w in verdict["warnings"])
    assert verdict["exit_code"] == 1


if __name__ == "__main__":
    import pytest as _pytest
    _pytest.main([__file__, "-v"])
