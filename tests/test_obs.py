"""Telemetry subsystem tests: event-log schema round-trip, counter/gauge
aggregation on the virtual multi-device CPU mesh, trace-scope no-op
safety under ``JAX_PLATFORMS=cpu``, named-scope presence in a fused-step
lowering, and memory-analysis capture for one fused kernel."""

import os
import sys
import threading

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)
import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.obs import events, metrics
from pystella_tpu.obs.events import EventLog, rotated_family
from pystella_tpu.obs.ledger import PerfLedger


@pytest.fixture
def event_log(tmp_path):
    """Point the process-default event log at a temp file; restore the
    disabled sink afterwards so tests don't leak configuration."""
    path = tmp_path / "events.jsonl"
    events.configure(str(path))
    yield str(path)
    events.configure(None)


def _small_fused(decomp, n=8, dtype=np.float32, **kwargs):
    grid_shape = (n, n, n)
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=dtype)
    dt = dtype(0.1 * min(lattice.dx))
    sector = ps.ScalarSector(1, potential=lambda f: 0.5 * f[0]**2)
    stepper = ps.FusedScalarStepper(sector, decomp, grid_shape,
                                    lattice.dx, 2, dtype=dtype, dt=dt,
                                    **kwargs)
    rng = np.random.default_rng(17)
    state = {k: decomp.shard(
        0.1 * rng.standard_normal((1,) + grid_shape).astype(dtype))
        for k in ("f", "dfdt")}
    return stepper, state, dt


# -- events ----------------------------------------------------------------

def test_event_schema_roundtrip(event_log):
    events.emit("unit_test", step=3, value=1.5, name="x",
                arr=np.float32(2.0))
    events.emit("other_kind")
    recs = events.read_events(event_log)
    assert len(recs) == 2
    ev = recs[0]
    assert ev["v"] == events.SCHEMA_VERSION
    assert isinstance(ev["ts"], float) and isinstance(ev["mono"], float)
    assert ev["host"] == 0  # single-process run
    assert ev["kind"] == "unit_test" and ev["step"] == 3
    assert ev["data"] == {"value": 1.5, "name": "x", "arr": 2.0}
    assert recs[1]["step"] is None
    # monotonic timestamps order events within one process
    assert recs[1]["mono"] >= ev["mono"]
    # kind filter
    assert [r["kind"] for r in events.read_events(
        event_log, kind="other_kind")] == ["other_kind"]


def test_event_log_tolerates_torn_lines(tmp_path):
    path = tmp_path / "ev.jsonl"
    with events.EventLog(str(path)) as log:
        log.emit("ok", value=1)
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "torn", "da')  # killed mid-write
    recs = events.read_events(str(path))
    assert [r["kind"] for r in recs] == ["ok"]


def test_disabled_sink_is_noop(tmp_path):
    log = events.EventLog(None)
    assert not log.enabled
    assert log.emit("anything", x=1) is None


# -- event-log rotation -----------------------------------------------------

def test_event_log_rotation_and_family_read(tmp_path):
    path = str(tmp_path / "run_events.jsonl")
    log = EventLog(path, rotate_bytes=600)
    log.emit("run_start", mode="long")
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


def test_subscribers_survive_rotation(tmp_path):
    """A subscriber registered before a size-triggered rollover keeps
    receiving every record emitted after it (subscribers hang off the
    log object, not the file handle)."""
    path = str(tmp_path / "run_events.jsonl")
    log = EventLog(path, rotate_bytes=600)
    seen = []
    log.subscribe(seen.append)
    for i in range(40):
        log.emit("step_time", step=i, ms=1.0 + 0.01 * i)
    log.close()
    family = rotated_family(path)
    assert len(family) > 2, "600-byte threshold must have rotated"
    assert [r["step"] for r in seen] == list(range(40))
    # and the on-disk family still carries the same whole stream
    full = events.read_events(path, include_rotated=True)
    assert [e["step"] for e in full] == list(range(40))


# -- metrics ---------------------------------------------------------------

def test_counter_gauge_timer_exports():
    reg = metrics.MetricsRegistry()
    reg.counter("steps").inc(5)
    reg.counter("steps").inc()  # get-or-create returns the same object
    reg.gauge("peak", reduce="max").set(7.0)
    t = reg.timer("halo", ema_alpha=0.5)
    t.observe(0.010)
    t.observe(0.020)
    snap = reg.snapshot()
    assert snap["steps"] == 6.0
    assert snap["peak"] == 7.0
    assert snap["halo.count"] == 2.0
    assert snap["halo.total_s"] == pytest.approx(0.030)
    assert snap["halo.ema_ms"] == pytest.approx(15.0)  # 0.5*20 + 0.5*10
    assert list(snap) == sorted(snap)  # stable cross-host ordering
    with pytest.raises(TypeError):
        reg.gauge("steps")  # kind mismatch


def test_snapshot_consistent_under_concurrent_updates():
    """A snapshot racing another thread's timer updates is consistent:
    never a Timer between its count bump and its total accumulation.
    observe(1.0) keeps total_s == count exactly (1.0 sums without
    rounding), so any torn read is detectable."""
    reg = metrics.MetricsRegistry()
    t = reg.timer("hammer")
    stop = threading.Event()

    def work():
        while not stop.is_set():
            t.observe(1.0)

    switch0 = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # make torn reads likely without locks
    worker = threading.Thread(target=work, daemon=True)
    worker.start()
    try:
        for _ in range(300):
            snap = reg.snapshot()
            assert snap["hammer.total_s"] == snap["hammer.count"]
    finally:
        stop.set()
        worker.join(timeout=10)
        sys.setswitchinterval(switch0)
    assert t.count > 0


def test_reduce_snapshots_multihost_semantics():
    """The cross-host reduction core, fed per-host snapshots directly —
    testable without a multi-process cluster."""
    reg = metrics.MetricsRegistry()
    reg.counter("steps")
    reg.gauge("ms_per_step", reduce="mean")
    reg.gauge("peak_hbm", reduce="max")
    hosts = [{"steps": 100.0, "ms_per_step": 10.0, "peak_hbm": 1.0},
             {"steps": 100.0, "ms_per_step": 20.0, "peak_hbm": 5.0},
             {"steps": 101.0, "ms_per_step": 30.0, "peak_hbm": 2.0}]
    out = reg.reduce_snapshots(hosts)
    assert out["steps"] == 301.0          # counters sum
    assert out["ms_per_step"] == 20.0     # gauges reduce as declared
    assert out["peak_hbm"] == 5.0

    # a host that has registered but not yet set a gauge (NaN — e.g.
    # it hasn't crossed its StepTimer report cadence) must not poison
    # the fleet-wide reduction
    hosts[1]["ms_per_step"] = float("nan")
    out = reg.reduce_snapshots(hosts)
    assert out["ms_per_step"] == 20.0     # mean of the two reporters
    # all-NaN stays NaN rather than disappearing
    for h in hosts:
        h["peak_hbm"] = float("nan")
    assert np.isnan(reg.reduce_snapshots(hosts)["peak_hbm"])


def test_aggregate_on_virtual_mesh(decomp):
    """aggregate() runs the real gather path (all_gather_hosts) with the
    8-device CPU mesh live; single-process it must equal the local
    snapshot."""
    from pystella_tpu.parallel.multihost import all_gather_hosts
    stacked = all_gather_hosts([1.0, 2.0, 3.0])
    assert stacked.shape == (1, 3)
    np.testing.assert_array_equal(stacked[0], [1.0, 2.0, 3.0])

    reg = metrics.MetricsRegistry()
    reg.counter("steps").inc(42)
    reg.gauge("rate", reduce="mean").set(3.5)
    assert reg.aggregate() == reg.snapshot()
    assert reg.aggregate()["steps"] == 42.0


def test_default_registry_accessors():
    c = metrics.counter("obs_test_counter")
    c.inc(2)
    assert metrics.registry().snapshot()["obs_test_counter"] == 2.0


# -- trace scopes ----------------------------------------------------------

def test_trace_scope_noop_safety():
    """Scopes must be free of side effects on CPU with no profiler
    attached — eager, jitted, and as a host span."""
    with obs.trace_scope("eager_region"):
        x = jnp.sum(jnp.ones(8))
    assert float(x) == 8.0

    @jax.jit
    def f(x):
        with obs.trace_scope("jit_region"):
            return x * 2

    assert float(f(jnp.float32(3.0))) == 6.0

    def g(x):
        with obs.host_span("host_region"):
            return x + 1

    assert g(1) == 2
    # a span inside someone's jit names no run-time host work: it must
    # neither fail nor leave a row
    with obs.recording() as rows:
        assert float(jax.jit(g)(jnp.float32(1.0))) == 2.0
        assert g(2) == 3
    assert [r[0] for r in rows] == ["host_region"]


def test_fused_step_lowering_has_named_scopes(make_decomp):
    """The acceptance check: a fused step's lowering carries named
    scopes for the RK stage, the halo exchange, and the stencil kernel
    regions (the CPU-lowering stand-in for inspecting a Perfetto
    trace)."""
    decomp = make_decomp((2, 2, 1))
    stepper, state, dt = _small_fused(decomp, n=16)
    lowered = stepper._jit_step.lower(state, 0.0, dt, {})
    assert obs.has_scope(lowered, "rk_stage")       # RK stage region
    assert obs.has_scope(lowered, "halo_exchange")  # ppermute halos
    assert obs.has_scope(lowered, "pallas_stencil")  # stencil kernel


def test_generic_stepper_lowering_has_stage_scopes(make_decomp):
    decomp = make_decomp((1, 1, 1))
    fd = ps.FiniteDifferencer(decomp, 1, (1.0, 1.0, 1.0))

    def rhs(state, t):
        return {"f": state["dfdt"], "dfdt": fd.lap(state["f"])}

    stepper = ps.LowStorageRK54(rhs, dt=0.1)
    rng = np.random.default_rng(3)
    state = {"f": decomp.shard(rng.standard_normal((8, 8, 8))),
             "dfdt": decomp.zeros((8, 8, 8), np.float64)}
    lowered = stepper._jit_step.lower(state, 0.0, 0.1, {})
    assert obs.has_scope(lowered, "rk_stage0")
    assert obs.has_scope(lowered, "rk_stage4")


# -- memory / compile instrumentation --------------------------------------

def test_compile_report_for_fused_kernel(event_log, make_decomp):
    """Memory-analysis capture for one fused kernel: compile seconds and
    the XLA byte counts land in the record and the event log."""
    decomp = make_decomp((1, 1, 1))
    stepper, state, dt = _small_fused(decomp, n=8)
    compiled, rec = obs.compile_with_report(
        stepper._jit_step, state, 0.0, dt, {}, label="fused-8^3")
    assert rec.label == "fused-8^3"
    # the ledger splits Python-side tracing from the backend compile
    # (lumping them misattributes tracing cost to XLA)
    assert rec.trace_seconds > 0
    assert rec.compile_seconds > 0
    assert rec.total_seconds == rec.trace_seconds + rec.compile_seconds
    # an explicit AOT compile carries the full lowered-module fingerprint
    assert rec.fingerprint and rec.fingerprint_kind == "lowered"
    # CPU's memory analysis reports real argument/output byte counts
    state_bytes = 2 * 8**3 * 4
    assert rec.argument_bytes >= state_bytes
    assert rec.output_bytes >= state_bytes
    assert rec.peak_bytes >= state_bytes
    # the compiled executable is directly callable (no second compile)
    out = compiled(state, 0.0, dt, {})
    assert out["f"].shape == (1, 8, 8, 8)

    # instrumented package jits may add source="dispatch" rows; the
    # explicit AOT report is the one labeled event
    evs = [e for e in events.read_events(event_log, kind="compile")
           if e["data"].get("label") == "fused-8^3"]
    assert len(evs) == 1
    assert evs[0]["data"]["source"] == "aot"
    assert evs[0]["data"]["compile_seconds"] == rec.compile_seconds
    assert evs[0]["data"]["trace_seconds"] == rec.trace_seconds
    assert evs[0]["data"]["fingerprint"] == rec.fingerprint
    assert evs[0]["data"]["peak_bytes"] == rec.peak_bytes


def test_device_memory_report_degrades_on_cpu(event_log):
    """CPU devices keep no allocator stats; the report must return None
    without raising or emitting."""
    assert obs.device_memory_report(label="cpu") is None
    assert events.read_events(event_log, kind="device_memory") == []


# -- instrumentation wired through the subsystems --------------------------

def test_health_monitor_emits_diverged_event(event_log):
    mon = ps.HealthMonitor(every=1)
    state = {"f": jnp.ones((4, 4, 4)),
             "dfdt": jnp.full((4, 4, 4), np.nan)}
    with pytest.raises(ps.SimulationDiverged):
        mon(7, state)
    evs = events.read_events(event_log, kind="diverged")
    assert len(evs) == 1
    assert evs[0]["step"] == 7
    assert evs[0]["data"]["fields"] == ["dfdt"]


def test_subscriber_sees_records_and_cannot_break_emit(tmp_path, capsys):
    """``EventLog.subscribe`` is how the benchmark and ``chip_smoke.py``
    read a run's plan events: a subscriber gets every record after the
    write, with or without a file; one that raises is reported once on
    stderr and the emit path goes on."""
    seen = []

    def bad(rec):
        raise RuntimeError("boom")

    path = tmp_path / "ev.jsonl"
    with events.EventLog(str(path)) as log:
        log.subscribe(bad)
        tap = log.subscribe(seen.append)
        log.subscribe(seen.append)  # again: still delivered once
        assert log.emit("unit_test", value=1)["kind"] == "unit_test"
        log.emit("unit_test", value=2)
        log.unsubscribe(tap)
        log.emit("unit_test", value=3)
    assert [r["data"]["value"] for r in seen] == [1, 2]
    assert [r["data"]["value"]
            for r in events.read_events(str(path))] == [1, 2, 3]
    assert capsys.readouterr().err.count("event subscriber") == 1
    # a file-less sink still feeds its taps
    sink = events.EventLog(None)
    sink.subscribe(seen.append)
    assert sink.emit("unit_test", value=4)["data"] == {"value": 4}
    assert seen[-1]["data"]["value"] == 4


def test_subscriber_that_emits_does_not_recurse(tmp_path, capsys):
    """An emit made from a subscriber is written and not pushed again:
    the per-thread guard in ``EventLog._notify``."""
    path = tmp_path / "ev.jsonl"
    seen = []
    with EventLog(str(path)) as log:
        def echo(rec):
            seen.append(rec["kind"])
            log.emit("echo", of=rec["kind"])

        log.subscribe(echo)
        log.emit("unit_test", value=1)
        log.emit("unit_test", value=2)
    assert seen == ["unit_test", "unit_test"]
    assert [r["kind"] for r in events.read_events(str(path))] == [
        "unit_test", "echo", "unit_test", "echo"]
    assert "event subscriber" not in capsys.readouterr().err


def test_step_timer_takes_no_monitor():
    """The step timer times steps: it feeds the registry's ``step``
    timer and the event log, and no detector (PR 45: the ``perf=`` and
    ``signature=`` arguments went with ``obs/perf.py``)."""
    with pytest.raises(TypeError):
        ps.StepTimer(perf=False)
    with pytest.raises(TypeError):
        ps.StepTimer(signature="step")


def test_step_timer_feeds_metrics_and_events(event_log):
    st = ps.StepTimer(report_every=0.0)
    assert st.tick() is None  # first tick arms the clock
    report = st.tick()
    assert report is not None
    ms, rate = report
    evs = events.read_events(event_log, kind="step_timer")
    assert len(evs) == 1
    assert evs[0]["data"]["ms_per_step"] == ms
    assert metrics.gauge("ms_per_step").value == ms


def test_step_timer_registry_is_the_accumulator(event_log):
    """Satellite: the registry's ``step`` Timer is the one timing store
    — every tick observes the per-step duration there, the window
    report derives from its deltas, and per-step samples are retained
    for the PerfLedger (``step_time`` events with ``emit_steps``)."""
    t = metrics.timer("step")
    count0, total0 = t.count, t.total_s
    st = ps.StepTimer(report_every=1e9, emit_steps=True)
    st.tick()  # arm
    for _ in range(3):
        st.tick()
    assert t.count == count0 + 3  # one observation PER STEP, not window
    assert t.total_s > total0
    assert len(st.samples_ms) == 3
    evs = events.read_events(event_log, kind="step_time")
    assert [e["data"]["ms"] for e in evs] == \
        pytest.approx(list(st.samples_ms))
    # report_every not reached: no window report, no window event
    assert events.read_events(event_log, kind="step_timer") == []


def test_fused_step_counter(make_decomp):
    decomp = make_decomp((1, 1, 1))
    stepper, state, dt = _small_fused(decomp, n=8)
    before = metrics.counter("steps").value
    state = stepper.step(state, 0.0, dt, {"a": 1.0, "hubble": 0.0})
    jax.block_until_ready(state)
    assert metrics.counter("steps").value == before + 1


def test_multigrid_unknown_kwargs_raise(make_decomp):
    """Satellite: a misspelled FullApproximationScheme kwarg (e.g.
    ``defer_error=``) must raise, not be silently swallowed."""
    from pystella_tpu.multigrid import (
        FullApproximationScheme, NewtonIterator)
    decomp = make_decomp((1, 1, 1))
    f = ps.Field("f")
    solver = NewtonIterator(
        decomp, {f: (ps.Field("lap_f") - f, ps.Field("rho"))},
        halo_shape=1)
    with pytest.raises(TypeError, match="defer_error"):
        FullApproximationScheme(solver=solver, halo_shape=1,
                                defer_error=True)
    # the documented spelling still works
    FullApproximationScheme(solver=solver, halo_shape=1,
                            defer_errors=False)


def test_multigrid_cycle_emits_event(event_log, make_decomp):
    """One tiny FAS V-cycle logs an mg_cycle event with final errors and
    bumps the cycle counters."""
    from pystella_tpu.multigrid import (
        FullApproximationScheme, NewtonIterator, v_cycle)
    decomp = make_decomp((1, 1, 1))
    dtype = np.float64
    n = 16
    f = ps.Field("f")
    solver = NewtonIterator(
        decomp, {f: (ps.Field("lap_f") - f, ps.Field("rho"))},
        halo_shape=1, omega=2 / 3, dtype=dtype)
    mg = FullApproximationScheme(solver=solver, halo_shape=1)
    rng = np.random.default_rng(5)
    rho_np = rng.standard_normal((n, n, n)).astype(dtype)
    rho = decomp.shard(rho_np - rho_np.mean())
    before = metrics.counter("mg_cycles").value
    errors, sol = mg(decomp, dx0=1.0, cycle=v_cycle(2, 2, 1),
                     f=decomp.zeros((n, n, n), dtype), rho=rho)
    assert metrics.counter("mg_cycles").value == before + 1
    evs = events.read_events(event_log, kind="mg_cycle")
    assert len(evs) == 1
    assert evs[0]["data"]["grid_shape"] == [n, n, n]
    assert "f" in evs[0]["data"]["final_errors"]
