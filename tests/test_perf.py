"""Continuous-performance plane tests (PR 17): the mergeable
step-time quantile digest (accuracy, merge associativity, the
cross-host merge path), the robust CUSUM change-point detector
(constant series stays quiet, a single spike cannot fire, a sustained
shift fires and recovers, short windows guard), single-host straggler
attribution, flight-recorder rate limiting (at most one capture per
cooldown, injectable tracer + clock), the SLO ``perf_regression``
routing, StepTimer / default-monitor integration, the ledger ``perf``
section, the gate's perf-anomaly consistency audit, and the seeded
``loadgen.run_perf`` drill end to end through ledger + gate — the
PR's acceptance pin."""

import copy
import os
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

from pystella_tpu import obs
from pystella_tpu.obs import events, gate, metrics, slo, stragglers
from pystella_tpu.obs.ledger import PerfLedger
from pystella_tpu.obs.ledger import render_markdown as ledger_markdown
from pystella_tpu.obs.perf import (
    CusumDetector, Digest, FlightRecorder, PerfMonitor)
from pystella_tpu.obs import perf as perfmod
from pystella_tpu.service import loadgen
from pystella_tpu.utils.profiling import StepTimer


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure(path)
    yield path
    obs.configure(None)


# -- digest ----------------------------------------------------------------

def test_digest_empty_short_and_quantile_accuracy():
    d = Digest()
    # empty digest: every quantile is None, summary reports nothing
    assert d.quantile(50) is None and d.mean() is None
    assert d.summary()["count"] == 0
    # a single sample IS every quantile (within bin resolution)
    d.add(10.0)
    assert abs(d.quantile(50) - 10.0) / 10.0 < 0.05
    # log-spaced bins hold ~4-5% relative quantile error across the
    # whole dynamic range
    d2 = Digest()
    rng = np.random.default_rng(7)
    samples = np.sort(rng.uniform(1.0, 100.0, size=4000))
    for s in samples:
        d2.add(float(s))
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q))
        est = d2.quantile(q)
        assert abs(est - exact) / exact < 0.05, (q, est, exact)
    assert abs(d2.mean() - samples.mean()) / samples.mean() < 1e-6
    # out-of-range samples clamp into the edge bins, never crash
    d2.add(0.0)
    d2.add(1e9)
    assert d2.count == 4002


def test_digest_merge_associative_and_roundtrip():
    """Summing counts IS the merge — so merge is associative and
    commutative, which is what lets hosts be summed in any gather
    order."""
    rng = np.random.default_rng(3)
    parts = []
    for _ in range(3):
        d = Digest()
        for s in rng.uniform(0.5, 50.0, size=300):
            d.add(float(s))
        parts.append(d)
    a, b, c = parts
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert left.counts == right.counts
    assert left.count == right.count == 900
    assert abs(left.total_ms - right.total_ms) < 1e-9
    # merge does not mutate its operands
    assert a.count == 300
    # commutativity
    assert b.merge(a).counts == a.merge(b).counts
    # from_counts round-trips the wire format merge_across_hosts uses
    rt = Digest.from_counts(left.counts, total_ms=left.total_ms)
    assert rt.counts == left.counts
    assert rt.quantile(95) == left.quantile(95)
    # incompatible geometries refuse to merge
    with pytest.raises(ValueError):
        a.merge(Digest(bins=16))


def test_digest_merge_across_hosts_single_process():
    """On one host the federated digest is the local one — the
    all_gather degenerates to identity."""
    d = Digest()
    for s in (1.0, 2.0, 3.0, 4.0):
        d.add(s)
    merged = perfmod.merge_across_hosts(d)
    assert merged.counts == d.counts
    assert merged.count == 4
    assert abs(merged.total_ms - d.total_ms) < 1e-9


# -- change-point detector -------------------------------------------------

def test_detector_constant_series_stays_quiet():
    """MAD of a constant series is 0 — the relative sigma floor keeps
    a usable band, so neither the constant run nor its first tiny
    jitter pages."""
    det = CusumDetector(window=32, min_samples=8, k=1.0, h=8.0)
    for _ in range(200):
        assert det.update(5.0) is None
    assert det.state()["anomalous"] is False
    assert det.state()["fires"] == 0
    # a one-off 10% wiggle on the constant baseline: still quiet
    assert det.update(5.5) is None
    assert det.cusum < det.h


def test_detector_below_min_samples_never_fires():
    det = CusumDetector(window=32, min_samples=16, k=1.0, h=8.0)
    # even absurd samples can't fire before the baseline exists
    for _ in range(15):
        assert det.update(1e6) is None
    assert det.state()["anomalous"] is False
    assert det.state()["baseline_ms"] is None


def test_detector_spike_vs_sustained_shift_and_recovery():
    rng = np.random.default_rng(11)
    det = CusumDetector(window=16, min_samples=8, k=1.0, h=8.0,
                        clip=4.0, recover_n=4)

    def healthy():
        return 5.0 + float(rng.uniform(0.0, 0.2))

    for _ in range(30):
        assert det.update(healthy()) is None
    # a single 10x spike contributes at most `clip` sigmas — no fire
    assert det.update(50.0) is None
    assert det.state()["anomalous"] is False
    # drain the spike's partial accumulation with healthy samples
    for _ in range(10):
        det.update(healthy())
    assert det.cusum < det.h
    # a sustained 5x shift MUST fire within ceil(h/clip)=2..3 samples
    transitions = [det.update(25.0) for _ in range(5)]
    assert "fired" in transitions
    st = det.state()
    assert st["anomalous"] is True and st["fires"] == 1
    # the reference window froze: the open anomaly cannot absorb the
    # regression it is reporting
    assert st["baseline_ms"] < 10.0
    # recovery: recover_n consecutive samples back inside the band
    transitions = [det.update(healthy()) for _ in range(8)]
    assert "recovered" in transitions
    st = det.state()
    assert st["anomalous"] is False and st["recoveries"] == 1
    assert det.cusum == 0.0
    # and it can fire again (flap counting upstream relies on this)
    assert "fired" in [det.update(25.0) for _ in range(5)]


# -- straggler attribution -------------------------------------------------

def test_straggler_single_host_degrades_to_one_row():
    att = stragglers.attribute([5.0, 5.1, 4.9])
    assert att["hosts"] == 1
    assert att["skewed"] is False
    assert att["skew"] == 1.0
    assert att["slowest"]["host"] == 0
    assert abs(att["slowest"]["mean_ms"] - att["median_ms"]) < 1e-9
    # empty window: nothing to attribute
    assert stragglers.attribute([]) is None


# -- flight recorder -------------------------------------------------------

class _StubTracer:
    """Injectable start/stop backend: records calls, fabricates an
    artifact path, optionally fails on start."""

    def __init__(self, fail_start=False):
        self.started = []
        self.stopped = []
        self.fail_start = fail_start

    def start(self, logdir):
        if self.fail_start:
            raise RuntimeError("profiler unavailable")
        os.makedirs(logdir, exist_ok=True)
        self.started.append(logdir)

    def stop(self, logdir):
        self.stopped.append(logdir)
        return os.path.join(logdir, "trace.json.gz")


def test_flight_recorder_rate_limit_one_per_cooldown(tmp_path,
                                                     event_log):
    clk = [0.0]
    tracer = _StubTracer()
    rec = FlightRecorder(str(tmp_path / "caps"), steps=3,
                         cooldown_s=100.0, tracer=tracer,
                         clock=lambda: clk[0])
    assert rec.request("sig") is True
    # a second request while one is ACTIVE is refused outright
    assert rec.request("sig") is False
    for _ in range(3):
        rec.tick()
    assert len(rec.captures) == 1
    assert rec.captures[0]["artifact"].endswith("trace.json.gz")
    assert rec.captures[0]["steps"] == 3
    # inside the cooldown: suppressed, counted, no second trace
    clk[0] = 50.0
    assert rec.request("sig") is False
    assert rec.suppressed == 1 and len(tracer.started) == 1
    # cooldown elapsed: the next anomaly may capture again
    clk[0] = 150.0
    assert rec.request("sig") is True
    rec.flush()
    assert len(rec.captures) == 2
    assert rec.captures[1]["suppressed"] == 1
    # the capture events landed in the log
    kinds = [r["kind"] for r in events.read_events(event_log)]
    assert kinds.count("perf_capture") == 2


def test_flight_recorder_disabled_and_error_degrade(tmp_path,
                                                    event_log):
    # logdir=None disables capturing entirely
    off = FlightRecorder(None, steps=2, cooldown_s=0.0,
                         tracer=_StubTracer())
    assert off.request("sig") is False
    assert off.state()["enabled"] is False
    # a failing profiler start degrades to telemetry, never raises
    rec = FlightRecorder(str(tmp_path / "caps"), steps=2,
                         cooldown_s=0.0,
                         tracer=_StubTracer(fail_start=True))
    assert rec.request("sig") is False
    assert rec.errors == 1 and rec.captures == []
    recs = [r["data"] for r in events.read_events(event_log)
            if r["kind"] == "perf_capture"]
    assert recs and recs[-1]["artifact"] is None
    assert "profiler unavailable" in recs[-1]["error"]


# -- monitor: metrics, events, SLO routing, StepTimer feed -----------------

def _quiet_monitor(**kw):
    kw.setdefault("recorder", FlightRecorder(None))
    kw.setdefault("metrics", metrics.MetricsRegistry())
    kw.setdefault("window", 16)
    kw.setdefault("min_samples", 8)
    kw.setdefault("k", 1.0)
    kw.setdefault("h", 8.0)
    kw.setdefault("recover_n", 4)
    return PerfMonitor(**kw)


def test_monitor_gauges_and_state(event_log):
    reg = metrics.MetricsRegistry()
    mon = _quiet_monitor(metrics=reg, digest_every=0)
    for _ in range(20):
        mon.observe("stepper", 5.0)
    snap = reg.snapshot()
    assert abs(snap["perf.stepper.p50_ms"] - 5.0) / 5.0 < 0.05
    assert snap["perf.stepper.anomalous"] == 0.0
    st = mon.state()
    assert st["signatures"]["stepper"]["count"] == 20
    assert st["anomalous"] == []
    assert st["observed"] == 20 and st["observe_s"] > 0.0
    # sustained shift flips the anomalous gauge and counts the fire
    for _ in range(4):
        mon.observe("stepper", 25.0)
    assert reg.snapshot()["perf.stepper.anomalous"] == 1.0
    assert reg.snapshot()["perf.anomalies"] == 1.0
    assert mon.state()["anomalous"] == ["stepper"]


def test_monitor_events_route_into_slo_leg(event_log):
    """perf_anomaly / perf_recovered land as 1.0 / 0.0 samples on the
    ``perf_regression`` burn leg — fire and resolve are deterministic
    with a one-sample window, the deadline_miss pattern."""
    mon = _quiet_monitor()
    sm = slo.SLOMonitor(legs={
        "perf_regression": {"window_samples": 1, "min_samples": 1},
    })
    events.get_log().subscribe(sm.handle)
    try:
        for _ in range(20):
            mon.observe("drill", 5.0)
        for _ in range(4):
            mon.observe("drill", 25.0)
        sm.evaluate()
        assert "perf_regression" in sm.state()["alerting"]
        for _ in range(8):
            mon.observe("drill", 5.0)
        sm.evaluate()
    finally:
        events.get_log().unsubscribe(sm.handle)
    st = sm.state()
    assert st["alerting"] == []
    assert st["alerts_total"] == 1 and st["resolved_total"] == 1
    kinds = [r["kind"] for r in events.read_events(event_log)]
    assert "perf_anomaly" in kinds and "perf_recovered" in kinds
    assert "slo_alert" in kinds and "slo_resolved" in kinds
    # the anomaly payload carries attribution + quantiles
    anom = [r["data"] for r in events.read_events(event_log)
            if r["kind"] == "perf_anomaly"][0]
    assert anom["straggler"]["hosts"] == 1
    assert anom["baseline_ms"] < anom["ms"]
    assert anom["p50_ms"] is not None


def test_step_timer_feeds_monitor_and_min_over_rounds(event_log):
    mon = _quiet_monitor()
    timer = StepTimer(report_every=1e9, signature="tick",
                      perf=mon)
    for _ in range(5):
        timer.tick()
    # tick N+1 times -> N inter-step samples
    assert mon.state()["signatures"]["tick"]["count"] == 4
    # perf=False opts a timer out of the plane entirely
    mon2 = _quiet_monitor()
    t2 = StepTimer(report_every=1e9, perf=False)
    for _ in range(3):
        t2.tick()
    assert mon2.state()["signatures"] == {}
    # the timer() micro-benchmark grew the paired min-estimator
    from pystella_tpu.utils.profiling import timer as bench_timer
    calls = []

    def kernel():
        calls.append(1)

    dt = bench_timer(kernel, ntime=3, nwarmup=1, reps=1,
                     min_over_rounds=4)
    assert dt > 0.0
    # warmup runs once; the R rounds each re-time ntime calls
    assert len(calls) == 1 + 4 * 3


def test_module_observe_gated_by_env(monkeypatch, event_log):
    perfmod._reset_default()
    monkeypatch.setenv("PYSTELLA_PERF", "0")
    assert perfmod.enabled() is False
    assert perfmod.observe("sig", 5.0) is None
    assert perfmod._default is None      # never constructed when off
    monkeypatch.setenv("PYSTELLA_PERF", "1")
    assert perfmod.enabled() is True
    perfmod.observe("sig", 5.0)
    assert perfmod._default is not None
    assert perfmod.default_monitor().observed == 1
    perfmod._reset_default()


# -- ledger + gate ---------------------------------------------------------

def _minimal_report(**extra):
    rep = {"steps": {"count": 16, "p50_ms": 1.0, "mad_ms": 0.0},
           "samples_ms": [1.0] * 16, "env": {"platform": "cpu"}}
    rep.update(extra)
    return rep


def _perf_section(unresolved=(), alerts=1, resolved=1, captures=1):
    return {
        "anomalies": {"alerts": alerts, "resolved": resolved,
                      "flaps": 0, "unresolved": list(unresolved),
                      "by_leg": {}},
        "digests": {"drill": {"count": 64, "p50_ms": 5.0,
                              "p95_ms": 5.2, "p99_ms": 25.0}},
        "captures": [{"signature": "drill", "reason": "perf_anomaly",
                      "artifact": "/tmp/t/trace.json.gz",
                      "steps": 4}] * captures,
        "captures_suppressed": 0,
        "straggler": {"hosts": 1, "skew": 1.0, "skewed": False},
    }


def test_gate_unresolved_anomaly_green_steps_refuses():
    open_anom = {"leg": "drill", "since_ts": 1.0, "value": 25.0,
                 "bar": 5.0}
    base = _minimal_report()
    cur = _minimal_report(perf=_perf_section(unresolved=[open_anom],
                                             resolved=0))
    v = gate.compare_reports(base, cur)
    assert v["exit_code"] == 2 and v["ok"] is False
    assert any("invalid_evidence" in r and "change-point detector" in r
               for r in v["reasons"])
    # --no-perf opts out
    assert gate.compare_reports(base, cur,
                                check_perf=False)["exit_code"] == 0
    # resolved anomalies pass clean and surface in the verdict
    v = gate.compare_reports(base, _minimal_report(perf=_perf_section()))
    assert v["exit_code"] == 0
    assert v["perf"] == {"anomalies": 1, "recovered": 1, "flaps": 0,
                         "unresolved": 0, "captures": 1}


def test_gate_unresolved_anomaly_corroborates_failed_steps():
    """When the post-hoc median comparison ALSO failed, the open
    anomaly corroborates — exit stays 1, no refusal."""
    open_anom = {"leg": "drill", "since_ts": 1.0, "value": 25.0,
                 "bar": 5.0}
    base = _minimal_report()
    cur = {"steps": {"count": 16, "p50_ms": 10.0, "mad_ms": 0.0},
           "samples_ms": [10.0] * 16, "env": {"platform": "cpu"},
           "perf": _perf_section(unresolved=[open_anom], resolved=0)}
    v = gate.compare_reports(base, cur)
    assert v["exit_code"] == 1
    assert any("median step time" in r for r in v["reasons"])
    assert not any("invalid_evidence: perf" in r for r in v["reasons"])
    assert any("corroborates" in w for w in v["warnings"])


def test_gate_perf_warnings_never_fail():
    base = _minimal_report(perf=_perf_section())
    # anomalies with no capture recorded: warn (capture dir unset)
    v = gate.compare_reports(base,
                             _minimal_report(perf=_perf_section(
                                 captures=0)))
    assert v["exit_code"] == 0
    assert any("no flight-recorder capture" in w for w in v["warnings"])
    # flap growth vs the baseline: warn
    flappy = _perf_section(alerts=4, resolved=4)
    flappy["anomalies"]["flaps"] = 3
    v = gate.compare_reports(base, _minimal_report(perf=flappy))
    assert v["exit_code"] == 0
    assert any("flap" in w for w in v["warnings"])
    # lost perf coverage: warn
    v = gate.compare_reports(base, _minimal_report())
    assert v["exit_code"] == 0
    assert any("change-point coverage was lost" in w
               for w in v["warnings"])
    # and a report with NO perf section against a baseline without one
    # stays silent
    v = gate.compare_reports(_minimal_report(), _minimal_report())
    assert not any("perf" in w for w in v["warnings"])


def test_ledger_perf_section_from_events(tmp_path, event_log):
    mon = _quiet_monitor(
        recorder=FlightRecorder(str(tmp_path / "caps"), steps=2,
                                cooldown_s=3600.0,
                                tracer=_StubTracer()),
        digest_every=16)
    for _ in range(20):
        mon.observe("drill", 5.0)
    for _ in range(4):
        mon.observe("drill", 25.0)
    for _ in range(8):
        mon.observe("drill", 5.0)
    mon.recorder.flush()
    led = PerfLedger.from_events(event_log, label="perf-unit")
    pf = led.perf()
    assert pf["anomalies"]["alerts"] == 1
    assert pf["anomalies"]["resolved"] == 1
    assert pf["anomalies"]["unresolved"] == []
    assert pf["digests"]["drill"]["count"] >= 16
    assert len(pf["captures"]) == 1
    assert pf["captures"][0]["artifact"].endswith("trace.json.gz")
    assert pf["straggler"]["hosts"] == 1
    rep = led.report()
    assert rep["perf"] == pf
    md = ledger_markdown(rep)
    assert "Continuous performance" in md
    assert "trace.json.gz" in md


# -- the seeded drill, end to end ------------------------------------------

def test_perf_drill_through_ledger_and_gate(tmp_path, event_log):
    """The acceptance pin: injected slowdown -> perf_anomaly (with
    straggler attribution) -> exactly one rate-limited real
    jax.profiler capture linked from the ledger's perf section ->
    perf_recovered -> the gate passes the honest record and refuses
    the same record doctored to leave the anomaly unresolved. The drill
    runs on an injected clock: its step times are its schedule, not
    what real sleeps come to beside five other xdist workers (it failed
    one run in four that way)."""
    events.emit("run_start", label="perf-drill-test")
    stats = loadgen.run_perf(str(tmp_path / "caps"),
                             clock=loadgen.VirtualClock())
    assert stats["ok"] is True, stats
    assert stats["anomalies"] >= 2
    assert stats["recovered"] == stats["anomalies"]
    assert stats["captures"] == 1 and stats["suppressed"] >= 1
    assert stats["artifact"] and os.path.exists(stats["artifact"])
    assert stats["straggler"]["hosts"] == 1
    assert stats["slo"]["alerts"] >= 1 and stats["slo"]["alerting"] == []

    kinds = [r["kind"] for r in events.read_events(event_log)]
    assert kinds.count("perf_capture") == 1
    assert kinds.count("perf_anomaly") == stats["anomalies"]
    assert kinds.count("perf_recovered") == stats["recovered"]
    assert "perf_loadgen" in kinds and "step_time" in kinds

    led = PerfLedger.from_events(event_log, label="perf-drill-test")
    rep = led.report()
    pf = rep["perf"]
    assert pf["anomalies"]["unresolved"] == []
    assert pf["captures"][0]["artifact"] == stats["artifact"]

    # the gate passes the honest record (contamination check off: the
    # drill's bimodal sleep schedule IS a contamination signature)
    v = gate.compare_reports(rep, rep, check_contamination="never")
    assert v["ok"] is True, v
    assert v["perf"]["unresolved"] == 0
    assert v["perf"]["captures"] == 1

    # ...and refuses the doctored one claiming green step times while
    # an anomaly was left open
    doctored = copy.deepcopy(rep)
    doctored["perf"]["anomalies"]["unresolved"] = [
        {"leg": "drill", "since_ts": 1.0, "value": 25.0, "bar": 5.0}]
    v = gate.compare_reports(rep, doctored,
                             check_contamination="never")
    assert v["ok"] is False and v["exit_code"] == 2
    assert any("invalid_evidence" in r for r in v["reasons"])


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
