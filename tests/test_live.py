"""Live operations plane tests (PR 14): EventLog subscriber hook
hardening (error degradation, rotation survival, byte-identical
off-path), thread-consistent MetricsRegistry snapshots under a
concurrent scrape, SLO burn-rate monitor fire/resolve semantics, the
``PYSTELLA_LIVE_PORT`` endpoint (``/metrics`` Prometheus parity with
the ledger's ingested figures, ``/healthz``, ``/slo``), the
``status --follow`` live tail, and the gate's unresolved-alert /
green-SLO refusal."""

import json
import os
import sys
import threading
import time as _time
import urllib.request

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.obs import events, gate, live, metrics, slo
from pystella_tpu.obs.events import EventLog, rotated_family
from pystella_tpu.obs.ledger import PerfLedger
from pystella_tpu.service import (
    FairShareScheduler, ScenarioRequest, ScenarioService,
    request_signature)
from pystella_tpu.service import __main__ as service_cli

GRID = (8, 8, 8)
SIG = request_signature("toy", GRID)


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure(path)
    yield path
    obs.configure(None)


def _toy_builder(grid_shape, decomp=None):
    """The same tiny roll-based Klein-Gordon system test_service uses:
    fast to trace/compile, deterministic sampler."""
    dt = 0.05

    def rhs(state, t, m2):
        f = state["f"]
        lap = sum(jnp.roll(f, 1, i) + jnp.roll(f, -1, i) - 2 * f
                  for i in (-3, -2, -1))
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


def _scrape(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.read().decode()


def _parse_prom(text):
    out = {}
    for ln in text.splitlines():
        if ln.startswith("#") or " " not in ln:
            continue
        name, _, val = ln.rpartition(" ")
        try:
            out[name] = float(val)
        except ValueError:
            pass
    return out


# -- EventLog subscriber hook ------------------------------------------------

def test_subscriber_push_and_error_degradation(event_log):
    log = events.get_log()
    seen = []

    def bad(rec):
        raise RuntimeError("boom")

    log.subscribe(seen.append)
    log.subscribe(bad)
    try:
        events.emit("unit_test", x=1)
        events.emit("unit_test", x=2)
    finally:
        log.unsubscribe(bad)
        log.unsubscribe(seen.append)
    # the emit path survived and both records flowed to the good
    # subscriber AND the file
    assert [r["data"]["x"] for r in seen
            if r["kind"] == "unit_test"] == [1, 2]
    assert len(events.read_events(event_log, kind="unit_test")) == 2
    # the raising subscriber degraded to ONE obs_subscriber_error
    errs = events.read_events(event_log, kind="obs_subscriber_error")
    assert len(errs) == 1
    assert "boom" in errs[0]["data"]["error"]


def test_subscriber_works_on_disabled_sink():
    log = EventLog(None)
    seen = []
    log.subscribe(seen.append)
    rec = log.emit("unit_test", x=3)
    assert rec is not None and seen == [rec]
    log.unsubscribe(seen.append)
    # back to the cheap no-op contract
    assert log.emit("unit_test", x=4) is None


def test_subscribers_survive_rotation(tmp_path):
    """The rotation-straddling pin: a subscriber registered before a
    size-triggered rollover keeps receiving every record emitted after
    it (subscribers hang off the log object, not the file handle)."""
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


def test_live_plane_off_is_byte_identical(tmp_path, monkeypatch):
    """PYSTELLA_LIVE_PORT=0 / no subscribers: the emit path must write
    byte-identical v2 records to a build without the live plane —
    pinned against a literal, and against a log whose subscriber
    machinery was exercised and detached."""
    monkeypatch.setattr(_time, "time", lambda: 1234.5)
    monkeypatch.setattr(_time, "monotonic", lambda: 777.25)
    plain = tmp_path / "plain.jsonl"
    with EventLog(str(plain)) as log:
        log.emit("unit_test", step=1, x=1)
    exercised = tmp_path / "exercised.jsonl"
    with EventLog(str(exercised)) as log:
        fn = log.subscribe(lambda rec: None)
        log.unsubscribe(fn)
        log.emit("unit_test", step=1, x=1)
    assert plain.read_bytes() == exercised.read_bytes()
    assert plain.read_bytes() == (
        b'{"v": 2, "ts": 1234.5, "mono": 777.25, "host": 0, '
        b'"kind": "unit_test", "step": 1, "data": {"x": 1}}\n')


# -- MetricsRegistry thread-safety pin ---------------------------------------

def test_snapshot_consistent_under_concurrent_updates():
    """A scrape racing the serve loop's timer updates must return a
    consistent snapshot — never a Timer between its count bump and its
    total accumulation. observe(1.0) keeps total_s == count exactly
    (1.0 sums without rounding), so any torn read is detectable."""
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


# -- SLO burn-rate monitor ---------------------------------------------------

def test_slo_fire_resolve_and_flap(event_log):
    mon = slo.SLOMonitor(
        legs={"deadline_miss": {"window_samples": 1, "min_samples": 1}},
        label="unit")

    def verdictev(ts, missed):
        return {"kind": "member_result", "ts": ts,
                "data": {"deadline_missed": missed}}

    mon.handle(verdictev(100.0, True))
    st = mon.state()
    assert st["alerting"] == ["deadline_miss"]
    assert st["legs"]["deadline_miss"]["alerts"] == 1
    mon.handle(verdictev(101.0, False))
    st = mon.state()
    assert st["alerting"] == []
    assert st["resolved_total"] == 1 and st["flaps_total"] == 0
    # a re-fire is a flap
    mon.handle(verdictev(102.0, True))
    assert mon.state()["flaps_total"] == 1
    # both transitions landed as registered events
    assert len(events.read_events(event_log, kind="slo_alert")) == 2
    assert len(events.read_events(event_log, kind="slo_resolved")) == 1
    resolved = events.read_events(event_log, kind="slo_resolved")[0]
    assert resolved["data"]["leg"] == "deadline_miss"
    assert resolved["data"]["duration_s"] == pytest.approx(1.0)


def test_slo_multiwindow_breach_and_aging(event_log):
    """The fast/slow rule: a breach must hold over both windows to
    fire, and resolution happens when the offending samples age out of
    the fast window."""
    mon = slo.SLOMonitor(legs={"queue_p95": {}}, fast_window_s=60,
                         slow_window_s=300, min_samples=1)

    def dispatch(ts, q):
        return {"kind": "service_dispatch", "ts": ts,
                "data": {"queue_latency_s": q}}

    # bar = max(0 * 2.5, 0 + 0.5) = 0.5 s
    assert mon.state()["legs"]["queue_p95"]["bar"] == 0.5
    mon.handle(dispatch(1000.0, 2.0))
    assert mon.state()["alerting"] == ["queue_p95"]
    # a fast sample inside the window does not resolve (p95 still high)
    mon.handle(dispatch(1010.0, 0.01))
    assert mon.state()["alerting"] == ["queue_p95"]
    # 120 s later the slow sample left the fast window: p95 of the
    # fast window is now the compliant sample -> resolved
    mon.handle(dispatch(1120.0, 0.01))
    assert mon.state()["alerting"] == []
    # incident leg: bar 0, any detected fault burns, aging resolves
    mon2 = slo.SLOMonitor(legs={"incident_rate": {}}, fast_window_s=60,
                          slow_window_s=60)
    mon2.handle({"kind": "fault_detected", "ts": 50.0, "data": {}})
    assert mon2.state()["alerting"] == ["incident_rate"]
    assert mon2.evaluate(now=200.0) == [("incident_rate", "resolved")]


def test_slo_min_samples_guard():
    mon = slo.SLOMonitor(legs={"queue_p95": {"min_samples": 3}},
                         fast_window_s=60, slow_window_s=300)
    for i in range(2):
        mon.handle({"kind": "service_dispatch", "ts": 100.0 + i,
                    "data": {"queue_latency_s": 5.0}})
    assert mon.state()["alerting"] == []  # not enough samples yet
    mon.handle({"kind": "service_dispatch", "ts": 103.0,
                "data": {"queue_latency_s": 5.0}})
    assert mon.state()["alerting"] == ["queue_p95"]


# -- the live endpoint -------------------------------------------------------

def test_live_endpoints_scrape_parity(tmp_path, event_log):
    """The tentpole e2e: serve a small mix with the endpoint up, scrape
    /metrics mid-run AND after the last lease, and pin the scraped
    service counters equal to the ledger's ingested figures."""
    base = dict(metrics.registry().snapshot())
    monitor = slo.SLOMonitor(label="live-test")
    svc = _make_service(tmp_path)
    svc.arm(SIG)
    for seed, tenant in enumerate(("a", "b", "a")):
        svc.submit(ScenarioRequest(tenant, SIG, 4, seed=seed))
    server = live.LiveServer(service=svc, slo=monitor)
    server.start()
    mid = {}
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                mid["metrics"] = _parse_prom(
                    _scrape(server.url("/metrics")))
                hz = json.loads(_scrape(server.url("/healthz")))
                mid["healthz"] = hz
                # sticky: the loop being seen serving ONCE is the
                # contract; a last poll racing serve()'s return on a
                # loaded box must not clobber it with serving=False.
                if hz.get("serving"):
                    mid["served"] = True
                mid["n"] = mid.get("n", 0) + 1
            except OSError:
                pass
            stop.wait(0.05)

    thread = threading.Thread(target=scraper, daemon=True)
    thread.start()
    try:
        events.get_log().subscribe(monitor.handle)
        try:
            svc.serve()
        finally:
            events.get_log().unsubscribe(monitor.handle)
    finally:
        stop.set()
        thread.join(timeout=10)
    assert mid.get("n", 0) >= 1, "no successful mid-run scrape"

    # the final scrape (server still up, loop done) vs the ledger
    final = _parse_prom(_scrape(server.url("/metrics")))
    healthz = json.loads(_scrape(server.url("/healthz")))
    slo_state = json.loads(_scrape(server.url("/slo")))
    server.close()

    led = PerfLedger.from_events(event_log)

    def delta(key):
        return final[f"pystella_{key.replace('.', '_')}"] \
            - base.get(key, 0.0)

    assert delta("service.dispatches") == len(led.service_dispatches)
    assert delta("service.leases") == len(led.service_leases)
    assert delta("service.completed") == len(
        [r for r in led.service_results
         if r.get("status") == "completed"])
    assert delta("service.submitted") == led.service_done["submitted"]
    # service gauges are rendered with labels
    assert final["pystella_service_queue_depth"] == 0.0
    assert final['pystella_service_warm_pool_entries{fingerprint="ok"}'] \
        == 1.0
    assert final["pystella_service_last_chunk_member_steps_per_s"] > 0
    # healthz: the loop has finished -> alive but not ready
    assert healthz["ok"] is True and healthz["serving"] is False
    assert healthz["queue_depth"] == 0
    # /slo carries every default leg
    assert slo_state["enabled"] is True
    assert set(slo_state["legs"]) == set(slo.DEFAULT_LEGS)
    # a mid-run scrape saw the loop serving
    assert mid.get("served") is True


def test_serve_wires_live_plane_from_env(tmp_path, event_log,
                                         monkeypatch):
    """PYSTELLA_LIVE_PORT alone brings the endpoint + a default SLO
    monitor up for the duration of serve() and tears both down after;
    the run record carries the live_serve event."""
    import socket
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    monkeypatch.setenv("PYSTELLA_LIVE_PORT", str(port))
    svc = _make_service(tmp_path)
    svc.arm(SIG)
    svc.submit(ScenarioRequest("a", SIG, 8, seed=1))
    got = {}
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                got["healthz"] = json.loads(_scrape(
                    f"http://127.0.0.1:{port}/healthz"))
                got["slo"] = json.loads(_scrape(
                    f"http://127.0.0.1:{port}/slo"))
            except OSError:
                pass
            stop.wait(0.02)

    thread = threading.Thread(target=scraper, daemon=True)
    thread.start()
    try:
        svc.serve()
    finally:
        stop.set()
        thread.join(timeout=10)
    assert got.get("healthz", {}).get("serving") is True
    assert got.get("slo", {}).get("enabled") is True
    assert svc.slo is not None  # the default monitor was built
    assert svc.live_server is None  # ...and torn down with the loop
    evs = events.read_events(event_log, kind="live_serve")
    assert len(evs) == 1 and evs[0]["data"]["port"] == port
    # the port is released: serving again rebinds cleanly
    svc.submit(ScenarioRequest("a", SIG, 4, seed=2))
    svc.serve()
    assert len(events.read_events(event_log, kind="live_serve")) == 2


def test_prometheus_label_escaping_and_readiness_probe():
    """Tenant names are arbitrary caller strings: label values must be
    escaped per the text format (a quote/newline must not break or
    inject into the exposition); /healthz?ready keys the status code
    on readiness while bare /healthz stays a 200 liveness probe."""
    status = {"queue_depth": 1, "queue_by_priority": {"1": 1},
              "queue_by_tenant": {'acme"corp\n': 1},
              "active_leases": 0, "warm_pool": {"ok": 0, "stale": 0},
              "last_chunk_member_steps_per_s": None, "serving": False}
    text = live.render_prometheus(
        registry=metrics.MetricsRegistry(), status=status)
    assert '{tenant="acme\\"corp\\n"}' in text
    assert all(ln.startswith(("#", "pystella_"))
               for ln in text.splitlines() if ln)
    # the build-info gauge: constant 1, its LABELS are the payload —
    # the fleet aggregator's skew key reads straight off the exposition
    info = [ln for ln in text.splitlines()
            if ln.startswith("pystella_build_info{")]
    assert len(info) == 1 and info[0].endswith(" 1")
    labels = live.build_info_labels()
    assert {"jax", "jaxlib", "libtpu", "flags_fingerprint",
            "device_kind"} <= set(labels)
    for key in ("jax", "flags_fingerprint", "device_kind"):
        assert f'{key}="' in info[0]

    class _Idle:
        def live_status(self):
            return {"serving": False, "queue_depth": 0}

    import urllib.error
    server = live.LiveServer(service=_Idle())
    server.start()
    try:
        # bare /healthz: alive -> 200 even while not serving
        with urllib.request.urlopen(server.url("/healthz"),
                                    timeout=5) as r:
            assert r.status == 200
            assert json.loads(r.read())["ready"] is False
        # ?ready keys the status code on readiness -> 503 while idle
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(server.url("/healthz?ready"),
                                   timeout=5)
        assert exc.value.code == 503
    finally:
        server.close()


def test_start_from_env_bad_port_degrades(monkeypatch, capsys):
    """An unbindable PYSTELLA_LIVE_PORT (out of range, or in use) must
    degrade to no-endpoint with a warning — live telemetry never kills
    the serving process."""
    monkeypatch.setenv("PYSTELLA_LIVE_PORT", "70000")  # > 65535
    assert live.start_from_env() is None
    assert "cannot bind port 70000" in capsys.readouterr().err


def test_live_status_shape(tmp_path, event_log):
    svc = _make_service(tmp_path)
    svc.arm(SIG)
    svc.submit(ScenarioRequest("a", SIG, 4, seed=1, priority=2))
    svc.submit(ScenarioRequest("b", SIG, 4, seed=2))
    status = svc.live_status()
    assert status["serving"] is False
    assert status["queue_depth"] == 2
    assert status["queue_by_priority"] == {"1": 1, "2": 1}
    assert status["queue_by_tenant"] == {"a": 1, "b": 1}
    assert status["warm_pool"] == {"ok": 1, "stale": 0}
    assert status["active_lease"] is None
    # a stale entry flips the fingerprint split
    entry = svc.pool.get(SIG)
    entry.components = {**entry.components,
                        "versions": {"jax": "0.0.1", "jaxlib": "0.0.1",
                                     "libtpu": None}}
    assert svc.live_status()["warm_pool"] == {"ok": 0, "stale": 1}


# -- status --follow ---------------------------------------------------------

def test_status_follow_offline_fallback(tmp_path, capsys):
    path = str(tmp_path / "ev.jsonl")
    with EventLog(path) as log:
        log.emit("service_request", id=1, tenant="a", signature=SIG,
                 priority=1, nsteps=4, seed=0, deadline_s=None,
                 label="t")
    rc = service_cli.main(["status", "--follow", "--events", path,
                           "--count", "2", "--interval", "0"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2
    assert all("offline: queue 1" in ln for ln in out)


def test_status_follow_polls_live_endpoint(tmp_path, capsys):
    monitor = slo.SLOMonitor(label="follow")
    server = live.LiveServer(slo=monitor)
    server.start()
    try:
        rc = service_cli.main(["status", "--follow", "--url",
                               server.url(""), "--count", "1"])
    finally:
        server.close()
    assert rc == 0
    out = capsys.readouterr().out
    assert "live:" in out and "slo ok" in out


def test_status_follow_no_source_errors(capsys, monkeypatch):
    monkeypatch.delenv("PYSTELLA_EVENT_LOG", raising=False)
    monkeypatch.setenv("PYSTELLA_LIVE_PORT", "0")
    rc = service_cli.main(["status", "--follow", "--count", "1"])
    assert rc == 2


# -- gate: live-alert consistency -------------------------------------------

def _minimal_report(**extra):
    rep = {"steps": {"count": 16, "p50_ms": 1.0, "mad_ms": 0.0},
           "samples_ms": [1.0] * 16, "env": {"platform": "cpu"}}
    rep.update(extra)
    return rep


def test_gate_unresolved_alert_green_slo_refuses():
    burning = {"alerts": 1, "resolved": 0, "flaps": 0,
               "unresolved": [{"leg": "queue_p95", "since_ts": 1.0,
                               "value": 9.0, "bar": 0.5}],
               "by_leg": {}}
    base = _minimal_report()
    cur = _minimal_report(alerts=burning)
    v = gate.compare_reports(base, cur)
    assert v["exit_code"] == 2
    assert any("live burn alert" in r and "claims green" in r
               for r in v["reasons"])
    # --no-alerts opts out
    assert gate.compare_reports(base, cur,
                                check_alerts=False)["exit_code"] == 0


def test_gate_unresolved_alert_with_failed_slo_is_consistent():
    """When the post-hoc queue SLO ALSO failed, the unresolved live
    alert corroborates — exit stays 1, no refusal."""
    svc_base = {"queue_latency_s": {"overall": {"p95_s": 0.1,
                                                "count": 8}},
                "ttfs_s": {}}
    svc_cur = {"queue_latency_s": {"overall": {"p95_s": 30.0,
                                               "count": 8}},
               "ttfs_s": {}}
    burning = {"alerts": 1, "resolved": 0, "flaps": 0,
               "unresolved": [{"leg": "queue_p95", "since_ts": 1.0,
                               "value": 30.0, "bar": 0.5}],
               "by_leg": {}}
    base = _minimal_report(service=svc_base)
    cur = _minimal_report(service=svc_cur, alerts=burning)
    v = gate.compare_reports(base, cur)
    assert v["exit_code"] == 1
    assert any("queue-latency p95" in r for r in v["reasons"])
    assert any("corroborates" in w for w in v["warnings"])


def test_gate_alert_flap_growth_and_coverage():
    resolved = {"alerts": 1, "resolved": 1, "flaps": 0,
                "unresolved": [], "by_leg": {}}
    flappy = {"alerts": 4, "resolved": 4, "flaps": 3,
              "unresolved": [], "by_leg": {}}
    base = _minimal_report(alerts=resolved)
    # resolved alerts pass clean
    v = gate.compare_reports(base, _minimal_report(alerts=resolved))
    assert v["exit_code"] == 0 and v["alerts"]["unresolved"] == 0
    # flap growth warns, never fails
    v = gate.compare_reports(base, _minimal_report(alerts=flappy))
    assert v["exit_code"] == 0
    assert any("flap" in w for w in v["warnings"])
    # lost live-alert coverage warns
    v = gate.compare_reports(base, _minimal_report())
    assert v["exit_code"] == 0
    assert any("live SLO coverage was lost" in w for w in v["warnings"])


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
