"""Fleet observability plane tests (PR 16): the replica registry's
announce/heartbeat/withdraw/expire lifecycle, the Prometheus 0.0.4
exposition round trip (our own /metrics text through our own parser),
FleetAggregator merge semantics (counters sum, gauges stay
per-replica) and SLO sample federation (dedup, fleet-level
fire/resolve), both loss paths (expired heartbeat and
live-but-unreachable), skew + warm-divergence detection, the fleet
ops CLIs, the gate's fleet verdicts on synthetic reports, and the
deterministic two-replica kill drill end-to-end through the ledger's
``fleet`` section and the gate."""

import json
import os
import time

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import pystella_tpu as ps  # noqa: F401
from pystella_tpu import obs
from pystella_tpu.obs import events, fleet, gate, ledger, live, metrics
from pystella_tpu.service import __main__ as service_cli
from pystella_tpu.service import loadgen, registry


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure(path)
    yield path
    obs.configure(None)


def _announce(root, rid, url="http://127.0.0.1:9/", **fields):
    reg = registry.ReplicaRegistry(root, replica_id=rid,
                                   heartbeat_s=0, label=rid)
    reg.announce(url=url, **fields)
    return reg


# -- replica registry --------------------------------------------------------

def test_registry_lifecycle(tmp_path):
    """Announce -> live; heartbeat age past expire_s -> stale; clean
    withdraw -> tombstone; the kill seam leaves NO tombstone (a crash
    cannot clean up), and withdraw after kill is a no-op."""
    root = str(tmp_path / "reg")
    reg = _announce(root, "r1")
    recs = registry.read_records(root, expire_s=30.0)
    assert [r["replica"] for r in recs] == ["r1"]
    rec = recs[0]
    assert rec["status"] == "live"
    assert rec["url"] == "http://127.0.0.1:9/"
    assert rec["age_s"] >= 0.0
    assert rec["fingerprint"] == registry.stack_fingerprint()
    assert rec["pid"] == os.getpid()

    # the same record read with a future clock has expired
    later = time.time() + 60.0
    stale = registry.read_records(root, expire_s=30.0, now=later)[0]
    assert stale["status"] == "stale"

    # clean exit: tombstone survives any clock
    reg.withdraw()
    assert registry.read_records(
        root, expire_s=30.0, now=later)[0]["status"] == "withdrawn"

    # crash seam: no tombstone, and withdraw() after kill() stays a
    # no-op — readers must see the record go stale, not withdrawn
    reg2 = _announce(root, "r2")
    reg2.kill()
    reg2.withdraw()
    by_id = {r["replica"]: r for r in registry.read_records(
        root, expire_s=30.0, now=later)}
    assert by_id["r2"]["status"] == "stale"
    assert by_id["r2"]["withdrawn"] is False


def test_registry_reader_tolerates_garbage_and_ids_never_collide(
        tmp_path):
    root = str(tmp_path / "reg")
    _announce(root, "ok")
    with open(os.path.join(root, "junk.json"), "w") as f:
        f.write("{not json")
    with open(os.path.join(root, "list.json"), "w") as f:
        json.dump([1, 2], f)
    recs = registry.read_records(root, expire_s=30.0)
    assert [r["replica"] for r in recs] == ["ok"]
    # default ids carry a process-local discriminator: two same-label
    # in-process replicas never overwrite each other's record
    a = registry.ReplicaRegistry(root, heartbeat_s=0, label="twin")
    b = registry.ReplicaRegistry(root, heartbeat_s=0, label="twin")
    assert a.replica_id != b.replica_id


# -- exposition round trip ---------------------------------------------------

def test_exposition_round_trip_with_hostile_labels():
    """Our own /metrics exposition through our own parser: the fleet
    federation path consumes exactly what a real collector scrapes,
    including the label escapes (backslash, quote, newline) and the
    build-info gauge whose labels ARE the skew-detection payload."""
    tenant = 'we"ird\nten\\ant'
    status = {"queue_depth": 3, "queue_by_priority": {"1": 2, "3": 1},
              "queue_by_tenant": {tenant: 3}, "active_leases": 1,
              "warm_pool": {"ok": 2, "stale": 1},
              "last_chunk_member_steps_per_s": 123.5, "serving": True}
    text = live.render_prometheus(
        registry=metrics.MetricsRegistry(), status=status)
    fams = fleet.parse_prometheus(text)

    q = fams["pystella_service_queue_depth"]
    assert q["type"] == "gauge"
    assert [v for lbl, v in q["samples"] if not lbl] == [3.0]
    assert {lbl["tenant"]: v for lbl, v in q["samples"]
            if "tenant" in lbl} == {tenant: 3.0}
    assert {lbl["priority"]: v for lbl, v in q["samples"]
            if "priority" in lbl} == {"1": 2.0, "3": 1.0}

    info = fams["pystella_build_info"]
    assert info["type"] == "gauge"
    labels, value = info["samples"][0]
    assert value == 1.0
    assert labels == live.build_info_labels()
    assert {"jax", "jaxlib", "libtpu", "flags_fingerprint",
            "device_kind"} <= set(labels)

    warm = fams["pystella_service_warm_pool_entries"]
    assert {lbl["fingerprint"]: v for lbl, v in warm["samples"]} \
        == {"ok": 2.0, "stale": 1.0}


def test_parser_skips_malformed_lines():
    text = "\n".join([
        "# TYPE good counter",
        "good 2",
        "good 3",
        "bad{unclosed= 1",
        "alsobad not_a_number",
        "# random comment",
        "untyped_metric 7",
    ])
    fams = fleet.parse_prometheus(text)
    assert [v for _lbl, v in fams["good"]["samples"]] == [2.0, 3.0]
    assert fams["good"]["type"] == "counter"
    assert fams["untyped_metric"]["type"] == "untyped"
    assert "bad" not in fams


# -- aggregation + federation (synthetic replicas) ---------------------------

def _metrics_text(queue_depth, events_total):
    return "\n".join([
        "# TYPE pystella_events_total counter",
        f"pystella_events_total {events_total}",
        "# TYPE pystella_service_queue_depth gauge",
        f"pystella_service_queue_depth {queue_depth}",
        f'pystella_service_queue_depth{{tenant="t"}} {queue_depth}',
        "# TYPE pystella_build_info gauge",
        'pystella_build_info{jax="0.9",flags_fingerprint="abc",'
        'device_kind="cpu"} 1',
    ])


def _payload(queue_depth, events_total, slo_samples):
    return {
        "metrics": fleet.parse_prometheus(
            _metrics_text(queue_depth, events_total)),
        "slo": {"legs": {"queue_p95": {"samples": slo_samples}}},
        "healthz": {"serving": True, "queue_depth": queue_depth},
        "error": None,
    }


def test_aggregator_merges_and_federates(tmp_path):
    """Counters merge by sum, gauges stay per-replica (unlabeled
    headline samples only), and /slo samples replay — deduplicated by
    timestamp per replica+leg — through the fleet monitor: a breach on
    ONE replica fires the fleet alert, and aging out resolves it."""
    root = str(tmp_path / "reg")
    _announce(root, "r1")
    _announce(root, "r2")
    t0 = time.time()
    payloads = {
        "r1": _payload(2, 5, [[t0, 5.0]]),             # the breach
        "r2": _payload(7, 9, [[t0, 0.1], [t0 + 0.1, 0.2]]),
    }
    agg = fleet.FleetAggregator(
        registry_dir=root, expire_s=3600.0, emit=False, min_samples=1,
        legs={"queue_p95": {"objective": 1.0, "fast_window_s": 5.0,
                            "slow_window_s": 5.0},
              "dead_replicas": {}})
    agg._scrape_replica = lambda rec: payloads[rec["replica"]]

    s1 = agg.scrape(now=t0 + 0.2)
    assert s1["live"] == 2
    assert s1["counters"]["pystella_events_total"] == 14.0
    assert s1["gauges"]["pystella_service_queue_depth"] \
        == {"r1": 2.0, "r2": 7.0}
    # labeled gauge series stay replica-local detail, never federated
    assert set(s1["gauges"]) == {"pystella_service_queue_depth",
                                 "pystella_build_info"} \
        or "pystella_service_queue_depth" in s1["gauges"]
    leg = s1["legs"]["queue_p95"]
    assert leg["n_slow"] == 3          # both replicas' samples, merged
    assert leg["alerting"] is True     # p95 over {5.0, .1, .2} > bar
    assert s1["alerting"] == ["queue_p95"]

    # re-scraping the SAME samples must not double-ingest (dedup by
    # last-seen ts per replica+leg); past the window the alert resolves
    s2 = agg.scrape(now=t0 + 20.0)
    leg2 = s2["legs"]["queue_p95"]
    assert leg2["alerting"] is False
    assert s2["alerts_total"] == 1 and s2["resolved_total"] == 1
    assert [(e["leg"], e["change"]) for e in s2["alert_log"]] \
        == [("queue_p95", "fired"), ("queue_p95", "resolved")]
    # build-info labels from the exposition land on the replica row
    assert s2["replicas"]["r1"]["build_info"]["flags_fingerprint"] \
        == "abc"


def test_unreachable_replica_declared_lost(tmp_path):
    """A record that keeps beating while its endpoint fails
    _UNREACHABLE_AFTER consecutive scrapes is LOST (reason
    "unreachable") — emitted once, and counted into the dead_replicas
    leg until it recovers."""
    root = str(tmp_path / "reg")
    _announce(root, "wedged")
    agg = fleet.FleetAggregator(registry_dir=root, expire_s=3600.0,
                                emit=False, min_samples=1)
    agg._scrape_replica = lambda rec: {"error": "URLError: wedged"}
    s1 = agg.scrape()
    s2 = agg.scrape()
    assert s1["lost"] == [] and s2["lost"] == []
    s3 = agg.scrape()
    assert [(e["replica"], e["reason"]) for e in s3["lost"]] \
        == [("wedged", "unreachable")]
    assert s3["dead"] == 1
    assert "dead_replicas" in s3["alerting"]
    assert s3["scrape_success_rate"] == 0.0
    # once lost, not re-lost every pass
    s4 = agg.scrape()
    assert len(s4["lost"]) == 1
    # recovery: a clean scrape clears the loss immediately; the
    # dead_replicas rate leg resolves once the breach samples age out
    # of the slow window (it measures sustained loss, not the instant)
    agg._scrape_replica = lambda rec: _payload(0, 0, [])
    s5 = agg.scrape()
    assert s5["dead"] == 0
    assert s5["replicas"]["wedged"]["status"] == "live"
    s6 = agg.scrape(now=time.time() + 400.0)  # past the slow window
    assert "dead_replicas" not in s6["alerting"]
    assert s6["resolved_total"] >= 1


def test_skew_and_warm_divergence_detection(tmp_path):
    """Two live replicas with different stack fingerprints -> SKEW;
    the same warm signature under different fingerprints ->
    divergence (never share warm artifacts across that pair)."""
    root = str(tmp_path / "reg")
    a = _announce(root, "a", warm_fingerprints={"sig1": "aaa",
                                                "sig2": "common"})
    b = _announce(root, "b", warm_fingerprints={"sig1": "bbb",
                                                "sig2": "common"})
    b.record["fingerprint"] = "deadbeef0000"
    b.heartbeat()
    agg = fleet.FleetAggregator(registry_dir=root, expire_s=3600.0,
                                emit=False, min_samples=1)
    agg._scrape_replica = lambda rec: _payload(0, 0, [])
    state = agg.scrape()
    assert state["skew"]["skewed"] is True
    assert len(state["skew"]["fingerprints"]) == 2
    assert sorted(state["divergence"]["divergent"]) == ["sig1"]
    assert state["divergence"]["signatures"] == 2
    a.withdraw()
    b.withdraw()


# -- ops CLIs ----------------------------------------------------------------

def test_fleet_cli_status(tmp_path, capsys, monkeypatch):
    monkeypatch.delenv("PYSTELLA_FLEET_DIR", raising=False)
    assert fleet.main(["status"]) == 2
    assert "no registry directory" in capsys.readouterr().err
    root = str(tmp_path / "reg")
    reg = _announce(root, "solo", url=None)
    reg.withdraw()
    assert fleet.main(["status", "--dir", root, "--json"]) == 0
    state = json.loads(capsys.readouterr().out)
    assert state["replicas"]["solo"]["status"] == "withdrawn"
    assert fleet.main(["status", "--dir", root]) == 0
    out = capsys.readouterr().out
    assert "solo" in out and "withdrawn" in out


def test_service_status_fleet_view(tmp_path, capsys, monkeypatch):
    """`service status --fleet`: one row per registry record, each
    live replica annotated with its own endpoint's serve-loop + SLO
    line (poll injectable, so no HTTP in the unit test)."""
    root = str(tmp_path / "reg")
    _announce(root, "alive", url="http://127.0.0.1:1/")
    gone = _announce(root, "gone", url="http://127.0.0.1:2/")
    gone.withdraw()

    def fake_poll(url, timeout=2.0):
        return ({"serving": True, "queue_depth": 4, "active_lease": 7,
                 "leases_completed": 3},
                {"enabled": True, "alerting": ["queue_p95"]})

    lines = service_cli.fleet_lines(root, expire_s=3600.0,
                                    poll=fake_poll)
    assert lines[0].startswith("fleet: 1/2 replica(s) live")
    alive = [ln for ln in lines if "alive" in ln][0]
    assert "[live]" in alive and "SERVING" in alive \
        and "BURNING [queue_p95]" in alive
    assert any("gone [withdrawn]" in ln for ln in lines)
    # unreachable endpoint degrades to a marker, not a raise
    lines = service_cli.fleet_lines(root, expire_s=3600.0,
                                    poll=lambda u, timeout=2.0: None)
    assert any("endpoint UNREACHABLE" in ln for ln in lines)
    # the argparse path: --fleet-dir one-shot, and the no-dir error
    assert service_cli.main(["status", "--fleet-dir", root]) == 0
    assert "fleet:" in capsys.readouterr().out
    monkeypatch.delenv("PYSTELLA_FLEET_DIR", raising=False)
    assert service_cli.main(["status", "--fleet"]) == 2
    assert "no --fleet-dir" in capsys.readouterr().err


# -- gate fleet verdicts (synthetic reports) ---------------------------------

def _report(samples_ms=None):
    led = ledger.PerfLedger(label="synthetic", sites=32**3)
    rng = np.random.default_rng(0)
    led.samples_ms = list(
        samples_ms if samples_ms is not None
        else (10.0 + 0.05 * rng.standard_normal(60)))
    return led.report()


def _fleet_section(**over):
    base = {
        "replicas": [{"replica": "replica-a", "status": "live"},
                     {"replica": "replica-b", "status": "lost"}],
        "scrapes": 3, "endpoint_ok": 4, "endpoint_failed": 1,
        "scrape_success_rate": 0.8,
        "replicas_lost": [{"replica": "replica-b",
                           "reason": "expired", "age_s": 0.9}],
        "dead": 1,
        "legs": {"queue_p95": {"value_fast": 0.5, "bar": 300.0},
                 "warm_ttfs": {"value_fast": 0.8, "bar": 300.0}},
        "alerts": {"alerts": 2, "resolved": 1, "flaps": 0},
        "skew": {"skewed": False, "stacks": 1},
        "divergence": [],
        "announces": 2, "withdraws": 1,
        "coverage": {"replicas": 2, "lost": 1, "endpoint_failed": 1,
                     "complete": False},
    }
    base.update(over)
    return base


def _clean_fleet(**over):
    return _fleet_section(
        replicas=[{"replica": "replica-a", "status": "live"},
                  {"replica": "replica-b", "status": "live"}],
        endpoint_ok=6, endpoint_failed=0, scrape_success_rate=1.0,
        replicas_lost=[], dead=0,
        coverage={"replicas": 2, "lost": 0, "endpoint_failed": 0,
                  "complete": True},
        **over)


def test_gate_refuses_complete_claim_over_lossy_record():
    """A report claiming complete fleet coverage while its own scrape
    record shows a lost replica / failed scrapes is invalid evidence:
    exit 2, before any baseline comparison."""
    cur = _report()
    cur["fleet"] = _fleet_section()
    cur["fleet"]["coverage"]["complete"] = True
    v = gate.compare_reports(_report(), cur)
    assert v["exit_code"] == 2 and v["ok"] is False
    assert any(r.startswith("invalid_evidence: report claims complete "
                            "fleet coverage") for r in v["reasons"])
    # --no-fleet opts the whole family out
    v = gate.compare_reports(_report(), cur, check_fleet=False)
    assert v["exit_code"] == 0


def test_gate_annotates_honest_degraded_fleet():
    cur = _report()
    cur["fleet"] = _fleet_section()
    v = gate.compare_reports(_report(), cur)
    assert v["exit_code"] == 0 and v["ok"] is True
    assert v["degraded"] is True
    assert any("degraded fleet evidence" in w and "replica-b" in w
               for w in v["warnings"])


def test_gate_fleet_slo_regression_and_hygiene():
    base = _report()
    base["fleet"] = _clean_fleet()
    # regression: factor 2.5 AND floor 0.5 s both exceeded
    cur = _report()
    cur["fleet"] = _clean_fleet()
    cur["fleet"]["legs"]["queue_p95"]["value_fast"] = 900.0
    v = gate.compare_reports(base, cur)
    assert v["exit_code"] == 1
    assert any("fleet SLO regression" in r and "queue-latency p95" in r
               for r in v["reasons"])
    assert v["fleet"]["queue_p95"]["current_s"] == 900.0
    # inside factor*baseline: clean pass, comparison recorded
    ok = _report()
    ok["fleet"] = _clean_fleet()
    v = gate.compare_reports(base, ok)
    assert v["exit_code"] == 0
    assert not any(w.startswith("fleet") for w in v["warnings"])
    # skew appearing (baseline had none) and divergence: warn, exit 0
    skewed = _report()
    skewed["fleet"] = _clean_fleet(
        skew={"skewed": True, "stacks": 2}, divergence=["sig1"])
    v = gate.compare_reports(base, skewed)
    assert v["exit_code"] == 0
    assert any("SKEW" in w for w in v["warnings"])
    assert any("divergence" in w and "sig1" in w for w in v["warnings"])
    # coverage loss: baseline had a fleet section, current has none
    v = gate.compare_reports(base, _report())
    assert v["exit_code"] == 0
    assert any("fleet SLO coverage was lost" in w for w in v["warnings"])


# -- the two-replica drill, end to end ---------------------------------------

def test_two_replica_drill_through_ledger_and_gate(tmp_path, event_log):
    """The whole tentpole chain on one deterministic record: run_fleet
    (two live replicas aggregated, seeded fleet alert fired AND
    resolved, replica-b wedged then killed -> fleet_replica_lost with
    reason "expired") -> the ledger's fleet section -> the gate
    annotating the honest degraded record and refusing the same
    record mutated into a complete-coverage claim."""
    stats = loadgen.run_fleet(str(tmp_path / "fleet"))

    assert stats["replicas"] == ["replica-a", "replica-b"]
    assert stats["killed"] == "replica-b"
    assert stats["completed"] == {"replica-a": 3, "replica-b": 2}
    # aggregation pass 1 ran against two provably-live replicas, and
    # the queue-depth gauge federated per replica, never averaged
    assert stats["live_both_pass"] == 2
    assert stats["queue_gauge_replicas"] == ["replica-a", "replica-b"]
    # the wedge: exactly one scrape recorded b live-but-unreachable
    assert stats["endpoint_failed"] == 1
    assert 0.5 < stats["scrape_success_rate"] < 1.0
    assert stats["scrapes"] >= 3
    # the crash: heartbeat expiry, not a tombstone
    assert [e["reason"] for e in stats["lost"]] == ["expired"]
    assert stats["lost"][0]["replica"] == "replica-b"
    assert stats["dead"] == 1
    # the seeded fleet SLO story: replica-a's deadline miss federates
    # and fires, its hit resolves; dead_replicas fires UNRESOLVED
    assert stats["alerts"] == 2 and stats["resolved"] == 1
    assert stats["flaps"] == 0
    assert stats["alerting"] == ["dead_replicas"]
    assert stats["legs"]["queue_p95"]["n_slow"] >= 3
    # same process, same stack: no skew, no warm divergence
    assert stats["skewed"] is False and stats["divergent"] == []
    # the registry distinguishes a's shutdown from b's crash
    assert stats["registry"] == {"replica-a": "withdrawn",
                                 "replica-b": "stale"}

    kinds = [r["kind"] for r in events.read_events(event_log)]
    assert kinds.count("fleet_announce") == 2
    assert kinds.count("fleet_withdraw") == 1
    assert kinds.count("fleet_replica_lost") == 1
    assert kinds.count("fleet_scrape") == stats["scrapes"]
    assert "fleet_alert" in kinds and "fleet_resolved" in kinds
    assert "fleet_loadgen" in kinds

    # -- ledger: the fleet section derives from exactly this record --
    led = ledger.PerfLedger.from_events(event_log, label="fleet-e2e")
    fl = led.fleet()
    assert fl["coverage"]["complete"] is False
    assert fl["coverage"]["lost"] == 1
    assert fl["endpoint_failed"] == 1
    assert fl["replicas_lost"][0]["replica"] == "replica-b"
    assert fl["replicas_lost"][0]["reason"] == "expired"
    assert [r["replica"] for r in fl["replicas"]] \
        == ["replica-a", "replica-b"]
    lost_row = fl["replicas"][1]
    assert lost_row["status"] == "lost" \
        and lost_row["lost_reason"] == "expired"
    assert fl["alerts"]["alerts"] == 2
    assert fl["alerts"]["resolved"] == 1
    assert fl["announces"] == 2 and fl["withdraws"] == 1
    assert fl["skew"]["skewed"] is False and fl["divergence"] == []

    rep = _report()
    rep["fleet"] = fl
    md = ledger.render_markdown(rep)
    assert "## Fleet (replica registry + federation)" in md
    assert "replica-b" in md

    # -- gate: honest degraded annotated, dishonest claim refused ----
    v = gate.compare_reports(rep, rep)
    assert v["exit_code"] == 0 and v["degraded"] is True
    assert any("degraded fleet evidence" in w for w in v["warnings"])
    fake = json.loads(json.dumps(rep))
    fake["fleet"]["coverage"]["complete"] = True
    v = gate.compare_reports(rep, fake)
    assert v["exit_code"] == 2
    assert any("invalid_evidence" in r for r in v["reasons"])
    assert gate.compare_reports(rep, fake,
                                check_fleet=False)["exit_code"] == 0
