"""Capacity & goodput plane tests (pystella_tpu.obs.capacity): the
footprint ledger round trip + the stale-fingerprint refusal (the
``WarmstartStore.load`` rule), memory-aware admission accept/reject/
headroom pins, the honest CPU predicted-only degrade, the OOM forensic
bundle from an injected RESOURCE_EXHAUSTED fault, chip-second
attribution summing to the measured lease wall (the PR-13 audit bar),
the report's ``capacity`` section, and all three gate verdict families
(coverage refusal exit 2, goodput regression exit 1, degraded/
reconciliation warnings at exit 0)."""

import copy
import json
import os

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.obs import capacity as cap_mod
from pystella_tpu.obs import gate, ledger, memory, spans
from pystella_tpu.obs.capacity import CapacityMonitor, FootprintLedger
from pystella_tpu.service import (
    ScenarioRequest, ScenarioService, request_signature)

GRID = (8, 8, 8)
SIG = request_signature("toy", GRID)


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure(path)
    yield path
    obs.configure(None)


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _toy_builder(grid_shape, decomp=None):
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


# -- footprint ledger ------------------------------------------------------

def test_aval_estimate_doubles_argument_bytes():
    """Signature-only estimate: Σ prod(shape)×itemsize over the leaves,
    doubled for the output state; shapeless leaves estimate nothing."""
    avals = [[[8, 8, 8], "float32"], [[8, 8, 8], "float32"]]
    predicted, breakdown = cap_mod.estimate_bytes_from_avals(avals)
    assert breakdown["argument_bytes"] == 2 * 512 * 4
    assert predicted == 2 * breakdown["argument_bytes"]
    assert cap_mod.estimate_bytes_from_avals([]) == (None, {})
    assert cap_mod.estimate_bytes_from_avals(
        [["not-a-shape", "float32"]]) == (None, {})


def test_footprint_roundtrip(tmp_path, event_log):
    """record → persisted *.footprint.json → a fresh ledger loads it
    back when the live versions/flags match."""
    root = str(tmp_path / "fp")
    led = FootprintLedger(root=root)
    comps = memory.fingerprint_components("prog")
    rec = led.record("prog", "fp1", 1234, source="memory_analysis",
                     components=comps)
    assert rec["predicted_bytes"] == 1234
    files = [n for n in os.listdir(root)
             if n.endswith(".footprint.json")]
    assert files == ["prog-fp1.footprint.json"]

    fresh = FootprintLedger(root=root)
    loaded = fresh.load("prog")
    assert loaded is not None
    assert loaded["predicted_bytes"] == 1234
    assert loaded["source"] == "memory_analysis"
    assert fresh.predicted("prog", "fp1") == 1234
    kinds = [e["kind"] for e in _events(event_log)]
    assert "capacity_footprint" in kinds
    assert "capacity_stale" not in kinds


def test_footprint_stale_refusal(tmp_path, event_log):
    """The WarmstartStore.load rule: a footprint recorded under a
    different compiler stack is refused (``capacity_stale``), never
    silently trusted — and a stale newer record must not shadow an
    older matching one."""
    root = str(tmp_path / "fp")
    led = FootprintLedger(root=root)
    stale = dict(memory.fingerprint_components("prog"))
    stale["versions"] = {"jax": "0.0.0-ancient"}
    led.record("prog", "fpold", 777, components=stale)

    fresh = FootprintLedger(root=root)
    assert fresh.load("prog") is None
    evs = _events(event_log)
    stale_evs = [e for e in evs if e["kind"] == "capacity_stale"]
    assert stale_evs and "versions" in stale_evs[-1]["data"]["reason"]

    # an older record that DOES match the live process still wins
    led.record("prog", "fpgood", 888,
               components=memory.fingerprint_components("prog"))
    again = FootprintLedger(root=root)
    loaded = again.load("prog")
    assert loaded is not None and loaded["predicted_bytes"] == 888

    # unknown label: stale event with an honest "no footprint" reason
    assert again.load("never-recorded") is None
    evs = _events(event_log)
    assert any(e["kind"] == "capacity_stale"
               and e["data"]["reason"] == "no footprint" for e in evs)


def test_memory_analysis_never_downgraded(event_log):
    """A backend-measured footprint is never replaced by a later
    signature-only estimate for the same program."""
    led = FootprintLedger(root=None)
    led.record("p", "f", 100, source="memory_analysis")
    rec = led.record("p", "f", 999, source="aval_estimate")
    assert rec["predicted_bytes"] == 100
    assert led.predicted("p", "f") == 100
    # the reverse direction upgrades
    led.record("q", "f", 50, source="aval_estimate")
    led.record("q", "f", 60, source="memory_analysis")
    assert led.predicted("q", "f") == 60


# -- memory-aware admission ------------------------------------------------

def test_admission_accept_reject_headroom(event_log):
    """resident + candidate vs capacity × headroom, with the already-
    armed candidate excluded from the resident sum, and the honest
    admits when capacity or footprint is unknown."""
    mon = CapacityMonitor(ledger=FootprintLedger(root=None),
                          capacity_bytes=1000, headroom=0.5,
                          policy="reject")
    # budget = 1000 × 0.5 = 500
    d = mon.admission_check("a", 400)
    assert d["admitted"] and d["reason"] == "fits"
    assert d["budget_bytes"] == 500
    d = mon.admission_check("b", 600)
    assert not d["admitted"] and "budget" in d["reason"]

    mon.resident["a"] = {"predicted_bytes": 300}
    assert mon.resident_bytes() == 300
    # new program must fit alongside the resident pool
    assert not mon.admission_check("c", 300)["admitted"]
    # re-leasing the armed program adds no new footprint
    d = mon.admission_check("a", 300)
    assert d["admitted"] and d["resident_bytes"] == 0

    # the headroom knob is the whole difference
    roomy = CapacityMonitor(ledger=FootprintLedger(root=None),
                            capacity_bytes=1000, headroom=1.0,
                            policy="reject")
    assert roomy.admission_check("b", 600)["admitted"]

    # unknown footprint / no capacity limit: audited skips, not guesses
    d = mon.admission_check("x", None)
    assert d["admitted"] and d["reason"] == "unknown-footprint"
    nolimit = CapacityMonitor(ledger=FootprintLedger(root=None),
                              capacity_bytes=None, policy="reject")
    d = nolimit.admission_check("y", 10**15)
    assert d["admitted"] and d["reason"] == "no-capacity-limit"

    with pytest.raises(ValueError):
        CapacityMonitor(policy="best-effort")


def test_cpu_predicted_only_degrade(event_log):
    """CPU keeps no allocator stats: poll_watermark returns None and
    the live snapshot reports 0 samples rather than inventing
    numbers — the coverage block the gate's degrade warning keys on."""
    mon = CapacityMonitor(ledger=FootprintLedger(root=None),
                          capacity_bytes=1 << 30, policy="reject")
    assert mon.poll_watermark(lease="L1", step=3) is None
    assert mon.watermarks == []
    fields = mon.live_fields()
    assert fields["watermark_samples"] == 0
    assert fields["bytes_in_use"] is None
    assert fields["capacity_bytes"] == 1 << 30
    # the lease still registers for coverage: an unsampled lease is a
    # hole in the record, not an omission
    assert "L1" in mon._lease_samples
    assert not any(e["kind"] == "capacity_watermark"
                   for e in _events(event_log))


# -- OOM forensics ---------------------------------------------------------

def test_oom_bundle_from_injected_resource_exhausted(tmp_path,
                                                     event_log):
    """An injected RESOURCE_EXHAUSTED classifies as an allocator OOM
    and the bundle records the admission decision that let the lease
    through, the footprint table, and the watermark series."""
    err = cap_mod.resource_exhausted_error("fault drill")
    assert cap_mod.is_resource_exhausted(err)
    assert not cap_mod.is_resource_exhausted(ValueError("benign"))

    mon = CapacityMonitor(ledger=FootprintLedger(root=None),
                          capacity_bytes=1000, headroom=0.9,
                          policy="reject")
    mon.ledger.record(f"service.{SIG}", "fp1", 400, persist=False)
    mon.resident[SIG] = {"predicted_bytes": 400}
    mon.admission_check(SIG, 400)

    path = mon.write_oom_bundle(str(tmp_path / "oom"), err,
                                signature=SIG, lease="L7")
    assert os.path.exists(path) and mon.oom_bundles == [path]
    with open(path) as f:
        bundle = json.load(f)
    cfg = bundle["config"]
    assert "RESOURCE_EXHAUSTED" in cfg["error"]
    assert cfg["signature"] == SIG and cfg["lease"] == "L7"
    assert cfg["admission"]["admitted"] is True
    assert cfg["resident_bytes"] == 400
    assert any(r["fingerprint"] == "fp1" for r in cfg["footprints"])
    evs = _events(event_log)
    oom = [e for e in evs if e["kind"] == "capacity_oom"]
    assert oom and oom[0]["data"]["path"] == path


# -- chip-second attribution (service e2e) ---------------------------------

def test_chip_seconds_sum_to_lease_wall(tmp_path, event_log):
    """The PR-13 audit bar applied to billing: Σ per-request chip-
    seconds over the run equals Σ (lease wall × chips leased) within
    5% — co-leased members split their lease's chips, so nothing is
    double-billed and nothing leaks."""
    svc = ScenarioService(str(tmp_path / "ck"), slots=2, chunk=2)
    svc.register_model("toy", _toy_builder)
    for i, tenant in enumerate(["alice", "alice", "bob", "bob"]):
        svc.submit(ScenarioRequest(tenant, SIG, 4, seed=i))
    svc.serve()

    evs = _events(event_log)
    usage = [e for e in evs if e["kind"] == "capacity_usage"]
    assert usage, "serve() must finalize usage at retire time"
    usage = usage[-1]["data"]
    accounts = [e["data"] for e in evs
                if e["kind"] == "capacity_account"]
    assert usage["requests"] == len(accounts) == 4
    assert usage["committed_steps"] == 4 * 4
    assert usage["goodput"] and usage["goodput"] > 0

    # tenant rows partition the account list exactly
    tenants = usage["tenants"]
    assert set(tenants) == {"alice", "bob"}
    assert abs(sum(t["chip_s"] for t in tenants.values())
               - usage["total_chip_s"]) < 1e-4
    assert sum(t["committed_steps"] for t in tenants.values()) == 16

    # measured lease wall × chips, from the assembled span trees: the
    # post-dispatch segment the lease span times, plus the cold
    # build+compile the lease record itself measures (chips are held
    # through both — ON_LEASE_PHASES bills service_compile)
    trees = spans.SpanAssembler.from_events(event_log).assemble()
    lease_data = {e["span"]: e["data"] for e in evs
                  if e["kind"] == "service_lease"}
    walls = {}
    for tree in trees.values():
        for row in tree.spans:
            if row["name"] == "service_lease_span":
                walls[row["span"]] = max(
                    walls.get(row["span"], 0.0), row["dur_s"])
    assert walls, "no lease spans assembled"
    wall_chip_s = sum(
        (dur + (lease_data.get(span, {}).get("cold_build_s") or 0.0))
        * (lease_data.get(span, {}).get("chips") or 1)
        for span, dur in walls.items())
    rel_err = abs(usage["total_chip_s"] - wall_chip_s) / wall_chip_s
    assert rel_err < 0.05, (usage["total_chip_s"], wall_chip_s)

    # CPU run: coverage degrades honestly, never claims completeness
    cov = usage["coverage"]
    assert cov["predicted_only"] is True
    assert cov["watermark_samples"] == 0
    assert cov["complete"] is False

    # the same events feed the report's capacity section + md block
    led = ledger.PerfLedger.from_events(event_log)
    rep = led.report()
    cap = rep["capacity"]
    assert cap["goodput"] == usage["goodput"]
    assert cap["coverage"]["predicted_only"] is True
    assert cap["footprints"], "armed programs must be footprinted"
    assert len(cap["accounts"]) == 4
    md = ledger.render_markdown(rep)
    assert "Capacity & goodput" in md


# -- gate verdict families -------------------------------------------------

def _report(samples_ms):
    led = ledger.PerfLedger(label="synthetic", sites=32**3)
    led.samples_ms = list(samples_ms)
    return led.report()


def _steady(n=60, base=10.0, jitter=0.05, seed=0):
    rng = np.random.default_rng(seed)
    return (base + jitter * rng.standard_normal(n)).tolist()


def _with_capacity(rep, goodput=20.0, samples=5, complete=True,
                   predicted_only=False, rel_err=0.02):
    out = copy.deepcopy(rep)
    out["capacity"] = {
        "goodput": goodput,
        "total_chip_s": 1.0,
        "committed_steps": int(goodput),
        "waste_chip_s": 0.0,
        "coverage": {"leases": 3, "leases_sampled": 3 if samples else 0,
                     "watermark_samples": samples,
                     "predicted_only": predicted_only,
                     "complete": complete},
        "reconciliation": (None if samples == 0 else
                           {"predicted_bytes": 1000,
                            "peak_bytes_in_use": 1000,
                            "rel_err": rel_err}),
        "tenants": {"a": {"requests": 3, "rejected": 0,
                          "chip_s": 1.0, "waste_chip_s": 0.0,
                          "committed_steps": int(goodput),
                          "goodput": goodput}},
    }
    return out


def test_gate_refuses_complete_coverage_without_watermarks():
    """Verdict family 1 (exit 2): a complete-coverage claim over zero
    device readings is doctored evidence, not a warning."""
    base = _with_capacity(_report(_steady()))
    doctored = _with_capacity(_report(_steady(seed=1)),
                              samples=0, complete=True)
    verdict = gate.compare_reports(base, doctored)
    assert not verdict["ok"] and verdict["exit_code"] == 2
    assert any("capacity" in r and "invalid_evidence" in r
               for r in verdict["reasons"])
    # the opt-out restores the non-capacity verdict
    ok = gate.compare_reports(base, doctored, check_capacity=False)
    assert ok["ok"] and ok["exit_code"] == 0


def test_gate_goodput_regression_fails():
    """Verdict family 2 (exit 1): goodput collapsing past factor AND
    floor is a gate failure; a small dip is not."""
    base = _with_capacity(_report(_steady()), goodput=20.0)
    burned = _with_capacity(_report(_steady(seed=1)), goodput=5.0)
    verdict = gate.compare_reports(base, burned)
    assert not verdict["ok"] and verdict["exit_code"] == 1
    assert any("goodput regression" in r for r in verdict["reasons"])
    assert verdict["capacity"]["baseline_goodput"] == 20.0

    dip = _with_capacity(_report(_steady(seed=2)), goodput=15.0)
    verdict = gate.compare_reports(base, dip)
    assert verdict["ok"] and verdict["exit_code"] == 0

    # factor/floor knobs move the bar
    verdict = gate.compare_reports(base, dip, goodput_factor=1.1,
                                   goodput_floor=0.5)
    assert not verdict["ok"] and verdict["exit_code"] == 1


def test_gate_degraded_and_reconciliation_warnings():
    """Verdict family 3 (exit 0 + warnings): the honest CPU degrade is
    annotated, and a >25% predicted-vs-measured error warns that the
    footprint model drifts from the device."""
    base = _with_capacity(_report(_steady()))
    cpu = _with_capacity(_report(_steady(seed=1)), samples=0,
                         complete=False, predicted_only=True)
    verdict = gate.compare_reports(base, cpu)
    assert verdict["ok"] and verdict["exit_code"] == 0
    assert verdict.get("degraded") is True
    assert any("predicted-only" in w for w in verdict["warnings"])

    drifted = _with_capacity(_report(_steady(seed=2)), rel_err=0.6)
    verdict = gate.compare_reports(base, drifted)
    assert verdict["ok"] and verdict["exit_code"] == 0
    assert any("footprint" in w and "60%" in w
               for w in verdict["warnings"])
    # under the bar: silent
    quiet = _with_capacity(_report(_steady(seed=3)), rel_err=0.1)
    verdict = gate.compare_reports(base, quiet)
    assert not any("drifting" in w for w in verdict["warnings"])


def test_gate_warns_on_capacity_coverage_loss():
    """A baseline with capacity evidence that the current run lost is
    a coverage regression worth a warning, not silence."""
    base = _with_capacity(_report(_steady()))
    bare = _report(_steady(seed=1))
    verdict = gate.compare_reports(base, bare)
    assert verdict["ok"] and verdict["exit_code"] == 0
    assert any("capacity" in w for w in verdict["warnings"])
