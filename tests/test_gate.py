"""Perf evidence pipeline tests: Perfetto-trace parsing, PerfLedger
ingestion/derivation, the noise-aware regression gate's verdicts and
exit codes on synthetic ledgers."""

import gzip
import json
import os
import shutil

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

from pystella_tpu.obs import events, gate, ledger
from pystella_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MINI_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "mini_perfetto_trace.json")


# -- trace parsing ---------------------------------------------------------

def test_mini_trace_scope_durations():
    """The checked-in miniature Perfetto JSON exercises the matching
    rules: longest scope wins (pair events don't leak into their
    prefix), ``rk_stage0..4`` fold into ``rk_stage``, token boundaries
    exclude look-alike names, non-complete events are ignored."""
    evs = obs_trace.parse_trace_file(MINI_TRACE)
    assert len(evs) == 12
    table = obs_trace.scope_durations(evs)
    assert table["fused_rk_stage_pair"]["count"] == 1
    assert table["fused_rk_stage_pair"]["total_ms"] == pytest.approx(1.5)
    # the jit(...)/fused_rk_stage/fusion.1 device row lands in
    # fused_rk_stage, NOT in the longer pair scope
    assert table["fused_rk_stage"]["count"] == 1
    assert table["fused_rk_stage"]["total_ms"] == pytest.approx(0.5)
    assert table["halo_exchange"]["count"] == 2
    assert table["halo_exchange"]["total_ms"] == pytest.approx(0.5)
    assert table["halo_exchange"]["mean_ms"] == pytest.approx(0.25)
    # rk_stage0 + rk_stage4 fold; my_rk_stage_helper and rk_stagey are
    # boundary-excluded
    assert table["rk_stage"]["count"] == 2
    assert table["rk_stage"]["total_ms"] == pytest.approx(0.22)
    assert table["pallas_stencil"]["count"] == 1
    assert "unrelated_op" not in table
    assert all("rk_stagey" not in k and "helper" not in k for k in table)


def test_trace_parser_reads_gzip(tmp_path):
    gz = tmp_path / "mini.trace.json.gz"
    with open(MINI_TRACE, "rb") as src, gzip.open(gz, "wb") as dst:
        shutil.copyfileobj(src, dst)
    assert obs_trace.parse_trace_file(str(gz)) \
        == obs_trace.parse_trace_file(MINI_TRACE)
    # find_trace_file locates it under a nested profile dir
    nested = tmp_path / "plugins" / "profile" / "run1"
    nested.mkdir(parents=True)
    shutil.move(str(gz), nested / "host.trace.json.gz")
    found = obs_trace.find_trace_file(str(tmp_path))
    assert found and found.endswith("host.trace.json.gz")


def test_trace_parser_tolerates_garbage(tmp_path):
    bad = tmp_path / "x.trace.json"
    bad.write_text("not json at all")
    assert obs_trace.parse_trace_file(str(bad)) == []
    assert obs_trace.parse_trace_file(str(tmp_path / "absent.json")) == []
    assert obs_trace.find_trace_file(str(tmp_path / "nowhere")) is None


def test_summarize_trace_missing_degrades(tmp_path):
    """No trace file -> None plus a trace_missing event, never a
    raise (the CPU/interpret degradation contract)."""
    log_path = tmp_path / "ev.jsonl"
    with events.EventLog(str(log_path)) as log:
        assert obs_trace.summarize_trace(
            str(tmp_path / "empty_logdir"), log=log) is None
    kinds = [r["kind"] for r in events.read_events(str(log_path))]
    assert kinds == ["trace_missing"]


# -- ledger ----------------------------------------------------------------

def test_step_stats_and_mad():
    st = ledger.step_stats([10.0, 12.0, 11.0, 10.0, 50.0])
    assert st["count"] == 5
    assert st["p50_ms"] == 11.0
    assert st["max_ms"] == 50.0
    assert st["mad_ms"] == 1.0  # robust: the 50 ms outlier barely moves it
    assert ledger.step_stats([])["count"] == 0
    assert ledger.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5


def test_ledger_from_events(tmp_path):
    """End-to-end ingestion: run metadata, per-step samples, a compile
    record, and a trace summary all land in the report."""
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[16, 16, 16], nsteps=4)
        log.emit("compile", label="smoke_step", compile_seconds=1.0,
                 argument_bytes=1000, output_bytes=600, temp_bytes=50)
        log.emit("compile", label="helper", compile_seconds=0.1,
                 argument_bytes=10, output_bytes=5)
        for i, ms in enumerate([2.0, 2.2, 2.1, 2.3]):
            log.emit("step_time", step=i, ms=ms)
        log.emit("trace_summary", trace_file="/t.json.gz",
                 scopes={"driver_step": {"count": 4, "total_ms": 8.0,
                                        "mean_ms": 2.0}})
    led = ledger.PerfLedger.from_events(path, label="unit",
                                        step_label="smoke_step")
    assert led.sites == 16**3
    assert led.samples_ms == [2.0, 2.2, 2.1, 2.3]
    assert led.bytes_per_step == 1600  # the labeled record, not helper
    rep = led.report()
    assert rep["schema"] == ledger.REPORT_SCHEMA_VERSION
    assert rep["steps"]["count"] == 4
    assert rep["steps"]["p50_ms"] == pytest.approx(2.15)
    assert rep["throughput"]["site_updates_per_s"] == pytest.approx(
        16**3 * 1e3 / 2.15)
    assert rep["scopes"]["driver_step"]["count"] == 4
    assert rep["roofline"]["achieved_gbps"] == pytest.approx(
        1600 / (2.15e-3) / 1e9)
    # jax is imported in this process, so the fingerprint is complete
    assert rep["env"]["jax"] and rep["env"]["platform"] == "cpu"
    # markdown renders without blowing up on real content
    md = ledger.render_markdown(rep)
    assert "driver_step" in md and "Roofline" in md


@pytest.mark.parametrize("section", ["service", "alerts", "fleet", "perf",
                                     "capacity", "latency"])
def test_report_has_no_service_section(section):
    """The report describes a run of the engine; the scenario service's
    six sections went with it (PR 45), and a report that carries one
    (an old baseline) is compared on what remains."""
    led = ledger.PerfLedger(label="unit", sites=8)
    for ms in (2.0, 2.1, 2.2, 2.1):
        led.add_step_ms(ms)
    rep = led.report()
    assert section not in rep
    old = dict(rep, **{section: {"alerts": 3, "unresolved": ["x"]}})
    verdict = gate.compare_reports(old, rep, check_contamination="never")
    assert verdict["ok"] and section not in verdict


def test_ledger_scopes_to_latest_run(tmp_path):
    """EventLog appends; a reused log holds several runs. The ledger
    must describe only the LATEST run — mixing two runs' step times
    would average a regression away."""
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        for ms in (100.0, 101.0):      # stale run: 10x slower
            log.emit("step_time", ms=ms)
        log.emit("run_start", grid_shape=[16, 16, 16])
        for ms in (10.0, 10.5, 9.5):
            log.emit("step_time", ms=ms)
    led = ledger.PerfLedger.from_events(path)
    assert led.samples_ms == [10.0, 10.5, 9.5]
    assert led.sites == 16**3


def test_ledger_step_timer_fallback(tmp_path):
    """A run that only kept step_timer window reports still yields a
    (coarser) distribution."""
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("step_timer", step=100, ms_per_step=3.0, steps_per_s=333.0)
        log.emit("step_timer", step=200, ms_per_step=3.2, steps_per_s=312.0)
    led = ledger.PerfLedger.from_events(path)
    assert led.samples_ms == [3.0, 3.2]


def test_ledger_write_files(tmp_path):
    led = ledger.PerfLedger(label="unit", sites=1000)
    for ms in (1.0, 1.1, 0.9):
        led.add_step_ms(ms)
    json_path = led.write(str(tmp_path / "out"))
    assert os.path.exists(json_path)
    assert os.path.exists(json_path.replace(".json", ".md"))
    rep = json.load(open(json_path))
    assert rep["steps"]["count"] == 3


# -- gate: synthetic ledgers ----------------------------------------------

def _report(samples_ms, **env_overrides):
    led = ledger.PerfLedger(label="synthetic", sites=32**3)
    led.samples_ms = list(samples_ms)
    rep = led.report()
    rep["env"].update(env_overrides)
    return rep


def _steady(n=60, base=10.0, jitter=0.05, seed=0):
    rng = np.random.default_rng(seed)
    return (base + jitter * rng.standard_normal(n)).tolist()


def test_gate_pass_on_self_comparison():
    rep = _report(_steady())
    verdict = gate.compare_reports(rep, rep)
    assert verdict["ok"] and verdict["exit_code"] == 0


def test_gate_flags_20pct_regression():
    """The acceptance case: a clean 20% step-time regression exits
    nonzero; statistically-insignificant jitter does not."""
    base = _steady(seed=1)
    verdict = gate.compare_reports(
        _report(base), _report([x * 1.2 for x in base]))
    assert not verdict["ok"] and verdict["exit_code"] == 1
    assert any("regression" in r for r in verdict["reasons"])
    assert verdict["comparison"]["delta_pct"] == pytest.approx(20.0,
                                                               abs=1.0)
    # same magnitude of change, hidden inside the noise: no flag
    noisy = _steady(n=12, jitter=2.0, seed=2)
    verdict = gate.compare_reports(
        _report(noisy), _report([x + 0.05 for x in noisy]))
    assert verdict["ok"]


def test_gate_flags_contamination_burst():
    """The round-5 scenario, automated: a concurrent probe slows a
    stretch of steps mid-run on the TPU -> invalid evidence (exit 2),
    NOT a pass or a mere regression. (The detector auto-arms for
    accelerator reports; platform-tagged synthetics exercise that
    default path.)"""
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite"}
    samples = _steady(n=50, seed=3)
    for i in range(20, 27):
        samples[i] *= 5.0
    verdict = gate.compare_reports(_report(_steady(seed=4), **tpu),
                                   _report(samples, **tpu))
    assert not verdict["ok"] and verdict["exit_code"] == 2
    assert any(r.startswith("invalid_evidence") for r in verdict["reasons"])
    assert verdict["contamination"]["max_burst"] >= 4
    # the identical CPU-platform report is NOT auto-checked: shared-host
    # scheduler stalls are legitimate there and the median comparison
    # absorbs them (force with check_contamination="always")
    cpu_verdict = gate.compare_reports(_report(_steady(seed=4)),
                                       _report(samples))
    assert cpu_verdict["exit_code"] != 2
    forced = gate.compare_reports(_report(_steady(seed=4)),
                                  _report(samples),
                                  check_contamination="always")
    assert forced["exit_code"] == 2


def test_gate_detect_bimodal():
    det = gate.detect_contamination([10.0] * 30 + [14.0] * 15)
    assert det["contaminated"]
    assert any("bimodal" in r for r in det["reasons"])
    # a clean distribution is not contaminated
    assert not gate.detect_contamination(_steady())["contaminated"]
    # too few samples: detection is a no-op, not a false positive
    assert not gate.detect_contamination([1.0, 50.0])["contaminated"]


def test_gate_empty_report_is_invalid():
    verdict = gate.compare_reports(_report(_steady()), _report([]))
    assert verdict["exit_code"] == 2
    assert any("no step samples" in r for r in verdict["reasons"])


def test_gate_env_mismatch_is_invalid():
    """A CPU-fallback number must never gate a TPU claim (the round-5
    headline failure mode)."""
    base = _report(_steady(), platform="tpu", device_kind="TPU v5 lite")
    cur = _report(_steady(seed=5), platform="cpu", device_kind="cpu")
    verdict = gate.compare_reports(base, cur)
    assert verdict["exit_code"] == 2
    assert any("different hardware" in r for r in verdict["reasons"])
    verdict = gate.compare_reports(base, cur, allow_env_mismatch=True)
    assert verdict["exit_code"] == 0
    assert any("env mismatch" in w for w in verdict["warnings"])


def _with_numerics(rep, drift, name="constraint", n=50):
    rep = dict(rep)
    rep["numerics"] = {
        "invariants": {name: {"n": n, "first": 1e-8,
                              "last": 1e-8 + n * drift,
                              "drift_per_step": drift}},
        "health_events": n, "diverged": [], "forensic_bundles": []}
    return rep


def test_gate_numerics_drift_regression():
    """The tentpole acceptance: a constraint-drift regression fails the
    gate (exit 1) exactly like a step-time regression — and names the
    offending invariant."""
    base = _with_numerics(_report(_steady()), 1e-10)
    cur = _with_numerics(_report(_steady(seed=9)), 5e-7)
    verdict = gate.compare_reports(base, cur)
    assert not verdict["ok"] and verdict["exit_code"] == 1
    assert any("numerics regression" in r and "'constraint'" in r
               for r in verdict["reasons"])
    assert verdict["numerics"]["constraint"]["current_drift"] == 5e-7
    # same drift: pass; modest growth within the factor: pass
    assert gate.compare_reports(base, _with_numerics(
        _report(_steady(seed=9)), 2e-10))["exit_code"] == 0
    # numerics checks can be disabled
    assert gate.compare_reports(base, cur,
                                check_numerics=False)["exit_code"] == 0
    # a ~zero baseline slope cannot flag drift under the floor
    z = gate.compare_reports(_with_numerics(_report(_steady()), 0.0),
                             _with_numerics(_report(_steady(seed=9)),
                                            5e-12))
    assert z["exit_code"] == 0


def test_gate_numerics_skips_degenerate_series():
    """A baseline invariant with <2 samples has no usable slope (the
    ledger's least-squares degenerates to 0.0) — the gate must warn
    and skip, not flag honest roundoff against the bare floor."""
    base = _with_numerics(_report(_steady()), 0.0, n=1)
    cur = _with_numerics(_report(_steady(seed=9)), 1e-9)
    verdict = gate.compare_reports(base, cur)
    assert verdict["exit_code"] == 0
    assert any("too few samples" in w for w in verdict["warnings"])
    assert "constraint" not in verdict["numerics"]


def test_gate_numerics_coverage_loss_warns():
    base = _with_numerics(_report(_steady()), 1e-10)
    verdict = gate.compare_reports(base, _report(_steady(seed=9)))
    assert verdict["exit_code"] == 0
    assert any("sentinel coverage was lost" in w
               for w in verdict["warnings"])


def test_gate_diverged_run_is_invalid_evidence():
    """A sentinel trip invalidates the run: broken step times prove
    nothing in either direction — and the verdict points at the
    forensic bundle."""
    cur = _report(_steady())
    cur["numerics"] = {"invariants": {}, "health_events": 3,
                       "diverged": [{"step": 33, "fields": ["dfdt"],
                                     "offending_invariant": None}],
                       "forensic_bundles": ["/x/bundle.json"]}
    verdict = gate.compare_reports(_report(_steady(seed=1)), cur)
    assert verdict["exit_code"] == 2
    assert any("diverged at step 33" in r for r in verdict["reasons"])
    assert any("bundle" in r for r in verdict["reasons"])
    # --no-numerics downgrades it back to a plain perf comparison
    assert gate.compare_reports(_report(_steady(seed=1)), cur,
                                check_numerics=False)["exit_code"] == 0


def _with_cold_start(rep, ttfs, claimed=False, artifacts=None):
    rep = dict(rep)
    rep["cold_start"] = {
        "time_to_first_step_s": ttfs,
        "phases": {"import_s": 1.0, "trace_s": 0.5,
                   "compile_s": max(0.0, ttfs - 2.0),
                   "first_dispatch_s": 0.1},
        "compiles": [], "n_compile_events": 0,
        "cache": {"dir": "/c", "hits": 4, "misses": 1,
                  "hit_rate": 0.8},
        "warmstart": {"claimed": claimed,
                      "artifacts": artifacts or []},
    }
    return rep


def test_gate_cold_start_regression():
    """A time-to-first-step blowup fails CI like a slow step — but only
    past BOTH the relative factor and the absolute floor (small-run
    cold starts jitter by whole seconds)."""
    base = _with_cold_start(_report(_steady()), 10.0)
    bad = _with_cold_start(_report(_steady(seed=7)), 40.0)
    verdict = gate.compare_reports(base, bad)
    assert not verdict["ok"] and verdict["exit_code"] == 1
    assert any("cold-start regression" in r for r in verdict["reasons"])
    assert verdict["cold_start"]["baseline_s"] == 10.0
    # within the factor: pass
    ok = gate.compare_reports(
        base, _with_cold_start(_report(_steady(seed=7)), 13.0))
    assert ok["exit_code"] == 0
    # past the factor but under the absolute floor: pass (2 s vs 5 s)
    ok = gate.compare_reports(
        _with_cold_start(_report(_steady()), 1.0),
        _with_cold_start(_report(_steady(seed=7)), 3.0))
    assert ok["exit_code"] == 0
    # losing cold-start coverage warns, never fails
    lost = gate.compare_reports(base, _report(_steady(seed=7)))
    assert lost["exit_code"] == 0
    assert any("cold-start coverage was lost" in w
               for w in lost["warnings"])
    # ... including a current cold_start section whose
    # time-to-first-step is None (compile telemetry but the driver
    # never reached a first step) — the metric is gone, not passing
    none_cs = _with_cold_start(_report(_steady(seed=7)), 2.0)
    none_cs["cold_start"]["time_to_first_step_s"] = None
    lost2 = gate.compare_reports(base, none_cs)
    assert lost2["exit_code"] == 0
    assert any("coverage was lost" in w for w in lost2["warnings"])
    # opt-out
    assert gate.compare_reports(base, bad,
                                check_cold_start=False)["exit_code"] == 0


def test_gate_warmstart_fingerprint_mismatch_refused(tmp_path):
    """The invalid-evidence refusal: a report CLAIMING warm start over
    artifacts whose fingerprints mismatch measured something other than
    the programs it says it ran — exit 2, never 0 or 1."""
    base = _with_cold_start(_report(_steady()), 10.0)
    cur = _with_cold_start(
        _report(_steady(seed=7)), 3.0, claimed=True,
        artifacts=[{"label": "step", "fingerprint": "abc123",
                    "match": False,
                    "reason": "versions: exported 0.4.0 vs live 0.4.37"}])
    verdict = gate.compare_reports(base, cur)
    assert verdict["exit_code"] == 2
    assert any("claims warm start" in r and "mismatch" in r
               for r in verdict["reasons"])
    # matched artifacts pass clean
    ok = gate.compare_reports(base, _with_cold_start(
        _report(_steady(seed=7)), 3.0, claimed=True,
        artifacts=[{"label": "step", "fingerprint": "abc123",
                    "match": True}]))
    assert ok["exit_code"] == 0
    # an artifact that LOADED fine but computed different numbers than
    # the jit path (the cached-donated-executable failure mode) is
    # equally invalid evidence
    ne = gate.compare_reports(base, _with_cold_start(
        _report(_steady(seed=7)), 3.0, claimed=True,
        artifacts=[{"label": "step", "fingerprint": "abc123",
                    "match": True, "bitexact": False}]))
    assert ne["exit_code"] == 2
    assert any("different results" in r for r in ne["reasons"])
    # the CLI pins the exit code (and --no-cold-start opts out)
    bp = tmp_path / "b.json"
    cp = tmp_path / "c.json"
    bp.write_text(json.dumps(base))
    cp.write_text(json.dumps(cur))
    assert gate.main(["--baseline", str(bp), "--current", str(cp)]) == 2
    assert gate.main(["--baseline", str(bp), "--current", str(cp),
                      "--no-cold-start"]) == 0


def test_ledger_cold_start_ingestion(tmp_path):
    """cold_start/compile_cache/warmstart events land in the report's
    cold_start section with the trace/compile split per program."""
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        log.emit("compile_cache", dir="/c", enabled=True)
        log.emit("compile", label="step", source="aot",
                 trace_seconds=0.4, compile_seconds=1.6,
                 fingerprint="abc", fingerprint_kind="lowered",
                 cache_hits=0, cache_misses=1, cache_hit=False)
        log.emit("compile", label="helper", source="dispatch",
                 trace_seconds=0.1, compile_seconds=0.0,
                 cache_hits=1, cache_misses=0, cache_hit=True)
        log.emit("warmstart_load", label="step", fingerprint="abc",
                 path="/w/step.jaxexport")
        log.emit("warmstart_mismatch", label="old_step",
                 fingerprint="stale1",
                 reason="versions: exported 0.4.0 vs live 0.4.37")
        log.emit("cold_start", time_to_first_step_s=4.5,
                 phases={"import_s": 2.0, "trace_s": 0.4,
                         "compile_s": 1.6, "first_dispatch_s": 0.1})
        log.emit("step_time", ms=2.0)
    led = ledger.PerfLedger.from_events(path)
    cs = led.cold_start()
    assert cs["time_to_first_step_s"] == 4.5
    assert cs["phases"]["import_s"] == 2.0
    assert cs["cache"]["dir"] == "/c"
    assert cs["cache"]["hits"] == 1 and cs["cache"]["misses"] == 1
    assert cs["cache"]["hit_rate"] == 0.5
    # rows sorted slowest-first, trace/compile split carried through
    assert cs["compiles"][0]["label"] == "step"
    assert cs["compiles"][0]["trace_s"] == 0.4
    assert cs["compiles"][0]["compile_s"] == 1.6
    assert cs["compiles"][0]["cache_hit"] is False
    assert cs["warmstart"]["claimed"] is True
    assert cs["warmstart"]["artifacts"][0]["match"] is True
    # a refused artifact is an HONEST fallback: it lands in
    # `fallbacks` (the gate warns), never in `artifacts` as a
    # match:False row (which the gate would refuse as invalid evidence)
    assert len(cs["warmstart"]["artifacts"]) == 1
    assert cs["warmstart"]["fallbacks"][0]["label"] == "old_step"
    rep_full = led.report()
    verdict = gate.compare_reports(rep_full, rep_full)
    assert verdict["exit_code"] == 0
    assert any("cold fallback" in w for w in verdict["warnings"])
    md = ledger.render_markdown(led.report())
    assert "Cold start" in md and "time to first step" in md
    # a ledger with no compile telemetry has no cold_start section
    assert ledger.PerfLedger(label="bare").cold_start() is None


def test_gate_cli_exit_codes(tmp_path):
    """main() drives argparse -> comparison -> exit code, including the
    missing-baseline paths."""
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_report(_steady())))
    reg = tmp_path / "reg.json"
    reg.write_text(json.dumps(_report([x * 1.3 for x in _steady()])))
    assert gate.main(["--baseline", str(good),
                      "--current", str(good)]) == 0
    assert gate.main(["--baseline", str(good),
                      "--current", str(reg)]) == 1
    missing = str(tmp_path / "absent.json")
    assert gate.main(["--baseline", missing,
                      "--current", str(good)]) == 3
    assert gate.main(["--baseline", missing, "--current", str(good),
                      "--allow-missing-baseline"]) == 0
    assert gate.main(["--baseline", str(good),
                      "--current", missing]) == 4
    # a custom threshold turns the same delta into a pass
    assert gate.main(["--baseline", str(good), "--current", str(reg),
                      "--threshold-pct", "50"]) == 0


def test_ledger_comm_join_from_events(tmp_path):
    """The modeled-vs-measured comm join, from synthetic events: the
    lint event's static_comm block supplies the model, halo_traffic
    the measured side, and the ledger pairs them class-against-class
    (the halo counter joins the model's halo class, not the program
    total that also carries scalar all-reduces)."""
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[16, 16, 16], nsteps=4)
        for ms in (2.0, 2.1):
            log.emit("step_time", ms=ms)
        log.emit("trace_summary", scopes={
            "halo_overlap": {"count": 6, "total_ms": 3.0}})
        log.emit("halo_traffic", bytes_per_step=5120)
        log.emit("lint", ok=True, static_comm={
            "smoke_overlap": {
                "modeled": True, "total_bytes": 5632,
                "per_invocation_bytes": {"halo": 5120, "scalar": 512},
                "collectives": 3},
            "smoke_spectra": {
                "modeled": True, "total_bytes": 4096,
                "per_invocation_bytes": {"transpose": 4096},
                "collectives": 1}})
    led = ledger.PerfLedger.from_events(path)
    comm = led.report()["comm"]
    assert comm["covered"] is True
    legs = {leg["target"]: leg for leg in comm["legs"]}
    halo = legs["smoke_overlap"]
    # class-matched join: 5120 (halo class), not the 5632 total
    assert halo["class"] == "halo"
    assert halo["modeled_bytes"] == 5120
    assert halo["modeled_total_bytes"] == 5632
    assert halo["measured_bytes"] == 5120.0
    assert halo["measured_source"] == "halo_traffic"
    assert halo["calls"] == 6
    assert halo["excess_pct"] == 0.0 and halo["within"] is True
    # no byte counter for the spectra program: model-only row
    spectra = legs["smoke_spectra"]
    assert spectra["modeled_bytes"] == 4096
    assert spectra["measured_bytes"] is None
    assert spectra["within"] is None
    # a run with neither model nor counter carries no comm section
    bare = str(tmp_path / "bare.jsonl")
    with events.EventLog(bare) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        log.emit("step_time", ms=1.0)
    assert ledger.PerfLedger.from_events(bare).report()["comm"] is None
