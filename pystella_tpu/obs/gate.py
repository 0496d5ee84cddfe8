"""Noise-aware perf regression gate over two ``perf_report.json`` files.

::

    python -m pystella_tpu.obs.gate --baseline old.json --current new.json

Exit codes (CI and the armed-hardware-revalidation scripts key on them):

====  ====================================================================
0     pass (no regression beyond noise, evidence valid)
1     regression: current median step time exceeds baseline by more than
      the threshold AND more than ``mad_k`` robust sigmas of noise — or
      a NUMERICS regression: a sentinel invariant's drift slope exceeds
      ``drift_factor`` x the baseline's (constraint drift worse than
      baseline fails CI the same way a slow step does) — or a
      COLD-START regression: time-to-first-step exceeds the baseline's
      by both ``cold_start_factor`` and ``cold_start_floor`` seconds —
      or an ENSEMBLE regression: batched member throughput
      (member-steps/s) drops more than ``ensemble_threshold_pct`` below
      the baseline's — or a SPECTRAL regression: the ``fft`` section's
      spectra p50 ms/call exceeds the baseline's by more than
      ``fft_threshold_pct`` — or a SERVICE SLO regression: the
      ``service`` section's queue-latency p95 (or warm-lease
      time-to-first-step p50) exceeds the baseline's by both the
      configured factor and floor — or a DEADLINE-MISS SLO regression:
      the ``latency`` section's deadline-miss rate exceeds the
      baseline's by both ``latency_miss_factor`` and
      ``latency_miss_floor`` (``--no-latency`` opts out; traced
      requests whose span tree fails to assemble degrade to a
      coverage-loss warning) — or a FLEET SLO regression: the
      ``fleet`` section's aggregated queue-p95 or warm-TTFS exceeds
      the baseline's by both the configured factor and floor
      (``--no-fleet`` opts out) — or a COMM EXCESS: a ``comm`` leg's
      measured collective traffic exceeds the dataflow lint tier's
      static model by more than ``comm_excess_pct`` (the model is an
      upper bound on what the program's collectives can move per
      invocation; measured above it means traffic the model does not
      attribute — ``--no-comm`` opts out)
2     invalid evidence: the contamination detector flagged the run
      (outlier burst / bimodal step times — the round-5 concurrent-probe
      signature), the report has no step samples, the run DIVERGED (a
      sentinel trip in the ``numerics`` section — broken step times
      prove nothing), the report CLAIMS warm start over AOT artifacts
      whose fingerprints mismatch the live compiler stack, the
      ``service`` section claims warm ADMISSIONS over mismatched
      fingerprints (the leases did not dispatch the programs the
      admission contract names), the report claims fewer incidents
      than its ``resilience`` event record carries (a clean headline
      over a degraded fleet), the report's ``alerts`` section carries a
      live burn alert UNRESOLVED at exit while the matching post-hoc
      SLO section claims green (the live and post-hoc halves
      contradict; ``--no-alerts`` opts out, alert-FLAP growth merely
      warns), the report's ``perf`` section carries a ``perf_anomaly``
      UNRESOLVED at exit while the post-hoc step-time verdict claims
      green (same contradiction for the continuous-performance plane;
      ``--no-perf`` opts out), the report's ``fleet`` section claims COMPLETE fleet
      coverage while its own scrape record shows lost replicas or
      failed scrapes (fleet aggregates over the survivors are partial
      evidence; an HONESTLY-partial fleet record is annotated
      degraded instead), the report's ``comm`` section claims
      modeled-vs-measured coverage (``covered: true``) while no leg
      actually carries a static model (a coverage claim with nothing
      behind it — the dataflow lint tier never ran, or the section
      was assembled by hand), or baseline and current were measured on
      different hardware. Exception: a
      run that recorded AND recovered REAL (non-harness-injected)
      incidents (``resilience`` section,
      :mod:`pystella_tpu.resilience`) keeps its evidence —
      regressions and contamination-like bursts measured across the
      recovery stalls are ANNOTATED as degraded (warnings +
      ``verdict["degraded"]``) rather than failed or refused; a
      harness DRILL (``faults_injected`` covers the incident count)
      annotates without softening any verdict
3     missing or unreadable baseline (suppress with
      ``--allow-missing-baseline``, e.g. on a branch's first run)
4     unreadable current report / bad usage
====  ====================================================================

The comparison is ``median +- k*MAD``, not single wall-clock numbers: a
regression must clear both a relative threshold (``--threshold-pct``,
default 10%) and a noise bar (``--mad-k`` Gaussian-consistent sigmas,
default 3) before the gate fails, so ordinary scheduler jitter cannot
flip CI, and a real 20% step-time regression reliably does.

The contamination detector automates what round 5 did by hand (a fresh
hardware run was invalidated because a concurrent probe stole the chip
mid-measurement): a burst of consecutive outlier steps, an excessive
outlier fraction, or a bimodal step-time distribution marks the run
``invalid_evidence`` — *neither pass nor fail*, because a contaminated
measurement can prove nothing in either direction.

The module body is stdlib-only on purpose (report comparison must not
require a working accelerator stack), but the ``python -m`` entry point
imports the ``pystella_tpu`` package — and therefore jax — like any
in-repo CI environment has. A truly jax-free supervisor should call
:func:`compare_reports` from a by-file module load
(``importlib.util.spec_from_file_location``), loading ``ledger.py`` the
same way first.
"""

from __future__ import annotations

import argparse
import json
import sys

from pystella_tpu import config as _config
from pystella_tpu.obs import events as _events
from pystella_tpu.obs.ledger import mad as _mad
from pystella_tpu.obs.ledger import percentile as _percentile

__all__ = ["detect_contamination", "compare_reports", "load_report",
           "main"]

#: MAD -> Gaussian-consistent sigma
MAD_SIGMA = 1.4826


def load_report(path):
    """Parse one ``perf_report.json``; raises ``OSError``/``ValueError``
    on unreadable input (callers map these to exit codes)."""
    with open(path) as f:
        rep = json.load(f)
    if not isinstance(rep, dict) or "steps" not in rep:
        raise ValueError(f"{path}: not a perf report (no 'steps' key)")
    return rep


def detect_contamination(samples_ms, outlier_k=5.0, rel_floor=0.25,
                         burst_limit=4, frac_limit=0.10,
                         check_bimodal=True):
    """Flag step-time samples that look contaminated by concurrent load.

    An *outlier* is a step slower than
    ``median + max(outlier_k * 1.4826 * MAD, rel_floor * median)`` (the
    relative floor keeps a quantized, near-zero-MAD distribution from
    flagging ordinary jitter). The run is contaminated when

    - outliers form a consecutive burst of ``burst_limit`` or more (a
      probe holding the device for a stretch — the round-5 signature),
    - outliers exceed ``frac_limit`` of all samples, or
    - with ``check_bimodal``, the distribution is bimodal: a 2-means
      split finds two clusters, each holding >= 20% of samples,
      separated by far more than the within-cluster spread (device
      timesharing alternating fast/slow).

    :func:`compare_reports` arms this detector for ACCELERATOR reports
    (``check_contamination="auto"``): OS scheduling on a shared CPU
    host legitimately stalls and multi-modalizes millisecond step times
    (measured on the smoke bench), and the median-based comparison
    absorbs that by construction, while an accelerator's step times are
    tight unless someone else holds the chip.

    Returns a dict: ``contaminated`` (bool), ``reasons`` (list of
    strings), plus the measured diagnostics.
    """
    out = {"contaminated": False, "reasons": [], "n_samples":
           len(samples_ms), "outlier_fraction": 0.0, "max_burst": 0,
           "threshold_ms": None}
    if len(samples_ms) < 8:
        # too few samples to characterize noise; detection is a no-op
        # (the gate separately rejects EMPTY reports as invalid)
        return out
    s = sorted(samples_ms)
    med = _percentile(s, 50)
    sigma = MAD_SIGMA * (_mad(s) or 0.0)
    thresh = med + max(outlier_k * sigma, rel_floor * med)
    out["threshold_ms"] = thresh

    flags = [x > thresh for x in samples_ms]
    nout = sum(flags)
    out["outlier_fraction"] = nout / len(flags)
    burst = longest = 0
    for f in flags:
        burst = burst + 1 if f else 0
        longest = max(longest, burst)
    out["max_burst"] = longest

    if longest >= burst_limit:
        out["reasons"].append(
            f"outlier burst: {longest} consecutive steps above "
            f"{thresh:.3f} ms (limit {burst_limit})")
    if out["outlier_fraction"] > frac_limit:
        out["reasons"].append(
            f"outlier fraction {out['outlier_fraction']:.1%} above "
            f"{frac_limit:.0%}")

    if check_bimodal:
        lo_c, hi_c, lo_n, hi_n, gap, spread = _two_means(samples_ms)
        minority = min(lo_n, hi_n) / len(samples_ms)
        if (minority >= 0.2
                and gap > max(6 * MAD_SIGMA * spread, rel_floor * med)):
            out["reasons"].append(
                f"bimodal step times: clusters at {lo_c:.3f} / "
                f"{hi_c:.3f} ms ({lo_n}/{hi_n} samples)")
    out["contaminated"] = bool(out["reasons"])
    return out


def _two_means(xs, iters=16):
    """1-D 2-means: ``(lo_center, hi_center, lo_n, hi_n, gap,
    within_cluster_mad)``."""
    s = sorted(xs)
    lo, hi = float(s[0]), float(s[-1])
    if lo == hi:
        return lo, hi, len(s), 0, 0.0, 0.0
    for _ in range(iters):
        cut = (lo + hi) / 2
        a = [x for x in s if x <= cut]
        b = [x for x in s if x > cut]
        if not a or not b:
            break
        nlo, nhi = sum(a) / len(a), sum(b) / len(b)
        if (nlo, nhi) == (lo, hi):
            break
        lo, hi = nlo, nhi
    a = [x for x in s if x <= (lo + hi) / 2]
    b = [x for x in s if x > (lo + hi) / 2]
    devs = [abs(x - lo) for x in a] + [abs(x - hi) for x in b]
    return lo, hi, len(a), len(b), hi - lo, (_mad(devs) or 0.0)


def _env_comparable(base_env, cur_env):
    """Hardware identity check: a baseline measured on different silicon
    proves nothing about the current run (the round-5 failure mode was
    exactly a CPU-fallback number standing in for a TPU claim)."""
    mismatches = []
    for key in ("platform", "device_kind"):
        b, c = base_env.get(key), cur_env.get(key)
        if b is not None and c is not None and b != c:
            mismatches.append(f"{key}: baseline {b!r} vs current {c!r}")
    return mismatches


def compare_reports(baseline, current, threshold_pct=10.0, mad_k=3.0,
                    outlier_k=5.0, burst_limit=4, frac_limit=0.10,
                    allow_env_mismatch=False,
                    check_contamination="auto", check_numerics=True,
                    drift_factor=10.0, drift_floor=1e-12,
                    check_lint=True, check_cold_start=True,
                    cold_start_factor=1.5, cold_start_floor=5.0,
                    check_ensemble=True, ensemble_threshold_pct=20.0,
                    check_resilience=True,
                    check_fft=True, fft_threshold_pct=25.0,
                    check_comm=True, comm_excess_pct=25.0,
                    check_service=True, service_queue_factor=2.5,
                    service_queue_floor_s=0.5,
                    service_ttfs_factor=2.5,
                    service_ttfs_floor_s=1.0,
                    check_latency=True, latency_miss_factor=2.0,
                    latency_miss_floor=0.05, check_alerts=True,
                    check_fleet=True, fleet_queue_factor=2.5,
                    fleet_queue_floor_s=0.5, fleet_ttfs_factor=2.5,
                    fleet_ttfs_floor_s=1.0, check_perf=True,
                    check_capacity=True, goodput_factor=2.0,
                    goodput_floor=1.0, reconciliation_warn_pct=25.0):
    """Pure comparison core (the CLI is a thin wrapper; tests drive
    this). Returns a verdict dict with ``exit_code``.

    ``check_contamination``: ``"auto"`` (default) arms the detector for
    accelerator reports only — on a CPU host the OS scheduler
    legitimately stalls a tail of steps (measured: 12% of smoke steps
    15x slower under this container's scheduler), which the
    MEDIAN-based comparison absorbs by construction, while on a TPU the
    step times are tight unless someone else holds the chip (the
    round-5 scenario the detector exists for). ``"always"`` /
    ``"never"`` force it either way.

    ``check_lint`` (default on): a run whose ``lint`` section records a
    FAILED static analysis (:mod:`pystella_tpu.lint` — donation misses,
    unexpected collectives, host syncs on the step path, ...) is
    invalid evidence (exit 2): its step times measure a program known
    to be off the fast path, so they prove nothing about the code as
    designed. A baseline with lint coverage that the current run lost
    degrades to a warning.

    ``check_cold_start`` (default on): a report whose ``cold_start``
    section *claims* warm start while any loaded artifact's fingerprint
    mismatches is invalid evidence (exit 2 — the run did not execute
    the programs it says it did), and a time-to-first-step more than
    ``cold_start_factor`` x the baseline's AND ``cold_start_floor``
    seconds above it fails the gate like a step-time regression (exit
    1) — cold-start time IS a production metric.

    ``check_numerics`` (default on) extends the gate beyond step times:
    a run whose ``numerics`` section records a sentinel trip is invalid
    evidence (exit 2 — diverged step times prove nothing), and a
    physics-invariant **drift slope** more than ``drift_factor`` times
    the baseline's (each floored at ``drift_floor``/step so a ~zero
    baseline slope cannot make any finite drift a regression) fails the
    gate exactly like a perf regression (exit 1) — a silent numerics
    regression fails CI the same way a slow step does.

    ``check_ensemble`` (default on): when both reports carry an
    ``ensemble`` section (:mod:`pystella_tpu.ensemble` batch totals), a
    **member-throughput** drop of more than ``ensemble_threshold_pct``
    vs the baseline's member-steps/s fails the gate (exit 1) — batched
    population throughput is a first-class production metric, gated
    like single-run step time. Lost ensemble coverage (baseline has the
    section, current does not) degrades to a warning, and an eviction
    count exceeding the baseline's warns too (evictions are legitimate
    per-draw physics, but a jump usually means a broken sampler).

    ``check_resilience`` (default on): the degraded-fleet triage for
    reports carrying a ``resilience`` section
    (:mod:`pystella_tpu.resilience`). A run that **recorded and
    recovered incidents** (device loss, numerics trips) and still
    completed is *degraded, not broken*: its step-time regression and
    contamination-like sample bursts are measured ACROSS the recovery
    stalls, so the gate **annotates** them (warning +
    ``verdict["degraded"]``) instead of failing or refusing — slow
    because the fleet was on fire is a different verdict from slow.
    Only REAL incidents earn that softening: a harness-injected drill
    (``faults_injected`` covers the incident count, e.g. the smoke
    pipeline's scripted device loss) still marks the verdict degraded
    but leaves the regression/contamination verdicts fully armed —
    otherwise the ever-present smoke drill would permanently disarm
    the CI gate.
    The refusal cuts the other way: a report whose supervisor CLAIMS
    fewer incidents than its event log records
    (``resilience.consistent`` false) is hiding a degraded fleet
    behind a clean headline — invalid evidence, exit 2. Lost
    resilience coverage warns, and unresolved incidents (detected but
    never resumed) warn too. Degraded-MODE accounting (the re-mesh
    library, :mod:`pystella_tpu.resilience.remesh`): a report whose
    ``resilience.degraded`` block records a re-mesh but whose
    ``throughput.per_chip`` still normalizes by the full pre-loss
    mesh is claiming full-mesh throughput from a degraded run —
    invalid evidence, exit 2 (the honest figure divides by the
    survivors; the ledger produces it automatically from the
    ``remesh_plan`` record) — and a run that finished degraded
    without any ``remesh_plan`` record warns (unauditable).

    ``check_fleet`` (default on): the federation half of the same
    honesty rule, for reports carrying a ``fleet`` section
    (:mod:`pystella_tpu.obs.fleet`). A report whose fleet coverage
    block claims ``complete`` while its own scrape record shows lost
    replicas or failed scrapes is refused (exit 2) — fleet aggregates
    over the survivors are partial evidence. The honest version of the
    same record (coverage says partial) is annotated
    (``verdict["degraded"]`` + warning), never silently accepted.
    Against a baseline, fleet queue-p95 and fleet warm-TTFS regress
    under the same factor+floor bars as the single-replica service
    legs (exit 1); version/flag skew appearing, warm-fingerprint
    divergence, and fleet-alert flap growth warn. ``--no-fleet`` opts
    out.

    ``check_perf`` (default on): the continuous-performance half of
    the alert-evidence rule, for reports carrying a ``perf`` section
    (:mod:`pystella_tpu.obs.perf`). A ``perf_anomaly`` still
    unresolved when the run record ended — the change-point detector
    watched a sustained step-time shift never recover — beside a GREEN
    post-hoc step-time verdict is the same live/post-hoc contradiction
    as an unresolved burn alert: invalid evidence, exit 2
    (``--no-perf`` opts out). An unresolved anomaly whose post-hoc
    step verdict also failed is corroboration (warning). Anomalies
    that fired with NO flight-recorder capture recorded warn (the
    profiling evidence the plane exists to capture is missing —
    usually ``PYSTELLA_PERF_CAPTURE_DIR`` unset); anomaly-flap growth
    and lost perf coverage warn like the other sections.

    ``check_capacity`` (default on): the capacity-and-goodput half of
    the evidence rule, for reports carrying a ``capacity`` section
    (:mod:`pystella_tpu.obs.capacity`). A report whose capacity
    coverage block claims ``complete`` watermark coverage while
    recording ZERO live watermark samples is refused (exit 2) — a
    full-coverage reconciliation claim with no device readings behind
    it proves nothing. The honest version (coverage says
    ``predicted_only``, the CPU degrade) is annotated
    (``verdict["degraded"]`` + warning), never silently accepted, and
    a predicted-vs-measured reconciliation error beyond
    ``reconciliation_warn_pct`` warns (the footprint model is
    drifting from the device). Against a baseline, **goodput**
    (committed member-steps per chip-second) regresses DOWNWARD: the
    gate fails (exit 1) when current goodput drops below baseline /
    ``goodput_factor`` AND by more than ``goodput_floor``
    steps/chip-s absolute — the factor+floor shape of every other SLO
    leg, with the inequality flipped because higher is better. Waste
    chip-second growth (replay + preempt-drain share) and lost
    capacity coverage warn. ``--no-capacity`` opts out.
    """
    verdict = {"ok": True, "exit_code": 0, "reasons": [],
               "warnings": []}

    cres = current.get("resilience") or {}
    n_incidents = int(cres.get("n_incidents") or 0)
    injected = int(cres.get("faults_injected") or 0)
    if check_resilience and cres and cres.get("consistent") is False:
        verdict.update(ok=False, exit_code=2)
        verdict["reasons"].append(
            "invalid_evidence: run claims "
            f"{cres.get('claimed_incidents')} incident(s) but its "
            f"event record carries {n_incidents} — a clean headline "
            "over a degraded fleet proves nothing; trust the event "
            "log, not the claim")
        return verdict
    # degraded-mode accounting (the re-mesh library,
    # resilience.remesh): a run that finished on a DEGRADED mesh must
    # say so auditable. A recorded remesh whose throughput section
    # still normalizes per pre-loss chip is claiming full-mesh
    # throughput from a degraded run — invalid evidence; a run that
    # degraded (run_degraded) without any remesh_plan record cannot be
    # audited at all and warns.
    deg = cres.get("degraded")
    if check_resilience and isinstance(deg, dict):
        if deg.get("new_mesh"):
            used = deg.get("devices_used")
            rate = (current.get("throughput") or {}).get(
                "site_updates_per_s")
            pc = (current.get("throughput") or {}).get("per_chip")
            if used and rate and (not pc
                                  or pc.get("basis") != "surviving"
                                  or pc.get("chips") != used):
                verdict.update(ok=False, exit_code=2)
                verdict["reasons"].append(
                    "invalid_evidence: run re-meshed to "
                    f"{deg.get('new_mesh')} ({used} surviving "
                    "device(s)) but its throughput claims a "
                    "full-mesh per-chip normalization — a degraded "
                    "run's per-chip figure divides by the SURVIVORS")
                return verdict
        elif deg.get("events") and not deg.get("remesh_plans"):
            verdict["warnings"].append(
                "resilience: the run finished degraded (run_degraded "
                "recorded) without a matching remesh_plan record — "
                "the degraded mesh cannot be audited; use the "
                "RemeshPlanner (or emit remesh_plan from the hook)")
    elif check_resilience and deg:
        # pre-remesh-library reports: a bare run_degraded event list
        verdict["warnings"].append(
            "resilience: the run finished degraded (run_degraded "
            "recorded) without a matching remesh_plan record — "
            "the degraded mesh cannot be audited; use the "
            "RemeshPlanner (or emit remesh_plan from the hook)")
    # ANY recorded incident marks the evidence degraded (annotated) —
    # but only REAL (non-injected) incidents soften the verdicts
    # below. A harness DRILL (faults_injected covers the incident
    # count — e.g. the smoke pipeline's scripted device loss, which
    # runs outside the timed step window) proves the recovery
    # machinery without excusing anything: if every drill-carrying
    # report earned the shield, the regression gate would never fail
    # on smoke evidence again.
    if check_resilience and n_incidents > 0:
        verdict["degraded"] = True
        verdict["warnings"].append(
            f"resilience: {n_incidents} recorded incident(s)"
            + (f" ({min(injected, n_incidents)} harness-injected "
               "drill(s))" if injected else "")
            + " — evidence from a degraded fleet; see the report's "
            "resilience section")
    real_incidents = max(0, n_incidents - injected)
    degraded_evidence = bool(
        check_resilience and real_incidents > 0
        and cres.get("completed") is not False
        and not cres.get("unresolved"))
    if check_resilience and cres.get("unresolved"):
        verdict["warnings"].append(
            f"resilience: {cres['unresolved']} detected incident(s) "
            "never resumed — the run likely died mid-recovery; treat "
            "its samples with care")

    cur_samples = current.get("samples_ms") or []
    cur_steps = current.get("steps") or {}
    if not cur_steps.get("count"):
        verdict.update(ok=False, exit_code=2)
        verdict["reasons"].append(
            "invalid_evidence: current report has no step samples")
        return verdict

    if check_lint:
        cur_lint = current.get("lint")
        if cur_lint and not cur_lint.get("ok", True):
            verdict.update(ok=False, exit_code=2)
            verdict["reasons"].append(
                "invalid_evidence: the run's static analysis FAILED "
                f"({cur_lint.get('errors', '?')} lint error(s)) — the "
                "measured program is known to be off the fast path; "
                "fix the lint findings "
                + (f"({'; '.join(cur_lint['first_errors'][:3])}) "
                   if cur_lint.get("first_errors") else "")
                + "and re-measure")
            return verdict
        if (baseline is not None and baseline.get("lint")
                and not current.get("lint")):
            verdict["warnings"].append(
                "lint: baseline carried a static-analysis verdict but "
                "the current run has none — lint coverage was lost")

    if check_cold_start:
        ws = (current.get("cold_start") or {}).get("warmstart") or {}
        if ws.get("claimed"):
            bad = [a for a in ws.get("artifacts") or []
                   if a.get("match") is False
                   or a.get("bitexact") is False]
            if bad:
                # the report says it ran AOT-loaded programs whose
                # fingerprints do not match the live compiler stack —
                # or whose outputs diverged from the jit reference (the
                # cached-donated-executable failure mode): whatever it
                # measured, it was not the warm path it claims —
                # neither pass nor fail
                verdict.update(ok=False, exit_code=2)
                for a in bad:
                    if a.get("bitexact") is False:
                        verdict["reasons"].append(
                            "invalid_evidence: report claims warm "
                            "start but the loaded artifact computed "
                            "different results than the jit path: "
                            f"{a.get('label')!r} "
                            f"({a.get('fingerprint')})")
                    else:
                        verdict["reasons"].append(
                            "invalid_evidence: report claims warm "
                            "start but the loaded artifact's "
                            "fingerprint mismatches: "
                            f"{a.get('label')!r} "
                            f"({a.get('reason') or a.get('fingerprint')})")
                return verdict
        # refused-stale-artifact fallbacks are HONEST (the mismatched
        # program was never run warm — the driver took the cold jit
        # path by design), so they warn rather than refuse: the
        # operator likely wants to re-export
        for a in (ws.get("fallbacks") or [])[:3]:
            verdict["warnings"].append(
                "warmstart: stale artifact refused, cold fallback "
                f"taken: {a.get('label')!r} "
                f"({a.get('reason') or a.get('fingerprint')})")

    if check_service:
        csv = current.get("service") or {}
        if csv.get("warm_claimed"):
            bad = [a for a in csv.get("warm_admissions") or []
                   if a.get("fingerprint_ok") is False]
            if bad:
                # the report says requests were admitted WARM — served
                # from the ready pool, latency = dispatch — over
                # program fingerprints that do not match the live
                # compiler stack: whatever those leases dispatched, it
                # was not the programs the admission contract names;
                # neither pass nor fail
                verdict.update(ok=False, exit_code=2)
                for a in bad[:5]:
                    verdict["reasons"].append(
                        "invalid_evidence: report claims warm "
                        "admission over a mismatched fingerprint: "
                        f"request {a.get('id')} "
                        f"({a.get('fingerprint')})")
                return verdict
        if csv.get("warm_lease_backend_compiles"):
            # an honest-but-broken warm path: the fingerprints match
            # but the compile ledger recorded backend compiles inside
            # warm leases — the dispatch-never-compile contract
            # regressed; warn loudly (the TTFS comparison below is
            # what fails CI when it costs latency)
            verdict["warnings"].append(
                "service: "
                f"{csv['warm_lease_backend_compiles']} backend "
                "compile(s) recorded inside warm leases — the warm "
                "path is supposed to be pure dispatch; check the "
                "service section's lease records")

    if check_fleet:
        cfl = current.get("fleet") or {}
        cov = cfl.get("coverage") or {}
        lossy = bool((cfl.get("replicas_lost") or [])
                     or (cov.get("endpoint_failed") or 0) > 0)
        if cfl and cov.get("complete") and lossy:
            # the report CLAIMS its fleet numbers cover the whole
            # fleet while its own scrape record shows replicas lost or
            # scrapes failed: whatever the aggregated legs measured,
            # it was the survivors — a full-fleet throughput/SLO claim
            # over partial evidence proves nothing either way
            verdict.update(ok=False, exit_code=2)
            verdict["reasons"].append(
                "invalid_evidence: report claims complete fleet "
                "coverage but its scrape record shows "
                f"{len(cfl.get('replicas_lost') or [])} lost "
                f"replica(s) and {cov.get('endpoint_failed') or 0} "
                "failed scrape(s) — fleet aggregates over the "
                "survivors are partial evidence, not a fleet claim")
            return verdict
        if cfl and lossy:
            # the honest version of the same record: the report SAYS
            # its coverage is partial — degraded evidence, annotated
            # like a recovered incident, never silently accepted
            verdict["degraded"] = True
            lost_ids = sorted({str(r.get("replica"))
                               for r in cfl.get("replicas_lost") or []})
            verdict["warnings"].append(
                "fleet: degraded fleet evidence — "
                f"{len(lost_ids)} replica(s) lost mid-run "
                f"({', '.join(lost_ids) or '?'}), scrape success "
                f"{cfl.get('scrape_success_rate')} — fleet legs "
                "aggregate the survivors; see the report's fleet "
                "section before trusting fleet-wide claims")

    if check_capacity:
        ccap = current.get("capacity") or {}
        ccov = ccap.get("coverage") or {}
        n_samples = ccov.get("watermark_samples")
        if ccap and ccov.get("complete") and not n_samples:
            # the report CLAIMS its footprint reconciliation covered
            # every lease with live watermarks while recording zero
            # device samples: the "measured" side of the ledger never
            # existed, so the reconciliation (and any OOM headroom
            # claim built on it) proves nothing either way
            verdict.update(ok=False, exit_code=2)
            verdict["reasons"].append(
                "invalid_evidence: report claims complete capacity "
                "coverage but records 0 live watermark sample(s) — "
                "a predicted-vs-measured reconciliation with no "
                "device readings is not evidence of headroom")
            return verdict
        if ccap and ccov.get("predicted_only"):
            # the honest CPU degrade: no device.memory_stats() on
            # this host, so the ledger carries predictions only —
            # annotated, never silently accepted as measured headroom
            verdict["degraded"] = True
            verdict["warnings"].append(
                "capacity: predicted-only footprint evidence (no "
                "live watermark samples on this host) — HBM "
                "headroom claims rest on the aval/memory-analysis "
                "model, not device readings")
        rec = ccap.get("reconciliation") or {}
        rel = rec.get("rel_err")
        if isinstance(rel, (int, float)) \
                and abs(rel) > reconciliation_warn_pct / 100.0:
            verdict["warnings"].append(
                "capacity: predicted footprints disagree with the "
                f"measured HBM peak by {abs(rel):.0%} (warn bar "
                f"{reconciliation_warn_pct:g}%) — the footprint "
                "model is drifting from the device; re-arm with "
                "fresh compile records before trusting admission "
                "decisions")

    if check_latency:
        clat = current.get("latency") or {}
        bad_asm = clat.get("unassembled") or []
        n_bad = clat.get("unassembled_total")
        if not isinstance(n_bad, int):
            n_bad = len(bad_asm)  # pre-truncation-marker reports
        if n_bad:
            # traced requests whose span tree failed to close: the
            # latency attribution silently lost coverage — warn (the
            # requests may legitimately still be in flight, so this is
            # evidence quality, not invalid evidence)
            verdict["warnings"].append(
                f"latency: {n_bad} traced request(s) failed to "
                "assemble a span tree — critical-path coverage was "
                "lost; see the report's latency.unassembled list")
        chk = clat.get("phase_sum_check") or {}
        if chk.get("ok") is False:
            err = chk.get("max_rel_err")
            tol = chk.get("tolerance")
            detail = (
                f" (worst rel err {err:.2%} over tolerance {tol:.0%})"
                if isinstance(err, (int, float))
                and isinstance(tol, (int, float)) else "")
            verdict["warnings"].append(
                "latency: the critical-path phases do not sum to the "
                f"measured wall time{detail} — the span record is "
                "internally inconsistent; treat phase attribution "
                "with care")

    cur_num = current.get("numerics") or {}
    if check_numerics and cur_num.get("diverged"):
        # a diverged run's step times measure a broken computation;
        # neither pass nor fail — and the reason points at the bundle
        verdict.update(ok=False, exit_code=2)
        for d in cur_num["diverged"]:
            inv = d.get("offending_invariant")
            verdict["reasons"].append(
                "invalid_evidence: run diverged at step "
                f"{d.get('step')} (fields {d.get('fields')}"
                + (f", invariant {inv!r}" if inv else "") + ")")
        for b in cur_num.get("forensic_bundles") or []:
            verdict["reasons"].append(f"forensic bundle: {b}")
        return verdict

    run_detector = (check_contamination == "always"
                    or (check_contamination == "auto"
                        and (current.get("env") or {}).get(
                            "platform") not in (None, "cpu")))
    if run_detector:
        contamination = detect_contamination(
            cur_samples, outlier_k=outlier_k, burst_limit=burst_limit,
            frac_limit=frac_limit)
        verdict["contamination"] = contamination
        if contamination["contaminated"]:
            if degraded_evidence:
                # a recovery stall IS an outlier burst: across real
                # recorded incidents the detector's signature is
                # expected, so the evidence is degraded (annotated),
                # not refused
                verdict["degraded"] = True
                verdict["warnings"] += [
                    f"degraded fleet ({real_incidents} real recorded "
                    f"incident(s)): contamination-like samples "
                    f"annotated, not refused — {r}"
                    for r in contamination["reasons"]]
            else:
                verdict.update(ok=False, exit_code=2)
                verdict["reasons"] += ["invalid_evidence: " + r
                                       for r in contamination["reasons"]]
                return verdict

    if baseline is None:
        verdict["warnings"].append("no baseline: contamination check "
                                   "only, no regression comparison")
        return verdict

    env_mismatch = _env_comparable(baseline.get("env") or {},
                                   current.get("env") or {})
    if env_mismatch:
        if allow_env_mismatch:
            verdict["warnings"] += ["env mismatch (allowed): " + m
                                    for m in env_mismatch]
        else:
            verdict.update(ok=False, exit_code=2)
            verdict["reasons"] += [
                "invalid_evidence: measured on different hardware — "
                + m for m in env_mismatch]
            return verdict

    # same silicon but different XLA scheduler/async-collective flags
    # (or halo-overlap policy): the comparison still runs — the flags
    # change scheduling, not what is measured — but the verdict carries
    # a warning, because a latency-hiding-scheduler baseline is not a
    # like-for-like baseline for a run without it
    bflags = (baseline.get("env") or {}).get("xla_flags")
    cflags = (current.get("env") or {}).get("xla_flags")
    if bflags is not None and cflags is not None and bflags != cflags:
        diffs = sorted(k for k in set(bflags) | set(cflags)
                       if bflags.get(k) != cflags.get(k))
        verdict["warnings"].append(
            "XLA scheduler/overlap flags differ between baseline and "
            "current (comparison kept, but treat deltas with care): "
            + ", ".join(
                f"{k}: {bflags.get(k)!r} vs {cflags.get(k)!r}"
                for k in diffs))

    base_steps = baseline.get("steps") or {}
    base_p50 = base_steps.get("p50_ms")
    cur_p50 = cur_steps.get("p50_ms")
    if not isinstance(base_p50, (int, float)) or not isinstance(
            cur_p50, (int, float)):
        verdict.update(ok=False, exit_code=2)
        verdict["reasons"].append(
            "invalid_evidence: missing p50_ms in baseline or current")
        return verdict

    # the compared statistic is each run's MEDIAN, so the noise bar is
    # the standard error of a median (1.2533 * sigma / sqrt(n), sigma
    # from the Gaussian-consistent MAD), both runs combined in
    # quadrature — more steps legitimately tighten the bar
    def _median_se(steps):
        n = steps.get("count") or 1
        return 1.2533 * MAD_SIGMA * (steps.get("mad_ms") or 0.0) \
            / n ** 0.5

    noise_ms = mad_k * (_median_se(base_steps) ** 2
                        + _median_se(cur_steps) ** 2) ** 0.5
    delta = cur_p50 - base_p50
    rel = delta / base_p50 if base_p50 else 0.0
    verdict["comparison"] = {
        "baseline_p50_ms": base_p50, "current_p50_ms": cur_p50,
        "delta_ms": delta, "delta_pct": 100.0 * rel,
        "noise_bar_ms": noise_ms, "threshold_pct": threshold_pct,
    }
    if rel * 100.0 > threshold_pct and delta > noise_ms:
        if degraded_evidence:
            # a throughput drop measured across a REAL recorded
            # incident is the cost of the recovery, not (necessarily)
            # of the code: annotate so a human reads it next to the
            # incident table, instead of failing CI on a fleet that
            # was on fire. Drill-only runs do NOT take this branch.
            verdict["degraded"] = True
            verdict["warnings"].append(
                f"degraded fleet ({real_incidents} real recorded "
                "incident(s)): "
                f"median step time {cur_p50:.3f} ms is "
                f"{100 * rel:+.1f}% vs baseline {base_p50:.3f} ms — "
                "annotated, not gated; re-measure on a quiet fleet "
                "before trusting either direction")
        else:
            verdict.update(ok=False, exit_code=1)
            verdict["reasons"].append(
                f"regression: median step time {cur_p50:.3f} ms is "
                f"{100 * rel:+.1f}% vs baseline {base_p50:.3f} ms "
                f"(threshold {threshold_pct:.0f}%, noise bar "
                f"{noise_ms:.3f} ms)")
    elif rel * 100.0 < -threshold_pct and -delta > noise_ms:
        verdict["warnings"].append(
            f"improvement: median step time {100 * rel:+.1f}% vs "
            "baseline — consider refreshing the baseline")

    if check_numerics:
        _compare_numerics(verdict, baseline, current,
                          drift_factor=drift_factor,
                          drift_floor=drift_floor)
    if check_cold_start:
        _compare_cold_start(verdict, baseline, current,
                            factor=cold_start_factor,
                            floor_s=cold_start_floor)
    if check_ensemble:
        _compare_ensemble(verdict, baseline, current,
                          threshold_pct=ensemble_threshold_pct)
    if check_fft:
        _compare_fft(verdict, baseline, current,
                     threshold_pct=fft_threshold_pct)
    if check_comm:
        _check_comm(verdict, baseline, current,
                    excess_pct=comm_excess_pct)
    if check_service:
        _compare_service(verdict, baseline, current,
                         queue_factor=service_queue_factor,
                         queue_floor_s=service_queue_floor_s,
                         ttfs_factor=service_ttfs_factor,
                         ttfs_floor_s=service_ttfs_floor_s)
    if check_latency:
        _compare_latency(verdict, baseline, current,
                         miss_factor=latency_miss_factor,
                         miss_floor=latency_miss_floor)
    if check_fleet:
        _compare_fleet(verdict, baseline, current,
                       queue_factor=fleet_queue_factor,
                       queue_floor_s=fleet_queue_floor_s,
                       ttfs_factor=fleet_ttfs_factor,
                       ttfs_floor_s=fleet_ttfs_floor_s)
    if check_capacity:
        _compare_capacity(verdict, baseline, current,
                          goodput_factor=goodput_factor,
                          goodput_floor=goodput_floor)
    if check_resilience and (baseline or {}).get("resilience") \
            and not current.get("resilience"):
        verdict["warnings"].append(
            "resilience: baseline carried a resilience section but the "
            "current run has none — incident/checkpoint coverage was "
            "lost")
    if check_alerts:
        _check_alerts(verdict, baseline, current)
    if check_perf:
        _check_perf(verdict, baseline, current)
    return verdict


def _check_alerts(verdict, baseline, current):
    """Live-alert consistency audit (mutates ``verdict`` in place; runs
    AFTER the post-hoc SLO comparisons because it needs their
    outcomes). The ``alerts`` report section
    (:mod:`pystella_tpu.obs.slo` via the ledger) is the live half of
    each SLO; the post-hoc sections are the other. The two must agree:

    - an **unresolved-at-exit burn alert** for a leg whose post-hoc
      verdict came out GREEN is a live/post-hoc contradiction — the
      monitor watched the SLO burn until the record ended while the
      report claims the SLO held, so one of them is wrong and the
      evidence proves nothing either way: invalid evidence, exit 2
      (``--no-alerts`` opts out). An unresolved alert whose post-hoc
      leg ALSO failed is consistent (the gate already failed; the
      alert is corroboration, noted as a warning).
    - **alert-flap growth** (more fire→resolve→fire churn than the
      baseline recorded) warns: a flapping SLO is a bar sitting on the
      noise floor or a service oscillating around saturation — either
      deserves an operator before it deserves a page.
    - lost coverage (baseline carried an ``alerts`` section, current
      does not) warns like every other section."""
    cal = current.get("alerts") or {}
    bal = (baseline or {}).get("alerts") or {}
    if bal and not cal:
        verdict["warnings"].append(
            "alerts: baseline carried a live-alert (SLO burn) section "
            "but the current run has none — live SLO coverage was "
            "lost; attach the SLOMonitor (obs.slo)")
        return
    if not cal:
        return
    reasons = verdict.get("reasons") or []
    # which post-hoc legs came out green (no failing reason / no
    # recorded incidents)? keyed by the monitor's leg names
    post_hoc_green = {
        "queue_p95": not any("queue-latency p95" in r for r in reasons),
        "warm_ttfs": not any("warm time-to-first-step" in r
                             for r in reasons),
        "deadline_miss": not any("deadline-miss SLO regression" in r
                                 for r in reasons),
        "incident_rate": not (current.get("resilience")
                              or {}).get("n_incidents"),
    }
    for rec in cal.get("unresolved") or []:
        leg = str(rec.get("leg"))
        if post_hoc_green.get(leg, True):
            verdict.update(ok=False, exit_code=2)
            verdict["reasons"].append(
                f"invalid_evidence: live burn alert {leg!r} was still "
                f"firing when the run record ended (value "
                f"{rec.get('value')} vs bar {rec.get('bar')}) but the "
                "post-hoc SLO section claims green — the live and "
                "post-hoc halves contradict; trust neither")
        else:
            verdict["warnings"].append(
                f"alerts: unresolved live burn alert {leg!r} "
                "corroborates the failed post-hoc verdict for the "
                "same SLO")
    b_flaps = bal.get("flaps")
    c_flaps = cal.get("flaps")
    if isinstance(b_flaps, int) and isinstance(c_flaps, int) \
            and c_flaps > b_flaps:
        verdict["warnings"].append(
            f"alerts: {c_flaps} alert flap(s) vs {b_flaps} in the "
            "baseline — an SLO oscillating around its bar; check the "
            "report's alerts section before trusting either verdict")
    verdict["alerts"] = {
        "alerts": cal.get("alerts"), "resolved": cal.get("resolved"),
        "flaps": c_flaps, "unresolved": len(cal.get("unresolved") or []),
    }


def _check_perf(verdict, baseline, current):
    """Continuous-performance consistency audit (mutates ``verdict``
    in place; runs AFTER the step-time comparison because it needs its
    outcome). The ``perf`` report section
    (:mod:`pystella_tpu.obs.perf` via the ledger) is the live
    change-point record of the same step times the post-hoc median
    comparison gates; the two must agree:

    - an **unresolved-at-exit** ``perf_anomaly`` beside a GREEN
      post-hoc step-time verdict is a live/post-hoc contradiction —
      the detector watched a sustained shift never recover while the
      report claims step times held: invalid evidence, exit 2
      (``--no-perf`` opts out). Unresolved beside an already-failed
      step verdict is corroboration (warning).
    - anomalies that fired with **no flight-recorder capture**
      recorded warn: the plane's whole point is profiling evidence
      captured while the regression was live
      (``PYSTELLA_PERF_CAPTURE_DIR`` probably unset).
    - **anomaly-flap growth** vs the baseline and lost perf coverage
      warn like the alert section's equivalents."""
    cpf = current.get("perf") or {}
    bpf = (baseline or {}).get("perf") or {}
    if bpf and not cpf:
        verdict["warnings"].append(
            "perf: baseline carried a continuous-performance section "
            "but the current run has none — change-point coverage was "
            "lost (PYSTELLA_PERF=0?)")
        return
    if not cpf:
        return
    can = cpf.get("anomalies") or {}
    reasons = verdict.get("reasons") or []
    step_green = not any("median step time" in r for r in reasons)
    for rec in can.get("unresolved") or []:
        leg = str(rec.get("leg"))
        if step_green:
            verdict.update(ok=False, exit_code=2)
            verdict["reasons"].append(
                f"invalid_evidence: perf anomaly {leg!r} was still "
                f"open when the run record ended ({rec.get('value')} "
                f"ms vs baseline {rec.get('bar')} ms) but the "
                "post-hoc step-time verdict claims green — the "
                "change-point detector and the report contradict; "
                "trust neither")
        else:
            verdict["warnings"].append(
                f"perf: unresolved anomaly {leg!r} corroborates the "
                "failed post-hoc step-time verdict")
    if can.get("alerts") and not cpf.get("captures"):
        verdict["warnings"].append(
            f"perf: {can['alerts']} anomaly(ies) fired but no "
            "flight-recorder capture was recorded — set "
            "PYSTELLA_PERF_CAPTURE_DIR so the next regression "
            "profiles itself")
    b_flaps = (bpf.get("anomalies") or {}).get("flaps")
    c_flaps = can.get("flaps")
    if isinstance(b_flaps, int) and isinstance(c_flaps, int) \
            and c_flaps > b_flaps:
        verdict["warnings"].append(
            f"perf: {c_flaps} anomaly flap(s) vs {b_flaps} in the "
            "baseline — a detector oscillating around its threshold; "
            "check the report's perf section before trusting either "
            "verdict")
    verdict["perf"] = {
        "anomalies": can.get("alerts"),
        "recovered": can.get("resolved"),
        "flaps": c_flaps,
        "unresolved": len(can.get("unresolved") or []),
        "captures": len(cpf.get("captures") or []),
    }


def _compare_fft(verdict, baseline, current, threshold_pct=25.0):
    """Spectra-throughput comparison (mutates ``verdict`` in place):
    the current ``fft.ms.p50_ms`` — the median per-call wall time of
    the run's spectra outputs (:mod:`pystella_tpu.fourier.pencil`'s
    report section) — must stay within ``threshold_pct`` of the
    baseline's. Spectra are the dominant cost of any run that outputs
    them (the 241 ms/call gw-spectra-256³ headline vs a sub-ms step),
    so a spectral-tier regression fails CI like a slow step does. The
    threshold is wider than the step gate's: a spectra call is one
    sample per output cadence, not thousands per run. Coverage loss
    (baseline had an ``fft`` section, current does not) degrades to a
    warning; a scheme CHANGE between reports warns too — a pencil-tier
    baseline is not a like-for-like baseline for a replicate-tier
    run."""
    bff = (baseline or {}).get("fft") or {}
    cff = current.get("fft") or {}
    if bff and not cff:
        verdict["warnings"].append(
            "fft: baseline carried a spectral (fft) section but the "
            "current run has none — spectra-throughput coverage was "
            "lost")
        return
    if not bff or not cff:
        return
    bs, cs = bff.get("scheme"), cff.get("scheme")
    if bs is not None and cs is not None and bs != cs:
        verdict["warnings"].append(
            f"fft: transform scheme changed between reports (baseline "
            f"{bs!r} vs current {cs!r}) — spectra times are compared, "
            "but the tiers move different bytes")
    b = (bff.get("ms") or {}).get("p50_ms")
    c = (cff.get("ms") or {}).get("p50_ms")
    if not isinstance(b, (int, float)) or b <= 0:
        return
    if not isinstance(c, (int, float)):
        verdict["warnings"].append(
            "fft: baseline tracked a spectra p50 ms/call but the "
            "current run's fft section carries none — "
            "spectra-throughput coverage was lost")
        return
    slow_pct = 100.0 * (c - b) / b
    verdict["fft"] = {
        "baseline_p50_ms": b, "current_p50_ms": c,
        "slowdown_pct": slow_pct, "threshold_pct": threshold_pct,
    }
    if slow_pct > threshold_pct:
        verdict.update(ok=False, exit_code=max(verdict["exit_code"], 1))
        verdict["reasons"].append(
            f"fft regression: spectra p50 {c:.4g} ms/call is "
            f"{slow_pct:.1f}% above baseline {b:.4g} (threshold "
            f"{threshold_pct:g}%) — check the fft section's per-stage "
            "rows and transpose exposed time")
    elif -slow_pct > threshold_pct:
        verdict["warnings"].append(
            f"fft improvement: spectra p50 {-slow_pct:.1f}% below "
            "baseline — consider refreshing the baseline")


def _check_comm(verdict, baseline, current, excess_pct=25.0):
    """Modeled-vs-measured communication check (mutates ``verdict``
    in place) over the current report's ``comm`` section — the
    ledger's join of the dataflow lint tier's static comm model
    against the run's measured collective traffic.

    Three verdicts. A leg whose measured bytes exceed its modeled
    bytes by more than ``excess_pct`` fails (exit 1): the model counts
    every collective the compiled program CAN issue per invocation, so
    measured traffic above it is traffic the model does not attribute
    — an extra collective the partitioner materialized after the
    audit, or a byte counter measuring a different program than the
    one modeled. A ``comm`` section claiming ``covered: true`` while
    no leg carries a static model is refused (exit 2): coverage means
    modeled AND measured sides joined, so the claim is unsupportable —
    the dataflow tier never ran, or the section was assembled by hand.
    Coverage loss (baseline's comm was covered, current's is absent or
    uncovered) degrades to a warning, like every lost-coverage
    pattern here. Reports predating the section (no ``comm`` key and
    no claim) pass through untouched."""
    ccm = current.get("comm")
    bcm = (baseline or {}).get("comm") or {}
    if not ccm:
        if bcm.get("covered"):
            verdict["warnings"].append(
                "comm: baseline carried a covered modeled-vs-measured "
                "comm section but the current run has none — "
                "communication coverage was lost")
        return
    legs = ccm.get("legs") or []
    modeled_legs = [leg for leg in legs
                    if isinstance(leg.get("modeled_bytes"), (int, float))
                    and leg["modeled_bytes"] > 0]
    if ccm.get("covered") and not modeled_legs:
        verdict.update(ok=False, exit_code=2)
        verdict["reasons"].append(
            "invalid_evidence: report claims modeled-vs-measured comm "
            "coverage (comm.covered) but no leg carries a static "
            "model — a coverage claim with no model behind it; run "
            "the dataflow lint tier (python -m pystella_tpu.lint) or "
            "drop the claim")
        return
    checked = []
    for leg in modeled_legs:
        meas = leg.get("measured_bytes")
        if not isinstance(meas, (int, float)):
            continue
        modeled = float(leg["modeled_bytes"])
        over = 100.0 * (meas / modeled - 1.0)
        checked.append({
            "target": leg.get("target"), "class": leg.get("class"),
            "modeled_bytes": modeled, "measured_bytes": float(meas),
            "excess_pct": over,
        })
        if over > excess_pct:
            verdict.update(ok=False,
                           exit_code=max(verdict["exit_code"], 1))
            verdict["reasons"].append(
                f"comm excess: {leg.get('target')} "
                f"({leg.get('class')}) measured {meas:,.0f} B per "
                f"invocation is {over:.1f}% above the static model's "
                f"{modeled:,.0f} B (threshold {excess_pct:g}%) — "
                "collective traffic the model does not attribute; "
                "re-audit the program or find the unmodeled "
                "collective")
    if checked:
        verdict["comm"] = {"legs": checked,
                           "excess_threshold_pct": excess_pct}
    if bcm.get("covered") and not ccm.get("covered"):
        verdict["warnings"].append(
            "comm: baseline's comm section was covered (modeled and "
            "measured joined) but the current run's is not — "
            "communication coverage was lost")


def _compare_service(verdict, baseline, current, queue_factor=2.5,
                     queue_floor_s=0.5, ttfs_factor=2.5,
                     ttfs_floor_s=1.0):
    """Scenario-service SLO comparison (mutates ``verdict`` in place):
    two production latency metrics from the ``service`` report section
    (:mod:`pystella_tpu.service`), each gated by a relative factor AND
    an absolute floor — service latencies on a small smoke mix are
    single-sample-scale and jitter with host load, so a pure ratio
    would flap:

    - **queue-p95**: the overall p95 queue latency (submit ->
      dispatch). A regression means the scheduler is falling behind
      the offered load — the user-facing SLO.
    - **warm TTFS**: the warm leases' median time-to-first-step. The
      warm pool's whole contract is dispatch-never-compile; warm TTFS
      drifting toward cold TTFS means requests are paying compiles
      again.

    Coverage loss (baseline had a ``service`` section, current does
    not) degrades to a warning. The warm-over-mismatched-fingerprints
    refusal runs earlier, before any baseline is consulted."""
    bsv = (baseline or {}).get("service") or {}
    csv = current.get("service") or {}
    if bsv and not csv:
        verdict["warnings"].append(
            "service: baseline carried a service section but the "
            "current run has none — queue/TTFS SLO coverage was lost")
        return
    if not bsv or not csv:
        return
    compared = {}

    def _leg(name, b, c, factor, floor_s, what):
        if not isinstance(b, (int, float)) or b < 0 \
                or not isinstance(c, (int, float)):
            if isinstance(b, (int, float)) and c is None:
                verdict["warnings"].append(
                    f"service: baseline tracked {what} but the "
                    "current run's service section carries none — "
                    "SLO coverage was lost")
            return
        compared[name] = {"baseline_s": b, "current_s": c,
                          "factor": factor, "floor_s": floor_s}
        if c > b * factor and c - b > floor_s:
            verdict.update(ok=False,
                           exit_code=max(verdict["exit_code"], 1))
            verdict["reasons"].append(
                f"service SLO regression: {what} {c:.3g} s vs "
                f"baseline {b:.3g} s (allowed factor {factor:g}, "
                f"floor {floor_s:g} s) — see the report's service "
                "section")
        elif b > c * factor and b - c > floor_s:
            verdict["warnings"].append(
                f"service improvement: {what} {c:.3g} s vs baseline "
                f"{b:.3g} s — consider refreshing the baseline")

    _leg("queue_p95",
         ((bsv.get("queue_latency_s") or {}).get("overall")
          or {}).get("p95_s"),
         ((csv.get("queue_latency_s") or {}).get("overall")
          or {}).get("p95_s"),
         queue_factor, queue_floor_s, "queue-latency p95")
    _leg("warm_ttfs",
         ((bsv.get("ttfs_s") or {}).get("warm") or {}).get("p50_s"),
         ((csv.get("ttfs_s") or {}).get("warm") or {}).get("p50_s"),
         ttfs_factor, ttfs_floor_s, "warm time-to-first-step p50")
    if compared:
        verdict["service"] = compared


def _compare_fleet(verdict, baseline, current, queue_factor=2.5,
                   queue_floor_s=0.5, ttfs_factor=2.5,
                   ttfs_floor_s=1.0):
    """Fleet SLO comparison (mutates ``verdict`` in place): the fleet
    ``legs`` of the ``fleet`` report section
    (:mod:`pystella_tpu.obs.fleet` — each leg's windowed value at the
    last aggregation pass, computed over EVERY replica's samples), held
    to the same factor+floor bars as the single-replica service legs.
    Also the fleet hygiene warnings: version/flag skew appearing when
    the baseline fleet had none, warm-fingerprint divergence (the
    hard precondition for cross-replica warm-artifact reuse), and
    fleet-alert flap growth. Coverage loss (baseline had a fleet
    section, current does not) degrades to a warning. The
    partial-evidence refusal and the degraded annotation run earlier,
    before any baseline is consulted."""
    bfl = (baseline or {}).get("fleet") or {}
    cfl = current.get("fleet") or {}
    if bfl and not cfl:
        verdict["warnings"].append(
            "fleet: baseline carried a fleet section but the current "
            "run has none — fleet SLO coverage was lost")
        return
    if not cfl:
        return
    # hygiene findings need no baseline: skew and divergence are
    # absolute properties of THIS fleet
    if (cfl.get("skew") or {}).get("skewed") \
            and not (bfl.get("skew") or {}).get("skewed"):
        verdict["warnings"].append(
            "fleet: version/flag SKEW across live replicas "
            f"({(cfl.get('skew') or {}).get('stacks')} distinct "
            "compiler stacks) — fleet aggregates mix incomparable "
            "programs; align the stacks before trusting fleet legs")
    if cfl.get("divergence"):
        verdict["warnings"].append(
            "fleet: warm-fingerprint divergence across replicas for "
            f"signature(s) {', '.join(cfl['divergence'])} — the same "
            "signature is served by different programs; do not share "
            "warm artifacts across this fleet")
    if not bfl:
        return
    compared = {}

    def _leg(name, factor, floor_s, what):
        b = ((bfl.get("legs") or {}).get(name) or {}).get("value_fast")
        c = ((cfl.get("legs") or {}).get(name) or {}).get("value_fast")
        if not isinstance(b, (int, float)) or b < 0 \
                or not isinstance(c, (int, float)):
            if isinstance(b, (int, float)) and c is None:
                verdict["warnings"].append(
                    f"fleet: baseline tracked {what} but the current "
                    "run's fleet section carries none — fleet SLO "
                    "coverage was lost")
            return
        compared[name] = {"baseline_s": b, "current_s": c,
                          "factor": factor, "floor_s": floor_s}
        if c > b * factor and c - b > floor_s:
            verdict.update(ok=False,
                           exit_code=max(verdict["exit_code"], 1))
            verdict["reasons"].append(
                f"fleet SLO regression: {what} {c:.3g} s vs "
                f"baseline {b:.3g} s (allowed factor {factor:g}, "
                f"floor {floor_s:g} s) — see the report's fleet "
                "section")
        elif b > c * factor and b - c > floor_s:
            verdict["warnings"].append(
                f"fleet improvement: {what} {c:.3g} s vs baseline "
                f"{b:.3g} s — consider refreshing the baseline")

    _leg("queue_p95", queue_factor, queue_floor_s,
         "fleet queue-latency p95")
    _leg("warm_ttfs", ttfs_factor, ttfs_floor_s,
         "fleet warm time-to-first-step p50")
    b_flaps = (bfl.get("alerts") or {}).get("flaps")
    c_flaps = (cfl.get("alerts") or {}).get("flaps")
    if isinstance(b_flaps, int) and isinstance(c_flaps, int) \
            and c_flaps > b_flaps:
        verdict["warnings"].append(
            f"fleet: {c_flaps} fleet alert flap(s) vs {b_flaps} in "
            "the baseline — a fleet SLO oscillating around its bar")
    if compared:
        verdict["fleet"] = compared


def _compare_capacity(verdict, baseline, current, goodput_factor=2.0,
                      goodput_floor=1.0):
    """Goodput comparison (mutates ``verdict`` in place): the current
    ``capacity.goodput`` — committed member-steps per chip-second
    leased (:mod:`pystella_tpu.obs.capacity` attribution over the
    span phases × chips) — held to the same factor+floor shape as the
    service SLO legs, with the inequality FLIPPED: goodput regresses
    downward, so the gate fails (exit 1) when current drops below
    baseline / ``goodput_factor`` AND by more than ``goodput_floor``
    steps/chip-s absolute. Waste chip-second growth (replay +
    preempt-drain share of the leased chip time) warns against the
    baseline, and coverage loss (baseline had a capacity section,
    current does not) degrades to a warning. The partial-evidence
    refusal and the predicted-only annotation run earlier, before any
    baseline is consulted."""
    bcap = (baseline or {}).get("capacity") or {}
    ccap = current.get("capacity") or {}
    if bcap and not ccap:
        verdict["warnings"].append(
            "capacity: baseline carried a capacity section but the "
            "current run has none — HBM-footprint/goodput coverage "
            "was lost")
        return
    if not ccap or not bcap:
        return
    b = bcap.get("goodput")
    c = ccap.get("goodput")
    if isinstance(b, (int, float)) and b > 0 \
            and isinstance(c, (int, float)):
        verdict["capacity"] = {
            "baseline_goodput": b, "current_goodput": c,
            "factor": goodput_factor, "floor": goodput_floor}
        if c < b / goodput_factor and b - c > goodput_floor:
            verdict.update(ok=False,
                           exit_code=max(verdict["exit_code"], 1))
            verdict["reasons"].append(
                f"goodput regression: {c:.3g} committed "
                f"steps/chip-s vs baseline {b:.3g} (allowed factor "
                f"{goodput_factor:g}, floor {goodput_floor:g}) — "
                "chips are burning on waste (replay, drain, idle "
                "leases); see the report's capacity section")
        elif c > b * goodput_factor and c - b > goodput_floor:
            verdict["warnings"].append(
                f"goodput improvement: {c:.3g} steps/chip-s vs "
                f"baseline {b:.3g} — consider refreshing the "
                "baseline")
    elif isinstance(b, (int, float)) and c is None:
        verdict["warnings"].append(
            "capacity: baseline tracked goodput but the current "
            "run's capacity section carries none — chip-second "
            "attribution coverage was lost")
    b_waste = bcap.get("waste_chip_s")
    c_waste = ccap.get("waste_chip_s")
    if isinstance(b_waste, (int, float)) \
            and isinstance(c_waste, (int, float)) \
            and c_waste > 2.0 * b_waste and c_waste - b_waste > 1.0:
        verdict["warnings"].append(
            f"capacity: {c_waste:.3g} waste chip-second(s) (replay + "
            f"preempt-drain) vs {b_waste:.3g} in the baseline — "
            "recovery/eviction churn is eating leased chip time")


def _compare_latency(verdict, baseline, current, miss_factor=2.0,
                     miss_floor=0.05):
    """Deadline-miss SLO comparison (mutates ``verdict`` in place):
    the current ``latency.deadline.miss_rate`` — the fraction of
    deadlined requests that retired after their deadline
    (:mod:`pystella_tpu.obs.spans` /
    :class:`~pystella_tpu.service.results.ResultEmitter`) — must stay
    within ``miss_factor`` × the baseline's AND within ``miss_floor``
    absolute above it before the gate fails (exit 1). Both bars, like
    the other service SLOs: a smoke mix deadlines a handful of
    requests, so one flipped verdict moves the rate by a whole
    quantum — the floor keeps that honest while a real scheduler
    regression (misses doubling AND growing by 5+ points) reliably
    fails. Coverage loss (baseline had a ``latency`` section or a
    deadline ledger, current does not) degrades to a warning; the
    unassembled-span-tree warning runs earlier, before any baseline
    is consulted."""
    blat = (baseline or {}).get("latency") or {}
    clat = current.get("latency") or {}
    if blat and not clat:
        verdict["warnings"].append(
            "latency: baseline carried a latency (critical-path) "
            "section but the current run has none — deadline-miss SLO "
            "coverage was lost")
        return
    if not blat or not clat:
        return
    bdl = blat.get("deadline") or {}
    cdl = clat.get("deadline") or {}
    b = bdl.get("miss_rate")
    c = cdl.get("miss_rate")
    if isinstance(b, (int, float)) and c is None:
        verdict["warnings"].append(
            "latency: baseline tracked a deadline-miss rate but the "
            "current run deadlined no requests — deadline-miss SLO "
            "coverage was lost")
        return
    if not isinstance(b, (int, float)) or not isinstance(
            c, (int, float)):
        return
    verdict["latency"] = {
        "baseline_miss_rate": b, "current_miss_rate": c,
        "baseline_missed": bdl.get("missed"),
        "current_missed": cdl.get("missed"),
        "miss_factor": miss_factor, "miss_floor": miss_floor,
    }
    if c > b * miss_factor and c - b > miss_floor:
        verdict.update(ok=False, exit_code=max(verdict["exit_code"], 1))
        verdict["reasons"].append(
            f"deadline-miss SLO regression: miss rate {c:.1%} "
            f"({cdl.get('missed')}/{cdl.get('deadlined')} deadlined "
            f"request(s)) vs baseline {b:.1%} (allowed factor "
            f"{miss_factor:g}, floor {miss_floor:g}) — see the "
            "report's latency section for the dominant phase behind "
            "the misses")
    elif b > c * miss_factor and b - c > miss_floor:
        verdict["warnings"].append(
            f"deadline-miss improvement: miss rate {c:.1%} vs baseline "
            f"{b:.1%} — consider refreshing the baseline")


def _compare_ensemble(verdict, baseline, current, threshold_pct=20.0):
    """Member-throughput comparison (mutates ``verdict`` in place): the
    current ``ensemble.member_steps_per_s`` must stay within
    ``threshold_pct`` of the baseline's. The threshold is wider than
    the step-time gate's because a driver run's wall time includes
    host-side queue management (occupancy changes jitter it); a real
    batching regression (a lost vmap, a per-member re-trace) costs far
    more than 20%. Coverage loss and eviction-count growth degrade to
    warnings."""
    ben = (baseline or {}).get("ensemble") or {}
    cen = current.get("ensemble") or {}
    if ben and not cen:
        verdict["warnings"].append(
            "ensemble: baseline carried an ensemble section but the "
            "current run has none — member-throughput coverage was "
            "lost")
        return
    # eviction growth is independent of the throughput metric: it must
    # warn even when either run's rate is missing (a driver that died
    # mid-run still counted its member_evicted events)
    bev, cev = ben.get("evictions"), cen.get("evictions")
    if isinstance(bev, int) and isinstance(cev, int) and cev > bev:
        verdict["warnings"].append(
            f"ensemble: {cev} member eviction(s) vs {bev} in the "
            "baseline — more bad draws than the baseline configuration "
            "produced")
    b = ben.get("member_steps_per_s")
    c = cen.get("member_steps_per_s")
    if not isinstance(b, (int, float)) or b <= 0:
        return
    if not isinstance(c, (int, float)):
        # the section exists (chunk/eviction events landed) but the
        # throughput metric is gone — a driver that died mid-run never
        # emits ensemble_done; a baseline-gated metric must not vanish
        # silently
        verdict["warnings"].append(
            "ensemble: baseline tracked member_steps_per_s but the "
            "current run's ensemble section carries none — "
            "member-throughput coverage was lost")
        return
    drop_pct = 100.0 * (b - c) / b
    verdict["ensemble"] = {
        "baseline_member_steps_per_s": b,
        "current_member_steps_per_s": c,
        "drop_pct": drop_pct, "threshold_pct": threshold_pct,
    }
    if drop_pct > threshold_pct:
        verdict.update(ok=False, exit_code=max(verdict["exit_code"], 1))
        verdict["reasons"].append(
            f"ensemble regression: member throughput {c:.4g} "
            f"member-steps/s is {drop_pct:.1f}% below baseline "
            f"{b:.4g} (threshold {threshold_pct:g}%) — check batch "
            "occupancy and the chunk-dispatch distribution in the "
            "report's ensemble section")
    elif -drop_pct > threshold_pct:
        verdict["warnings"].append(
            f"ensemble improvement: member throughput {-drop_pct:.1f}% "
            "above baseline — consider refreshing the baseline")


def _compare_cold_start(verdict, baseline, current, factor=1.5,
                        floor_s=5.0):
    """Time-to-first-step comparison (mutates ``verdict`` in place): a
    regression must clear BOTH the relative factor and the absolute
    floor — cold start on a small smoke run jitters by seconds
    (interpreter + jax import), so a pure ratio would flap. Coverage
    loss (baseline had a ``cold_start`` section, current does not)
    degrades to a warning."""
    bcs = (baseline or {}).get("cold_start") or {}
    ccs = current.get("cold_start") or {}
    b = bcs.get("time_to_first_step_s")
    c = ccs.get("time_to_first_step_s")
    if bcs and not ccs:
        verdict["warnings"].append(
            "cold_start: baseline carried a cold-start section but the "
            "current run has none — cold-start coverage was lost")
        return
    if (isinstance(b, (int, float)) and b > 0
            and not isinstance(c, (int, float))):
        # the current run has compile telemetry but never measured a
        # time-to-first-step (driver crashed pre-step, or a custom
        # driver without the cold_start event) — the metric the
        # baseline gated on is GONE, which must be visible, not a
        # silent pass
        verdict["warnings"].append(
            "cold_start: baseline carried a time-to-first-step but the "
            "current run's cold_start section has none — cold-start "
            "coverage was lost")
        return
    if not isinstance(b, (int, float)) or not isinstance(
            c, (int, float)) or b <= 0:
        return
    verdict["cold_start"] = {
        "baseline_s": b, "current_s": c,
        "factor": factor, "floor_s": floor_s,
    }
    if c > b * factor and c - b > floor_s:
        verdict.update(ok=False, exit_code=max(verdict["exit_code"], 1))
        verdict["reasons"].append(
            f"cold-start regression: time-to-first-step {c:.1f} s vs "
            f"baseline {b:.1f} s (allowed factor {factor:g}, floor "
            f"{floor_s:g} s) — check the compile table and cache hit "
            "rate in the report's cold_start section")
    elif b > c * factor and b - c > floor_s:
        verdict["warnings"].append(
            f"cold-start improvement: {c:.1f} s vs baseline {b:.1f} s "
            "— consider refreshing the baseline")


def _compare_numerics(verdict, baseline, current, drift_factor=10.0,
                      drift_floor=1e-12):
    """Invariant-drift comparison (mutates ``verdict`` in place): for
    every invariant both reports tracked, the current |drift/step| must
    stay within ``drift_factor`` x the baseline's (both floored at
    ``drift_floor``). Invariants only one side tracked degrade to a
    warning — losing numerics coverage should be visible, not fatal."""
    bnum = (baseline.get("numerics") or {}).get("invariants") or {}
    cnum = (current.get("numerics") or {}).get("invariants") or {}
    if not bnum and not cnum:
        return
    if bnum and not cnum:
        verdict["warnings"].append(
            "numerics: baseline tracked invariants "
            f"{sorted(bnum)} but the current run has no numerics "
            "section — sentinel coverage was lost")
        return
    compared = {}
    for name in sorted(set(bnum) & set(cnum)):
        bn = bnum[name].get("n") or 0
        cn = cnum[name].get("n") or 0
        if bn < 2 or cn < 2:
            # a degenerate series yields slope 0.0 (ledger._slope),
            # indistinguishable from a genuinely flat invariant —
            # gating against the bare floor would flag honest roundoff
            verdict["warnings"].append(
                f"numerics: invariant {name!r} has too few samples "
                f"for a drift slope (baseline n={bn}, current "
                f"n={cn}); not compared")
            continue
        b = abs(bnum[name].get("drift_per_step") or 0.0)
        c = abs(cnum[name].get("drift_per_step") or 0.0)
        allowed = drift_factor * max(b, drift_floor)
        compared[name] = {"baseline_drift": b, "current_drift": c,
                          "allowed": allowed}
        if c > allowed:
            verdict.update(ok=False, exit_code=max(
                verdict["exit_code"], 1))
            verdict["reasons"].append(
                f"numerics regression: invariant {name!r} drift "
                f"{c:.3e}/step vs baseline {b:.3e}/step (allowed "
                f"factor {drift_factor:g}, floor {drift_floor:g})")
    for name in sorted(set(bnum) - set(cnum)):
        verdict["warnings"].append(
            f"numerics: invariant {name!r} tracked in the baseline "
            "but not the current run")
    verdict["numerics"] = compared


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m pystella_tpu.obs.gate",
        description="noise-aware perf regression gate over perf_report"
                    ".json files (0 pass, 1 regression, 2 invalid "
                    "evidence, 3 missing baseline)")
    p.add_argument("--baseline", required=True,
                   help="baseline perf_report.json")
    p.add_argument("--current", required=True,
                   help="current perf_report.json")
    p.add_argument("--threshold-pct", type=float, default=10.0,
                   help="relative p50 step-time slowdown that counts as "
                        "a regression (default 10)")
    p.add_argument("--mad-k", type=float, default=3.0,
                   help="noise bar in Gaussian-consistent MAD sigmas a "
                        "regression must also clear (default 3)")
    p.add_argument("--outlier-k", type=float, default=5.0,
                   help="contamination: outlier threshold in sigmas "
                        "above the median (default 5)")
    p.add_argument("--burst", type=int, default=4,
                   help="contamination: consecutive outlier steps that "
                        "invalidate the run (default 4)")
    p.add_argument("--outlier-frac", type=float, default=0.10,
                   help="contamination: outlier fraction that "
                        "invalidates the run (default 0.10)")
    p.add_argument("--check-contamination",
                   choices=("auto", "always", "never"), default="auto",
                   help="auto (default): run the contamination detector "
                        "on accelerator reports only (CPU step times "
                        "are legitimately scheduler-noisy; the median "
                        "comparison absorbs that); always/never force")
    p.add_argument("--drift-factor", type=float, default=10.0,
                   help="numerics: allowed multiple of the baseline's "
                        "invariant drift slope before the gate fails "
                        "(default 10)")
    p.add_argument("--drift-floor", type=float, default=1e-12,
                   help="numerics: drift-per-step floor applied to both "
                        "sides, so a ~zero baseline slope cannot make "
                        "any finite drift a regression (default 1e-12)")
    p.add_argument("--cold-start-factor", type=float, default=1.5,
                   help="cold start: allowed multiple of the baseline's "
                        "time-to-first-step before the gate fails "
                        "(default 1.5)")
    p.add_argument("--cold-start-floor", type=float, default=5.0,
                   help="cold start: absolute seconds a regression must "
                        "also exceed (default 5; small-run cold starts "
                        "jitter by whole seconds)")
    p.add_argument("--ensemble-threshold-pct", type=float, default=20.0,
                   help="ensemble: allowed member-steps/s drop vs the "
                        "baseline before the gate fails (default 20)")
    p.add_argument("--no-ensemble", action="store_true",
                   help="skip the ensemble member-throughput check")
    p.add_argument("--fft-threshold-pct", type=float, default=25.0,
                   help="fft: allowed spectra p50 ms/call slowdown vs "
                        "the baseline before the gate fails (default "
                        "25)")
    p.add_argument("--no-fft", action="store_true",
                   help="skip the spectral-tier (fft section) "
                        "spectra-throughput check")
    p.add_argument("--comm-excess-pct", type=float,
                   default=_config.get_float(
                       "PYSTELLA_GATE_COMM_EXCESS_PCT"),
                   help="comm: allowed measured-over-modeled collective"
                        "-traffic excess before the gate fails "
                        "(default 25, env "
                        "PYSTELLA_GATE_COMM_EXCESS_PCT)")
    p.add_argument("--no-comm", action="store_true",
                   help="skip the modeled-vs-measured communication "
                        "check (comm section)")
    p.add_argument("--service-queue-factor", type=float, default=2.5,
                   help="service: allowed multiple of the baseline's "
                        "queue-latency p95 before the gate fails "
                        "(default 2.5)")
    p.add_argument("--service-queue-floor", type=float, default=0.5,
                   help="service: absolute seconds a queue-p95 "
                        "regression must also exceed (default 0.5)")
    p.add_argument("--service-ttfs-factor", type=float, default=2.5,
                   help="service: allowed multiple of the baseline's "
                        "warm time-to-first-step p50 before the gate "
                        "fails (default 2.5)")
    p.add_argument("--service-ttfs-floor", type=float, default=1.0,
                   help="service: absolute seconds a warm-TTFS "
                        "regression must also exceed (default 1)")
    p.add_argument("--no-service", action="store_true",
                   help="skip the scenario-service checks (queue-p95 / "
                        "warm-TTFS SLO regressions, warm-admission-"
                        "over-mismatched-fingerprints refusal)")
    p.add_argument("--latency-miss-factor", type=float, default=2.0,
                   help="latency: allowed multiple of the baseline's "
                        "deadline-miss rate before the gate fails "
                        "(default 2)")
    p.add_argument("--latency-miss-floor", type=float, default=0.05,
                   help="latency: absolute miss-rate increase a "
                        "regression must also exceed (default 0.05 — "
                        "one flipped verdict on a small smoke mix "
                        "moves the rate by a whole quantum)")
    p.add_argument("--no-latency", action="store_true",
                   help="skip the request-latency checks (deadline-"
                        "miss SLO regression, span-assembly coverage "
                        "warnings)")
    p.add_argument("--fleet-queue-factor", type=float, default=2.5,
                   help="fleet: allowed multiple of the baseline's "
                        "fleet queue-latency p95 before the gate "
                        "fails (default 2.5)")
    p.add_argument("--fleet-queue-floor", type=float, default=0.5,
                   help="fleet: absolute seconds a fleet queue-p95 "
                        "regression must also exceed (default 0.5)")
    p.add_argument("--fleet-ttfs-factor", type=float, default=2.5,
                   help="fleet: allowed multiple of the baseline's "
                        "fleet warm-TTFS p50 before the gate fails "
                        "(default 2.5)")
    p.add_argument("--fleet-ttfs-floor", type=float, default=1.0,
                   help="fleet: absolute seconds a fleet warm-TTFS "
                        "regression must also exceed (default 1)")
    p.add_argument("--no-fleet", action="store_true",
                   help="skip the fleet checks (full-coverage-claim-"
                        "over-lossy-scrapes refusal, degraded-fleet "
                        "annotation, fleet queue-p95/warm-TTFS "
                        "regressions, skew/divergence/flap warnings)")
    p.add_argument("--goodput-factor", type=float, default=2.0,
                   help="capacity: allowed divisor of the baseline's "
                        "goodput (committed steps/chip-s) before the "
                        "gate fails (default 2)")
    p.add_argument("--goodput-floor", type=float, default=1.0,
                   help="capacity: absolute steps/chip-s a goodput "
                        "regression must also exceed (default 1)")
    p.add_argument("--no-capacity", action="store_true",
                   help="skip the capacity checks (complete-coverage-"
                        "with-no-watermarks refusal, predicted-only "
                        "annotation, reconciliation-drift warning, "
                        "goodput regression, waste-chip-second "
                        "growth)")
    p.add_argument("--no-alerts", action="store_true",
                   help="skip the live-alert consistency audit (an "
                        "unresolved burn alert beside a green post-hoc "
                        "SLO section refuses the evidence; alert-flap "
                        "growth warns)")
    p.add_argument("--no-perf", action="store_true",
                   help="skip the continuous-performance consistency "
                        "audit (an unresolved perf_anomaly beside a "
                        "green step-time verdict refuses the "
                        "evidence; missing flight-recorder captures "
                        "and anomaly-flap growth warn)")
    p.add_argument("--no-resilience", action="store_true",
                   help="skip the resilience triage (degraded-fleet "
                        "annotation of regressions/contamination across "
                        "recorded incidents; claims-clean-with-"
                        "incidents refusal)")
    p.add_argument("--no-cold-start", action="store_true",
                   help="skip the cold-start checks (time-to-first-step "
                        "regression, warm-start fingerprint-mismatch "
                        "refusal)")
    p.add_argument("--no-numerics", action="store_true",
                   help="skip the numerics checks (invariant drift, "
                        "diverged-run invalidation)")
    p.add_argument("--no-lint", action="store_true",
                   help="skip the lint check (a failed static analysis "
                        "in the current report's `lint` section refuses "
                        "the evidence)")
    p.add_argument("--allow-missing-baseline", action="store_true",
                   help="exit 0 (after the contamination check) when "
                        "the baseline file does not exist")
    p.add_argument("--allow-env-mismatch", action="store_true",
                   help="downgrade a baseline/current hardware mismatch "
                        "from invalid evidence to a warning")
    args = p.parse_args(argv)

    try:
        current = load_report(args.current)
    except (OSError, ValueError) as e:
        print(f"gate: cannot read current report: {e}", file=sys.stderr)
        return 4

    baseline = None
    try:
        baseline = load_report(args.baseline)
    except (OSError, ValueError) as e:
        if not args.allow_missing_baseline:
            print(f"gate: cannot read baseline: {e} "
                  "(--allow-missing-baseline to tolerate)",
                  file=sys.stderr)
            return 3
        print(f"gate: no baseline ({e}); contamination check only",
              file=sys.stderr)

    verdict = compare_reports(
        baseline, current, threshold_pct=args.threshold_pct,
        mad_k=args.mad_k, outlier_k=args.outlier_k,
        burst_limit=args.burst, frac_limit=args.outlier_frac,
        allow_env_mismatch=args.allow_env_mismatch,
        check_contamination=args.check_contamination,
        check_numerics=not args.no_numerics,
        drift_factor=args.drift_factor, drift_floor=args.drift_floor,
        check_lint=not args.no_lint,
        check_cold_start=not args.no_cold_start,
        cold_start_factor=args.cold_start_factor,
        cold_start_floor=args.cold_start_floor,
        check_ensemble=not args.no_ensemble,
        ensemble_threshold_pct=args.ensemble_threshold_pct,
        check_resilience=not args.no_resilience,
        check_fft=not args.no_fft,
        fft_threshold_pct=args.fft_threshold_pct,
        check_comm=not args.no_comm,
        comm_excess_pct=args.comm_excess_pct,
        check_service=not args.no_service,
        service_queue_factor=args.service_queue_factor,
        service_queue_floor_s=args.service_queue_floor,
        service_ttfs_factor=args.service_ttfs_factor,
        service_ttfs_floor_s=args.service_ttfs_floor,
        check_latency=not args.no_latency,
        latency_miss_factor=args.latency_miss_factor,
        latency_miss_floor=args.latency_miss_floor,
        check_alerts=not args.no_alerts,
        check_perf=not args.no_perf,
        check_fleet=not args.no_fleet,
        fleet_queue_factor=args.fleet_queue_factor,
        fleet_queue_floor_s=args.fleet_queue_floor,
        fleet_ttfs_factor=args.fleet_ttfs_factor,
        fleet_ttfs_floor_s=args.fleet_ttfs_floor,
        check_capacity=not args.no_capacity,
        goodput_factor=args.goodput_factor,
        goodput_floor=args.goodput_floor)

    print(json.dumps(verdict, indent=1, sort_keys=True))
    for w in verdict.get("warnings", []):
        print(f"gate: WARNING: {w}", file=sys.stderr)
    for r in verdict.get("reasons", []):
        print(f"gate: {r}", file=sys.stderr)
    print(f"gate: {'PASS' if verdict['ok'] else 'FAIL'} "
          f"(exit {verdict['exit_code']})", file=sys.stderr)
    # the verdict joins the run record when an event log is configured
    _events.emit("gate_verdict", ok=verdict["ok"],
                 exit_code=verdict["exit_code"],
                 reasons=verdict["reasons"])
    return verdict["exit_code"]


if __name__ == "__main__":
    sys.exit(main())
