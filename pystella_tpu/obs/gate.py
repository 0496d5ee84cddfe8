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
      ``fft_threshold_pct`` — or a COMM EXCESS: a ``comm`` leg's
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
      whose fingerprints mismatch the live compiler stack, the report
      claims fewer incidents than its ``resilience`` event record
      carries (a clean headline over a degraded fleet), the report's
      ``comm`` section claims
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

The comparison itself touches no device: it reads two JSON reports
(importing this module imports the ``pystella_tpu`` package, and
therefore jax, like any in-repo CI environment has).
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
                    check_comm=True, comm_excess_pct=25.0):
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
    if check_resilience and (baseline or {}).get("resilience") \
            and not current.get("resilience"):
        verdict["warnings"].append(
            "resilience: baseline carried a resilience section but the "
            "current run has none — incident/checkpoint coverage was "
            "lost")
    return verdict


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
        comm_excess_pct=args.comm_excess_pct)

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
