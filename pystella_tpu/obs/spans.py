"""Causal spans over the run-event stream: request-scoped tracing.

PR 12's scenario service emits a flat JSONL record (``service_request``
/ ``service_admit`` / ``service_dispatch`` / ... keyed by request id),
so "where did this request's latency go?" meant hand-joining events.
This module closes that gap the way ``obs.trace`` closed the profiler
gap: the schema-v2 ``trace``/``span``/``parent`` fields
(:mod:`pystella_tpu.obs.events`) make every emitted event a node in a
per-request causal tree, and the :class:`SpanAssembler` reconstructs

- the **span tree** per request: a root ``service_request_span``
  (submit → retire) with ``service_lease_span`` children (one per lease
  the request rode — a preempted request keeps ONE trace id across all
  of them), and leaf spans for every attributable cost inside a lease
  (checkpoint barriers, recovery replay, the preemption drain);
- the **critical-path decomposition**: the submit→retire wall time
  partitioned into the :data:`PHASES` vocabulary — queue wait,
  admission, backend compile, chunk compute, checkpoint barrier,
  recovery replay, preemption drain. The phases are a *partition by
  construction* (compute is the lease residual after the measured
  inner costs), so they sum to the measured wall time; the summary
  records the worst relative error so the property is auditable, not
  assumed;
- the **deadline ledger**: per-request ``margin_s`` (retire vs
  ``deadline_ts``, recorded hit or miss by
  :class:`~pystella_tpu.service.results.ResultEmitter`) and miss rates
  per priority class — the report's ``latency`` section and the gate's
  deadline-miss SLO consume exactly this.

The assembled timeline exports as a Perfetto-loadable trace file
(:meth:`SpanAssembler.export_perfetto`) whose span names are registered
trace scopes (:mod:`pystella_tpu.obs.scope`), so hardware profiler
captures and service traces read through one parser
(:func:`pystella_tpu.obs.trace.scope_durations` folds both).

Stdlib-only and jax-free, like ``obs.events``: a jax-free supervisor
and offline analysis load it by file. CLI::

    python -m pystella_tpu.obs.spans --events run_events.jsonl \
        [--perfetto service_trace.json] [--trace <id>]

Old (v1) logs carry no trace fields: every reader here tolerates their
absence and simply assembles nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

__all__ = ["PHASES", "RequestTrace", "SpanAssembler", "main"]

#: the critical-path phase vocabulary, in lifecycle order. Every name
#: is a registered trace scope (obs.scope), so Perfetto exports fold
#: through obs.trace.scope_durations like hardware captures do.
PHASES = (
    "service_admission",          # submit -> admission verdict
    "service_queue_wait",         # queued behind the scheduler (per leg)
    "service_compile",            # cold lease: build+trace+compile paid
    "service_chunk_compute",      # supervised chunk loop (residual)
    "service_checkpoint_barrier",  # durability-barrier waits
    "service_recovery_replay",    # device-loss/numerics recovery (MTTR)
    "service_preempt_drain",      # drain to a durable checkpoint
)

#: event kinds that terminate a request's root span
_TERMINAL_KINDS = ("member_result", "service_reject")


def _get(ev, key):
    return ev.get(key) if isinstance(ev, dict) else None


def _data(ev):
    d = ev.get("data")
    return d if isinstance(d, dict) else {}


def _num(x, default=0.0):
    return float(x) if isinstance(x, (int, float)) else default


def _stats(samples):
    """Latency summary in seconds — the ledger's ``_lat_stats`` shape,
    so the ``latency`` and ``service`` sections quantify identically.
    Imported lazily: the ledger imports this module (inside
    ``latency()``), so a module-level import back would be fragile."""
    from pystella_tpu.obs.ledger import _lat_stats
    return _lat_stats([x for x in samples
                       if isinstance(x, (int, float))])


class RequestTrace:
    """One request's assembled span tree + critical path.

    Attributes: ``trace`` (the trace id), ``request_id``, ``tenant``,
    ``priority``, ``signature``, ``status``, ``submit_ts`` /
    ``retire_ts`` / ``wall_s``, ``phases`` (phase name → seconds, a
    partition of the wall), ``spans`` (flat list of
    ``{name, span, parent, t0, dur_s}`` rows, root first), ``leases``
    (lease span ids in ride order), and the deadline fields
    (``deadline_ts`` / ``margin_s`` / ``deadline_missed``, ``None``
    for undeadlined requests). ``assembled`` is False (with
    ``problems``) when the tree cannot be closed — e.g. the request
    never retired in the ingested window.
    """

    def __init__(self, trace):
        self.trace = trace
        self.request_id = None
        self.tenant = None
        self.priority = None
        self.signature = None
        self.status = None
        self.submit_ts = None
        self.retire_ts = None
        self.wall_s = None
        self.phases = {}
        self.spans = []
        self.leases = []
        self.deadline_ts = None
        self.margin_s = None
        self.deadline_missed = None
        self.assembled = False
        self.problems = []

    @property
    def dominant_phase(self):
        if not self.phases:
            return None
        return max(self.phases, key=lambda p: self.phases[p])

    @property
    def phase_sum_s(self):
        return sum(self.phases.values())

    def phase_sum_rel_err(self):
        """|Σ phases − wall| / wall — the partition-audit statistic
        (``None`` for an unassembled or zero-wall tree)."""
        if not self.assembled or not self.wall_s:
            return None
        return abs(self.phase_sum_s - self.wall_s) / self.wall_s

    def as_row(self):
        return {
            "id": self.request_id, "trace": self.trace,
            "tenant": self.tenant, "priority": self.priority,
            "status": self.status,
            "wall_s": (round(self.wall_s, 6)
                       if self.wall_s is not None else None),
            "phases_s": {p: round(s, 6)
                         for p, s in self.phases.items()},
            "dominant_phase": self.dominant_phase,
            "leases": len(self.leases),
            "deadline_missed": self.deadline_missed,
            "margin_s": (round(self.margin_s, 6)
                         if self.margin_s is not None else None),
        }


class SpanAssembler:
    """Reconstruct per-request span trees from an event stream.

    Build with :meth:`from_events` (reads the whole rotated family, so
    a request whose spans straddle a ``rotate_bytes`` boundary still
    assembles) or :meth:`from_records` (already-loaded dicts). The
    heavy lifting happens once in :meth:`assemble`; :meth:`summary`
    and :meth:`export_perfetto` derive from it.
    """

    def __init__(self, records):
        self.records = [r for r in records if isinstance(r, dict)]
        self._by_trace = {}
        self._by_span = {}
        self._by_parent = {}
        for ev in self.records:
            trace = ev.get("trace")
            if trace is not None:
                self._by_trace.setdefault(str(trace), []).append(ev)
            span = ev.get("span")
            if span is not None:
                self._by_span.setdefault(str(span), []).append(ev)
            parent = ev.get("parent")
            if parent is not None and parent != span:
                self._by_parent.setdefault(str(parent), []).append(ev)
        self._trees = None

    @classmethod
    def from_records(cls, records):
        return cls(records)

    @classmethod
    def from_events(cls, path):
        """Load from a JSONL event log — the whole rotated family,
        oldest first, so one request's spans reassemble across
        rotation boundaries (loaded by file to stay importable in the
        jax-free supervisor)."""
        from pystella_tpu.obs import events as _events
        return cls(_events.read_events(path, include_rotated=True))

    # -- assembly ------------------------------------------------------------

    def _span_events(self, span, kind=None):
        """Events belonging to a span: ``span`` field matches, or the
        event opened a child span under it (``parent`` matches) — the
        recovery incidents open child spans, and their costs must stay
        attributable to the lease. Index lookups only: assembly over a
        long-lived service's rotated family must stay linear in the
        record count."""
        out = list(self._by_span.get(str(span), []))
        out += self._by_parent.get(str(span), [])
        if kind is not None:
            out = [ev for ev in out if ev.get("kind") == kind]
        return sorted(out, key=lambda ev: _num(ev.get("ts")))

    def assemble(self):
        """``{trace_id: RequestTrace}`` for every trace id the stream
        carries (memoized)."""
        if self._trees is not None:
            return self._trees
        self._trees = {t: self._assemble_one(t, evs)
                       for t, evs in sorted(self._by_trace.items())}
        return self._trees

    def _assemble_one(self, trace, events):
        tree = RequestTrace(trace)
        events = sorted(events, key=lambda ev: _num(ev.get("ts")))
        submit = next((ev for ev in events
                       if ev.get("kind") == "service_request"), None)
        admit = next((ev for ev in events
                      if ev.get("kind") == "service_admit"), None)
        terminal = [ev for ev in events
                    if ev.get("kind") in _TERMINAL_KINDS]
        dispatches = [ev for ev in events
                      if ev.get("kind") == "service_dispatch"]
        requeues = [ev for ev in events
                    if ev.get("kind") == "service_requeue"]
        if submit is None:
            tree.problems.append("no service_request event in the "
                                 "ingested window")
            return tree
        sdata = _data(submit)
        tree.request_id = sdata.get("id")
        tree.tenant = sdata.get("tenant")
        tree.priority = sdata.get("priority")
        tree.signature = sdata.get("signature")
        tree.submit_ts = _num(submit.get("ts"))
        root = submit.get("span") or f"root:{trace}"
        if not terminal:
            tree.problems.append(
                "no terminal event (member_result / service_reject) — "
                "request still in flight, or its retire rotated away")
            return tree
        last = terminal[-1]
        tree.retire_ts = _num(last.get("ts"))
        tree.status = (_data(last).get("status")
                       if last.get("kind") == "member_result"
                       else "rejected")
        tree.wall_s = max(0.0, tree.retire_ts - tree.submit_ts)
        tree.spans.append({"name": "service_request_span", "span": root,
                           "parent": None, "t0": tree.submit_ts,
                           "dur_s": tree.wall_s})
        phases = {p: 0.0 for p in PHASES}

        admit_ts = _num(admit.get("ts")) if admit else tree.submit_ts
        admit_ts = min(max(admit_ts, tree.submit_ts), tree.retire_ts)
        phases["service_admission"] = admit_ts - tree.submit_ts
        if phases["service_admission"] > 0:
            tree.spans.append({
                "name": "service_admission", "span": f"{root}.admit",
                "parent": root, "t0": tree.submit_ts,
                "dur_s": phases["service_admission"]})

        if tree.status == "rejected" or not dispatches:
            # a rejected (or never-dispatched) request: the whole wall
            # is ingestion — fold any residual into admission so the
            # partition property holds for every assembled tree
            phases["service_admission"] = tree.wall_s
            tree.phases = phases
            tree.assembled = True
            return tree

        # one segment per lease leg: [seg_start -> dispatch -> seg_end]
        # where seg_start is the submit (first leg) or the requeue that
        # returned the request to the queue, and seg_end is the next
        # requeue or the retire
        seg_starts = [admit_ts] + [_num(rq.get("ts")) for rq in requeues]
        seg_ends = [_num(rq.get("ts")) for rq in requeues] \
            + [tree.retire_ts]
        for i, disp in enumerate(dispatches):
            dts = _num(disp.get("ts"))
            start = seg_starts[i] if i < len(seg_starts) else dts
            end = seg_ends[i] if i < len(seg_ends) else tree.retire_ts
            end = max(end, dts)
            lease_span = disp.get("span")
            lease_rec = None
            if lease_span is not None:
                tree.leases.append(lease_span)
                recs = self._span_events(lease_span, "service_lease")
                lease_rec = _data(recs[-1]) if recs else None
            # a cold lease's build+compile ran between the queue pop
            # and the dispatch stamp: split it out of the wait
            cold_s = _num((lease_rec or {}).get("cold_build_s"))
            cold_s = min(cold_s, max(0.0, dts - start))
            wait_s = max(0.0, dts - start - cold_s)
            phases["service_queue_wait"] += wait_s
            phases["service_compile"] += cold_s
            if wait_s > 0:
                tree.spans.append({
                    "name": "service_queue_wait",
                    "span": f"{root}.q{i}", "parent": root,
                    "t0": start, "dur_s": wait_s})
            if cold_s > 0:
                tree.spans.append({
                    "name": "service_compile",
                    "span": f"{root}.c{i}", "parent": root,
                    "t0": dts - cold_s, "dur_s": cold_s})
            seg_s = max(0.0, end - dts)
            inner = 0.0
            if lease_span is not None and seg_s > 0:
                tree.spans.append({
                    "name": "service_lease_span", "span": lease_span,
                    "parent": root, "t0": dts, "dur_s": seg_s})
                inner = self._lease_inner(tree, phases, lease_span,
                                          dts, end, seg_s)
            compute_s = max(0.0, seg_s - inner)
            phases["service_chunk_compute"] += compute_s
            if compute_s > 0:
                # the exported span carries the RESIDUAL duration, so
                # folding the Perfetto file through scope_durations
                # agrees with the phase decomposition instead of
                # double-counting the barrier/recovery/drain children
                tree.spans.append({
                    "name": "service_chunk_compute",
                    "span": f"{lease_span or root}.compute{i}",
                    "parent": lease_span or root,
                    "t0": dts, "dur_s": compute_s})
        tree.phases = phases
        tree.assembled = True
        self._deadline(tree, sdata, terminal[-1])
        return tree

    def _lease_inner(self, tree, phases, lease_span, t0, t1, seg_s):
        """Attribute the measurable inner costs of one lease leg
        (barriers, recoveries, the drain) to their phases + spans;
        returns their sum, capped at the segment so the compute
        residual stays a partition."""
        inner = 0.0
        rows = (
            ("checkpoint_durable", "wait_s",
             "service_checkpoint_barrier"),
            ("run_resumed", "mttr_s", "service_recovery_replay"),
            ("run_preempted", "drain_s", "service_preempt_drain"),
        )
        for kind, field, phase in rows:
            for ev in self._span_events(lease_span, kind):
                ts = _num(ev.get("ts"))
                if not (t0 - 1e-6 <= ts <= t1 + 1e-6):
                    continue
                if kind == "run_resumed" and not _data(ev).get(
                        "incident"):
                    continue  # restart-resumes are not recovery cost
                dur = _num(_data(ev).get(field))
                dur = min(dur, max(0.0, seg_s - inner))
                if dur <= 0:
                    continue
                phases[phase] += dur
                inner += dur
                tree.spans.append({
                    "name": phase, "span": ev.get("span") or lease_span,
                    "parent": lease_span, "t0": ts - dur, "dur_s": dur})
        return inner

    def _deadline(self, tree, sdata, last):
        ldata = _data(last)
        deadline_ts = ldata.get("deadline_ts")
        if deadline_ts is None and isinstance(
                sdata.get("deadline_s"), (int, float)):
            deadline_ts = tree.submit_ts + float(sdata["deadline_s"])
        if deadline_ts is None:
            return
        tree.deadline_ts = float(deadline_ts)
        margin = ldata.get("margin_s")
        tree.margin_s = (float(margin)
                         if isinstance(margin, (int, float))
                         else tree.deadline_ts - tree.retire_ts)
        missed = ldata.get("deadline_missed")
        tree.deadline_missed = (bool(missed) if missed is not None
                                else tree.margin_s < 0.0)

    # -- reports -------------------------------------------------------------

    def summary(self, max_requests=64, tolerance=0.05):
        """The ``latency`` report-section payload: per-phase
        percentiles over assembled requests, the dominant-phase
        histogram, the deadline ledger per priority class, the
        partition audit, and the coverage split (``unassembled`` names
        the traces whose tree failed to close — the gate's
        coverage-loss warning keys on it). ``None`` when the stream
        carries no traced request at all."""
        trees = self.assemble()
        if not trees:
            return None
        ok = [t for t in trees.values() if t.assembled]
        bad = [t for t in trees.values() if not t.assembled]
        phase_samples = {p: [] for p in PHASES}
        dominant = {}
        walls, errs = [], []
        deadlined, missed, margins = [], [], []
        by_cls = {}
        for t in ok:
            walls.append(t.wall_s)
            for p in PHASES:
                if t.phases.get(p, 0.0) > 0:
                    phase_samples[p].append(t.phases[p])
            dom = t.dominant_phase
            if dom:
                dominant[dom] = dominant.get(dom, 0) + 1
            err = t.phase_sum_rel_err()
            if err is not None:
                errs.append(err)
            if t.deadline_missed is not None:
                deadlined.append(t)
                margins.append(t.margin_s)
                cls = str(t.priority)
                row = by_cls.setdefault(cls, {"deadlined": 0,
                                              "missed": 0})
                row["deadlined"] += 1
                if t.deadline_missed:
                    missed.append(t)
                    row["missed"] += 1
        for row in by_cls.values():
            row["miss_rate"] = row["missed"] / row["deadlined"]
        return {
            "traced": len(trees),
            "assembled": len(ok),
            "unassembled": [
                {"trace": t.trace, "id": t.request_id,
                 "problems": t.problems} for t in bad[:16]],
            "unassembled_total": len(bad),
            "wall_s": _stats(walls),
            "phases_s": {p: _stats(v)
                         for p, v in phase_samples.items() if v},
            "dominant_phase": dict(sorted(dominant.items())),
            "requests": [t.as_row() for t in
                         sorted(ok, key=lambda t: t.submit_ts or 0.0)
                         [:max_requests]],
            "phase_sum_check": {
                "max_rel_err": max(errs) if errs else None,
                "tolerance": tolerance,
                "ok": (max(errs) <= tolerance) if errs else None,
            },
            "deadline": {
                "deadlined": len(deadlined),
                "missed": len(missed),
                "miss_rate": (len(missed) / len(deadlined)
                              if deadlined else None),
                "by_priority": by_cls,
                "margin_s": _stats(margins),
            },
        }

    def export_perfetto(self, path):
        """Write the assembled service timeline as a Perfetto/Chrome
        ``traceEvents`` file: one complete-span (``ph="X"``) row per
        span, one timeline row (``tid``) per request, span names from
        the registered scope vocabulary — load it at ``ui.perfetto.dev``
        next to a hardware capture, or fold it through
        :func:`pystella_tpu.obs.trace.scope_durations` like any other
        trace. Returns the path (``None`` when nothing assembled)."""
        trees = [t for t in self.assemble().values() if t.assembled]
        if not trees:
            return None
        t_origin = min(t.submit_ts for t in trees)
        events = []
        for tid, tree in enumerate(
                sorted(trees, key=lambda t: t.submit_ts), start=1):
            events.append({
                "ph": "M", "pid": 1, "tid": tid, "name": "thread_name",
                "args": {"name": f"request {tree.request_id} "
                                 f"({tree.tenant}, p{tree.priority})"}})
            for span in tree.spans:
                events.append({
                    "ph": "X", "pid": 1, "tid": tid, "cat": "service",
                    "name": span["name"],
                    "ts": (span["t0"] - t_origin) * 1e6,
                    "dur": max(span["dur_s"], 0.0) * 1e6,
                    "args": {"trace": tree.trace,
                             "request": tree.request_id,
                             "span": span["span"],
                             "parent": span["parent"]}})
        out = {"traceEvents": events, "displayTimeUnit": "ms"}
        parent = os.path.dirname(os.path.abspath(path))
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f)
        return path


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m pystella_tpu.obs.spans",
        description="assemble request-scoped span trees from a run-"
                    "event log (rotated families included) and report "
                    "critical-path latency / export a Perfetto "
                    "timeline")
    p.add_argument("--events", required=True,
                   help="run-event JSONL path (the rotated family is "
                        "read automatically)")
    p.add_argument("--perfetto", default=None,
                   help="write the assembled service timeline here "
                        "(default: the registered PYSTELLA_TRACE_EXPORT "
                        "when set)")
    p.add_argument("--trace", default=None,
                   help="print one trace's span tree instead of the "
                        "summary")
    args = p.parse_args(argv)

    asm = SpanAssembler.from_events(args.events)
    if args.trace:
        tree = asm.assemble().get(args.trace)
        if tree is None:
            print(f"spans: no trace {args.trace!r} in {args.events}",
                  file=sys.stderr)
            return 1
        print(json.dumps({"trace": tree.trace, "row": tree.as_row(),
                          "spans": tree.spans,
                          "problems": tree.problems},
                         indent=1, sort_keys=True))
        return 0
    summary = asm.summary()
    if summary is None:
        print(f"spans: no traced requests in {args.events}",
              file=sys.stderr)
        return 1
    perfetto = args.perfetto
    if perfetto is None:
        from pystella_tpu import config as _config
        perfetto = _config.getenv("PYSTELLA_TRACE_EXPORT")
    if perfetto:
        out = asm.export_perfetto(perfetto)
        summary["perfetto"] = out
    print(json.dumps(summary, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
