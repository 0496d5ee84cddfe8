"""Request-scoped tracing (obs schema v2 + obs.spans): the ambient
tracing context, span-tree assembly across rotated event-log families,
trace-id survival across preempt -> requeue -> resume, the critical-path
partition audit (phases sum to the measured wall), the Perfetto export
folding through the shared scope vocabulary, the event-kind registry,
the `python -m pystella_tpu.service status` ops view, and the
PYSTELLA_TRACE_SERVICE opt-out."""

import os
import sys
import threading
import time

import pytest

import common  # noqa: F401  (side effect: enables x64)

import pystella_tpu as ps  # noqa: F401  (package import for the service)
from pystella_tpu import obs
from pystella_tpu.obs import events, spans
from pystella_tpu.obs import trace as obs_trace
from pystella_tpu.obs.events import EventLog, rotated_family, tracing
from pystella_tpu.obs.ledger import PerfLedger
from pystella_tpu.service import ScenarioRequest
from pystella_tpu.service import __main__ as service_cli

from test_service import _make_service, SIG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure(path)
    yield path
    obs.configure(None)


# -- the tracing context (events schema v2) ---------------------------------

def test_tracing_context_nesting_and_inheritance(event_log):
    assert events.current_trace() is None
    with tracing(trace="T1", span="ROOT"):
        assert events.current_trace() == {"trace": "T1", "span": "ROOT",
                                          "parent": None}
        with tracing(span="LEASE"):
            # opening a new span under an active one: trace inherited,
            # the enclosing span becomes the parent
            ctx = events.current_trace()
            assert ctx == {"trace": "T1", "span": "LEASE",
                           "parent": "ROOT"}
            with tracing(trace="T2", parent="OTHER"):
                # explicit fields override, unset ones inherit
                assert events.current_trace() == {
                    "trace": "T2", "span": "LEASE", "parent": "OTHER"}
        assert events.current_trace()["span"] == "ROOT"
    assert events.current_trace() is None


def test_emit_carries_trace_fields_only_in_context(event_log):
    obs.emit("step_time", ms=1.0)
    with tracing(trace="T", span="S", parent="P"):
        obs.emit("step_time", ms=2.0)
    evs = events.read_events(event_log)
    assert evs[0]["v"] == events.SCHEMA_VERSION == 2
    # no context: v1-shaped record (absent fields, not nulls)
    assert "trace" not in evs[0] and "span" not in evs[0]
    assert evs[1]["trace"] == "T" and evs[1]["span"] == "S" \
        and evs[1]["parent"] == "P"


def test_tracing_context_is_thread_local(event_log):
    seen = {}

    def worker():
        seen["ctx"] = events.current_trace()
        obs.emit("step_time", ms=3.0)

    with tracing(trace="T", span="S"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert seen["ctx"] is None
    ev = events.read_events(event_log)[-1]
    assert "trace" not in ev  # helper threads never mis-attribute


def test_ids_are_fresh():
    assert events.new_trace_id() != events.new_trace_id()
    assert len(events.new_trace_id()) == 16
    assert len(events.new_span_id()) == 8


# -- the event-kind registry ------------------------------------------------

def test_event_kind_registry():
    kinds = events.registered_event_kinds()
    assert {"service_request", "member_result", "deadline_missed",
            "checkpoint_durable", "run_resumed", "service_trace",
            "step_time"} <= set(kinds)
    assert all(isinstance(v, str) for v in kinds.values())
    # idempotent, live
    events.register_event_kind("service_request", "different text")
    assert kinds["service_request"] == events.registered_event_kinds()[
        "service_request"]


def test_every_emit_literal_is_registered():
    """The package's own emit vocabulary is fully registered — the
    event-registry lint IS the CI gate (same pattern as the scope
    registry)."""
    from pystella_tpu.lint import source as lint_source
    violations, stats = lint_source.check_package(
        os.path.join(REPO, "pystella_tpu"),
        checks={"event-registry"})
    assert "service_dispatch" in stats["emit_literals"]
    assert "deadline_missed" in stats["emit_literals"]
    assert violations == [], "\n".join(str(v) for v in violations)
    # ... and the checker itself catches a fresh kind (no vacuous pass)
    registered = set(events.registered_event_kinds()) - {"member_result"}
    violations, _ = lint_source.check_package(
        os.path.join(REPO, "pystella_tpu"),
        checks={"event-registry"},
        registered_event_kinds=frozenset(registered))
    assert any(v.detail.get("kind") == "member_result"
               for v in violations)


# -- span assembly across a rotated family ----------------------------------

def test_span_assembly_across_rotated_family(tmp_path):
    """A request whose lifecycle straddles rotation boundaries still
    assembles: the assembler reads the family like the ledger does.
    Synthetic stream, rotate_bytes small enough that the filler between
    lifecycle events forces several rollovers."""
    path = str(tmp_path / "run_events.jsonl")
    log = EventLog(path, rotate_bytes=500)

    def filler(n=8):
        for i in range(n):
            log.emit("step_time", step=i, ms=1.0)

    with tracing(trace="TR", span="ROOT"):
        log.emit("service_request", id=7, tenant="a", priority=2,
                 signature="toy/8x8x8/1x1x1/float32", nsteps=4,
                 deadline_s=100.0)
        log.emit("service_admit", id=7, warm=True)
    filler()
    with tracing(span="LEASE1"):
        with tracing(trace="TR", parent="ROOT"):
            log.emit("service_dispatch", id=7, lease=1,
                     queue_latency_s=0.0, warm=True)
        time.sleep(0.01)  # the segment must hold its claimed costs
        log.emit("checkpoint_durable", step=2, wait_s=1e-4)
        log.emit("run_preempted", step=2, drain_s=1e-4)
        with tracing(trace="TR", parent="ROOT"):
            log.emit("service_requeue", id=7, lease=1, steps_done=2)
        log.emit("service_lease", lease=1, warm=True, cold_build_s=0.0,
                 preempted=True)
    filler()
    with tracing(span="LEASE2"):
        with tracing(trace="TR", parent="ROOT"):
            log.emit("service_dispatch", id=7, lease=2,
                     queue_latency_s=0.0, warm=True, resumed=True)
        log.emit("service_lease", lease=2, warm=True, cold_build_s=0.0,
                 preempted=False)
        with tracing(trace="TR", parent="ROOT"):
            log.emit("member_result", id=7, tenant="a", priority=2,
                     status="completed", deadline_ts=0.0,
                     margin_s=-0.5, deadline_missed=True)
    log.close()
    family = rotated_family(path)
    assert len(family) > 2, "the filler must have rotated the log"

    # the live tail alone cannot assemble the tree...
    tail = spans.SpanAssembler.from_records(events.read_events(path))
    tail_tree = tail.assemble().get("TR")
    assert tail_tree is None or not tail_tree.assembled
    # ...the family read can
    asm = spans.SpanAssembler.from_events(path)
    tree = asm.assemble()["TR"]
    assert tree.assembled, tree.problems
    assert tree.request_id == 7
    assert tree.leases == ["LEASE1", "LEASE2"]
    assert tree.phases["service_checkpoint_barrier"] > 0
    assert tree.phases["service_preempt_drain"] > 0
    assert tree.phase_sum_rel_err() < 0.05
    assert tree.deadline_missed is True and tree.margin_s == -0.5
    summary = asm.summary()
    assert summary["assembled"] == summary["traced"] == 1
    assert summary["deadline"]["miss_rate"] == 1.0
    assert summary["phase_sum_check"]["ok"] is True


# -- trace survival through the real service --------------------------------

@pytest.fixture(scope="module")
def preempted_run(tmp_path_factory):
    """One real preemption round trip (like test_service's tentpole
    pin), shared by the trace-continuity / assembler / ledger / CLI
    cases below."""
    tmp = tmp_path_factory.mktemp("spans_svc")
    path = str(tmp / "events.jsonl")
    obs.configure(path)
    try:
        svc = _make_service(tmp)
        svc.arm(SIG)
        r1 = ScenarioRequest("a", SIG, 8, seed=1)
        r2 = ScenarioRequest("b", SIG, 8, seed=2, deadline_s=600.0)
        svc.submit(r1)
        svc.submit(r2)
        high = ScenarioRequest("c", SIG, 4, seed=3, priority=3)
        svc.schedule_arrival(1, high)
        summary = svc.serve()
    finally:
        obs.configure(None)
    return path, summary, (r1, r2, high)


def test_trace_id_survives_preempt_requeue_resume(preempted_run):
    """THE tentpole continuity pin: a preempted request's SECOND lease
    extends the SAME trace — both its dispatch events (and its requeue
    and retire) carry one trace id, while the two leases are distinct
    spans."""
    path, summary, (r1, r2, high) = preempted_run
    assert summary["preemptions"] == 1 and r1.resume_step > 0
    evs = events.read_events(path)
    r1_disp = [e for e in evs if e["kind"] == "service_dispatch"
               and e["data"]["id"] == r1.id]
    assert len(r1_disp) == 2
    assert {e["trace"] for e in r1_disp} == {r1.trace_id}
    assert r1_disp[0]["span"] != r1_disp[1]["span"]  # two leases
    assert {e["parent"] for e in r1_disp} == {r1.span_id}
    requeue = [e for e in evs if e["kind"] == "service_requeue"
               and e["data"]["id"] == r1.id]
    assert len(requeue) == 1 and requeue[0]["trace"] == r1.trace_id
    result = [e for e in evs if e["kind"] == "member_result"
              and e["data"]["id"] == r1.id]
    assert result[0]["trace"] == r1.trace_id
    # the high-priority request rode its own trace
    high_disp = [e for e in evs if e["kind"] == "service_dispatch"
                 and e["data"]["id"] == high.id]
    assert high_disp[0]["trace"] == high.trace_id != r1.trace_id
    # supervisor/chunk-loop events inherited the lease spans
    lease_spans = {e["span"] for e in evs
                   if e["kind"] == "service_lease"}
    durable_spans = {e.get("span") for e in evs
                     if e["kind"] == "checkpoint_durable"}
    assert durable_spans <= lease_spans and durable_spans


def test_assembled_critical_path_sums_to_wall(preempted_run):
    """The acceptance pin: every request's phases sum to within 5% of
    the measured submit->retire wall, the preempted requests cross two
    leases, and the preempt-drain phase is measured on them."""
    path, _summary, (r1, r2, _high) = preempted_run
    asm = spans.SpanAssembler.from_events(path)
    trees = asm.assemble()
    assert all(t.assembled for t in trees.values())
    for t in trees.values():
        err = t.phase_sum_rel_err()
        assert err is not None and err < 0.05, (t.request_id, err)
    t1 = trees[r1.trace_id]
    assert len(t1.leases) == 2
    assert t1.phases["service_preempt_drain"] > 0
    assert t1.phases["service_chunk_compute"] > 0
    # r2 carried an un-missable deadline: margin recorded positive
    t2 = trees[r2.trace_id]
    assert t2.deadline_missed is False and t2.margin_s > 0
    summary = asm.summary()
    assert summary["phase_sum_check"]["ok"] is True
    assert summary["deadline"]["deadlined"] == 1
    assert summary["deadline"]["missed"] == 0
    assert summary["deadline"]["miss_rate"] == 0.0


def test_perfetto_export_folds_through_scope_parser(preempted_run,
                                                    tmp_path):
    path, _summary, _reqs = preempted_run
    asm = spans.SpanAssembler.from_events(path)
    out = asm.export_perfetto(str(tmp_path / "svc_trace.json"))
    rows = obs_trace.parse_trace_file(out)
    assert rows, "export must be parse_trace_file-compatible"
    table = obs_trace.scope_durations(rows)
    assert {"service_request_span", "service_lease_span",
            "service_queue_wait",
            "service_chunk_compute"} <= set(table)
    assert table["service_request_span"]["count"] == 3  # one per request
    # every exported span name is registered vocabulary (one parser
    # for hardware captures and service timelines)
    from pystella_tpu.obs.scope import registered_scopes
    names = {r["name"] for r in rows if r.get("ph") == "X"}
    assert names <= set(registered_scopes())


def test_ledger_latency_section_and_spans_cli(preempted_run):
    path, _summary, (r1, _r2, _high) = preempted_run
    led = PerfLedger.from_events(path, label="spans")
    lat = led.report()["latency"]
    assert lat["traced"] == lat["assembled"] == 3
    assert lat["unassembled"] == []
    assert lat["phase_sum_check"]["ok"] is True
    assert lat["deadline"]["deadlined"] == 1
    assert "service_chunk_compute" in lat["phases_s"]
    rows = {r["id"]: r for r in lat["requests"]}
    assert rows[r1.id]["leases"] == 2
    # the markdown section renders
    from pystella_tpu.obs.ledger import render_markdown
    md = render_markdown(led.report())
    assert "## Latency (request critical path)" in md
    # the spans CLI round-trips the same summary (driven in-process —
    # same argparse -> summary -> stdout path as a subprocess run,
    # without another interpreter + jax startup against the budget)
    assert spans.main(["--events", path]) == 0


def test_service_status_cli(preempted_run, capsys):
    path, _summary, (r1, _r2, high) = preempted_run
    state = service_cli.reconstruct(path)
    assert state["queue_depth"] == 0
    assert state["leases"]["active"] == []
    assert state["leases"]["completed"] >= 2
    assert state["done"] is not None
    retired = {r["id"]: r for r in state["retired"]}
    assert retired[r1.id]["status"] == "completed"
    assert retired[r1.id]["trace"] == r1.trace_id
    tenants = state["tenants"]
    assert tenants["a"]["retired"] == 1
    assert tenants["a"]["member_steps"] > 0
    # the CLI renders without a live server handle
    assert service_cli.main(["status", "--events", path,
                             "--last", "5"]) == 0
    text = capsys.readouterr().out
    assert "queue depth 0" in text
    assert str(r1.trace_id) in text


def test_status_cli_sees_midrun_queue(event_log, tmp_path):
    """The ops view reconstructs a LIVE queue: submitted-but-undispatched
    requests count as depth, and an armed signature is listed —
    including submissions that precede the serve loop's service_start
    marker (submit() emits at submit time, serve() marks later)."""
    svc = _make_service(tmp_path)
    svc.arm(SIG)
    r1 = ScenarioRequest("a", SIG, 4, seed=1)
    r2 = ScenarioRequest("b", SIG, 4, seed=2)
    svc.submit(r1)
    svc.submit(r2)
    state = service_cli.reconstruct(event_log)
    assert state["queue_depth"] == 2
    assert [a["signature"] for a in state["armed"]] == [SIG]
    assert {r["tenant"] for r in state["queue"]} == {"a", "b"}
    # a full serve retires them; the NEXT loop's pre-serve submissions
    # are then visible even though the current-loop scoping starts at
    # the previous loop's service_done
    svc.serve()
    r3 = ScenarioRequest("c", SIG, 4, seed=3)
    svc.submit(r3)
    state = service_cli.reconstruct(event_log)
    assert state["queue_depth"] == 1
    assert state["queue"][0]["id"] == r3.id
    assert state["queue"][0]["trace"] == r3.trace_id
    assert len(state["retired"]) == 2
    # the second serve loop cuts the first one away
    svc.serve()
    r4 = ScenarioRequest("d", SIG, 4, seed=4)
    svc.submit(r4)
    state = service_cli.reconstruct(event_log)
    assert state["queue_depth"] == 1
    assert state["queue"][0]["id"] == r4.id
    assert len(state["retired"]) == 1  # only loop 2's retire


def test_trace_service_opt_out(tmp_path, monkeypatch):
    monkeypatch.setenv("PYSTELLA_TRACE_SERVICE", "0")
    path = str(tmp_path / "ev.jsonl")
    obs.configure(path)
    try:
        svc = _make_service(tmp_path)
        svc.arm(SIG)
        r = ScenarioRequest("a", SIG, 4, seed=1)
        assert r.trace_id is None and r.span_id is None
        svc.submit(r)
        svc.serve()
    finally:
        obs.configure(None)
    evs = events.read_events(path)
    # the opt-out restores v1-SHAPED records: no trace, no span, no
    # parent — not even on lease/supervisor/checkpoint events — so the
    # ledger never collects a span stream at all
    assert all("trace" not in e and "span" not in e
               and "parent" not in e for e in evs)
    led = PerfLedger.from_events(path)
    assert led.span_records == []
    # no traced requests -> no latency section, and that is honest
    assert led.latency() is None


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
