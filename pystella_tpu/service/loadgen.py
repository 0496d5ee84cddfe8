"""Seeded synthetic load for the scenario service.

``run()`` stands up a :class:`~pystella_tpu.service.ScenarioService`
around a small scalar-preheating model and drives it with a
deterministic multi-tenant request mix that exercises every policy leg
in one pass (``tests/test_service.py``, ``tests/test_capacity.py``):

- **mixed tenants and priorities**: three tenants with 2:1:1 fair-share
  weights submit priority-1 work against one WARM signature (armed
  before any submission — those requests' time-to-first-step is pure
  dispatch, proven by the lease's ``backend_compiles == 0``);
- **one forced cold signature**: a request for a lattice no pool entry
  serves, handled per the cold policy (default: admitted queued behind
  the build+compile, its TTFS visibly paying it);
- **one forced preemption**: a priority-3 request arrives (via
  ``schedule_arrival``) while the first priority-1 lease is mid-flight;
  the lease drains to a durable checkpoint, the high-priority request
  is served next, and the preempted members resume bit-consistently —
  ``run()`` re-verifies that against an uninterrupted replay through
  the same warm program and reports ``preempt_bitexact``;
- **one quota rejection**: the heaviest tenant submits one request past
  its admission quota;
- **one certain capacity rejection**: after arming, the capacity
  monitor's budget (:mod:`pystella_tpu.obs.capacity`) is pinned to a
  deterministic multiple of the resident predicted footprint, and a
  seeded "hog" signature whose recorded footprint is TWICE the whole
  budget is submitted — ``CapacityExceeded`` by construction, so every
  smoke record carries one memory-aware rejection (and, at retire,
  per-tenant chip-second accounts with healthy goodput for the
  tenants that ran);
- **one certain SLO burn alert**: a seeded
  :class:`~pystella_tpu.obs.slo.SLOMonitor` rides the run
  (:func:`seeded_slo_monitor`) with its ``deadline_miss`` leg windowed
  to the last sample — bravo's impossible 20 ms deadline fires
  ``slo_alert`` at its guaranteed miss, charlie's unmissable 60 s
  deadline resolves it at the next retire, so BOTH live-alert
  transitions land in every smoke record deterministically (the
  queue/TTFS legs run with deliberately generous objectives so only
  the seeded leg can fire). The monitor's ingest cost is measured and
  reported (``slo.ingest_s``) — the emit-path overhead pin.

Everything lands in the configured event log; the perf ledger's
``service``/``latency``/``alerts`` sections and the gate's SLO + alert
verdicts consume it from there.

:func:`run_fleet` is the fleet-plane counterpart: a deterministic
TWO-replica drill — two in-process services with their own ephemeral
live endpoints and registry records, a split tenant mix, a
:class:`~pystella_tpu.obs.fleet.FleetAggregator` federating both, and
one replica killed mid-run (no tombstone) so the aggregator's expiry
path, the ``fleet_replica_lost`` record, and the unresolved
``dead_replicas`` fleet alert are all produced by real machinery in a
seconds-long run. The ledger's ``fleet`` section and the gate's fleet
verdicts are pinned against exactly this record in tier-1.

:func:`run_perf` is the continuous-performance counterpart
(:mod:`pystella_tpu.obs.perf`): a seeded sleep-in-step drill with two
injected sustained slowdowns that must fire ``perf_anomaly`` (with
straggler attribution), write exactly one rate-limited flight-recorder
capture, recover (``perf_recovered``), and fire+resolve the
``perf_regression`` SLO leg — the tier-1 proof of the whole plane in
about a second.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import slo as _slo
from pystella_tpu.service.admission import request_signature
from pystella_tpu.service.queue import (
    FairShareScheduler, ScenarioRequest)
from pystella_tpu.service.results import ResultEmitter
from pystella_tpu.service.server import ScenarioService

__all__ = ["run", "run_fleet", "run_perf", "VirtualClock",
           "build_preheat_model",
           "seeded_slo_monitor", "seeded_fleet_legs",
           "seeded_perf_monitor"]


def seeded_slo_monitor(label="loadgen"):
    """The loadgen's deterministic SLO-monitor configuration: the
    ``deadline_miss`` leg is capped at the LAST deadline verdict
    (``window_samples=1``), so the mix's one guaranteed miss fires the
    alert and the next guaranteed hit resolves it — one certain
    fire+resolve pair per run, independent of wall-clock windows. The
    queue/TTFS legs keep running with objectives far above anything a
    smoke mix produces (they exist so the ingest path is exercised, not
    to fire), and the incident leg keeps its default (it fires only
    when a drill injects faults)."""
    return _slo.SLOMonitor(legs={
        "queue_p95": {"objective": 120.0},
        "warm_ttfs": {"objective": 120.0},
        "deadline_miss": {"window_samples": 1, "min_samples": 1},
        "incident_rate": {},
    }, label=label)


def seeded_fleet_legs():
    """The fleet drill's deterministic
    :class:`~pystella_tpu.obs.fleet.FleetAggregator` leg
    configuration, mirroring :func:`seeded_slo_monitor`: the
    ``deadline_miss`` leg is windowed to the last federated sample so
    replica-a's one guaranteed miss fires the FLEET alert and its one
    guaranteed hit resolves it within a single aggregation pass; the
    queue/TTFS legs run with objectives no smoke mix can breach (the
    federation ingest path is exercised, they never fire); and
    ``dead_replicas`` keeps its zero bar — the killed replica's expiry
    is the drill's one certain unresolved fleet alert."""
    return {
        "queue_p95": {"objective": 120.0},
        "warm_ttfs": {"objective": 120.0},
        "deadline_miss": {"window_samples": 1, "min_samples": 1},
        "incident_rate": {},
        "dead_replicas": {},
    }


def build_preheat_model(dtype=np.float32):
    """The loadgen's scenario model: a 2-field scalar-preheating
    system on the generic XLA path. Returns the ``builder(grid_shape,
    decomp)`` the service's model registry wants."""

    def builder(grid_shape, decomp=None):
        import jax
        import pystella_tpu as ps

        lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=dtype)
        dt = dtype(0.1 * min(lattice.dx))
        if decomp is None:
            decomp = ps.DomainDecomposition(
                (1, 1, 1), devices=jax.devices()[:1])
        mphi, gsq = 1.20e-6, 2.5e-7

        def potential(f):
            phi, chi = f[0], f[1]
            return (mphi**2 / 2 * phi**2
                    + gsq / 2 * phi**2 * chi**2) / mphi**2

        sector = ps.ScalarSector(2, potential=potential)
        derivs = ps.FiniteDifferencer(decomp, 2, lattice.dx)
        sector_rhs = ps.compile_rhs_dict(sector.rhs_dict)

        def full_rhs(state, t, a, hubble):
            return sector_rhs(state, t, lap_f=derivs.lap(state["f"]),
                              a=a, hubble=hubble)

        stepper = ps.LowStorageRK54(full_rhs, dt=dt)

        def sample(seed):
            rng = np.random.default_rng(1000 + seed)
            state = {
                "f": decomp.shard(1e-3 * rng.standard_normal(
                    (2,) + tuple(grid_shape)).astype(dtype)),
                "dfdt": decomp.shard(1e-4 * rng.standard_normal(
                    (2,) + tuple(grid_shape)).astype(dtype)),
            }
            return state, {"a": 1.0, "hubble": 0.5}

        return stepper, sample, float(dt)

    return builder


class _CapturingEmitter(ResultEmitter):
    """Result emitter that additionally keeps the retired host states
    (the loadgen's bit-consistency re-verification needs them; a real
    deployment never holds them — events are the product)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.states = {}

    def emit(self, request, state, **kwargs):
        if state is not None:
            self.states[request.id] = state
        return super().emit(request, state, **kwargs)


def _uninterrupted_reference(entry, request, slots, chunk):
    """Replay ``request`` uninterrupted through the SAME warm chunk
    program (same chunk size, ballast co-members): the reference the
    preempted-and-resumed trajectory must match bit for bit."""
    import jax

    state, draw = entry.sample(request.seed)
    template_state, template_draw = entry.template
    states = [state] + [template_state] * (slots - 1)
    batch = entry.stack(states)
    td = entry.tick_dtype
    dt_vec = np.full(slots, entry.dt, dtype=td)
    params = {}
    for n in entry.param_names:
        col = np.full(slots, float((template_draw or {}).get(n, 0.0)),
                      dtype=td)
        col[0] = float((draw or {}).get(n, 0.0))
        params[n] = col
    n_chunks = -(-request.nsteps // chunk)
    start = np.zeros(slots, dtype=np.int64)
    for i in range(n_chunks):
        t_vec = ((start + i * chunk) * dt_vec).astype(td)
        batch, _m = entry.ens.multi_step(
            batch, chunk, t=t_vec, dt=dt_vec, rhs_args=params,
            sentinel=entry.sentinel)
    jax.block_until_ready(batch)
    return entry.ens.take_member(batch, 0)


def run(checkpoint_dir, seed=0, slots=None, chunk=None, grid=16,
        cold_grid=12, nsteps=8, quota=3, label="loadgen",
        spectra=True, faults=None, store=None, slo=None,
        capacity=None):
    """Drive one full synthetic service run (module docstring).
    Returns the stats dict (also emitted as a ``service_loadgen``
    event). ``grid``/``cold_grid`` are the warm/cold lattice edges;
    ``nsteps`` the per-request step budget (a multiple of the chunk
    keeps retire boundaries aligned); ``faults`` threads a
    FaultInjector into every lease's supervisor (drills); ``slo`` an
    :class:`~pystella_tpu.obs.slo.SLOMonitor` override (default: the
    :func:`seeded_slo_monitor`; ``False`` disables the live monitor
    entirely, restoring the pre-live event record byte for byte);
    ``capacity`` a :class:`~pystella_tpu.obs.capacity.CapacityMonitor`
    override (``False`` disables the capacity plane — no budget pin,
    no hog submission, no chip-second attribution)."""
    import pystella_tpu as ps

    rng = np.random.default_rng(seed)
    warm_sig = request_signature("preheat", (grid,) * 3)
    cold_sig = request_signature("preheat", (cold_grid,) * 3)

    if slo is None:
        slo = seeded_slo_monitor(label=label)
    elif slo is False:
        slo = None
    scheduler = FairShareScheduler(
        quota=quota, weights={"alpha": 2.0, "bravo": 1.0,
                              "charlie": 1.0})
    results = _CapturingEmitter(label=label)
    service = ScenarioService(checkpoint_dir, slots=slots, chunk=chunk,
                              scheduler=scheduler, results=results,
                              store=store, faults=faults, slo=slo,
                              capacity=capacity, label=label)
    service.register_model("preheat", build_preheat_model())

    # deploy-time arming: the warm signature's program is traced,
    # compiled, and dispatched once HERE — before any request exists,
    # so no request's latency ever contains it
    service.arm(warm_sig)
    # the capacity drill: pin a deterministic HBM budget AFTER the
    # warm program is armed — the resident footprint (plus the cold
    # build) fits with a wide margin, while a seeded "hog" signature
    # whose recorded footprint is twice the WHOLE budget cannot fit
    # under any headroom, so exactly one CapacityExceeded rejection
    # lands in every run regardless of lattice sizes or backend
    hog_sig = request_signature("preheat", (grid * 4,) * 3)
    cap_budget = None
    if service.capacity is not None:
        cap_budget = int(max(service.capacity.resident_bytes(), 1) * 64)
        service.capacity.capacity_bytes = cap_budget
        service.capacity.ledger.record(
            f"service.{hog_sig}", fingerprint="loadgen-hog",
            predicted_bytes=2 * cap_budget, source="aval_estimate",
            persist=False)
    if spectra:
        # retire-time per-member spectra through the planner-selected
        # transform tier (the fused pencil path whenever the service
        # mesh makes it feasible; the single-device smoke mesh serves
        # the same fused spectrum program through the DFT tier)
        entry = service.pool.get(warm_sig)
        sdec = entry.decomp or _default_decomp()
        lat = ps.Lattice((grid,) * 3, (5.0,) * 3, dtype=np.float32)
        fft = ps.make_dft(sdec, grid_shape=(grid,) * 3,
                          dtype=np.float32)
        results.spectra = ps.PowerSpectra(sdec, fft, lat.dk, lat.volume)
        results.spectra_field = "f"

    # the mix: priority-1 warm work across three tenants (alpha twice
    # the weight), one over-quota submission, one cold signature, and
    # a priority-3 arrival one chunk into the first lease. Two
    # requests carry deadlines, one of each verdict BY CONSTRUCTION:
    # bravo's 20 ms deadline cannot survive even a warm lease (the
    # seeded deadline MISS the latency section and the gate's
    # miss-rate SLO pin in tier-1), charlie's 60 s cannot be missed by
    # a smoke mix — so both margin polarities are exercised every run
    mix = [
        ScenarioRequest("alpha", warm_sig, nsteps, seed=1),
        ScenarioRequest("bravo", warm_sig, nsteps, seed=2,
                        deadline_s=0.02),
        ScenarioRequest("alpha", warm_sig, nsteps, seed=3),
        ScenarioRequest("charlie", warm_sig, nsteps, seed=4,
                        deadline_s=60.0),
        ScenarioRequest("alpha", warm_sig, nsteps, seed=5),
        ScenarioRequest("bravo", warm_sig, nsteps, seed=6),
        # over quota: alpha already holds `quota` queued requests
        ScenarioRequest("alpha", warm_sig, nsteps, seed=7),
        # the forced cold signature (no pool entry for cold_grid)
        ScenarioRequest("bravo", cold_sig, nsteps,
                        seed=int(rng.integers(100))),
    ]
    verdicts = [service.submit(r) for r in mix]
    hog_verdict = None
    if service.capacity is not None:
        # the certain CapacityExceeded: charlie is under quota, the
        # signature's recorded footprint is 2x the budget — the BASE
        # verdict admits, the capacity verdict must refuse
        hog = ScenarioRequest("charlie", hog_sig, nsteps, seed=99)
        hog_verdict = service.submit(hog)
    high = ScenarioRequest("charlie", warm_sig, nsteps,
                           seed=8, priority=3)
    service.schedule_arrival(1, high)

    t_serve0 = time.perf_counter()
    summary = service.serve()
    serve_wall_s = time.perf_counter() - t_serve0

    # bit-consistency re-verification: every preempted-and-resumed
    # request's final state must equal its uninterrupted replay
    # through the same warm chunk program
    entry = service.pool.get(warm_sig)
    preempted_ids = [r.id for r in mix + [high]
                     if r.resume_step > 0]
    bitexact = None
    for rid in preempted_ids:
        req = next(r for r in mix + [high] if r.id == rid)
        got = results.states.get(rid)
        if got is None:
            bitexact = False
            break
        ref = _uninterrupted_reference(entry, req, service.slots,
                                       service.chunk)
        ok = all(np.array_equal(np.asarray(got[k]),
                                np.asarray(ref[k])) for k in ref)
        bitexact = ok if bitexact is None else (bitexact and ok)

    deadlined = [r for r in mix + [high]
                 if r.deadline_missed is not None]
    stats = {
        **summary,
        "requests": len(mix) + 1 + (1 if hog_verdict is not None
                                    else 0),
        "warm_admissions": sum(1 for v in verdicts
                               if v.admitted and v.warm),
        "cold_admissions": sum(1 for v in verdicts
                               if v.admitted and not v.warm),
        "preempted_requests": len(preempted_ids),
        "preempt_bitexact": bitexact,
        "deadlined_requests": len(deadlined),
        "deadline_misses": sum(1 for r in deadlined
                               if r.deadline_missed),
        # one trace id per request, end to end: the preempted requests
        # prove trace survival across requeue (their several
        # service_dispatch events share the id)
        "traces": sorted(
            r.trace_id for r in mix + [high]
            + ([hog] if hog_verdict is not None else [])
            if r.trace_id is not None),
        "serve_wall_s": round(serve_wall_s, 4),
    }
    if service.capacity is not None:
        stats["capacity"] = {
            "budget_bytes": cap_budget,
            "hog_rejected": bool(
                hog_verdict is not None
                and getattr(hog_verdict, "kind", None)
                == "capacity_exceeded"),
            "resident_predicted_bytes":
                service.capacity.resident_bytes(),
            "watermark_samples": len(service.capacity.watermarks),
        }
        state = slo.state()
        stats["slo"] = {
            "alerts": state["alerts_total"],
            "resolved": state["resolved_total"],
            "flaps": state["flaps_total"],
            "alerting": state["alerting"],
            "ingested": state["ingested"],
            "ingest_s": state["ingest_s"],
            # the emit-path overhead pin: the monitor's whole ingest
            # cost as a share of the serve wall (< 2% by contract)
            "overhead_pct": round(100.0 * state["ingest_s"]
                                  / max(serve_wall_s, 1e-9), 4),
        }
    _events.emit("service_loadgen", seed=seed, **stats)
    return stats


def run_fleet(workdir, grid=12, nsteps=4, slots=1, chunk=2,
              heartbeat_s=0.1, expire_s=0.5, label="fleet-drill"):
    """The deterministic two-replica fleet drill (module docstring).

    Two in-process :class:`~pystella_tpu.service.ScenarioService`
    replicas (``replica-a``, ``replica-b``) serve a split tenant mix
    — a: ``alpha``/``bravo`` with both deadline polarities (the
    seeded SLO story of :func:`run`), b: ``delta``/``echo`` — each
    with its own ephemeral live endpoint (``live_port="auto"``) and
    registry record under ``<workdir>/registry``. The orchestration
    rides the event log's synchronous subscriber channel: a
    subscriber callback BLOCKS a replica's serve thread at a chosen
    event (b at its first retire, a at its ``service_done``, which is
    emitted while the live plane is still up), so the aggregation
    passes run against two replicas that are provably mid-serve —
    no sleep-and-hope scheduling.

    The drill then takes b down in the shape of a real wedge-then-
    crash: its endpoint closes first and one scrape records the
    live-but-unreachable failure against the still-beating record
    (the failed-scrape evidence), then the crash seam
    (:meth:`~pystella_tpu.service.registry.ReplicaRegistry.kill` — no
    tombstone) stops the heartbeats, and the drill scrapes past the
    expiry until the aggregator declares b LOST (reason
    ``"expired"``): ``fleet_replica_lost`` plus the unresolved
    ``dead_replicas`` fleet alert. Replica a withdraws
    cleanly (tombstone), so the final registry distinguishes the
    shutdown from the crash. Returns the stats dict (also emitted as
    ``fleet_loadgen``); every ``fleet_*`` event lands in the
    configured event log for the ledger's ``fleet`` section and the
    gate's fleet verdicts.

    ``heartbeat_s``/``expire_s`` default to drill-fast values (0.1 s
    beats, 0.5 s expiry) — the production defaults live in the
    registered ``PYSTELLA_FLEET_*`` knobs.
    """
    from pystella_tpu.obs import fleet as _fleet
    from pystella_tpu.service import registry as _registry

    t0 = time.perf_counter()
    workdir = os.path.abspath(str(workdir))
    registry_dir = os.path.join(workdir, "registry")
    env_names = ("PYSTELLA_FLEET_DIR", "PYSTELLA_FLEET_HEARTBEAT_S")
    # the services read both knobs through config.getenv at serve
    # time; two in-process replicas share the process env, so the
    # drill pins it for the duration and restores the caller's values
    # env-registry: PYSTELLA_FLEET_DIR, PYSTELLA_FLEET_HEARTBEAT_S
    prior = {n: os.environ.get(n) for n in env_names}
    os.environ["PYSTELLA_FLEET_DIR"] = registry_dir
    os.environ["PYSTELLA_FLEET_HEARTBEAT_S"] = str(float(heartbeat_s))

    warm_sig = request_signature("preheat", (grid,) * 3)
    svc_a = ScenarioService(
        os.path.join(workdir, "ckpt-a"), slots=slots, chunk=chunk,
        slo=seeded_slo_monitor(label="replica-a"),
        label="replica-a", live_port="auto", fleet_id="replica-a")
    # replica-b carries NO deadline leg: its monitor sees replica-a's
    # retire events through the shared process log, and a second copy
    # of the deadline samples on b's /slo would federate as a
    # fire/resolve/fire flap at fleet level
    svc_b = ScenarioService(
        os.path.join(workdir, "ckpt-b"), slots=slots, chunk=chunk,
        slo=_slo.SLOMonitor(legs={
            "queue_p95": {"objective": 120.0},
            "warm_ttfs": {"objective": 120.0},
            "incident_rate": {},
        }, label="replica-b"),
        label="replica-b", live_port="auto", fleet_id="replica-b")
    for svc in (svc_a, svc_b):
        svc.register_model("preheat", build_preheat_model())
        svc.arm(warm_sig)

    # the pause points: a subscriber callback runs synchronously on
    # the EMITTING thread, so waiting on a gate inside it holds that
    # replica's serve loop at the event — mid-lease for b, live-plane-
    # still-up for a — while the main thread aggregates
    b_seen, b_gate = threading.Event(), threading.Event()
    a_done, a_gate = threading.Event(), threading.Event()

    def orchestrate(rec):
        kind = rec.get("kind")
        data = rec.get("data") or {}
        if (kind == "member_result"
                and data.get("label") == "replica-b"
                and not b_seen.is_set()):
            b_seen.set()
            b_gate.wait(timeout=120.0)
        elif (kind == "service_done"
                and data.get("label") == "replica-a"
                and not a_done.is_set()):
            a_done.set()
            a_gate.wait(timeout=120.0)

    _events.get_log().subscribe(orchestrate)
    summaries, errors = {}, {}

    def serve_in_thread(name, svc):
        try:
            summaries[name] = svc.serve()
        except Exception as e:  # noqa: BLE001 — reported after join
            errors[name] = e

    thread_a = thread_b = None
    try:
        # -- replica-b up first: mid-serve by its first retire --------
        for req in (ScenarioRequest("delta", warm_sig, nsteps, seed=21),
                    ScenarioRequest("echo", warm_sig, nsteps, seed=22)):
            svc_b.submit(req)
        thread_b = threading.Thread(
            target=serve_in_thread, args=("b", svc_b),
            name="fleet-drill-b", daemon=True)
        thread_b.start()
        if not b_seen.wait(timeout=120.0):
            raise RuntimeError(
                "fleet drill: replica-b never retired a member")

        # -- replica-a: the seeded deadline mix (slots=1 leases the
        # requests one at a time; fair-share picks bravo's EDF-first
        # miss, then alpha, then bravo's hit — miss fires the alert,
        # hit resolves it, deterministically)
        for req in (ScenarioRequest("bravo", warm_sig, nsteps, seed=11,
                                    deadline_s=0.02),
                    ScenarioRequest("alpha", warm_sig, nsteps, seed=12),
                    ScenarioRequest("bravo", warm_sig, nsteps, seed=13,
                                    deadline_s=60.0)):
            svc_a.submit(req)
        thread_a = threading.Thread(
            target=serve_in_thread, args=("a", svc_a),
            name="fleet-drill-a", daemon=True)
        thread_a.start()
        if not a_done.wait(timeout=120.0):
            raise RuntimeError(
                "fleet drill: replica-a never finished its mix")

        # -- aggregation pass 1: both replicas provably live ----------
        agg = _fleet.FleetAggregator(
            registry_dir=registry_dir, expire_s=expire_s,
            legs=seeded_fleet_legs(), label=label)
        both_live = agg.scrape()
        queue_gauge_replicas = sorted(
            both_live["gauges"].get("pystella_service_queue_depth", {}))

        # -- the mid-run kill, staged like a real wedge-then-crash:
        # b's endpoint dies first (close blocks ~0.5 s on the serve
        # poll, so the record KEEPS beating past it), one scrape
        # records the live-but-unreachable failure, then the crash
        # seam stops the heartbeats; b's serve loop drains out
        svc_b.live_server.close()
        agg.scrape()
        svc_b.fleet_registry.kill()
        b_gate.set()
        thread_b.join(timeout=120.0)

        # -- expiry: b's record goes stale, the aggregator declares it
        # LOST and the dead_replicas fleet alert fires (unresolved)
        time.sleep(expire_s + 0.3)
        final = agg.scrape()
        for _ in range(50):
            if final["dead"]:
                break
            time.sleep(0.1)
            final = agg.scrape()

        # -- replica-a withdraws cleanly (tombstone) ------------------
        a_gate.set()
        thread_a.join(timeout=120.0)
        if errors:
            name, err = sorted(errors.items())[0]
            raise RuntimeError(
                f"fleet drill: replica-{name} serve failed: {err}") \
                from err
    finally:
        # release any still-held gate before unwinding so a failed
        # drill cannot leave a serve thread parked in the subscriber
        b_gate.set()
        a_gate.set()
        _events.get_log().unsubscribe(orchestrate)
        for name, value in prior.items():
            if value is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = value

    records = _registry.read_records(registry_dir, expire_s=expire_s)
    stats = {
        "label": label,
        "registry_dir": registry_dir,
        "replicas": ["replica-a", "replica-b"],
        "killed": "replica-b",
        "completed": {
            "replica-a": summaries.get("a", {}).get("completed"),
            "replica-b": summaries.get("b", {}).get("completed")},
        "live_both_pass": both_live["live"],
        "queue_gauge_replicas": queue_gauge_replicas,
        "scrapes": final["scrapes"],
        "endpoint_ok": final["endpoint_ok"],
        "endpoint_failed": final["endpoint_failed"],
        "scrape_success_rate": final["scrape_success_rate"],
        "lost": final["lost"],
        "dead": final["dead"],
        "alerts": final["alerts_total"],
        "resolved": final["resolved_total"],
        "flaps": final["flaps_total"],
        "alerting": final["alerting"],
        "legs": {name: {"value_fast": leg.get("value_fast"),
                        "bar": leg.get("bar"),
                        "n_slow": leg.get("n_slow"),
                        "alerting": leg.get("alerting")}
                 for name, leg in final["legs"].items()},
        "skewed": final["skew"]["skewed"],
        "divergent": sorted(final["divergence"]["divergent"]),
        "registry": {r["replica"]: r["status"] for r in records},
        "wall_s": round(time.perf_counter() - t0, 4),
    }
    _events.emit("fleet_loadgen", **stats)
    return stats


def seeded_perf_monitor(recorder, label="perf-drill"):
    """The perf drill's deterministic
    :class:`~pystella_tpu.obs.perf.PerfMonitor` configuration: a short
    baseline window (16 samples, armed after 8) so a seconds-long
    drill trains it, ``k=1``/``h=8`` with the standard 4-sigma
    increment clip — a 5x injected slowdown saturates the clip, so the
    detector fires on the THIRD consecutive slow step (ceil(8/4)=2
    full-clip increments plus one more crosses h=8) while a single
    container-scheduler stall (one clipped increment, then decay)
    cannot — and six consecutive in-band steps recover it."""
    from pystella_tpu.obs import perf as _perf
    return _perf.PerfMonitor(window=16, min_samples=8, k=1.0, h=8.0,
                             recover_n=6, recorder=recorder,
                             digest_every=32, label=label)


class VirtualClock:
    """A clock that moves only when slept on: ``clock()`` reads it,
    ``clock.sleep(s)`` advances it. Handed to :func:`run_perf` it makes
    the drill's step times exactly its schedule, whatever else the
    machine is doing."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, seconds):
        self.now += float(seconds)


def run_perf(capture_dir, base_ms=5.0, slow_ms=25.0, healthy=30,
             slow=12, cooldown=20, capture_steps=4, cooldown_s=3600.0,
             seed=0, label="perf-drill", tracer=None, clock=None):
    """The seeded continuous-performance drill: a sleep-in-step loop
    through a real :class:`~pystella_tpu.utils.profiling.StepTimer`
    with TWO injected sustained slowdowns, proving the whole plane in
    about a second of wall time:

    - ``healthy`` steps of ``base_ms`` sleeps train the detector's
      baseline, then ``slow`` steps of ``slow_ms`` (5x) MUST fire
      ``perf_anomaly`` — with straggler attribution in the payload —
      and start the flight recorder, which writes a real
      ``jax.profiler`` Perfetto artifact over the next
      ``capture_steps`` steps (``tracer`` overrides the backend for
      tests);
    - ``cooldown`` healthy steps recover it (``perf_recovered``);
    - a SECOND identical slowdown fires again, but the recorder's
      ``cooldown_s`` rate limit (default: far longer than the drill)
      suppresses its capture — exactly one artifact per drill, plus a
      recorded suppression count: the rate-limiting proof;
    - a seeded :class:`~pystella_tpu.obs.slo.SLOMonitor` rides the
      run with only the ``perf_regression`` leg, windowed to the last
      transition sample, so the anomaly fires ``slo_alert`` and the
      recovery resolves it deterministically.

    The StepTimer emits per-step ``step_time`` events, so the event
    log ingests into a complete :class:`~pystella_tpu.obs.ledger.
    PerfLedger` report whose ``perf`` section links the capture — the
    record the gate's ``check_perf`` audit consumes. Returns the stats
    dict (also emitted as ``perf_loadgen``), ``stats["ok"]`` rolling
    up the acceptance pins above.

    ``clock`` (a :class:`VirtualClock`) replaces the real sleeps and the
    StepTimer's clock: the schedule is then exact, so the detector sees
    the injected slowdowns and nothing the host's scheduler added (the
    tier-1 test runs it so, beside five other xdist workers; the
    profiler capture stays real)."""
    from pystella_tpu.obs import perf as _perf
    from pystella_tpu.utils.profiling import StepTimer

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    recorder = _perf.FlightRecorder(
        capture_dir, steps=capture_steps, cooldown_s=cooldown_s,
        tracer=tracer, label=label)
    monitor = seeded_perf_monitor(recorder, label=label)
    slo = _slo.SLOMonitor(legs={
        "perf_regression": {"window_samples": 1, "min_samples": 1},
    }, label=label)
    _events.get_log().subscribe(slo.handle)
    sleep = time.sleep if clock is None else clock.sleep
    timer = StepTimer(report_every=1e9, emit_steps=True,
                      signature="drill", perf=monitor,
                      clock=time.perf_counter if clock is None else clock)
    # the schedule: healthy/slow/healthy/slow/healthy, the jitter
    # seeded so the healthy phases are not a constant series (the
    # detector must stay quiet on realistic noise, not on zeros)
    plan = ([base_ms] * healthy + [slow_ms] * slow
            + [base_ms] * cooldown + [slow_ms] * slow
            + [base_ms] * cooldown)
    try:
        timer.tick()                      # arms the inter-step clock
        for ms in plan:
            sleep((ms + float(rng.uniform(0.0, 0.2))) * 1e-3)
            timer.tick()
        recorder.flush()                  # close a still-open capture
        slo.evaluate()
    finally:
        _events.get_log().unsubscribe(slo.handle)
    mstate = monitor.state()
    det = mstate["signatures"].get("drill") or {}
    sstate = slo.state()
    captures = recorder.captures
    artifact = captures[0].get("artifact") if captures else None
    straggler = None
    if det.get("fires"):
        # re-derive the attribution the anomaly payload carried
        straggler = monitor._attribution(  # noqa: SLF001 — drill introspection
            monitor._sigs["drill"]["recent"])
    stats = {
        "label": label,
        "steps": len(plan),
        "anomalies": int(det.get("fires") or 0),
        "recovered": int(det.get("recoveries") or 0),
        "anomalous_at_exit": bool(det.get("anomalous")),
        "captures": len(captures),
        "artifact": artifact,
        "suppressed": recorder.suppressed,
        "capture_errors": recorder.errors,
        "straggler": straggler,
        "digest": {k: det.get(k) for k in
                   ("count", "p50_ms", "p95_ms", "p99_ms")},
        "slo": {
            "alerts": sstate["alerts_total"],
            "resolved": sstate["resolved_total"],
            "alerting": sstate["alerting"],
        },
        "observe_s": mstate["observe_s"],
        "wall_s": round(time.perf_counter() - t0, 4),
    }
    stats["ok"] = bool(
        stats["anomalies"] >= 2
        and stats["recovered"] == stats["anomalies"]
        and not stats["anomalous_at_exit"]
        and stats["captures"] == 1
        and artifact is not None
        and stats["suppressed"] >= 1
        and stats["slo"]["alerts"] >= 1
        and not stats["slo"]["alerting"]
        and straggler is not None)
    _events.emit("perf_loadgen", **stats)
    return stats


def _default_decomp():
    import jax
    import pystella_tpu as ps
    return ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
