"""Numerics-observability tests: the in-graph health sentinel
(obs.sentinel), its asynchronous monitor (the driver must run >= every
steps ahead of any health poll), the in-graph step piggybacks, the
divergence forensic bundle on a sharded mesh, and the sentinel's cost
on the step path as dispatch/transfer counts."""

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.obs import events, forensics


def _state(val_f=3.0, val_df=0.0, shape=(2, 4, 4, 4)):
    return {"f": jnp.full(shape, val_f, jnp.float32),
            "dfdt": jnp.full(shape, val_df, jnp.float32)}


def _kinetic(st, aux):
    return 0.5 * jnp.mean(jnp.sum(jnp.square(st["dfdt"]), axis=0))


# -- health vector ---------------------------------------------------------

def test_health_vector_layout_and_values():
    state = _state(3.0, 0.5)
    sen = obs.Sentinel.for_state(state, invariants={"kin": _kinetic})
    assert sen.size == 2 * 3 + 1
    assert sen.slot_names == ["dfdt.finite", "dfdt.max_abs", "dfdt.rms",
                              "f.finite", "f.max_abs", "f.rms", "kin"]
    dec = sen.decode(sen.compute_jit(state))
    assert dec["fields"]["f"] == {"finite": True, "max_abs": 3.0,
                                  "rms": 3.0}
    assert dec["fields"]["dfdt"]["finite"]
    assert dec["fields"]["dfdt"]["rms"] == pytest.approx(0.5)
    # 2 fields of constant 0.5: kin = 0.5 * mean(2 * 0.25)
    assert dec["invariants"]["kin"] == pytest.approx(0.25)
    assert not sen.problems(dec)[0]


def test_health_vector_flags_nonfinite_and_bounds():
    state = _state()
    state["dfdt"] = state["dfdt"].at[0, 1, 2, 3].set(np.nan)
    sen = obs.Sentinel.for_state(state)
    dec = sen.decode(sen.compute_jit(state))
    assert not dec["fields"]["dfdt"]["finite"]
    assert dec["fields"]["f"]["finite"]  # per-field isolation
    bad, why = sen.problems(dec)
    assert bad == ["dfdt"] and "non-finite" in why[0]
    # magnitude bound: |f| = 3 trips a bound of 2, passes a bound of 4
    good = sen.decode(sen.compute_jit(_state()))
    assert sen.problems(good, max_abs=2.0)[0] == ["f"]
    assert not sen.problems(good, max_abs=4.0)[0]
    # invariant bounds
    sen2 = obs.Sentinel.for_state(state, invariants={"kin": _kinetic})
    dec2 = sen2.decode(sen2.compute_jit(_state(3.0, 10.0)))
    bad2, why2 = sen2.problems(dec2, invariant_bounds={"kin": (None, 1.0)})
    assert bad2 == ["kin"] and "outside bounds" in why2[0]


def test_large_finite_values_are_not_diverged():
    """Squaring may overflow the field dtype on legitimate
    large-but-finite data (f32 beyond ~1.8e19): the finite flag must
    not read that as divergence (review fix: only a NaN in the sum leg
    or a non-finite max vetoes)."""
    big = _state(1e20, 1.0)  # finite in f32; 1e40 overflows to inf
    sen = obs.Sentinel.for_state(big)
    dec = sen.decode(sen.compute_jit(big))
    assert dec["fields"]["f"]["finite"] is True
    assert dec["fields"]["f"]["max_abs"] == pytest.approx(1e20)
    assert not sen.problems(dec)[0]
    # while actual inf / NaN data still trips
    for poison in (np.inf, np.nan):
        bad = _state(1e20, 1.0)
        bad["f"] = bad["f"].at[0, 0, 0, 0].set(poison)
        assert sen.problems(sen.decode(sen.compute_jit(bad)))[0] == ["f"]


def test_scope_registration_reaches_parser_after_import():
    """register_scope() after obs is imported must be sufficient for
    the Perfetto parser to fold the new name (review fix: the
    vocabulary resolves at call time, not import time)."""
    from pystella_tpu.obs import trace as obs_trace
    from pystella_tpu.obs.scope import register_scope
    name = "late_registered_scope_for_test"
    register_scope(name)
    assert name in obs_trace.KNOWN_SCOPES
    table = obs_trace.scope_durations(
        [{"ph": "X", "name": f"jit(f)/{name}/fusion.1", "dur": 500}])
    assert table[name]["count"] == 1


def test_sentinel_compute_is_traceable():
    """The health vector must be computable INSIDE a jitted step —
    that is the whole no-host-sync design."""
    sen = obs.Sentinel.for_state(_state(), invariants={"kin": _kinetic})

    @jax.jit
    def step_and_health(state):
        new = {k: v * 2.0 for k, v in state.items()}
        return new, sen.compute(new)

    new, hv = step_and_health(_state(3.0, 0.5))
    assert isinstance(hv, jax.Array)
    assert sen.decode(hv)["fields"]["f"]["max_abs"] == pytest.approx(6.0)


# -- async monitor: the driver stays >= every steps ahead ------------------

def test_monitor_polls_lag_behind_driver():
    """Acceptance: the driver loop issues >= ``every`` steps ahead of
    the health poll — a poll never converts a vector younger than
    ``every`` steps behind the newest observe."""
    sen = obs.Sentinel.for_state(_state())
    mon = obs.SentinelMonitor(sen, every=5)
    state = _state()
    for step in range(1, 21):
        mon.observe(step, state)
        mon.poll()
        # everything younger than `every` behind is still pending
        assert mon.pending_steps == list(range(
            max(1, step - 5 + 1), step + 1))
        if mon.checked_through is not None:
            assert mon.checked_through <= step - 5
    assert mon.checked_through == 15
    # flush drains the tail (end of run / pre-checkpoint)
    assert mon.flush() == 5
    assert mon.checked_through == 20 and not mon.pending_steps


def test_monitor_trip_reports_actual_step_and_fields():
    sen = obs.Sentinel.for_state(_state())
    mon = obs.SentinelMonitor(sen, every=3)
    good, bad = _state(), _state()
    bad["dfdt"] = bad["dfdt"].at[0, 0, 0, 0].set(np.inf)
    for step in range(1, 8):
        mon.observe(step, good)
        mon.poll()
    # divergence at step 8; the driver keeps issuing ahead
    for step in range(8, 12):
        mon.observe(step, bad)
        if step < 11:
            mon.poll()
    with pytest.raises(ps.SimulationDiverged) as exc:
        mon.poll()
    assert exc.value.step == 8  # the actual offending step, not 0
    assert exc.value.bad_fields == ("dfdt",)
    assert mon.history[-1]["step"] == 8


def test_monitor_history_ring_buffer():
    sen = obs.Sentinel.for_state(_state())
    mon = obs.SentinelMonitor(sen, every=0, history=4)
    for step in range(10):
        mon.observe(step, _state())
        mon.poll()
    assert [h["step"] for h in mon.history] == [6, 7, 8, 9]


# -- in-graph piggybacks ---------------------------------------------------

def _tiny_stepper(dt=0.01):
    def rhs(st, t, **kw):
        return {"f": st["dfdt"], "dfdt": -st["f"]}
    return ps.LowStorageRK54(rhs, dt=dt)


def test_step_with_health_matches_step_plus_compute():
    stepper = _tiny_stepper()
    state = _state(1.0, 0.0)
    sen = obs.Sentinel.for_state(state, invariants={"kin": _kinetic})
    new, hv = stepper.step_with_health(state, sen, 0.0, 0.01)
    ref = stepper.step(state, 0.0, 0.01)
    assert jnp.allclose(new["f"], ref["f"])
    assert jnp.allclose(new["dfdt"], ref["dfdt"])
    assert np.allclose(np.asarray(hv), np.asarray(sen.compute_jit(ref)))
    # the sentinel reductions land inside the SAME lowered computation,
    # under the registered "sentinel" scope
    lowered = stepper._jit_health_step[id(sen)].lower(
        state, 0.0, 0.01, {}, {})
    assert obs.has_scope(lowered, "sentinel")
    assert obs.has_scope(lowered, "rk_stage")


@pytest.mark.slow  # interpret-mode Pallas chunk: ~25 s on the CPU host
def test_fused_multi_step_sentinel(proc_shape=(1, 1, 1)):
    """The fused chunk driver returns (state, health_vector) with
    ``sentinel=`` — the vector matches a separate compute on the same
    final state. (The same wrapper pattern as Stepper.step_with_health,
    which tier-1 covers on the generic path.)"""
    import pystella_tpu as ps
    grid_shape = (8, 8, 32)
    decomp = ps.DomainDecomposition(proc_shape,
                                    devices=jax.devices()[:1])
    sector = ps.ScalarSector(1, potential=lambda f: f[0] ** 2 / 2)
    stepper = ps.FusedScalarStepper(
        sector, decomp, grid_shape, 0.1, halo_shape=1,
        dtype=jnp.float32, dt=0.01, interpret=True)
    f0 = np.random.default_rng(3).standard_normal(
        (1,) + grid_shape).astype(np.float32)
    # two copies: multi_step donates its input state buffers
    state_a = {"f": jnp.asarray(f0),
               "dfdt": jnp.zeros((1,) + grid_shape, jnp.float32)}
    state_b = {"f": jnp.asarray(f0),
               "dfdt": jnp.zeros((1,) + grid_shape, jnp.float32)}
    sen = obs.Sentinel.for_state(state_a)
    ref = stepper.multi_step(state_a, 2, rhs_args={"a": 1.0,
                                                   "hubble": 0.0})
    new, hv = stepper.multi_step(state_b, 2,
                                 rhs_args={"a": 1.0, "hubble": 0.0},
                                 sentinel=sen)
    assert jnp.allclose(new["f"], ref["f"])
    assert np.allclose(np.asarray(hv), np.asarray(sen.compute_jit(ref)))


# -- forensic bundle -------------------------------------------------------

def test_forensic_bundle_roundtrip_sharded(tmp_path, decomp):
    """Satellite: divergence on a sharded (2,2,1) CPU mesh produces a
    bundle that round-trips — load identifies the bad field, the trip
    step, and the last-good checkpoint."""
    pytest.importorskip("orbax.checkpoint")
    assert decomp.proc_shape == (2, 2, 1)
    log_path = str(tmp_path / "run.jsonl")
    old_log = obs.configure(log_path)  # noqa: F841
    try:
        rng = np.random.default_rng(11)
        good = {"f": decomp.shard(rng.standard_normal(
            (16, 16, 16)).astype(np.float32))}
        with ps.Checkpointer(str(tmp_path / "ckpts")) as ckpt:
            ckpt.save(4, good, metadata={"t": 0.4})
            ckpt.wait()
            sink = forensics.ForensicSink(
                str(tmp_path / "forensics"), events_path=log_path,
                checkpoint=ckpt, config={"grid_shape": [16, 16, 16]},
                label="unit")
            sen = obs.Sentinel.for_state(good)
            mon = obs.SentinelMonitor(sen, every=2, history=8,
                                      forensics=sink)
            for step in range(5, 10):
                mon.observe(step, good)
                mon.poll()
            # placed through the decomposition like any state (an
            # eager .at[].set() on an explicitly-sharded array needs a
            # jax.set_mesh context of the caller's)
            host = np.asarray(good["f"]).copy()
            host[0, 0, 0] = np.nan
            bad = {"f": decomp.shard(host)}
            mon.observe(10, bad)
            with pytest.raises(ps.SimulationDiverged) as exc:
                mon.flush()
        assert exc.value.step == 10
        assert sink.last_bundle is not None
    finally:
        obs.configure(None)

    bundle = forensics.load_bundle(sink.last_bundle)
    assert bundle["schema"] == forensics.BUNDLE_SCHEMA_VERSION
    assert bundle["trip"]["step"] == 10
    assert bundle["trip"]["bad_fields"] == ["f"]
    assert "non-finite" in bundle["trip"]["reason"]
    # last-good checkpoint pointer: resume-from-here
    lg = bundle["last_good_checkpoint"]
    assert lg["step"] == 4 and lg["directory"].endswith("ckpts")
    # the blowup history: last-K health vectors plus the pivoted
    # per-field curve, ending at the offending step
    assert bundle["health_history"][-1]["step"] == 10
    assert bundle["health_history"][-1]["fields"]["f"]["finite"] is False
    assert bundle["field_history"]["f"]["steps"][-1] == 10
    # rms (not max_abs) is the guaranteed-poisoned stat: XLA
    # max-reductions may drop NaN (IEEE maxNum), sums never do
    assert not np.isfinite(bundle["field_history"]["f"]["rms"][-1])
    # event-log tail and environment made it in
    assert any(ev["kind"] == "diverged" for ev in bundle["events_tail"])
    assert bundle["env"]["jax"] and bundle["config"]["grid_shape"]
    # the bundle's own event landed in the log for the ledger to find
    kinds = [e["kind"] for e in events.read_events(log_path)]
    assert "forensic_bundle" in kinds and "diverged" in kinds
    # a non-bundle file fails loudly
    not_bundle = tmp_path / "not_a_bundle.json"
    not_bundle.write_text("{\"foo\": 1}")
    with pytest.raises(ValueError):
        forensics.load_bundle(str(not_bundle))


def test_bundle_names_offending_invariant(tmp_path):
    """Acceptance: when an INVARIANT (not a field) trips — the
    constraint-drift scenario — the bundle and the diverged event name
    it."""
    state = _state(3.0, 10.0)  # kin = 50, well above the bound
    sen = obs.Sentinel.for_state(state, invariants={"kin": _kinetic})
    sink = forensics.ForensicSink(str(tmp_path / "f"), label="unit")
    mon = obs.SentinelMonitor(sen, every=0, forensics=sink,
                              invariant_bounds={"kin": (None, 1.0)})
    mon.observe(7, state)
    with pytest.raises(ps.SimulationDiverged) as exc:
        mon.poll()
    assert "kin" in exc.value.bad_fields
    bundle = forensics.load_bundle(sink.last_bundle)
    assert bundle["trip"]["offending_invariant"] == "kin"
    assert bundle["trip"]["step"] == 7
    # the fields themselves were healthy — the invariant is the story
    assert bundle["health_history"][-1]["fields"]["f"]["finite"]


def test_forensic_sink_never_raises(tmp_path):
    """A failed bundle write must not mask the SimulationDiverged that
    triggered it."""
    sink = forensics.ForensicSink("/nonexistent\0dir")
    assert sink.write(step=3, reason="x", bad_fields=["f"]) is None


# -- overhead --------------------------------------------------------------

def test_sentinel_costs_one_dispatch_and_no_sync_per_step(monkeypatch):
    """What the always-on sentinel adds to the step path, as COUNTS (its
    time belongs to the chip, not to a CPU wall clock): ``observe`` is
    one dispatch of one program compiled once and moves nothing to the
    host; ``poll`` only ever converts vectors at least ``every`` steps
    old; the in-graph route adds no dispatch at all."""
    state = _state(3.0, 0.5)
    sen = obs.Sentinel.for_state(state, invariants={"kin": _kinetic})
    dispatched, decoded = [], []
    compute_jit, decode = sen.compute_jit, sen.decode

    def counting_compute(*args, **kwargs):
        dispatched.append(1)
        return compute_jit(*args, **kwargs)

    def counting_decode(vector):
        decoded.append(1)
        return decode(vector)

    monkeypatch.setattr(sen, "compute_jit", counting_compute)
    monkeypatch.setattr(sen, "decode", counting_decode)
    every, nsteps = 4, 10
    mon = obs.SentinelMonitor(sen, every=every)
    for step in range(1, nsteps + 1):
        ndecoded = len(decoded)
        mon.observe(step, state)
        # the vector stays a device array: nothing crossed to the host
        assert len(decoded) == ndecoded
        assert isinstance(mon._pending[-1][1], jax.Array)
        mon.poll()
        assert all(s > step - every for s in mon.pending_steps)
    assert len(dispatched) == nsteps          # one per observed step
    assert len(decoded) == nsteps - every     # matured vectors only
    assert sen._jit._cache_size() == 1        # one program, built once
    # in-graph (Stepper.step_with_health): the vector is an output of
    # the step's own program — pushing it dispatches nothing more
    stepper = ps.LowStorageRK54(
        lambda st, t: {"f": st["dfdt"], "dfdt": -st["f"]}, dt=0.01)
    _, hv = stepper.step_with_health(state, sen, 0.0, 0.01)
    mon.push(nsteps + 1, hv)
    assert len(dispatched) == nsteps


def test_health_events_feed_ledger_numerics(tmp_path):
    """health events -> PerfLedger numerics: invariant drift slope,
    check counts, and the markdown section."""
    from pystella_tpu.obs import ledger
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("run_start", grid_shape=[8, 8, 8])
        for i in range(10):
            log.emit("step_time", step=i, ms=2.0)
            log.emit("health", step=i, invariants={
                "constraint": 1e-8 + 2e-9 * i},
                fields={"f": {"finite": True, "max_abs": 1.0,
                              "rms": 0.5}})
    led = ledger.PerfLedger.from_events(path, label="unit")
    nm = led.numerics()
    inv = nm["invariants"]["constraint"]
    assert inv["n"] == 10
    assert inv["drift_per_step"] == pytest.approx(2e-9, rel=1e-6)
    assert inv["first"] == pytest.approx(1e-8)
    assert nm["health_events"] == 10
    rep = led.report()
    assert rep["numerics"]["invariants"]["constraint"]["n"] == 10
    md = ledger.render_markdown(rep)
    assert "Numerics health" in md and "constraint" in md


def test_ledger_numerics_records_divergence(tmp_path):
    from pystella_tpu.obs import ledger
    path = str(tmp_path / "run.jsonl")
    with events.EventLog(path) as log:
        log.emit("step_time", step=1, ms=2.0)
        log.emit("diverged", step=33, fields=["dfdt"],
                 offending_invariant=None)
        log.emit("forensic_bundle", step=33, path="/x/bundle.json")
    led = ledger.PerfLedger.from_events(path)
    nm = led.numerics()
    assert nm["diverged"] == [{"step": 33, "fields": ["dfdt"],
                               "offending_invariant": None}]
    assert nm["forensic_bundles"] == ["/x/bundle.json"]
    md = ledger.render_markdown(led.report())
    assert "DIVERGED" in md


if __name__ == "__main__":
    import pytest as _pytest
    _pytest.main([__file__, "-v"])
