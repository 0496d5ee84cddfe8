"""Names that reach the trace, and spans where the program waits.

Device side: every streaming kernel is dispatched under the scope of its
kind (``pallas_stencil_<kind>``), and every program ``instrument_jit``
builds is named after its label, so a breakdown by ``<XLA module>/<HLO
instruction>`` is a breakdown by layer. Host side: ``obs.host_span`` at
every dispatch and every fetch of the main path, recorded only while
``obs.recording()`` is active, never adding a sync. (The compiled-HLO
half of the kernel names is in ``tests/test_tpu_compile.py``.)
"""

import inspect
import json
import os
import sys
import time
import types

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.obs import events, scope
from pystella_tpu.obs.memory import InstrumentedJit, program_name

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import drivers, readers, trace_reduce  # noqa: E402
from benchmark.spans import PREFIX, Spans  # noqa: E402


def _potential(f):
    return 0.5 * f[0]**2 + 0.5 * f[0]**2 * f[1]**2


def _preheat(decomp, n=32, dtype=np.float32):
    """The flagship model at ``n**3``: stepper, operators, reductions
    and a seeded state, as ``benchmark/families/scalar_preheat.py``
    builds them."""
    grid = (n, n, n)
    lattice = ps.Lattice(grid, (5.0,) * 3, dtype=dtype)
    sector = ps.ScalarSector(2, potential=_potential)
    grid_size = float(np.prod(grid))
    stepper = ps.FusedScalarStepper(
        sector, decomp, grid, lattice.dx, 2, dtype=dtype,
        dt=dtype(0.1 * min(lattice.dx)), donate=True)
    derivs = ps.FiniteDifferencer(decomp, 2, lattice.dx)
    reduce_energy = ps.Reduction(decomp, sector, callback=ps.get_rho_and_p,
                                 grid_size=grid_size)
    stats = ps.FieldStatistics(decomp, grid_size=grid_size)
    rng = np.random.default_rng(5)
    state = {
        "f": decomp.shard((0.1 + 0.01 * rng.standard_normal(
            (2,) + grid)).astype(dtype)),
        "dfdt": decomp.shard((0.01 * rng.standard_normal(
            (2,) + grid)).astype(dtype))}

    def energy_of(st, a):
        return reduce_energy(f=st["f"], dfdt=st["dfdt"],
                             lap_f=derivs.lap(st["f"]), a=np.float64(a))

    return stepper, derivs, stats, state, energy_of, grid_size


@pytest.fixture
def event_log(tmp_path):
    """The process-default event log pointed at a temp file."""
    path = tmp_path / "events.jsonl"
    events.configure(str(path))
    yield str(path)
    events.configure(None)


# -- the recorder ----------------------------------------------------------

def test_recorder_off_means_no_rows_and_no_sync(make_decomp, monkeypatch):
    """With no recorder installed a span keeps nothing, and no span ever
    waits for the device: the coupled chunk, the energy and the
    statistics run without a single ``block_until_ready``."""
    stepper, derivs, stats, state, energy_of, grid_size = _preheat(
        make_decomp((1, 1, 1)), n=16)
    energy = energy_of(state, 1.0)
    expand = ps.Expansion(energy["total"], ps.LowStorageRK54)
    # compile first: the spy below is for the steady state
    state = stepper.coupled_multi_step(state, 2, expand, 0.0,
                                       grid_size=grid_size)
    calls = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append("jax") or x)
    monkeypatch.setattr(type(state["f"]), "block_until_ready",
                        lambda self: calls.append("array") or self)
    assert scope._RECORDER is None
    state = stepper.coupled_multi_step(state, 2, expand, 0.0,
                                       grid_size=grid_size)
    energy_of(state, expand.a)
    stats(state["f"])
    assert calls == []
    assert scope._RECORDER is None
    # and a recorder that was never installed has nothing to show
    with obs.recording() as rows:
        pass
    assert rows == []


def test_recording_is_exclusive_and_threads_do_not_mix():
    import threading
    with obs.recording() as rows:
        with pytest.raises(RuntimeError, match="already installed"):
            with obs.recording():
                pass
        t = threading.Thread(
            target=lambda: obs.host_span("other_thread").__enter__())
        t.start()
        t.join()
        with obs.host_span("mine"):
            pass
    assert [r[0] for r in rows] == ["mine"]
    with obs.recording() as again:  # the first one left cleanly
        pass
    assert again == []


def test_span_table_self_times_and_fetch_count():
    ms = 1_000_000
    rows = [["root", -1, 0, 100 * ms],
            ["a_dispatch", 0, 10 * ms, 20 * ms],
            ["a_fetch", 0, 20 * ms, 70 * ms],
            ["inner_fetch", 2, 30 * ms, 40 * ms],
            ["open_fetch", 0, 80 * ms, 0]]      # never closed: left out
    table = scope.span_table(rows, steps=4)
    spans = table["spans"]
    assert spans["root"]["self_ms"] == pytest.approx(40.0)
    assert spans["a_fetch"]["total_ms"] == pytest.approx(50.0)
    assert spans["a_fetch"]["self_ms"] == pytest.approx(40.0)
    assert spans["a_fetch"]["ms_per_step"] == pytest.approx(12.5)
    assert "open_fetch" not in spans
    assert table["fetches"] == 2
    assert table["host_syncs_per_step"] == pytest.approx(0.5)
    assert sum(r["self_ms"] for r in spans.values()) == pytest.approx(
        spans["root"]["total_ms"])
    assert "host_syncs_per_step" not in scope.span_table(rows)


def test_coupled_chunks_span_tree(make_decomp, tmp_path):
    """Two coupled chunks at 32**3 as the example and the benchmark's
    driver run them: the span tree of doc/observability.md "Host
    spans", three fetches per chunk (the chunk's background, the
    energy, the statistics), every child inside its parent."""
    stepper, derivs, stats, state, energy_of, grid_size = _preheat(
        make_decomp((1, 1, 1)))
    out = ps.OutputFile(name=str(tmp_path / "spans"), runfile=__file__)
    energy = energy_of(state, 1.0)
    expand = ps.Expansion(energy["total"], ps.LowStorageRK54)
    monitor = ps.HealthMonitor(every=1)

    def chunk(state, step):
        with obs.host_span("driver_step"):
            state = stepper.coupled_multi_step(state, 2, expand, 0.0,
                                               grid_size=grid_size)
            energy = energy_of(state, expand.a)
            f_stats = stats(state["f"])
            out.output("statistics/f", a=expand.a, **f_stats)
            monitor.observe(step, state)
            monitor.poll()
        return state, energy

    state, _ = chunk(state, 0)       # compiles, unrecorded
    with obs.recording() as rows:
        for i in (1, 2):
            state, _ = chunk(state, i)
    out.close()

    names = [r[0] for r in rows]
    roots = [i for i, r in enumerate(rows) if r[1] == -1]
    assert [names[i] for i in roots] == ["driver_step", "driver_step"]
    for lo, hi in zip(roots, roots[1:] + [len(rows)]):
        sub = rows[lo:hi]
        tree = [(r[0], rows[r[1]][0] if r[1] >= 0 else None) for r in sub]
        assert tree == [
            ("driver_step", None),
            ("step_dispatch", "driver_step"),
            ("step_fetch", "driver_step"),
            ("lap_dispatch", "driver_step"),
            ("reduce_dispatch", "driver_step"),
            ("reduce_fetch", "driver_step"),
            ("statistics", "driver_step"),
            ("reduce_dispatch", "statistics"),
            ("reduce_fetch", "statistics"),
            ("output_write", "driver_step"),
            ("sentinel_observe", "driver_step"),
            ("sentinel_poll", "driver_step"),
        ], tree
        assert sum(n.endswith("_fetch") for n, _ in tree) == 3
    for name, parent, t0, t1 in rows:
        assert t1 >= t0 > 0
        if parent >= 0:
            assert rows[parent][2] <= t0 and t1 <= rows[parent][3]
    table = scope.span_table(rows, steps=4)
    assert table["fetches"] == 6
    assert table["host_syncs_per_step"] == pytest.approx(1.5)
    spans = table["spans"]
    assert sum(r["self_ms"] for r in spans.values()) == pytest.approx(
        spans["driver_step"]["total_ms"])
    assert spans["statistics"]["self_ms"] < spans["statistics"]["total_ms"]
    # every name the program emitted is registered, so trace tables
    # fold it
    assert set(names) <= scope.registered_scopes()


def test_stage_loop_and_output_spans(make_decomp):
    """The other spans of the table: the per-stage protocol
    (``step_dispatch`` + ``expansion_step``), the gradient, the
    histogram and the spectra, each with its dispatch and fetch."""
    decomp = make_decomp((1, 1, 1))
    stepper, derivs, stats, state, energy_of, grid_size = _preheat(
        decomp, n=16)
    lattice = ps.Lattice((16,) * 3, (5.0,) * 3, dtype=np.float32)
    fft = ps.DFT(decomp, grid_shape=(16,) * 3, dtype=np.float32)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)
    hist = ps.FieldHistogrammer(decomp, 16, np.float32)
    energy = energy_of(state, 1.0)
    expand = ps.Expansion(energy["total"], ps.LowStorageRK54)
    with obs.recording() as rows:
        carry = stepper(0, state, 0.0, a=np.float64(expand.a),
                        hubble=np.float64(expand.hubble))
        expand.step(0, energy["total"], energy["pressure"], stepper.dt)
        f = stepper.current(carry)["f"]
        derivs.grad(f)
        hist(f)
        spectra(f)
    tree = [(r[0], rows[r[1]][0] if r[1] >= 0 else None) for r in rows]
    assert tree[:3] == [("step_dispatch", None), ("expansion_step", None),
                        ("grad_dispatch", None)]
    assert ("histogram", None) in tree and ("spectra", None) in tree
    for owner in ("histogram", "spectra"):
        kids = [n for n, p in tree if p == owner]
        assert set(kids) == {owner + "_dispatch", owner + "_fetch"}, kids
        assert kids[-1] == owner + "_fetch"
    # the histogram waits three times (bounds, linear, log), a spectrum
    # once
    assert sum(n == "histogram_fetch" for n, _ in tree) == 3
    assert sum(n == "spectra_fetch" for n, _ in tree) == 1
    assert {n for n, _ in tree} <= scope.registered_scopes()


def test_gw_output_spans_and_names(make_decomp):
    """The ``-gws`` output's own names: ``PowerSpectra.gw`` is one
    ``gw_spectra`` span holding the dispatches (six transforms and the
    TT projection, then the weights and the binning) and the binning's
    one fetch;
    the projection is a program of its own, ``jit_tt_project``, and the
    GW stepper's coupled chunk is ``jit_coupled_multi_step_N`` like the
    scalar stepper's, so a trace row reads
    ``jit_coupled_multi_step_4/pallas_stencil_energy``."""
    decomp = make_decomp((1, 1, 1))
    grid = (16,) * 3
    lattice = ps.Lattice(grid, (5.0,) * 3, dtype=np.float32)
    fft = ps.DFT(decomp, grid_shape=grid, dtype=np.float32)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)
    projector = ps.Projector(fft, 2, lattice.dk, lattice.dx)
    rng = np.random.default_rng(3)
    dhijdt = decomp.shard(
        rng.standard_normal((6,) + grid).astype(np.float32))
    with obs.recording() as rows:
        gw = spectra.gw(dhijdt, projector, 0.4)
    assert gw.shape == (spectra.num_bins,) and np.all(np.isfinite(gw))
    tree = [(r[0], rows[r[1]][0] if r[1] >= 0 else None) for r in rows]
    assert tree[0] == ("gw_spectra", None)
    kids = [n for n, p in tree if p == "gw_spectra"]
    assert kids[0] == "spectra_dispatch" and kids[-1] == "spectra_fetch"
    assert kids.count("spectra_fetch") == 1, kids   # six spectra, one wait
    assert {n for n, _ in tree} <= scope.registered_scopes()

    assert isinstance(projector._tt, InstrumentedJit)
    assert _module_name(projector._tt.lower(fft.dft(dhijdt))) == \
        "jit_tt_project"
    sector = ps.ScalarSector(2, potential=_potential)
    stepper = ps.FusedPreheatStepper(
        sector, ps.TensorPerturbationSector([sector]), decomp, grid,
        lattice.dx, 2, dtype=np.float32, carry_dtype=jnp.bfloat16)
    stepper._ensure_energy_call()
    state = {"f": decomp.zeros(grid, np.float32, outer_shape=(2,)),
             "dfdt": decomp.zeros(grid, np.float32, outer_shape=(2,)),
             "hij": dhijdt, "dhijdt": dhijdt}
    a = jnp.float32(1.0)
    lowered = stepper._coupled_jit(4, 16.0**3, 1.0, False).lower(
        state, t=0.0, dt=0.01, a=a, adot=a)
    assert _module_name(lowered) == "jit_coupled_multi_step_4"
    assert scope.has_scope(lowered, "pallas_stencil_energy")


# -- a recorder left on: prefix, drain, paths --------------------------------

@pytest.fixture
def annotations(monkeypatch):
    """The names ``host_span`` hands to ``TraceAnnotation``, through a
    stub in its place."""
    seen = []

    class Stub:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    scope.in_jax_trace()       # resolve jax's own first, then replace it
    monkeypatch.setattr(scope, "_TraceAnnotation", Stub)
    return seen


@pytest.mark.parametrize("prefix", ["", PREFIX])
def test_annotation_takes_the_recorders_prefix_and_the_row_does_not(
        annotations, prefix):
    with obs.recording(annotation_prefix=prefix) as rows:
        with obs.host_span("statistics"):
            with obs.host_span("reduce_fetch"):
                pass
    assert annotations == [prefix + "statistics", prefix + "reduce_fetch"]
    assert [r[:2] for r in rows] == [["statistics", -1],
                                     ["reduce_fetch", 0]]


def test_no_recorder_means_a_bare_annotation_and_no_row(annotations):
    assert scope._RECORDER is None
    with obs.host_span("reduce_fetch"):
        pass
    assert annotations == ["reduce_fetch"]
    with obs.recording(annotation_prefix=PREFIX) as rows:
        pass
    with obs.host_span("lap_dispatch"):     # the prefix left with it
        pass
    assert annotations == ["reduce_fetch", "lap_dispatch"] and rows == []


def test_drain_hands_closed_rows_over_once_and_refuses_an_open_span():
    with obs.recording() as rows:
        with obs.host_span("statistics"):
            with obs.host_span("reduce_dispatch"):
                pass
            with pytest.raises(RuntimeError, match="open span 'statistics'"):
                rows.drain()
        with obs.host_span("output_write"):
            pass
        first = rows.drain()
        assert rows == [] and rows.drain() == []
        with obs.host_span("statistics"):
            with obs.host_span("reduce_fetch"):
                pass
        second = rows.drain()
    assert [r[:2] for r in first] == [
        ["statistics", -1], ["reduce_dispatch", 0], ["output_write", -1]]
    # parents index into what was returned, not into all that was recorded
    assert [r[:2] for r in second] == [["statistics", -1],
                                       ["reduce_fetch", 0]]
    assert all(t1 >= t0 > 0 for _, _, t0, t1 in first + second)
    assert rows == []


def _tree_rows(ms=1_000_000):
    """A step's statistics beside the energy's own fetch: [name, parent,
    start_ns, end_ns]."""
    return [["reduce_dispatch", -1, 1 * ms, 2 * ms],
            ["reduce_fetch", -1, 2 * ms, 42 * ms],
            ["statistics", -1, 50 * ms, 62 * ms],
            ["reduce_dispatch", 2, 50 * ms, 51 * ms],
            ["reduce_fetch", 2, 51 * ms, 61 * ms],
            ["step_dispatch", -1, 70 * ms, 73 * ms]]


def test_span_paths_tell_the_statistics_fetch_from_the_energys():
    assert scope.span_paths(_tree_rows()) == [
        "reduce_dispatch", "reduce_fetch", "statistics",
        "statistics/reduce_dispatch", "statistics/reduce_fetch",
        "step_dispatch"]
    deep = [["histogram", -1, 1, 9], ["histogram_fetch", 0, 2, 3],
            ["output_write", 1, 2, 3]]
    assert scope.span_paths(deep)[-1] == \
        "histogram/histogram_fetch/output_write"
    assert scope.span_paths([]) == []


def test_span_table_counts_dispatches_beside_fetches():
    table = scope.span_table(_tree_rows(), steps=2)
    assert (table["fetches"], table["dispatches"]) == (2, 3)
    assert table["host_syncs_per_step"] == pytest.approx(1.0)
    assert table["dispatches_per_step"] == pytest.approx(1.5)
    assert table["spans"]["statistics"]["self_ms"] == pytest.approx(1.0)
    bare = scope.span_table(_tree_rows())
    assert bare["dispatches"] == 3 and "dispatches_per_step" not in bare


# -- the loop body that hands the recorder's rows to the benchmark ---------

RECORDED_CELL = "preheat-512-f32.stage-loop-recorded"
RECORDED_METRICS = (
    "step_dispatch_ms_per_step", "expansion_step_ms_per_step",
    "lap_dispatch_ms_per_step", "reduce_dispatch_ms_per_step",
    "reduce_fetch_ms_per_step", "statistics_ms_per_step",
    "output_write_ms_per_step", "sentinel_ms_per_step")


def _bench_json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def _recorded_driver(spans, block_steps=2):
    """``stage_loop_recorded.Driver`` on a system that is nothing but the
    package (no lattice is built: the loop body is stubbed where a test
    drives it)."""
    traffic = {"block_steps": block_steps, "chunk_steps": 1,
               "check_steps": 1}
    return drivers.load("stage_loop_recorded")(
        types.SimpleNamespace(ps=ps), traffic, spans)


class _FakeRecorder:
    def __init__(self, rows):
        self.rows = rows

    def drain(self):
        rows, self.rows = self.rows, []
        return rows


def _adopted_context(capsys):
    """One block's worth of hand-made recorder rows adopted into a real
    ``Spans``, and the context ``benchmark.readers`` reads."""
    ms = 1_000_000
    block = _tree_rows() + [
        ["expansion_step", -1, 80 * ms, 80.5 * ms],
        ["lap_dispatch", -1, 81 * ms, 83 * ms],
        ["output_write", -1, 84 * ms, 87 * ms],
        ["sentinel_observe", -1, 88 * ms, 89 * ms],
        ["sentinel_poll", -1, 89 * ms, 93 * ms]]
    spans = Spans(sync=False)
    driver = _recorded_driver(spans)
    try:
        driver._recorder = _FakeRecorder(
            [["step_dispatch", -1, 1 * ms, 5 * ms]])
        spans.unit = ("warmup", -1)
        driver.adopt()
        driver._recorder = _FakeRecorder(block)
        spans.unit = ("block", 7)
        driver.adopt()
    finally:
        driver.monitor = None
        driver.finish()
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("program spans: ")]
    ctx = {"spans": spans, "units": [("block", 7, 0.0, 0.1, True)],
           "block_steps": 2, "traced": {}, "counters": {},
           "rehearse": True}
    return spans, ctx, json.loads(line[0][len("program spans: "):])


def test_recorded_driver_adopts_rows_by_path_unit_and_clock(capsys):
    spans, ctx, table = _adopted_context(capsys)
    assert scope._RECORDER is None          # finish() left the recorder
    assert spans.rows[0] == ("step_dispatch", "warmup", -1, 0.001, 0.005)
    block = spans.rows[1:]
    assert [r[0] for r in block] == [
        "reduce_dispatch", "reduce_fetch", "statistics",
        "statistics/reduce_dispatch", "statistics/reduce_fetch",
        "step_dispatch", "expansion_step", "lap_dispatch", "output_write",
        "sentinel_observe", "sentinel_poll"]
    assert {r[1:3] for r in block} == {("block", 7)}
    assert block[1][3:] == pytest.approx((0.002, 0.042))
    # the table is of the window's block rows alone, per step
    assert table["steps"] == 2 and "step_dispatch" in table["spans"]
    assert table["spans"]["step_dispatch"]["count"] == 1
    assert table["host_syncs_per_step"] == pytest.approx(1.0)
    assert table["dispatches_per_step"] == pytest.approx(2.0)
    assert table["spans"]["statistics"]["self_ms"] == pytest.approx(1.0)


@pytest.mark.parametrize("metric, ms_per_step", zip(
    RECORDED_METRICS, (1.5, 0.25, 1.0, 0.5, 20.0, 6.0, 1.5, 2.5)))
def test_recorded_metric_files_read_the_adopted_rows(capsys, metric,
                                                     ms_per_step):
    """Each of the eight metric files, through the harness's own reader,
    gives the hand-computed milliseconds per step of ITS spans: the
    top-level ``reduce_*`` are the energy's, not the statistics'."""
    _, ctx, _ = _adopted_context(capsys)
    spec = _bench_json("benchmark", "metrics", metric + ".json")
    assert readers.read(spec, ctx) == pytest.approx(ms_per_step)
    entry = [m for m in _bench_json("BENCHMARK.json")["per_layer"]
             if m["name"] == metric]
    assert entry and entry[0]["workloads"] == [RECORDED_CELL]
    assert entry[0]["source"] == "program_span"


def test_recorded_driver_rows_lie_on_the_harness_clock():
    """The real recorder under the real loop skeleton: ``block()`` drives
    ``one_step`` / ``after_advance`` (stubbed: spans only), then hands
    the rows over; they lie inside the unit on ``time.perf_counter``."""
    spans = Spans(sync=False)
    driver = _recorded_driver(spans, block_steps=3)

    def one_step():
        with obs.host_span("step_dispatch"):
            pass
        with obs.host_span("reduce_fetch"):
            pass
        driver.step_count += 1

    def after_advance():
        with obs.host_span("statistics"):
            with obs.host_span("reduce_fetch"):
                pass

    driver.one_step, driver.after_advance = one_step, after_advance
    try:
        spans.unit = ("block", 0)
        t0 = time.perf_counter()
        driver.block()
        t1 = time.perf_counter()
        with pytest.raises(RuntimeError, match="already installed"):
            with obs.recording():
                pass
    finally:
        driver.finish()
    assert scope._RECORDER is None
    assert [r[0] for r in spans.rows] == [
        "step_dispatch", "reduce_fetch", "statistics",
        "statistics/reduce_fetch"] * 3
    assert all(t0 <= a <= b <= t1 for *_, a, b in spans.rows)
    assert spans.count(["reduce_fetch"], "block") == 3


def test_recorded_metric_spans_are_registered_and_no_reducer_name():
    """A span a metric file names is one the program can emit, and none
    is a name ``benchmark/trace_reduce.py`` looks up among the host
    annotations (the program's arrive there under the same prefix)."""
    looked_up = {"step_call", "unit:block", "unit:output"}
    source = inspect.getsource(trace_reduce.reduce)
    for name in looked_up:
        assert f'"{name}"' in source
    seen = set()
    for metric in RECORDED_METRICS:
        spec = _bench_json("benchmark", "metrics", metric + ".json")
        assert spec["reducer"] == "span_ms_per_step"
        seen.update(spec["args"]["spans"])
    assert len(seen) == 9
    assert seen <= scope.registered_scopes()
    assert not seen & looked_up
    assert not any(n.startswith("unit:") or n in looked_up
                   for n in scope.registered_scopes())
    # nor one of the harness's own span names, which the same rows hold
    assert not seen & {"feedback", "stats", "health", "output_other",
                       "spectra", "gw_spectra", "spectral_lap"}


def test_recorded_traffic_is_stage_loops_but_for_the_driver():
    mine = _bench_json("benchmark", "traffic", "stage-loop-recorded.json")
    theirs = _bench_json("benchmark", "traffic", "stage-loop.json")
    assert mine.pop("driver") == "stage_loop_recorded"
    assert theirs.pop("driver") == "stage_loop"
    assert mine.pop("what") != theirs.pop("what")
    assert mine == theirs
    bench = _bench_json("BENCHMARK.json")
    cell = [w for w in bench["workloads"] if w["name"] == RECORDED_CELL]
    twin = [w for w in bench["workloads"]
            if w["name"] == "preheat-512-f32.stage-loop"]
    assert cell[0]["config"] == twin[0]["config"]
    assert cell[0]["chips"] == twin[0]["chips"] == 1
    assert cell[0]["traffic"] == "stage-loop-recorded"
    # every metric that lists stage-loop lists its twin, so one traced run
    # prints the outside and the inside reading of a layer side by side
    for m in bench["per_layer"]:
        if twin[0]["name"] in m.get("workloads", ()):
            assert RECORDED_CELL in m["workloads"], m["name"]
    limits = _bench_json("benchmark", "limits", RECORDED_CELL + ".json")
    assert limits["limits"] == _bench_json(
        "benchmark", "limits", twin[0]["name"] + ".json")["limits"]


# -- capture ---------------------------------------------------------------

def _host_annotations(logdir):
    """Names of the complete-span events of the captured Perfetto trace
    that lie on host threads."""
    path = obs.trace.find_trace_file(logdir)
    assert path is not None
    return [ev["name"] for ev in obs.trace.parse_trace_file(path)
            if ev.get("ph") == "X" and isinstance(ev.get("name"), str)]


def test_capture_puts_host_spans_into_trace_summary(make_decomp, tmp_path,
                                                    event_log):
    stepper, derivs, stats, state, energy_of, grid_size = _preheat(
        make_decomp((1, 1, 1)), n=16)
    energy = energy_of(state, 1.0)
    expand = ps.Expansion(energy["total"], ps.LowStorageRK54)
    state = stepper.coupled_multi_step(state, 2, expand, 0.0,
                                       grid_size=grid_size)
    logdir = str(tmp_path / "trace")
    with obs.trace.capture(logdir, label="spans", steps=2) as cap:
        state = stepper.coupled_multi_step(state, 2, expand, 0.0,
                                           grid_size=grid_size)
        energy_of(state, expand.a)
        jax.block_until_ready(state)
    assert scope._RECORDER is None
    if cap.summary is None:
        pytest.skip("this backend wrote no trace file")
    table = cap.summary["host_spans"]
    assert table["steps"] == 2 and table["fetches"] == 2
    assert table["host_syncs_per_step"] == pytest.approx(1.0)
    assert set(table["spans"]) == {
        "step_dispatch", "step_fetch", "lap_dispatch", "reduce_dispatch",
        "reduce_fetch"}
    ev = events.read_events(event_log, kind="trace_summary")[-1]["data"]
    assert ev["host_spans"]["spans"]["step_fetch"]["count"] == 1
    # the same spans lie on the profiler's clock, by name
    seen = _host_annotations(logdir)
    assert "step_fetch" in seen and "reduce_fetch" in seen
    text = "\n".join(obs.trace.format_host_spans(table))
    assert "1 host syncs per step" in text and "step_fetch" in text


def test_trace_scope_under_jit_leaves_no_host_annotation(tmp_path):
    """A ``trace_scope`` entered while jax traces used to annotate the
    host timeline with how long Python took to trace the block, once,
    and ``trace_summary`` counted it as the scope's time. Pinned on a
    captured CPU trace: the traced scope leaves no host event, the
    eager one does."""
    def f(x):
        with obs.trace_scope("mg_smooth"):
            return x * 2 + 1

    logdir = str(tmp_path / "trace")
    with obs.trace.capture(logdir) as cap:
        y = jax.jit(f)(jnp.ones((8, 8)))      # traced here, in the window
        with obs.trace_scope("mg_residual"):   # eager
            z = y + 1
        jax.block_until_ready(z)
    if cap.summary is None:
        pytest.skip("this backend wrote no trace file")
    seen = _host_annotations(logdir)
    assert "mg_residual" in seen
    assert "mg_smooth" not in seen
    # the scope still names the compiled ops
    assert obs.has_scope(jax.jit(f).lower(jnp.ones((8, 8))), "mg_smooth")


# -- kernel kinds and program names ----------------------------------------

def _stencil_of_kind(kind):
    from pystella_tpu.ops.pallas_stencil import LANE, StreamingStencil

    def body(taps, extras, scalars):
        return {"out": taps(0, 0, 1) - taps(0, 0, -1)}

    return StreamingStencil((16, 16, LANE), 1, 1, body, {"out": (1,)},
                            bx=8, by=8, kind=kind), (1, 16, 16, LANE)


@pytest.mark.parametrize("kind", [
    None, "stage", "pair", "coupled_pair", "energy", "chunk", "lap",
    "grad", "grad_lap", "pdx", "pdy", "pdz", "div",
    "mg_smooth", "mg_residual", "mg_tau"])
def test_kernel_kind_scope(kind):
    """Each kind of streaming kernel is dispatched under its own
    registered scope; the shared prefix keeps ``pallas_stencil``
    matching all of them."""
    st, shape = _stencil_of_kind(kind)
    name = "pallas_stencil" + ("_" + kind if kind else "")
    assert scope.kernel_scope(kind) == name
    assert name in scope.registered_scopes()
    lowered = jax.jit(lambda x: st(x)["out"]).lower(
        jnp.zeros(shape, jnp.float32))
    paths = obs.lowered_scopes(lowered)
    assert any(name in p.split("/") for p in paths), sorted(paths)[:5]
    assert obs.has_scope(lowered, "pallas_stencil")
    # the interior/shell split keeps the kind
    assert st.with_lattice((8, 16, st.lattice_shape[2])).kind == kind


def test_unknown_kernel_kind_is_refused():
    with pytest.raises(ValueError, match="register_scope"):
        scope.kernel_scope("mystery")


def test_fused_and_derivs_kernels_carry_their_kind(make_decomp, event_log):
    decomp = make_decomp((1, 1, 1))
    stepper, derivs, stats, state, energy_of, grid_size = _preheat(
        decomp, n=16)
    assert stepper._scalar_st.kind == "stage"
    assert stepper._pair_st.kind == "pair"
    stepper._ensure_energy_call()
    assert stepper._ensure_coupled_pair_calls() is not None
    lowered = stepper._coupled_jit(1, grid_size, 1.0, True, None).lower(
        state, t=0.0, dt=stepper.dt, a=jnp.float32(1.0),
        adot=jnp.float32(0.1))
    assert obs.has_scope(lowered, "pallas_stencil_coupled_pair")
    assert obs.has_scope(lowered, "pallas_stencil_energy")
    pallas = ps.FiniteDifferencer(decomp, 2, (0.1,) * 3, mode="pallas")
    x = jnp.zeros((2, 16, 16, 128), jnp.float32)
    for name in ("lap", "grad"):
        # on one chip the operator is applied eagerly: the stencil is
        # ONE program, named after the operator (it was a jit_wrapped
        # per y-slab and a jit_concatenate, then jit_lap per y-slab and
        # a jit_lap_join)
        st = pallas._pallas_op(name, 2, x.dtype, False, x.shape[-3:])
        assert st.kind == name
        low = st._program.lower(x)
        assert obs.has_scope(low, "pallas_stencil_" + name)
        assert _module_name(low) == "jit_" + name
        two, shape = _stencil_of_kind(name)     # one with two y-blocks
        assert two.grid == (2, 2)
        y = jnp.zeros(shape, jnp.float32)
        assert _module_name(two._program.lower(y)) == "jit_" + name
        assert two(y)["out"].shape == shape
    # applied eagerly, each compiled exactly one program: no join
    labels = [e["data"]["label"]
              for e in events.read_events(event_log, kind="compile")
              if e["data"]["label"].startswith("pallas.")]
    assert labels == ["pallas.streaming(16, 16, 128)"] * 2, labels


def _module_name(lowered):
    return lowered.compiler_ir().operation.attributes[
        "sym_name"].value


def test_program_name_from_label():
    assert program_name("fused.coupled_multi_step[4]") == \
        "coupled_multi_step_4"
    assert program_name("fused.multi_step[4]") == "multi_step_4"
    assert program_name("step.LowStorageRK54.stage0") == \
        "LowStorageRK54_stage0"
    assert program_name("mg.smooth(32, 32, 32)") == "smooth_32_32_32"
    assert program_name("plain") == "plain"
    assert program_name("..") == "program"


def _programs(make_decomp):
    """(instrumented program, arguments to lower it with) for the hot
    path's ``instrument_jit`` sites."""
    decomp = make_decomp((1, 1, 1))
    stepper, derivs, stats, state, energy_of, grid_size = _preheat(
        decomp, n=16)
    a = jnp.float32(1.0)
    f = state["f"]
    lattice = ps.Lattice((16,) * 3, (5.0,) * 3, dtype=np.float32)
    fft = ps.DFT(decomp, grid_shape=(16,) * 3, dtype=np.float32)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)
    hist = ps.FieldHistogrammer(decomp, 16, np.float32)
    reduce_energy = ps.Reduction(
        decomp, ps.ScalarSector(2, potential=_potential),
        callback=ps.get_rho_and_p, grid_size=grid_size)
    env = dict(f=f, dfdt=state["dfdt"], lap_f=f, a=np.float64(1.0))
    sentinel = obs.sentinel.Sentinel(("dfdt", "f"))
    sentinel.compute_jit(state)
    rho_map = ps.ElementWiseMap({ps.Field("rho"): ps.Field("f") * 2})
    stepper._ensure_stage_jits()
    stepper._ensure_energy_call()
    from pystella_tpu.ops.histogram import _bincount_fn
    bins = jnp.zeros((2, 16, 16, 16), jnp.int32)
    decomp.share_halos(f, 1, outer_axes=1)
    yield stepper._jit_step, (state, 0.0, stepper.dt, {})
    yield stepper._multi_jit(2, None, None), (state,), dict(
        t=0.0, dt=stepper.dt, rhs_args={"a": a, "hubble": a}, rhs_seq={})
    yield stepper._coupled_jit(2, grid_size, 1.0, False, None), (
        state,), dict(t=0.0, dt=stepper.dt, a=a, adot=a)
    yield stepper._jit_stage0, (*stepper._split_carry((state, {})), 0.0,
                                stepper.dt, {"a": a, "hubble": a})
    yield derivs._sharded("lap", 1), (f,)
    yield derivs._sharded("grad", 1, True), (f,)
    yield reduce_energy._run, (env, grid_size)
    yield stats._run, ({"f": f}, grid_size)
    yield hist._prepare, ({"f": f, "max_f": a, "min_f": a,
                           "max_log_f": a, "min_log_f": a},)
    yield _bincount_fn(decomp, (2,), 16, False), (bins,)
    yield _bincount_fn(decomp, (2,), 16, True, owner="spectra"), (
        bins, jnp.zeros(bins.shape, jnp.float32))
    yield spectra._weights.__closure__[0].cell_contents, (
        fft.dft(f), 3, spectra._counts, spectra._kmags, spectra._bin_idx)
    yield fft._dft, (f,)
    yield sentinel._jit, (state, {})
    yield rho_map._run, ({"f": f},)
    yield decomp._share_halos_cache[((1, 1, 1), 1)], (f,)


def test_instrumented_programs_are_named_after_their_labels(make_decomp):
    """Every ``instrument_jit`` program's module carries its label's
    name: none is ``jit__unknown`` / ``jit__lambda`` / ``jit_wrapped``
    / ``jit_run`` / ``jit_local`` any more."""
    seen = {}
    for case in _programs(make_decomp):
        fn, args = case[0], case[1]
        kwargs = case[2] if len(case) > 2 else {}
        assert isinstance(fn, InstrumentedJit), fn
        name = _module_name(fn.lower(*args, **kwargs))
        assert name == "jit_" + program_name(fn._label), (fn._label, name)
        seen[fn._label] = name
    names = set(seen.values())
    for bad in ("jit__unknown", "jit__lambda", "jit_local", "jit_wrapped",
                "jit_run", "jit_concatenate", "jit_sharded_fn",
                "jit_body", "jit_impl"):
        assert not any(n == bad or n.startswith(bad + "_")
                       for n in names), (bad, sorted(names))
    for want in ("jit_coupled_multi_step_2", "jit_multi_step_2",
                 "jit_lap", "jit_grad", "jit_energy_reduce",
                 "jit_field_statistics", "jit_histogram_prepare",
                 "jit_histogram_bincount", "jit_spectra_bin",
                 "jit_spectra_bin_weights", "jit_halo_pad",
                 "jit_health_vector", "jit_map_rho", "jit_dft_forward"):
        assert want in names, (want, sorted(names))


def test_multigrid_walk_has_its_host_spans_and_kernel_kinds(make_decomp):
    """A V-cycle's walk on the host: one ``mg_smooth`` a smooth, one
    ``mg_transfer_down`` / ``mg_transfer_up`` a transfer, and the one
    ``mg_errors_fetch`` that waits for the cycle; a level's kernels are
    built under their own kinds (``doc/observability.md`` "Host spans",
    "Kernel kinds")."""
    from pystella_tpu.multigrid import (
        FullApproximationScheme, NewtonIterator, v_cycle)
    decomp = make_decomp((1, 1, 1))
    solver = NewtonIterator(
        decomp, {ps.Field("f"): (ps.Field("lap_f") - ps.Field("f"),
                                 ps.Field("rho"))},
        halo_shape=1, dtype=np.float32, smoother="pallas", omega=1 / 2)
    mg = FullApproximationScheme(solver=solver, halo_shape=1)
    rng = np.random.default_rng(4)
    f, rho = (jnp.asarray(rng.random((16,) * 3), jnp.float32)
              for _ in range(2))
    mg(decomp, dx0=0.5, cycle=v_cycle(2, 3, 1), f=f, rho=rho)  # builds
    with obs.recording() as rows:
        mg(decomp, dx0=0.5, cycle=v_cycle(2, 3, 1), f=f, rho=rho)
    table = scope.span_table(rows)
    counts = {k: v["count"] for k, v in table["spans"].items()}
    assert counts == {"mg_smooth": 3, "mg_transfer_down": 1,
                      "mg_transfer_up": 1, "mg_errors_fetch": 1}
    assert table["fetches"] == 1
    kinds = {key[1]: fn for key, fn in solver._compiled.items()
             if key[0] == "pallas"}
    assert set(kinds) == {"smooth", "residual", "tau"}
    level = next(key[2] for key in solver._compiled if key[0] == "pallas")
    for kind in kinds:
        lowered = solver._pallas_level(
            kind, level, decomp, jnp.dtype("float32"), ())._jitted.lower(
                f[None], rho[None], (), jnp.int32(2))
        assert obs.has_scope(lowered, "pallas_stencil_mg_" + kind)
