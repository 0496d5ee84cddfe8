"""The benchmark's self-test. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest -q -p no:cacheprovider

It is not part of the repository's tier-1 tests (those live in ``tests/``).

* the trace reducer on a small recorded TPU trace checked in beside it;
* a tiny-size rehearsal of each driver end to end (Pallas in interpret
  mode), which must print no device metric;
* the control of the ``correct`` decision: the plain reference computed
  in bfloat16 in the program's place has to miss the limits of
  ``field_gap``, ``spectra_gap``, ``hist_gap`` and ``stats_gap``;
* the timed path broken underneath (a step call that returns its state
  unchanged; a chunk that advances only part of its steps; spectra binned
  one bin off; a statistics row of a state one chunk old) has to make
  ``correct`` come out false;
* the plain reference's spectrum against a direct sum over modes.
"""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, run, trace_reduce  # noqa: E402

TINY = json.dumps({"grid_shape": [32, 32, 32]})
CELLS = [w["name"] for w in run.read_json("BENCHMARK.json")["workloads"]
         if w["chips"] == 1]


def rehearse(cell, seed=3, patch=None, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "1", "--trace", str(trace),
                       "--rehearse", "--config-override", TINY],
                      patch=patch)
    lines = out.getvalue().strip().splitlines()
    return rc, lines


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_and_prints_no_device_metric(cell):
    rc, lines = rehearse(cell, trace=1)
    assert rc == 0, "\n".join(lines[-12:])
    last = lines[-1]
    assert "rehearsal:" in last
    summary = json.loads(last.split("rehearsal: ", 1)[1])
    assert summary["correct"] is True and summary["failed"] == 0
    # names only, no value; and nothing read from a device trace
    assert not any(n in summary["metric_names"] for n in (
        "kernel_ms_per_step", "stencil_kernel_roofline",
        "device_idle_share", "copy_probe_gbps"))
    assert '"metrics"' not in last


def test_broken_step_makes_correct_false():
    """A step call that hands its state back unchanged."""
    def patch(system, driver):
        stepper = system.stepper

        def unchanged(state, nsteps, expansion, *a, **kw):
            return state
        stepper.coupled_multi_step = unchanged

    rc, lines = rehearse("preheat-512-f32.coupled-run", patch=patch)
    assert rc == 1
    assert any("check field_gap" in ln and "NOT OK" in ln for ln in lines)


def test_part_of_the_steps_left_out_makes_correct_false():
    """A chunk that advances three of its four steps."""
    def patch(system, driver):
        real = system.stepper.multi_step

        def short(state, nsteps, *a, **kw):
            return real(state, nsteps - 1, *a, **kw)
        system.stepper.multi_step = short

    rc, lines = rehearse("preheat-512-f32.fixed-bg", patch=patch)
    assert rc == 1
    assert any("check field_gap" in ln and "NOT OK" in ln for ln in lines)


def test_spectra_binned_one_bin_off_make_correct_false():
    def patch(system, driver):
        obs = system.observables()
        real = obs["spectra"]
        obs["spectra"] = lambda fx: np.roll(real(fx), 1, axis=-1)

    rc, lines = rehearse("preheat-512-f32.coupled-run", patch=patch)
    assert rc == 1
    assert any("check spectra_gap" in ln and "NOT OK" in ln for ln in lines)
    assert not any("check field_gap" in ln and "NOT OK" in ln for ln in lines)


def test_statistics_of_a_stale_state_make_correct_false():
    """Each statistics row describes the state one chunk before."""
    def patch(system, driver):
        obs = system.observables()
        real, held = obs["statistics"], {}

        def stale(f):
            row = held.get("row")
            held["row"] = real(f)
            return row if row is not None else real(f * 0 + 1)
        obs["statistics"] = stale

    rc, lines = rehearse("preheat-512-f32.coupled-run", patch=patch)
    assert rc == 1
    assert any("check stats_gap" in ln and "NOT OK" in ln for ln in lines)


def test_bfloat16_control_misses_the_limits():
    """The reference in bfloat16, and with bfloat16 carries only, put in
    the program's place against the float32 reference, at a size a test
    can hold: the stepping, the output, the statistics. On the chip, at
    512^3: ``benchmark/control.py``."""
    from benchmark import control
    rows = control.readings("preheat-512-f32.coupled-run", seeds=[1, 2, 3],
                            override=json.loads(TINY), rehearse=True)
    limits = check.limits_for("preheat-512-f32.coupled-run")
    for row in rows:
        assert row["bf16"] > limits["field_gap"], row
        assert row["bf16_carry"] > limits["field_gap"], row
        assert row["f32_again"] == 0.0, row
        for key in ("scalar0", "scalar1", "rho"):
            assert row["bf16_spectra_gap." + key] \
                > limits["spectra_gap." + key], row
        assert row["bf16_hist_gap"] > limits["hist_gap"], row
        assert row["bf16_stats_gap"] > limits["stats_gap"], row


def test_reference_spectrum_against_a_direct_sum():
    """``reference.spectrum`` (mean off, powers of +-k_x, +-k_y folded on
    the device, binned on the host) against the definition summed mode by
    mode in float64, on a lattice with unequal sides."""
    from benchmark import reference
    shape, box = (8, 12, 16), (2.0, 3.0, 5.0)
    rng = np.random.default_rng(5)
    x = rng.standard_normal(shape) + 3.0
    dk = [2 * np.pi / b for b in box]
    xk = np.fft.fftn(x)
    ks = np.meshgrid(*[np.fft.fftfreq(n, 1 / n) * d
                       for n, d in zip(shape, dk)], indexing="ij")
    kmag = np.sqrt(sum(k * k for k in ks))
    index = np.rint(kmag / min(dk)).astype(int)
    sums = np.bincount(index.ravel(),
                       weights=(kmag ** 3 * np.abs(xk) ** 2).ravel())
    count = np.bincount(index.ravel())
    volume = np.prod(box)
    want = (volume / x.size) ** 2 / (2 * np.pi ** 2 * volume) * sums / count
    got = reference.spectrum(np.asarray(x, np.float32),
                             reference.SpectrumBins(shape, box))
    assert got.shape == want.shape
    assert np.max(np.abs(got - want) / want.max()) < 1e-5
    assert got[0] == 0.0


# -- the trace reducer, on a recorded TPU trace ---------------------------

TRACE = os.path.join(HERE, "trace_v5e_coupled.json.gz")


@pytest.mark.skipif(not os.path.exists(TRACE), reason="no recorded trace")
def test_reducer_on_recorded_trace():
    rec = trace_reduce.load(TRACE)
    meta = rec["meta"]
    out, notes, breakdown = trace_reduce.reduce(
        rec, run.load_dir("kernels"), meta["local_shape"], 819.0,
        meta["steps_traced"])
    assert 0 < out["stencil_kernel_roofline"] <= 100.0
    assert 0 <= out["device_idle_share"] < 100.0
    assert 0 < out["kernel_ms_per_step"] < 1e3
    assert out["step_program_other_ms_per_step"] > 0
    assert out["busy_s"] <= out["window_s"]
    # the pair count, by the events: 5 coupled-pair calls per 2 steps, each
    # 16 y-slabs of 4 arrays read and 4 written
    pairs = next(n for n in notes if "4in/4out+2sums" in n)
    events = int(pairs.split(": ")[1].split(" events")[0])
    assert events == 16 * 5 * meta["steps_traced"] // 2
    assert "536.9 MB each" in pairs
    assert len(breakdown["device_ops"]) <= 10
    assert len(breakdown["idle_gaps"]) <= 10


def test_slab_bytes_from_the_instruction():
    text = ("%pallas_stencil.7 = (f32[2,512,32,512]{3,2,1,0:T(8,128)}, "
            "f32[2,512,32,512]{3,2,1,0}, f32[8,128]{1,0}) custom-call("
            "f32[2,512,512,512]{3,2,1,0} %f, f32[1]{0} %dt, "
            "f32[2,516,528,512]{3,2,1,0} %padded), "
            'custom_call_target="tpu_custom_call"')
    slab = 2 * 512 * 32 * 512 * 4
    assert trace_reduce.slab_stencil_bytes(text, (512, 512, 512)) \
        == 2 * slab + 8 * 128 * 4 + 2 * slab
    assert trace_reduce.signature(text) == "2in/2out+1sums"
    assert trace_reduce.short_name(text) == "pallas_stencil"


def test_interval_arithmetic():
    u = trace_reduce.union([[0, 2], [1, 3], [5, 6]])
    assert u == [[0, 3], [5, 6]]
    assert trace_reduce.length(trace_reduce.clip(u, [[2, 5.5]])) == 1.5
    assert trace_reduce.subtract([[0, 10]], u) == [[3, 5], [6, 10]]


def test_a_pallas_op_without_a_file_is_not_given_a_default():
    kernels = run.load_dir("kernels")
    known = '%pallas_stencil.3 = f32[2,8,8,8]{3,2,1,0} custom-call(), ' \
        'custom_call_target="tpu_custom_call"'
    other = '%histogram.3 = f32[2,8,8,8]{3,2,1,0} custom-call(), ' \
        'custom_call_target="tpu_custom_call"'
    assert trace_reduce.kernel_file(known, kernels) == "pallas_stencil"
    assert trace_reduce.kernel_file(other, kernels) is None
