"""Pencil-FFT subsystem tests: the fully distributed shard_map tier
(fourier/pencil.py) bit-compared against the declarative DFT tiers and
``numpy.fft``, the scheme planner, the spectra/projection fast path, and the
evidence pipeline's `fft` surface
(ledger section, gate verdict, lint collective audit)."""

import json

import numpy as np
import pytest

import pystella_tpu as ps
from pystella_tpu.fourier.pencil import pencil_feasible


# ---------------------------------------------------------------------------
# correctness pins: pencil vs numpy vs the DFT tiers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1), (1, 1, 2)],
                         indirect=True)
def test_pencil_matches_numpy_and_dft_tier(decomp, grid_shape, proc_shape):
    """r2c forward/backward on unsharded, x/y-sharded, and z-sharded
    meshes: the pencil transform must match numpy to f64 roundoff and
    the declarative DFT tier to a few-ulp bound (same local FFT kernel,
    different data movement — movement must not change values)."""
    pfft = ps.PencilFFT(decomp, grid_shape=grid_shape, dtype=np.float64)
    dfft = ps.DFT(decomp, grid_shape=grid_shape, dtype=np.float64)
    assert pfft.is_pencil and pfft.scheme == "pencil-a2a"
    rng = np.random.default_rng(31)
    fx = rng.standard_normal(grid_shape)

    fk = pfft.dft(decomp.shard(fx))
    assert fk.shape == grid_shape[:-1] + (grid_shape[-1] // 2 + 1,)
    ref = np.fft.rfftn(fx)
    assert np.allclose(np.asarray(fk), ref, atol=1e-10)
    # few-ulp bound vs the DFT tier (measured bit-identical on CPU —
    # both run the same per-axis kernels; the bound tolerates a
    # backend reassociating across the different transpose structure)
    fk_d = np.asarray(dfft.dft(decomp.shard(fx)))
    scale = np.abs(ref).max()
    assert np.abs(np.asarray(fk) - fk_d).max() <= 8 * np.spacing(scale)

    back = pfft.idft(fk)
    assert np.allclose(np.asarray(back), fx, atol=1e-12)
    # and by matrix products, which is what is right on the TPU
    mfft = ps.PencilFFT(decomp, grid_shape=grid_shape, dtype=np.float64,
                        real_inverse="matmul")
    assert np.allclose(np.asarray(mfft.idft(fk)), fx, atol=1e-12)


@pytest.mark.parametrize("proc_shape", [(2, 2, 2)], indirect=True)
def test_pencil_c2c_and_batched(decomp, grid_shape, proc_shape):
    """c2c round trip on the fully-sharded mesh, plus the batched
    (multi-field, pipelined-transpose) path: per-field results must
    equal the single-field transform exactly."""
    fft = ps.PencilFFT(decomp, grid_shape=grid_shape, dtype=np.complex128)
    assert not fft.is_real
    rng = np.random.default_rng(32)
    fx = rng.standard_normal((2,) + grid_shape) \
        + 1j * rng.standard_normal((2,) + grid_shape)

    fk = fft.dft(decomp.shard(fx))
    assert np.allclose(np.asarray(fk),
                       np.fft.fftn(fx, axes=(-3, -2, -1)), atol=1e-10)
    # the pipelined batched path is element-for-element the unbatched
    # transform
    single = np.asarray(fft.dft(decomp.shard(fx[0])))
    assert np.array_equal(np.asarray(fk)[0], single)
    assert np.allclose(np.asarray(fft.idft(fk)), fx, atol=1e-12)


def test_pencil_divisibility_errors(make_decomp):
    """Infeasible shapes raise EARLY (at construction) with actionable
    messages naming the failing divisibility; the planner falls back to
    the DFT tiers under auto and forces under scheme='pencil'."""
    decomp = make_decomp((2, 2, 1))
    ok, reasons = pencil_feasible(decomp, (6, 6, 8))
    assert not ok and any("divisible" in r for r in reasons)

    with pytest.raises(ValueError) as ei:
        ps.PencilFFT(decomp, grid_shape=(6, 6, 8), dtype=np.float64)
    msg = str(ei.value)
    # actionable: names the failing axis/count and the way out
    assert "6" in msg and "4" in msg and "advise_shapes" in msg

    with pytest.raises(ValueError):
        ps.make_dft(decomp, grid_shape=(6, 6, 8), dtype=np.float64,
                    scheme="pencil")
    # auto falls back to the DFT partial tier for the same shape
    fb = ps.make_dft(decomp, grid_shape=(6, 6, 8), dtype=np.float64,
                     scheme="auto")
    assert not fb.is_pencil and fb._scheme == "partial"
    # ... and selects the pencil tier when feasible
    auto = ps.make_dft(decomp, grid_shape=(8, 8, 8), dtype=np.float64)
    assert auto.is_pencil

    with pytest.raises(ValueError, match="unknown FFT scheme"):
        ps.make_dft(decomp, grid_shape=(8, 8, 8), scheme="bogus")


def test_replicate_limit_uses_half_spectrum(make_decomp):
    """The replicate-limit refusal sizes the r2c HALF spectrum (the
    array the fallback actually replicates), not the full complex
    grid: a shape whose half-spectrum fits under the limit constructs,
    one just above refuses with guidance pointing at the pencil tier
    (not at allow_replicate first)."""
    decomp = make_decomp((2, 1, 2))
    shape = (6, 6, 250)  # no distributed scheme (6 % 4 != 0, z sharded)
    kbytes = 6 * 6 * (250 // 2 + 1) * 16  # complex128 half spectrum
    # limit just above the half-spectrum size: must construct (the old
    # full-grid accounting would have refused at ~2x)
    fft = ps.DFT(decomp, grid_shape=shape, dtype=np.float64,
                 replicate_limit=kbytes + 1)
    assert fft._scheme == "replicate"
    with pytest.raises(ValueError) as ei:
        ps.DFT(decomp, grid_shape=shape, dtype=np.float64,
               replicate_limit=kbytes - 1)
    assert "pencil" in str(ei.value)
    assert "advise_shapes" in str(ei.value)


# ---------------------------------------------------------------------------
# spectra / projection / solver / collocator on the pencil tier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_pencil_spectra_match_dft_tier(decomp, grid_shape, proc_shape):
    """The pencil tier's fused one-dispatch spectra (transform +
    weighting + shard-local binning) match the DFT tier's three-
    dispatch path to a few-ulp bound, batched fields included."""
    lat = ps.Lattice(grid_shape, (5.0,) * 3, dtype=np.float64)
    pfft = ps.make_dft(decomp, grid_shape=grid_shape, dtype=np.float64,
                       scheme="pencil")
    dfft = ps.DFT(decomp, grid_shape=grid_shape, dtype=np.float64)
    sp_p = ps.PowerSpectra(decomp, pfft, lat.dk, lat.volume)
    sp_d = ps.PowerSpectra(decomp, dfft, lat.dk, lat.volume)
    rng = np.random.default_rng(41)
    fx = rng.standard_normal((2,) + grid_shape)

    a = sp_p(decomp.shard(fx))
    b = sp_d(decomp.shard(fx))
    assert a.shape == (2, sp_p.num_bins)
    nz = b != 0
    assert np.allclose(a[nz], b[nz], rtol=1e-12)

    # GW TT-projection end to end: pencil transform -> elementwise
    # projection in the natural k layout -> shard-local binning
    proj_p = ps.Projector(pfft, 1, lat.dk, lat.dx)
    proj_d = ps.Projector(dfft, 1, lat.dk, lat.dx)
    hij = rng.standard_normal((6,) + grid_shape)
    g_p = sp_p.gw(decomp.shard(hij), proj_p, hubble=1.0)
    g_d = sp_d.gw(decomp.shard(hij), proj_d, hubble=1.0)
    assert np.allclose(g_p[1:], g_d[1:], rtol=1e-10)


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_scheme_kwarg_and_env(decomp, grid_shape, proc_shape,
                              monkeypatch):
    """Consumers' scheme knob: scheme='pencil' upgrades a passed DFT,
    the env does the same, and auto never swaps a passed transform."""
    lat = ps.Lattice(grid_shape, (5.0,) * 3, dtype=np.float64)
    dfft = ps.DFT(decomp, grid_shape=grid_shape, dtype=np.float64)
    up = ps.PowerSpectra(decomp, dfft, lat.dk, lat.volume,
                         scheme="pencil")
    assert up.fft.is_pencil
    keep = ps.PowerSpectra(decomp, dfft, lat.dk, lat.volume)
    assert keep.fft is dfft
    monkeypatch.setenv("PYSTELLA_FFT_SCHEME", "pencil")
    env_up = ps.SpectralPoissonSolver(dfft, lat.dk, lat.dx,
                                      lambda k, dx: -k**2)
    assert env_up.fft.is_pencil


@pytest.mark.slow
@pytest.mark.parametrize("proc_shape", [(2, 1, 2)], indirect=True)
def test_pencil_poisson_and_collocator(decomp, grid_shape, proc_shape):
    """SpectralPoissonSolver and SpectralCollocator run on the pencil
    tier (z-sharded mesh — the transform makes z local itself) and
    match the DFT tier bit-for-bit at the f64 level. Slow-marked: two
    extra transform compiles on top of the core pins above; the same
    k_axis_array plumbing is covered fast by the spectra/projector
    test."""
    lat = ps.Lattice(grid_shape, (5.0,) * 3, dtype=np.float64)
    pfft = ps.make_dft(decomp, grid_shape=grid_shape, dtype=np.float64,
                       scheme="pencil")
    dfft = ps.DFT(decomp, grid_shape=grid_shape, dtype=np.float64)
    rng = np.random.default_rng(43)
    rho = rng.standard_normal(grid_shape)
    eig = ps.SecondCenteredDifference(1).get_eigenvalues
    sol_p = ps.SpectralPoissonSolver(pfft, lat.dk, lat.dx, eig)
    sol_d = ps.SpectralPoissonSolver(dfft, lat.dk, lat.dx, eig)
    f_p = np.asarray(sol_p(rho=decomp.shard(rho)))
    f_d = np.asarray(sol_d(rho=decomp.shard(rho)))
    assert np.allclose(f_p, f_d, atol=1e-12)

    col_p = ps.SpectralCollocator(pfft, lat.dk)
    col_d = ps.SpectralCollocator(dfft, lat.dk)
    l_p = np.asarray(col_p.lap(decomp.shard(rho)))
    l_d = np.asarray(col_d.lap(decomp.shard(rho)))
    assert np.allclose(l_p, l_d, atol=1e-9)


# ---------------------------------------------------------------------------
# evidence pipeline: lint collective audit, ledger `fft` section, gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_spectra_program_collective_audit(decomp, grid_shape,
                                          proc_shape):
    """The acceptance pin: the compiled pencil-spectra program carries
    all_to_all transposes (allowlisted BY NAME) and NO all-gather of
    any operand — the transform provably never replicates a
    field-sized array on one device."""
    from pystella_tpu import lint as _lint
    from pystella_tpu.lint.targets import TRANSPOSE_COLLECTIVES
    lat = ps.Lattice(grid_shape, (5.0,) * 3, dtype=np.float32)
    fft = ps.make_dft(decomp, grid_shape=grid_shape, dtype=np.float32,
                      scheme="pencil")
    spectra = ps.PowerSpectra(decomp, fft, lat.dk, lat.volume)
    fn, k_args = spectra.spectrum_program(outer_shape=(2,), k_power=3)
    rng = np.random.default_rng(53)
    fx = decomp.shard(
        rng.standard_normal((2,) + grid_shape).astype(np.float32))
    asm, hlo = _lint.lower_and_compile(fn, (fx,) + k_args)

    # transposes present and allowlisted; audit passes clean
    viol, stats = _lint.audit_artifacts(
        "spectra", asm, hlo, dtype_policy=_lint.POLICY_SPECTRAL_F32,
        collectives=dict(TRANSPOSE_COLLECTIVES),
        fused_scopes=("fft_stage", "fft_transpose"))
    assert viol == [], [str(v) for v in viol]
    seen = stats["collectives"]["seen"]
    small = stats["collectives"]["small"]
    assert "all-to-all" in {**seen, **small}
    assert "all-gather" not in seen and "all-gather" not in small
    assert "all-gather" not in hlo

    # ... and WITHOUT the allowlist the same transposes are flagged by
    # name (proving the audit actually sees them, not an empty module)
    viol2, _ = _lint.audit_artifacts(
        "spectra", asm, hlo, dtype_policy=_lint.POLICY_SPECTRAL_F32,
        collectives={})
    flagged = [v for v in viol2 if v.checker == "collectives"]
    small_only = not seen
    assert flagged or small_only


def _report_with_fft(p50_ms, scheme="pencil-a2a", platform="cpu"):
    return {
        "schema": 1,
        "env": {"platform": platform, "device_kind": platform,
                "num_devices": 8},
        "steps": {"count": 32, "p50_ms": 1.0, "mad_ms": 0.01},
        "samples_ms": [1.0] * 32,
        "fft": {"scheme": scheme,
                "calls": 5,
                "ms": {"count": 5, "p50_ms": p50_ms, "mad_ms": 0.1}},
    }


def test_gate_fft_regression_and_coverage():
    """The gate's spectra-throughput verdict: a >threshold slowdown of
    the fft section's p50 ms/call fails (exit 1), within-threshold
    passes, lost coverage and scheme changes warn."""
    from pystella_tpu.obs.gate import compare_reports
    base = _report_with_fft(100.0)

    ok = compare_reports(base, _report_with_fft(110.0))
    assert ok["exit_code"] == 0 and ok["fft"]["slowdown_pct"] == 10.0

    bad = compare_reports(base, _report_with_fft(200.0))
    assert bad["exit_code"] == 1
    assert any("fft regression" in r for r in bad["reasons"])

    # lost coverage: warning, not failure
    cur = _report_with_fft(100.0)
    del cur["fft"]
    lost = compare_reports(base, cur)
    assert lost["exit_code"] == 0
    assert any("coverage was lost" in w for w in lost["warnings"])

    # scheme change: compared, but flagged
    chg = compare_reports(base, _report_with_fft(100.0, scheme="dft"))
    assert chg["exit_code"] == 0
    assert any("scheme changed" in w for w in chg["warnings"])


def test_ledger_fft_section(tmp_path):
    """The ledger's `fft` section: spectra_time events fold into the
    per-call distribution, the fft_spectra leg record supplies the
    5 N log2 N flops model, and scope rows feed the transpose split."""
    from pystella_tpu.obs.events import EventLog
    from pystella_tpu.obs.ledger import PerfLedger
    path = tmp_path / "ev.jsonl"
    log = EventLog(str(path))
    log.emit("run_start", grid_shape=[16, 16, 16], nsteps=4)
    for ms in (10.0, 11.0, 12.0):
        log.emit("spectra_time", ms=ms)
    log.emit("fft_spectra", scheme="pencil-a2a",
             grid_shape=[256, 256, 256], nfields=2, calls=3,
             ms_per_call=11.0, complex_itemsize=8)
    log.emit("trace_summary", scopes={
        "fft_stage": {"count": 8, "total_ms": 80.0, "mean_ms": 10.0},
        "fft_transpose": {"count": 8, "total_ms": 160.0,
                          "mean_ms": 20.0}})
    log.emit("step_time", ms=1.0)
    led = PerfLedger.from_events(str(path))
    led.env["num_devices"] = 8
    ff = led.fft()
    assert ff["scheme"] == "pencil-a2a" and ff["calls"] == 3
    assert ff["ms"]["p50_ms"] == 11.0
    n = 256**3
    assert ff["model"]["model_flops"] == pytest.approx(
        2 * 5 * n * np.log2(n))
    assert ff["model"]["achieved_gflops"] > 0
    # transposes: 160/8 = 20 ms/device, stage compute 80/8 = 10 ->
    # 10 hidden, 10 exposed
    assert ff["transpose_hidden_ms"] == pytest.approx(10.0)
    assert ff["transpose_exposed_ms"] == pytest.approx(10.0)
    # the section lands in the report + markdown
    rep = led.report()
    assert rep["fft"]["ms"]["count"] == 3
    from pystella_tpu.obs.ledger import render_markdown
    md = render_markdown(json.loads(json.dumps(rep)))
    assert "FFT / spectra" in md and "roofline" in md
