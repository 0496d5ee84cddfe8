"""SpectralCollocator tests: plane waves differentiate exactly with
continuum momenta (analog of the spectral half of
/root/reference/test/test_derivs.py)."""

import numpy as np
import pytest

import pystella_tpu as ps


@pytest.fixture
def setup(proc_shape, grid_shape, make_decomp):
    decomp = make_decomp((proc_shape[0], proc_shape[1], 1))
    lattice = ps.Lattice(grid_shape, (4.0, 6.0, 8.0), dtype=np.float64)
    fft = ps.DFT(decomp, grid_shape=grid_shape, dtype=np.float64)
    return decomp, lattice, fft


@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1)], indirect=True)
def test_plane_wave_derivatives(setup, grid_shape, proc_shape):
    decomp, lattice, fft = setup
    sc = ps.SpectralCollocator(fft, lattice.dk)

    xs = [np.arange(n) * d for n, d in zip(grid_shape, lattice.dx)]
    X, Y, Z = np.meshgrid(*xs, indexing="ij")
    kx, ky, kz = 2 * lattice.dk[0], 3 * lattice.dk[1], 1 * lattice.dk[2]
    phase = kx * X + ky * Y + kz * Z
    f = np.sin(phase)
    arr = decomp.shard(f)

    grd = np.asarray(sc.grad(arr))
    for d, k in enumerate((kx, ky, kz)):
        assert np.abs(grd[d] - k * np.cos(phase)).max() < 1e-10

    lap = np.asarray(sc.lap(arr))
    ksq = kx**2 + ky**2 + kz**2
    assert np.abs(lap + ksq * f).max() < 1e-9

    g2, l2 = sc.grad_lap(arr)
    assert np.allclose(np.asarray(g2), grd, atol=1e-12)
    assert np.allclose(np.asarray(l2), lap, atol=1e-12)


@pytest.mark.parametrize("real_inverse", ["xla", "matmul"])
@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_pencil_tier_against_the_declarative_one(setup, grid_shape,
                                                 proc_shape, real_inverse,
                                                 tmp_path):
    """The collocator over ``PencilFFT`` (what ``make_dft`` hands a mesh:
    explicit ``all_to_all``, k space in its own layout) against the one
    over ``DFT`` (reshards the partitioner lowers) on ``(2, 2, 1)``:
    plane-wave derivatives to round-off on both inverses, the same
    answers from both, and a ``spectral_plan`` that tells them apart."""
    from pystella_tpu import obs
    from pystella_tpu.obs.events import read_events
    decomp, lattice, _ = setup
    kw = dict(grid_shape=grid_shape, dtype=np.float64,
              real_inverse=real_inverse)
    log = tmp_path / "events.jsonl"
    obs.configure(str(log))
    try:
        made = ps.make_dft(decomp, **kw)
        pencil = ps.SpectralCollocator(made, lattice.dk)
        declared = ps.SpectralCollocator(ps.DFT(decomp, **kw), lattice.dk)
    finally:
        obs.configure(None)
    assert made.is_pencil
    plans = [e["data"] for e in read_events(str(log), kind="spectral_plan")]
    assert [d["scheme"] for d in plans] == ["pencil-a2a", "pencil"]
    assert [(d["transposes_forward"], d["transposes_inverse"])
            for d in plans] == [(2, 2), (3, 3)]
    half = int(np.prod(grid_shape[:2])) * (grid_shape[2] // 2 + 1)
    for d in plans:
        assert d["proc_shape"] == [2, 2, 1]
        assert d["inverse"] == real_inverse
        assert d["transpose_bytes"] == half * 16 // 4

    xs = [np.arange(n) * d for n, d in zip(grid_shape, lattice.dx)]
    X, Y, Z = np.meshgrid(*xs, indexing="ij")
    kx, ky, kz = 3 * lattice.dk[0], 1 * lattice.dk[1], 2 * lattice.dk[2]
    phase = kx * X + ky * Y + kz * Z
    f = np.stack([np.sin(phase), 0.3 + np.cos(phase)])
    arr = decomp.shard(f)
    ksq = kx**2 + ky**2 + kz**2
    for sc in (pencil, declared):
        lap = np.asarray(sc.lap(arr))
        assert np.abs(lap[0] + ksq * f[0]).max() < 1e-9
        assert np.abs(lap[1] + ksq * (f[1] - 0.3)).max() < 1e-9
        grd = np.asarray(sc.grad(arr))
        for d, k in enumerate((kx, ky, kz)):
            assert np.abs(grd[0, d] - k * np.cos(phase)).max() < 1e-10
    assert np.allclose(np.asarray(pencil.lap(arr)),
                       np.asarray(declared.lap(arr)), atol=1e-10)
    assert np.allclose(np.asarray(pencil.grad(arr)),
                       np.asarray(declared.grad(arr)), atol=1e-11)


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_divergence_and_pd(setup, grid_shape, proc_shape):
    decomp, lattice, fft = setup
    sc = ps.SpectralCollocator(fft, lattice.dk)

    xs = [np.arange(n) * d for n, d in zip(grid_shape, lattice.dx)]
    X, Y, Z = np.meshgrid(*xs, indexing="ij")
    kx, ky, kz = 1 * lattice.dk[0], 2 * lattice.dk[1], 2 * lattice.dk[2]
    phase = kx * X + ky * Y + kz * Z
    f = np.sin(phase)

    vec = decomp.shard(np.stack([f, 2 * f, 3 * f]))
    div = np.asarray(sc.divergence(vec))
    expected = (kx + 2 * ky + 3 * kz) * np.cos(phase)
    assert np.abs(div - expected).max() < 1e-10

    arr = decomp.shard(f)
    assert np.abs(np.asarray(sc.pdx(arr)) - kx * np.cos(phase)).max() < 1e-10
    assert np.abs(np.asarray(sc.pdz(arr)) - kz * np.cos(phase)).max() < 1e-10


def test_refuses_xlas_inverse_on_a_tpu_backend(setup, monkeypatch):
    """On the TPU XLA's inverse real transform is wrong, so a collocator
    built on it there is refused with the option's name. The check reads
    the backend of the transform's devices and the transform's option:
    built on the CPU, refused on a TPU with the default inverse, built
    there with the inverse by matrix products or a complex transform."""
    from pystella_tpu.fourier import derivs
    decomp, lattice, fft = setup
    matmul = ps.DFT(decomp, grid_shape=fft.grid_shape, dtype=np.float64,
                    real_inverse="matmul")
    c2c = ps.DFT(decomp, grid_shape=fft.grid_shape, dtype=np.complex128)
    ps.SpectralCollocator(fft, lattice.dk)
    asked = []
    monkeypatch.setattr(derivs, "_platform",
                        lambda d: asked.append(d) or "tpu")
    with pytest.raises(ValueError, match='real_inverse="matmul"'):
        ps.SpectralCollocator(fft, lattice.dk)
    assert asked == [decomp]
    ps.SpectralCollocator(matmul, lattice.dk)
    ps.SpectralCollocator(c2c, lattice.dk)


@pytest.mark.parametrize("proc_shape", [(1, 1, 1)], indirect=True)
def test_programs_scopes_spans_and_plan(setup, grid_shape, tmp_path):
    """What a trace and the event log say of a collocator: its programs
    are named ``spectral_<op>``, their ops lie under the three scopes
    (inside a caller's program too), ``lap`` and ``grad`` dispatch under
    host spans of their own, and one ``spectral_plan`` event a built
    collocator says which transform and inverse it got."""
    import jax
    from pystella_tpu import obs
    from pystella_tpu.obs.events import read_events
    from pystella_tpu.obs.scope import has_scope
    decomp, lattice, fft = setup
    log = tmp_path / "events.jsonl"
    obs.configure(str(log))
    try:
        sc = ps.SpectralCollocator(fft, lattice.dk)
    finally:
        obs.configure(None)
    (event,) = read_events(str(log), kind="spectral_plan")
    assert event["data"]["inverse"] == "xla"
    assert event["data"]["scheme"] == fft.scheme
    assert event["data"]["grid_shape"] == list(grid_shape)
    assert event["data"]["dtype"] == "float64"
    # one device: no mesh to cross
    assert event["data"]["proc_shape"] == [1, 1, 1]
    assert (event["data"]["transposes_forward"],
            event["data"]["transposes_inverse"],
            event["data"]["transpose_bytes"]) == (0, 0, 0)

    x = jax.ShapeDtypeStruct(grid_shape, np.float64)
    vec = jax.ShapeDtypeStruct((3,) + grid_shape, np.float64)
    for name, fn, args in (("lap", sc._lap, (x,)), ("grad", sc._grad, (x,)),
                           ("grad_lap", sc._grad_lap, (x,)),
                           ("pd", sc._pd, (x, 0)), ("div", sc._div, (vec,))):
        lowered = fn.lower(*args)
        assert "jit_spectral_" + name in lowered.as_text()[:200], name
        for scope in ("spectral_forward", "spectral_symbol",
                      "spectral_inverse"):
            assert has_scope(lowered, scope), (name, scope)
    inlined = jax.jit(lambda f: 2 * sc.lap(f)).lower(x)
    assert has_scope(inlined, "spectral_inverse")
    # a one-device transform has no transpose and says so by silence
    assert not has_scope(inlined, "fft_transpose")

    arr = decomp.shard(np.ones(grid_shape))
    with obs.recording() as rows:
        sc.lap(arr)
        sc.grad(arr)
    assert [r[0] for r in rows] == ["spectral_lap_dispatch",
                                    "spectral_grad_dispatch"]


if __name__ == "__main__":
    # spectral-derivative microbenchmark (reference test/common.py:41-56):
    #   python tests/test_spectral_collocator.py -grid 256 256 256
    import common

    args = common.parse_args()
    decomp, lattice, fft = common.script_fft(args)
    sc = ps.SpectralCollocator(fft, lattice.dk)

    rng = np.random.default_rng(17)
    arr = decomp.shard(rng.standard_normal(args.grid_shape).astype(args.dtype))
    nsites = float(np.prod(args.grid_shape))
    for name, thunk in [("lap", lambda: sc.lap(arr)),
                        ("grad", lambda: sc.grad(arr)),
                        ("grad_lap", lambda: sc.grad_lap(arr))]:
        common.report(name, ps.timer(thunk, ntime=args.ntime),
                      nsites=nsites)
