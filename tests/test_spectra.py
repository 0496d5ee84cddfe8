"""Power-spectra tests against a direct numpy histogram reference
(analog of /root/reference/test/test_spectra.py:95-109)."""

import numpy as np
import pytest

import pystella_tpu as ps


@pytest.fixture(params=[np.float64, np.float32], ids=["f64", "f32"])
def dtype(request):
    return np.dtype(request.param)


@pytest.fixture
def setup(proc_shape, grid_shape, make_decomp, dtype):
    decomp = make_decomp(proc_shape)
    lattice = ps.Lattice(grid_shape, (5.0, 5.0, 5.0), dtype=dtype)
    fft = ps.DFT(decomp, grid_shape=grid_shape, dtype=dtype)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)
    return decomp, lattice, fft, spectra


def numpy_spectrum(fx, dk, volume, bin_width, num_bins, k_power=3):
    grid_shape = fx.shape
    fk = np.fft.rfftn(fx)
    kvec = [ps.fftfreq(n) for n in grid_shape[:-1]]
    kvec.append(np.arange(grid_shape[-1] // 2 + 1))
    kx, ky, kz = np.meshgrid(*kvec, indexing="ij", sparse=False)
    kmags = np.sqrt((dk[0] * kx)**2 + (dk[1] * ky)**2 + (dk[2] * kz)**2)

    counts = 2.0 * np.ones_like(kmags)
    counts[kz == 0] = 1.0
    counts[kz == grid_shape[-1] // 2] = 1.0

    bins = np.arange(-0.5, num_bins + 0.5) * bin_width
    bin_counts = np.histogram(kmags, weights=counts, bins=bins)[0]
    hist = np.histogram(kmags, weights=counts * kmags**k_power
                        * np.abs(fk)**2, bins=bins)[0]

    d3x = volume / np.prod(grid_shape)
    norm = (1 / 2 / np.pi**2 / volume) * d3x**2
    return norm * hist / bin_counts


@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1), (2, 2, 2)],
                         indirect=True)
@pytest.mark.parametrize("k_power", [3, 0])
def test_spectra_match_numpy(setup, grid_shape, proc_shape, k_power):
    decomp, lattice, fft, spectra = setup
    rng = np.random.default_rng(11)
    fx = rng.standard_normal(grid_shape)

    result = spectra(decomp.shard(fx.astype(fft.dtype)), k_power=k_power)
    expected = numpy_spectrum(fx, lattice.dk, lattice.volume,
                              spectra.bin_width, spectra.num_bins, k_power)

    # identical binning => near-exact agreement in f64; the f32 band
    # covers transform + shell-sum roundoff against the f64 reference
    rtol = 1e-10 if fft.dtype == np.float64 else 2e-3
    nonzero = expected != 0
    assert np.allclose(result[nonzero], expected[nonzero], rtol=rtol)


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_spectra_outer_axes(setup, grid_shape, proc_shape):
    decomp, lattice, fft, spectra = setup
    rng = np.random.default_rng(12)
    fx = rng.standard_normal((2,) + grid_shape)

    result = spectra(decomp.shard(fx))
    assert result.shape == (2, spectra.num_bins)
    for i in range(2):
        single = spectra(decomp.shard(fx[i]))
        assert np.allclose(result[i], single, rtol=1e-12)


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_parseval(setup, grid_shape, proc_shape):
    """Sum of the unnormalized k_power=0 spectrum recovers <|f|^2>."""
    decomp, lattice, fft, spectra = setup
    rng = np.random.default_rng(13)
    fx = rng.standard_normal(grid_shape)

    fk = fft.dft(decomp.shard(fx.astype(fft.dtype)))
    hist = spectra.bin_power(fk, k_power=0)
    total = np.sum(hist * spectra.bin_counts)
    # Parseval: sum(counts * |fk|^2) = N * sum(fx^2)
    rtol = 1e-10 if fft.dtype == np.float64 else 2e-4
    assert np.isclose(total, np.prod(grid_shape) * np.sum(fx**2), rtol=rtol)


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_gw_spectrum_shapes(setup, grid_shape, proc_shape):
    decomp, lattice, fft, spectra = setup
    proj = ps.Projector(fft, 1, lattice.dk, lattice.dx)
    rng = np.random.default_rng(14)
    hij = decomp.shard(
        rng.standard_normal((6,) + grid_shape).astype(fft.dtype))

    gw = spectra.gw(hij, proj, hubble=1.0)
    assert gw.shape == (spectra.num_bins,)
    assert np.all(np.isfinite(gw))
    assert np.all(gw >= 0)

    gw_pol = spectra.gw_polarization(hij, proj, hubble=1.0)
    assert gw_pol.shape == (2, spectra.num_bins)
    # polarization spectra sum to the total (both are TT power)
    rtol = 1e-8 if fft.dtype == np.float64 else 2e-3
    assert np.allclose(gw_pol.sum(0)[1:], gw[1:], rtol=rtol)


if __name__ == "__main__":
    # binned-spectra microbenchmark (reference test/common.py:41-56):
    #   python tests/test_spectra.py -grid 256 256 256
    import common

    args = common.parse_args()
    decomp, lattice, fft = common.script_fft(args)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)

    rng = np.random.default_rng(7)
    fx = decomp.shard(
        rng.standard_normal((2,) + args.grid_shape).astype(args.dtype))
    nsites = float(np.prod(args.grid_shape))
    common.report("spectra (2 fields)",
                  ps.timer(lambda: spectra(fx), ntime=args.ntime),
                  nsites=nsites)


@pytest.mark.parametrize("proc_shape", [(2, 2, 1)], indirect=True)
def test_vector_polarization_batching(setup, grid_shape, proc_shape):
    """polarization / vector_decomposition batch all outer slices through
    one transform + one binning pass; results must equal per-slice
    calls."""
    decomp, lattice, fft, spectra = setup
    proj = ps.Projector(fft, 1, lattice.dk, lattice.dx)
    rng = np.random.default_rng(19)
    vecs = rng.standard_normal((2, 3) + grid_shape).astype(fft.dtype)

    batched_pol = spectra.polarization(decomp.shard(vecs), proj)
    batched_dec = spectra.vector_decomposition(decomp.shard(vecs), proj)
    assert batched_pol.shape == (2, 2, spectra.num_bins)
    assert batched_dec.shape == (2, 3, spectra.num_bins)

    for i in range(2):
        single_pol = spectra.polarization(decomp.shard(vecs[i]), proj)
        single_dec = spectra.vector_decomposition(
            decomp.shard(vecs[i]), proj)
        assert np.allclose(batched_pol[i], single_pol, rtol=1e-6)
        assert np.allclose(batched_dec[i], single_dec, rtol=1e-6)

    # sanity: polarization power is contained in the full decomposition
    assert np.all(batched_dec[:, :2] >= 0)
    assert np.allclose(batched_pol, batched_dec[:, :2], rtol=1e-6)


def test_bin_counts_exact_past_2_to_24_modes():
    """The mode count per k-bin normalizes every spectrum. Accumulated
    in float32 it stops counting by ones at 2**24 modes: at 512**3 the
    sparse corner bins came out rounded and the last one empty (an inf
    in every spectrum — found by the chip smoke, PR 21). 288**3 holds
    2.4e7 modes, enough to show it."""
    import jax
    grid_shape = (288, 288, 288)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=np.float32)
    fft = ps.DFT(decomp, grid_shape=grid_shape, dtype=np.float32)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)
    # the same count from the device-side bin indices, in float64
    exact = np.bincount(
        np.asarray(spectra._bin_idx).ravel(),
        weights=np.asarray(spectra._counts, np.float64).ravel(),
        minlength=spectra.num_bins)
    assert exact.sum() == float(np.prod(grid_shape))
    assert np.array_equal(spectra.bin_counts, exact)
    assert np.all(spectra.bin_counts > 0)


@pytest.mark.parametrize("proc_shape", [(1, 1, 1), (2, 2, 1)], indirect=True)
@pytest.mark.parametrize("outer", [(), (2,), (6,)], ids=str)
def test_bin_power_matches_float64_binning(setup, proc_shape, outer):
    """``bin_power`` of momentum-space fields whose power spans 2**-40 ..
    2**40, one to six of them in one pass, against numpy's float64
    binning of the same weights: the one-hot contraction keeps the
    weights' float32 (the sums are exact products added in float32
    inside a partial, in float64 across partials and devices)."""
    decomp, lattice, fft, spectra = setup
    rng = np.random.default_rng(23 + len(outer))
    kshape = outer + tuple(fft.shape(True))
    fk = ((rng.standard_normal(kshape) + 1j * rng.standard_normal(kshape))
          * 2.0 ** rng.integers(-20, 21, kshape)).astype(fft.cdtype)
    got = spectra.bin_power(fk, k_power=3)
    assert got.shape == outer + (spectra.num_bins,)

    weights = (np.asarray(spectra._counts, np.float64)
               * np.asarray(spectra._kmags, np.float64)**3
               * np.abs(fk.astype(np.complex128))**2)
    index = np.asarray(spectra._bin_idx).ravel()
    expected = np.stack([
        np.bincount(index, weights=w.ravel(), minlength=spectra.num_bins)
        for w in weights.reshape((-1,) + weights.shape[-3:])])
    expected = expected.reshape(got.shape) / spectra.bin_counts
    # float32: the weights themselves are products of float32 factors
    rtol = 1e-12 if fft.dtype == np.float64 else 2e-6
    assert np.allclose(got, expected, rtol=rtol, atol=0)
