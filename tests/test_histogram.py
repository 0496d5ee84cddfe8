"""Histogram tests vs numpy (reference /root/reference/test/test_histogram.py:
generic weighted histograms and FieldHistogrammer binning both compared
against ``np.histogram``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu.field import Field, Var


@pytest.fixture(params=[(1, 1, 1), (2, 2, 1)])
def decomp(request):
    n = int(np.prod(request.param))
    return ps.DomainDecomposition(request.param, devices=jax.devices()[:n])


def test_weighted_histogram_matches_numpy(decomp, grid_shape):
    rng = np.random.default_rng(11)
    num_bins = 17

    fx = rng.standard_normal(grid_shape)
    bins = np.floor((fx - fx.min()) / (fx.max() - fx.min() + 1e-12)
                    * num_bins)
    weights = rng.uniform(0.5, 1.5, grid_shape)

    f, w = Field("f"), Field("w")
    hist = ps.Histogrammer(decomp, {"h": (f, w)}, num_bins)
    got = hist(f=decomp.shard(jnp.asarray(bins)),
               w=decomp.shard(jnp.asarray(weights)))["h"]

    expected = np.zeros(num_bins)
    np.add.at(expected, bins.astype(int).clip(0, num_bins - 1),
              weights)
    assert np.allclose(got, expected, rtol=1e-12)


def test_histogram_expression_binning(decomp, grid_shape):
    """Bin index computed from a symbolic expression with runtime scalars."""
    rng = np.random.default_rng(12)
    num_bins = 10
    fx = rng.uniform(0.0, 1.0, grid_shape)

    f = Field("f")
    norm = Var("norm")
    hist = ps.Histogrammer(decomp, {"counts": (f * norm, 1)}, num_bins)
    got = hist(f=decomp.shard(jnp.asarray(fx)), norm=float(num_bins))

    expected, _ = np.histogram(fx, bins=num_bins, range=(0, 1))
    # np.histogram puts x == 1.0 in the last bin; clipping matches
    assert np.allclose(got["counts"], expected)


def test_field_histogrammer_linear(decomp, grid_shape):
    rng = np.random.default_rng(13)
    fx = rng.standard_normal((2,) + grid_shape)
    num_bins = 12

    fh = ps.FieldHistogrammer(decomp, num_bins)
    out = fh(decomp.shard(jnp.asarray(fx)))

    assert out["linear"].shape == (2, num_bins)
    assert out["linear_bins"].shape == (2, num_bins + 1)
    for s in range(2):
        expected, edges = np.histogram(fx[s], bins=num_bins,
                                       range=(fx[s].min(), fx[s].max()))
        assert np.allclose(out["linear_bins"][s], edges, rtol=1e-10)
        # bin-edge assignment differs at edges by at most the edge items
        assert abs(out["linear"][s].sum() - expected.sum()) < 1e-9
        assert np.allclose(out["linear"][s], expected, atol=2)


def test_field_histogrammer_log(decomp, grid_shape):
    rng = np.random.default_rng(14)
    fx = np.exp(rng.uniform(-3, 2, grid_shape))
    num_bins = 8

    fh = ps.FieldHistogrammer(decomp, num_bins)
    out = fh(decomp.shard(jnp.asarray(fx)))
    assert out["log"].sum() == pytest.approx(np.prod(grid_shape))
    expected, edges = np.histogram(
        np.log(fx), bins=num_bins,
        range=(np.log(fx).min(), np.log(fx).max()))
    assert np.allclose(out["log_bins"], np.exp(edges), rtol=1e-10)
    assert np.allclose(out["log"], expected, atol=2)


def test_field_histogrammer_zero_field(decomp, grid_shape):
    """An identically-zero field must produce finite bins and counts (the
    log of |f| is -inf everywhere; the automatic bounds are sanitized)."""
    fh = ps.FieldHistogrammer(decomp, 8)
    out = fh(decomp.zeros(grid_shape, np.float64))
    for key in ("linear", "log", "linear_bins", "log_bins"):
        assert np.all(np.isfinite(out[key])), key
    # every site lands in some bin
    assert out["linear"].sum() == pytest.approx(np.prod(grid_shape))
    assert out["log"].sum() == pytest.approx(np.prod(grid_shape))


def test_reduction_requires_lattice_arg(decomp):
    red = ps.Reduction(decomp, {"e": [(ps.Field("f"), "avg")]})
    with pytest.raises(ValueError, match="lattice"):
        red(f=np.float64(3.0))


# -- the binning primitive (ops.histogram.bincount_core) ----------------------

def _mesh(proc_shape):
    n = int(np.prod(proc_shape))
    return ps.DomainDecomposition(proc_shape, devices=jax.devices()[:n])


def _numpy_bins(bins, weights, num_bins):
    """Per-outer-slice ``np.bincount``, float64 (int64 for counts)."""
    outer = bins.shape[:-3]
    flat = bins.reshape((-1, int(np.prod(bins.shape[-3:]))))
    wflat = (None if weights is None
             else np.asarray(weights, np.float64).reshape(flat.shape))
    out = np.stack([np.bincount(
        b, weights=None if wflat is None else wflat[i], minlength=num_bins)
        for i, b in enumerate(flat)])
    return out.reshape(outer + (num_bins,))


def _decade_weights(rng, shape, dtype):
    """Positive weights over 2**-20 .. 2**20: a float32 accumulator
    that rounds its inputs to bfloat16 cannot hold them."""
    return (rng.uniform(0.5, 1.5, shape)
            * 2.0 ** rng.integers(-20, 21, shape)).astype(dtype)


#: (num_bins, outer_shape): flat lengths 4, 444, 888, 1000, 2004, and
#: two over one 128 x 128 factorisation (20000; 6 x 3000)
_BIN_CASES = [(4, ()), (444, ()), (444, (2,)), (1000, ()), (334, (6,)),
              (20000, ()), (3000, (6,))]
#: lattices: less than one row of the tile (the sentinel pads it), whole
#: rows, a ragged last block, and (weighted) more than one partial with
#: the step past the last block read again and counted nowhere
_LATTICES = [(12, 10, 7), (16, 16, 16), (64, 64, 33)]


@pytest.mark.parametrize("lattice", _LATTICES, ids=str)
@pytest.mark.parametrize("num_bins, outer", _BIN_CASES, ids=str)
def test_bincount_counts_match_numpy(num_bins, outer, lattice):
    from pystella_tpu.ops.histogram import weighted_bincount
    rng = np.random.default_rng(num_bins + len(outer))
    bins = rng.integers(0, num_bins, outer + lattice).astype(np.int32)
    got = weighted_bincount(_mesh((1, 1, 1)), jnp.asarray(bins), None,
                            num_bins)
    assert got.dtype == np.int64 and got.shape == outer + (num_bins,)
    assert np.array_equal(got, _numpy_bins(bins, None, num_bins))


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize(
    "num_bins, outer, lattice",
    [(nb, outer, lattice)
     for nb, outer in [(4, ()), (444, (2,)), (1000, ()), (334, (6,)),
                       (20000, ())]
     # the long lattice, more than one partial, with one slice
     for lattice in _LATTICES + [(96, 96, 49)] * (not outer)], ids=str)
def test_bincount_weights_match_numpy(num_bins, outer, lattice, dtype):
    """Sums against numpy's float64: float32 weights to 1e-6 of a bin
    (every product exact, float32 sums inside a 2**18-element partial),
    float64 weights (x64, CPU) to float64 accuracy. The control: the
    same weights rounded to bfloat16, which is what one pass of the MXU
    on a float32 operand would bin, must fail that tolerance."""
    from pystella_tpu.ops.histogram import weighted_bincount
    rng = np.random.default_rng(num_bins)
    bins = rng.integers(0, num_bins, outer + lattice).astype(np.int32)
    weights = _decade_weights(rng, outer + lattice, dtype)
    decomp = _mesh((1, 1, 1))
    expected = _numpy_bins(bins, weights, num_bins)
    got = weighted_bincount(decomp, jnp.asarray(bins), jnp.asarray(weights),
                            num_bins)
    assert got.dtype == np.float64
    scale = np.where(expected == 0, 1.0, expected)
    rtol = 1e-6 if dtype == np.float32 else 1e-13
    assert np.max(np.abs(got - expected) / scale) < rtol
    if dtype == np.float32:
        rounded = jnp.asarray(weights).astype(jnp.bfloat16).astype(dtype)
        control = weighted_bincount(decomp, jnp.asarray(bins), rounded,
                                    num_bins)
        assert np.max(np.abs(control - expected) / scale) > 10 * rtol


@pytest.mark.parametrize("layout", ["position", "pencil-k"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["counts", "weights"])
def test_bincount_on_a_mesh(layout, weighted):
    """Per-device partials on a (2, 2, 1) mesh, stacked on axis 0: the
    position-space layout and the pencil transform's k-space layout
    (its y axis sharded over combined mesh axes)."""
    from pystella_tpu.ops.histogram import (
        bincount_core, fetch_partials, weighted_bincount)
    decomp = _mesh((2, 2, 1))
    grid_shape, num_bins, outer = (16, 16, 16), 444, (2,)
    names = None
    lattice = grid_shape
    if layout == "pencil-k":
        fft = ps.make_dft(decomp, grid_shape=grid_shape, dtype=np.float32,
                          scheme="pencil")
        names = tuple(fft.k_sharding(0).spec)
        lattice = fft.shape(True)
        assert any(isinstance(n, tuple) for n in names)
    rng = np.random.default_rng(5)
    bins = rng.integers(0, num_bins, outer + lattice).astype(np.int32)
    weights = (_decade_weights(rng, outer + lattice, np.float32)
               if weighted else None)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(decomp.mesh, P(None, *(
        names if names is not None else decomp.spec(0))))
    b = jax.device_put(bins, sharding)
    w = None if weights is None else jax.device_put(weights, sharding)
    expected = _numpy_bins(bins, weights, num_bins)
    got = weighted_bincount(decomp, b, w, num_bins, lattice_names=names)
    if weighted:
        assert np.allclose(got, expected, rtol=1e-6, atol=0)
    else:
        assert np.array_equal(got, expected)
    # the unjitted core, as the pencil tier composes it: one row of
    # partials per device
    core = bincount_core(decomp, outer, num_bins, weighted, names)
    partials = fetch_partials(jax.jit(core)(*((b, w) if weighted else (b,))))
    assert partials.shape == (4, 2 * num_bins)
    assert partials.dtype == (np.float32 if weighted else np.int32)


@pytest.mark.parametrize(
    "num_bins, elements, dtype, expected",
    [   # the cells' shapes: coupled-run's spectra and histogram, -gws' GW
        (444, 512 * 512 * 257, np.float32,
         dict(hi=4, passes=3, stack=16, partials=257, steps=4)),
        (1000, 512**3, None,
         dict(hi=8, passes=1, stack=16, partials=32, steps=64)),
        (334, 384 * 384 * 193, np.float32,
         dict(hi=3, passes=3, stack=16, partials=109, steps=4)),
        # float64 weights take one product; a tiny shard one short tile
        (444, 4096, np.float64,
         dict(hi=4, passes=1, stack=16, partials=1, steps=1,
              tile=(8, 512))),
        (20000, 840, np.float32,
         dict(hi=157, passes=3, stack=480, rows=2, tile=(8, 512)))])
def test_bincount_plan_follows_the_shapes(num_bins, elements, dtype,
                                          expected):
    """One path: the factorisation, the tile, the partial's length and
    the MXU passes are read off the shapes; a partial never covers more
    than 2**22 (counts) or 2**18 (weighted) elements."""
    from pystella_tpu.ops.histogram import _plan
    plan = _plan(num_bins, elements, None if dtype is None
                 else jnp.dtype(dtype))
    assert {k: plan[k] for k in expected} == expected
    assert plan["hi"] * plan["lo"] >= num_bins
    rows, lanes = plan["tile"]
    cap = (1 << 22) if dtype is None else (1 << 18)
    assert plan["steps"] * rows * lanes <= max(cap, rows * lanes)
    assert plan["partials"] * plan["steps"] * rows * lanes >= elements


def test_bincount_plan_event_and_scope(tmp_path):
    """What the kernel chose goes out once per built program as a
    ``bincount_plan`` event, and its call is traced under the
    registered ``pallas_bincount`` scope."""
    from pystella_tpu import obs
    from pystella_tpu.obs.events import read_events
    from pystella_tpu.obs.scope import has_scope
    from pystella_tpu.ops.histogram import bincount_core
    decomp = _mesh((1, 1, 1))
    core = bincount_core(decomp, (2,), 37, True)
    b = jax.ShapeDtypeStruct((2, 8, 8, 8), jnp.int32)
    w = jax.ShapeDtypeStruct((2, 8, 8, 8), jnp.float32)
    log = tmp_path / "events.jsonl"
    obs.configure(str(log))
    try:
        lowered = jax.jit(core).lower(b, w)
    finally:
        obs.configure(None)
    assert has_scope(lowered, "pallas_bincount")
    (event,) = read_events(str(log), kind="bincount_plan")
    data = event["data"]
    assert data["num_bins"] == 37 and data["nouter"] == 2
    assert data["elements"] == 512 and data["weights"] == "float32"
    assert (data["hi"], data["lo"], data["passes"]) == (1, 128, 3)


if __name__ == "__main__":
    # binning microbenchmark (reference test/common.py:41-56 pattern):
    #   python tests/test_histogram.py -grid 256 256 256
    import common

    args = common.parse_args()
    decomp = common.script_decomp(args.proc_shape)
    rng = np.random.default_rng(3)
    fx = decomp.shard(rng.standard_normal(args.grid_shape))

    hister = ps.FieldHistogrammer(decomp, num_bins=64, dtype=np.float64)
    nsites = float(np.prod(args.grid_shape))
    common.report("field histogram (lin+log)",
                  ps.timer(lambda: hister(fx), ntime=args.ntime),
                  nsites=nsites)


def test_field_histogrammer_f32_degenerate_bounds(decomp):
    """A constant f32 field with |value| above the dtype's exact-integer
    range: the degeneracy widening must survive the cast into the bin
    expressions' dtype (a +1.0 bump rounds away at 1e8 in f32, leaving
    0/0 = nan bin indices — code-review regression, round 4)."""
    fh = ps.FieldHistogrammer(decomp, 8, dtype=np.float64)
    f = decomp.shard(np.full((8, 8, 8), 1e8, np.float32))
    out = fh(f)
    assert out["linear"].sum() == 512
    assert out["linear"][0] == 512  # in bin 0 by value, not by nan cast
    assert np.all(np.isfinite(out["linear_bins"]))
    assert np.all(np.isfinite(out["log_bins"]))
