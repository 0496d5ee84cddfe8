"""Correctness tests for the streaming Pallas stencil kernels (interpret
mode on CPU). The TPU-compiled path is exercised by chip_smoke.py and
the benchmark on hardware; these verify the window/ring/wrap logic
bit-exactly against numpy rolls (reference analog:
/root/reference/test/test_derivs.py stencil checks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pystella_tpu.ops.pallas_stencil import LANE, StreamingStencil

# These bodies verify window/ring/wrap logic bit-exactly (f64, interpret
# mode) on small grids; compiled Mosaic kernels require Z % LANE == 0 and
# f32, so the on-device parity check lives in chip_smoke.py
# (fused_parity, 512^3 f32) rather than here. Applied per-test (not
# module-wide) so the backend-independent guard test below still runs on
# TPU.
interpret_only = pytest.mark.skipif(
    jax.default_backend() == "tpu",
    reason="interpret-mode f64 bodies on sub-lane-tile grids; compiled "
           "coverage: chip_smoke.py fused_parity at 512^3")


def test_compiled_requires_lane_aligned_z():
    """Compiled (non-interpret) construction rejects Z % LANE != 0 up
    front — Mosaic rejects windowed DMAs with unaligned lane slices
    (measured on v5e), and callers rely on this ValueError to fall back
    to the XLA halo path."""
    def body(taps, extras, scalars):
        return {"out": taps()}

    with pytest.raises(ValueError, match="lane"):
        StreamingStencil((16, 16, LANE // 2), 1, 1, body, {"out": (1,)},
                         interpret=False)


def test_choose_blocks_hardware_tuned_defaults():
    """Pin the measured-on-v5e selections (doc/performance.md): largest
    feasible by, smallest bx >= h, in the VMEM limit the kernels compile
    under. (bx=2, by=128) beat every bx>=4 blocking at 128^3; at 512^3
    the y block is the re-read ((by + 16) / by of every window): (2,
    256) and (2, 128) beat the (2, 64) and (2, 32) of the 24 MB budget
    (PR 39); regressions here silently cost 10-45% of headline
    bandwidth."""
    from pystella_tpu.ops.pallas_stencil import choose_blocks

    # fused single-stage scalar kernel (F=2): n_comp=2, 6 extras, 8 outs
    assert choose_blocks(2, (128,) * 3, 2, 4, 6, 8) == (2, 128)
    assert choose_blocks(2, (256,) * 3, 2, 4, 6, 8) == (2, 256)
    assert choose_blocks(2, (512,) * 3, 2, 4, 6, 8) == (2, 256)
    # stage-pair scalar kernel: 3 windows x F, 1 extra x F, 4 outs x F
    assert choose_blocks(6, (512,) * 3, 2, 4, 2, 8) == (2, 128)
    # bx respects the stencil radius
    assert choose_blocks(1, (64,) * 3, 4, 8, 0, 1)[0] >= 4


_lap_coefs = {
    1: {0: -2.0, 1: 1.0},
    2: {0: -30 / 12, 1: 16 / 12, 2: -1 / 12},
}


def _numpy_lap(fn, coefs, dx):
    ref = np.zeros_like(fn)
    for ax in range(3):
        for s, c in coefs.items():
            if s == 0:
                ref += c / dx**2 * fn
            else:
                ref += c / dx**2 * (np.roll(fn, s, 1 + ax)
                                    + np.roll(fn, -s, 1 + ax))
    return ref


def _lap_body(coefs, dx):
    def body(taps, extras, scalars):
        acc = 3 * coefs[0] / dx**2 * taps()
        for s, c in coefs.items():
            if s == 0:
                continue
            acc += c / dx**2 * (taps(s) + taps(-s) + taps(0, s)
                                + taps(0, -s) + taps(0, 0, s)
                                + taps(0, 0, -s))
        return {"lap": acc}
    return body


@interpret_only
@pytest.mark.parametrize("h", [1, 2])
@pytest.mark.parametrize("bx,by", [(4, 8), (2, 16), (8, 32), (16, 8)])
def test_streaming_lap_matches_numpy(h, bx, by):
    F, N = 2, 32
    dx = 5.0 / N
    coefs = _lap_coefs[h]
    rng = np.random.default_rng(1)
    f = jnp.asarray(rng.standard_normal((F, N, N, N)))

    st = StreamingStencil((N, N, N), F, h, _lap_body(coefs, dx),
                          {"lap": (F,)}, dtype=jnp.float64, bx=bx, by=by)
    out = np.asarray(st(f)["lap"])
    ref = _numpy_lap(np.asarray(f), coefs, dx)
    assert np.max(np.abs(out - ref)) / np.max(np.abs(ref)) < 1e-12


@interpret_only
def test_streaming_xhalo_mode():
    """x_halo=True consumes an x-padded input (sharded-x path)."""
    F, N, h = 1, 16, 2
    dx = 1.0 / N
    coefs = _lap_coefs[h]
    rng = np.random.default_rng(2)
    f = rng.standard_normal((F, N, N, N))
    fpad = np.concatenate([f[:, -h:], f, f[:, :h]], axis=1)

    st = StreamingStencil((N, N, N), F, h, _lap_body(coefs, dx),
                          {"lap": (F,)}, dtype=jnp.float64, bx=4, by=8,
                          x_halo=True)
    out = np.asarray(st(jnp.asarray(fpad))["lap"])
    ref = _numpy_lap(f, coefs, dx)
    assert np.max(np.abs(out - ref)) / np.max(np.abs(ref)) < 1e-12


@interpret_only
def test_streaming_extras_and_scalars():
    """Extra blockwise inputs and SMEM scalars reach the body."""
    F, N, h = 1, 16, 1
    rng = np.random.default_rng(3)
    f = jnp.asarray(rng.standard_normal((F, N, N, N)))
    g = jnp.asarray(rng.standard_normal((F, N, N, N)))

    def body(taps, extras, scalars):
        return {"out": taps() * scalars["alpha"] + extras["g"]}

    st = StreamingStencil((N, N, N), F, h, body, {"out": (F,)},
                          extra_defs={"g": (F,)}, scalar_names=("alpha",),
                          dtype=jnp.float64, bx=4, by=8)
    out = np.asarray(st(f, scalars={"alpha": 2.5}, extras={"g": g})["out"])
    assert np.allclose(out, 2.5 * np.asarray(f) + np.asarray(g))


@interpret_only
def test_streaming_multi_output():
    """Multiple named outputs with distinct leading shapes (grad + lap)."""
    F, N, h = 2, 16, 1
    dx = 1.0 / N
    grad_coefs = {1: 0.5}
    lap_coefs = _lap_coefs[1]

    def body(taps, extras, scalars):
        grads = []
        for d in range(3):
            acc = 0
            for s, c in grad_coefs.items():
                off = [0, 0, 0]
                off[d] = s
                offm = [0, 0, 0]
                offm[d] = -s
                acc = acc + c / dx * (taps(*off) - taps(*offm))
            grads.append(acc)
        lap = 3 * lap_coefs[0] / dx**2 * taps()
        for s, c in lap_coefs.items():
            if s:
                lap = lap + c / dx**2 * (
                    taps(s) + taps(-s) + taps(0, s) + taps(0, -s)
                    + taps(0, 0, s) + taps(0, 0, -s))
        return {"grad": jnp.stack(grads, axis=1), "lap": lap}

    rng = np.random.default_rng(4)
    f = jnp.asarray(rng.standard_normal((F, N, N, N)))
    st = StreamingStencil((N, N, N), F, h, body,
                          {"grad": (F, 3), "lap": (F,)},
                          dtype=jnp.float64, bx=4, by=8)
    out = st(f)
    fn = np.asarray(f)
    ref_lap = _numpy_lap(fn, lap_coefs, dx)
    assert np.max(np.abs(np.asarray(out["lap"]) - ref_lap)) < 1e-11
    for d in range(3):
        ref_g = (np.roll(fn, -1, 1 + d) - np.roll(fn, 1, 1 + d)) / (2 * dx)
        got = np.asarray(out["grad"][:, d])
        assert np.max(np.abs(got - ref_g)) < 1e-11


#: lattice of the one-call cases: by in (32, 16, 8) gives 1, 2, 4
#: y-blocks, bx in (16, 8, 4) gives 1, 2, 4 x-blocks
_ONE_CALL_SHAPE = (16, 32, 8)
_ONE_CALL_VARIANTS = ("plain", "x_halo", "y_halo", "xy_halo", "extras",
                      "win_halo", "x_slab", "y_slab", "xy_slab")


def _one_call_inputs(variant):
    """Integer-valued f64 inputs, so every product and every sum below
    is exact whatever order it is taken in: a blocking that drops,
    repeats or misplaces a site changes the bits, one that only
    reorders partial sums does not. Component 0 is a function of y
    alone (its x and z taps cancel: what is left is the y-window, with
    the wrap of the first and last y-block); component 1 is random."""
    from pystella_tpu.ops.pallas_stencil import HY
    X, Y, Z = _ONE_CALL_SHAPE
    rng = np.random.default_rng(7)
    f = np.empty((2, X, Y, Z))
    f[0] = rng.permutation(Y).astype(float)[None, :, None] - 11
    f[1] = rng.integers(-8, 9, size=(X, Y, Z))
    e = rng.integers(-8, 9, size=(2, X, Y, Z)).astype(float)
    wh = 2 if variant == "win_halo" else 1
    fin = f
    if variant in ("x_halo", "xy_halo"):
        fin = np.concatenate([fin[:, -wh:], fin, fin[:, :wh]], axis=1)
    if variant in ("y_halo", "xy_halo"):
        fin = np.concatenate([fin[:, :, -HY:], fin, fin[:, :, :HY]], axis=2)
    return f, e, fin


def _one_call_slabs(f, variant):
    """The slab operands of a slab-fed kernel whose neighbours are the
    lattice itself (a mesh of one): its own periodic faces, one row in
    x, the ``HY``-row y pieces holding their one row against the block
    and NaN in the rows no tap may read."""
    from pystella_tpu.ops.pallas_stencil import HY
    slabs = {}
    if variant in ("x_slab", "xy_slab"):
        slabs["x"] = (jnp.asarray(f[:, -1:]), jnp.asarray(f[:, :1]))
    if variant in ("y_slab", "xy_slab"):
        lo = np.full(f.shape[:2] + (HY,) + f.shape[3:], np.nan)
        hi = lo.copy()
        lo[:, :, -1:] = f[:, :, -1:]
        hi[:, :, :1] = f[:, :, :1]
        slabs["y"] = (jnp.asarray(lo), jnp.asarray(hi))
    return [slabs] if slabs else None


def _one_call_reference(f, e, variant):
    def sh(sx=0, sy=0, sz=0):
        return np.roll(f, (-sx, -sy, -sz), (1, 2, 3))
    lap = (-6 * f + sh(1) + sh(-1) + sh(0, 1) + sh(0, -1)
           + sh(0, 0, 1) + sh(0, 0, -1))
    if variant == "win_halo":
        lap = lap + sh(2) - 3 * sh(-2) + 5 * sh(0, 2) - 7 * sh(0, -2)
    if variant == "extras":
        lap = 3.0 * lap + e
    sums = np.array([(f[0]**2).sum(), (f[1]**2).sum(),
                     lap[0].sum(), lap[1].sum()])
    return lap, sums


def _one_call_stencil(variant, bx, by):
    def body(taps, extras, scalars):
        fv = taps()
        lap = (-6 * fv + taps(1) + taps(-1) + taps(0, 1) + taps(0, -1)
               + taps(0, 0, 1) + taps(0, 0, -1))
        if variant == "win_halo":
            # reaches the widened (chunk) window: 2h in x and in y
            lap = (lap + taps(2) - 3 * taps(-2) + 5 * taps(0, 2)
                   - 7 * taps(0, -2))
        if variant == "extras":
            lap = scalars["c"] * lap + extras["e"]
        sums = ([jnp.sum(fv[i] * fv[i]) for i in range(2)]
                + [jnp.sum(lap[i]) for i in range(2)])
        return {"lap": lap, "sums": sums}

    kw = {}
    if variant == "extras":
        kw.update(extra_defs={"e": (2,)}, scalar_names=("c",))
    if variant == "win_halo":
        kw.update(win_halo=2, stages=4)
    return StreamingStencil(
        _ONE_CALL_SHAPE, 2, 1, body, {"lap": (2,)}, dtype=jnp.float64,
        bx=bx, by=by, sum_defs={"sums": 4},
        x_halo=variant in ("x_halo", "xy_halo"),
        y_halo=variant in ("y_halo", "xy_halo"),
        x_slab=variant in ("x_slab", "xy_slab"),
        y_slab=variant in ("y_slab", "xy_slab"), **kw)


_ONE_CALL_RESULTS = {}


def _one_call_result(variant, bx, by):
    key = (variant, bx, by)
    if key not in _ONE_CALL_RESULTS:
        f, e, fin = _one_call_inputs(variant)
        st = _one_call_stencil(variant, bx, by)
        call = {"slabs": _one_call_slabs(f, variant)}
        if variant == "extras":
            call = dict(scalars={"c": 3.0}, extras={"e": jnp.asarray(e)})
        out = st(jnp.asarray(fin), **call)
        _ONE_CALL_RESULTS[key] = (
            st.grid, np.asarray(out["lap"]), np.asarray(out["sums"]))
    return _ONE_CALL_RESULTS[key]


@interpret_only
@pytest.mark.parametrize("variant", _ONE_CALL_VARIANTS)
@pytest.mark.parametrize("nbx", [1, 2, 4])
@pytest.mark.parametrize("nby", [1, 2, 4])
def test_streaming_one_call_blockings_agree(nby, nbx, variant):
    """The kernel is one ``pallas_call`` over a ``(nby, nbx)`` grid that
    writes each block where it lives: for every blocking, with either or
    both halo variants (pre-padded, or slab-fed: the ring's edge blocks
    and the edge y-blocks' halo pieces from thin slab operands, one,
    two and four x-blocks covering both ways of priming the ring), with
    extras and with the widened (chunk) window,
    the outputs and the ``sum_defs`` lattice sums (one revisited
    accumulator tile per y-block, finished over y outside the kernel —
    per-program partial columns do not compile on TPU) are bit-identical
    to the single-program call's and to the numpy reference."""
    X, Y, _ = _ONE_CALL_SHAPE
    grid, lap, sums = _one_call_result(variant, X // nbx, Y // nby)
    assert grid == (nby, nbx)
    f, e, _ = _one_call_inputs(variant)
    ref_lap, ref_sums = _one_call_reference(f, e, variant)
    assert np.array_equal(lap, ref_lap)
    assert np.array_equal(sums, ref_sums)
    _, lap1, sums1 = _one_call_result(variant, X, Y)
    assert np.array_equal(lap, lap1)
    assert np.array_equal(sums, sums1)


def _in_place_pair(variant, carry_dtype):
    """A stage-shaped kernel (window ``f``; extras ``d`` in the state's
    dtype and ``k``, one component, in the carry's; an output of each
    name) built twice, ``d`` and ``k`` in place and not, and its call
    arguments: wrapped, slab-fed from the lattice's own faces, or on the
    pre-padded window (``_build_xhalo``)."""
    X, Y, Z = shape = (8, 16, 8)
    rng = np.random.default_rng(23)
    f = rng.standard_normal((2,) + shape).astype(np.float32)
    d = jnp.asarray(rng.standard_normal((2,) + shape), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1,) + shape), carry_dtype)

    def body(taps, extras, scalars):
        lap = (-6 * taps() + taps(1) + taps(-1) + taps(0, 1)
               + taps(0, -1) + taps(0, 0, 1) + taps(0, 0, -1))
        k2 = scalars["A"] * extras["k"] + lap[:1] - lap[1:]
        d2 = extras["d"] + scalars["B"] * k2
        return {"f": taps() + 0.25 * d2, "d": d2, "k": k2}

    kw = {}
    fin, slabs = f, None
    if variant == "slab":
        kw.update(x_slab=True, y_slab=True)
        slabs = [{axis: tuple(x.astype(jnp.float32) for x in pair)
                  for axis, pair in group.items()}
                 for group in _one_call_slabs(f, "xy_slab")]
    elif variant == "x_halo":
        kw.update(x_halo=True)
        fin = np.concatenate([f[:, -1:], f, f[:, :1]], axis=1)
    built = [StreamingStencil(
        shape, {"f": 2}, 1, body, {"f": (2,), "d": (2,), "k": (1,)},
        extra_defs={"d": (2,), "k": (1,)}, scalar_names=("A", "B"),
        dtype=jnp.float32, dtypes={"k": carry_dtype}, bx=2, by=8,
        in_place=names, **kw) for names in (("d", "k"), ())]
    call = dict(scalars={"A": 0.5, "B": 1.25}, extras={"d": d, "k": k},
                slabs=slabs)
    return built, jnp.asarray(fin), call


@interpret_only
@pytest.mark.parametrize("carry_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16-carry"])
@pytest.mark.parametrize("variant", ["wrap", "slab", "x_halo"])
def test_streaming_in_place_equals_plain(variant, carry_dtype):
    """A kernel that writes its extras in place gives the bits of the one
    that does not, with float32 and with bfloat16 carries, wrapped,
    slab-fed and on the pre-padded window; its ``pallas_call`` pairs each
    named extra's operand (after the windows, the slabs and the scalars)
    with the output of that name, and an eager call (which owns nothing)
    leaves the caller's arrays as they were."""
    (st, plain), fin, call = _in_place_pair(variant, carry_dtype)
    assert st.in_place == ("d", "k") and plain.in_place == ()
    before = {n: np.asarray(v, np.float32) for n, v in call["extras"].items()}
    got, ref = st(fin, **call), plain(fin, **call)
    for n in ("f", "d", "k"):
        assert got[n].dtype == ref[n].dtype
        assert np.array_equal(np.asarray(got[n], np.float32),
                              np.asarray(ref[n], np.float32)), n
    assert got["k"].dtype == carry_dtype
    for n, v in call["extras"].items():
        assert np.array_equal(np.asarray(v, np.float32), before[n]), n

    def aliases(stencil):
        args = [fin]
        for group in call["slabs"] or ():
            args += [group[a][i] for a in "xy" for i in (0, 1)]
        args += [jnp.ones(1, jnp.float32)] * 2 + [
            call["extras"][n] for n in ("d", "k")]
        (eqn,) = [e for e in jax.make_jaxpr(stencil._call)(*args).eqns
                  if e.primitive.name == "pallas_call"]
        return dict(eqn.params["input_output_aliases"])

    first = 1 + (4 if variant == "slab" else 0) + 2
    assert aliases(st) == {first: 1, first + 1: 2}
    assert aliases(plain) == {}


def _inset_case(h, variant, shape):
    """A single-launch (wrapped) kernel at ``bx = h`` and the inputs of
    one call: ``one`` window and nothing else; ``several`` windows
    (one of them in a storage dtype of its own) and a lattice extra;
    ``in_place``: the stage shape, both extras written over."""
    rng = np.random.default_rng(31 + h)

    def lap_of(taps):
        lap = -6 * taps()
        for s in range(1, h + 1):   # taps out to the radius, on x too
            lap = lap + (taps(s) + taps(-s) + taps(0, s) + taps(0, -s)
                         + taps(0, 0, s) + taps(0, 0, -s)) / s
        return lap

    def arr(ncomp, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal((ncomp,) + shape), dtype)

    kw = dict(dtype=jnp.float32, scalar_names=("A",))
    if variant == "one":
        def body(taps, extras, scalars):
            return {"f": scalars["A"] * lap_of(taps)}
        wins, outs = {"f": 2}, {"f": (2,)}
        f, extras = arr(2), {}
    elif variant == "several":
        def body(taps, extras, scalars):
            g = taps["g"]().astype(jnp.float32)
            return {"f": lap_of(taps["f"]) + scalars["A"] * g[:1],
                    "g": g + taps["g"](h) - taps["g"](-h) + extras["e"]}
        wins, outs = {"f": 2, "g": 1}, {"f": (2,), "g": (1,)}
        kw.update(extra_defs={"e": (1,)}, dtypes={"g": jnp.bfloat16})
        f = {"f": arr(2), "g": arr(1, jnp.bfloat16)}
        extras = {"e": arr(1)}
    else:
        def body(taps, extras, scalars):
            lap = lap_of(taps)
            k2 = scalars["A"] * extras["k"] + lap[:1] - lap[1:]
            d2 = extras["d"] + 1.25 * k2
            return {"f": taps() + 0.25 * d2, "d": d2, "k": k2}
        wins, outs = {"f": 2}, {"f": (2,), "d": (2,), "k": (1,)}
        kw.update(extra_defs={"d": (2,), "k": (1,)}, in_place=("d", "k"))
        f, extras = arr(2), {"d": arr(2), "k": arr(1)}
    return wins, body, outs, kw, f, extras


@interpret_only
@pytest.mark.parametrize("variant", ["one", "several", "in_place"])
@pytest.mark.parametrize("nprog", [1, 2, 3, 6])
@pytest.mark.parametrize("h", [1, 2, 4])
def test_inset_kernel_equals_single_launch_rows(h, nprog, variant):
    """The overlap split's interior (PR 43) is the ring kernel with its
    grid short of the first and the last x-block: on rows ``h ... X -
    h`` it gives the single launch's bits, for radius 1, 2 and 4, one
    window and several, a lattice extra, extras written in place, and
    one, two (all blocks primed at once), three and six programs (the
    streaming prime) a y-block. Its extras and outputs are arrays of
    the whole lattice; it moves what the single launch moves
    (``reread``), and says where its x edges come from."""
    X, Y, Z = shape = ((nprog + 2) * h, 16, 8)
    wins, body, outs, kw, f, extras = _inset_case(h, variant, shape)
    st = StreamingStencil(shape, wins, h, body, outs, bx=h, by=8, **kw)
    inset = st.with_lattice(shape, bx=h, by=8, inset=True, kind="interior")
    assert st.grid == (2, nprog + 2) and inset.grid == (2, nprog)
    assert (st.halo, inset.halo) == (("wrap", "wrap"), ("inset", "wrap"))
    assert inset.x_inset == 1 and st.x_inset == 0
    assert inset.reread == st.reread
    assert inset.in_place == st.in_place and inset.kind == "interior"
    ref = st(f, scalars={"A": 0.5}, extras=extras)
    got = inset(f, scalars={"A": 0.5}, extras=extras)
    for n in outs:
        assert got[n].shape == ref[n].shape and got[n].dtype == ref[n].dtype
        assert np.array_equal(
            np.asarray(got[n][:, h:X - h], np.float32),
            np.asarray(ref[n][:, h:X - h], np.float32)), n


@pytest.mark.parametrize("kw, shape", [
    ({"x_slab": True}, (8, 16, 8)), ({"x_halo": True}, (8, 16, 8)),
    ({}, (4, 16, 8))], ids=["x-slab", "x-halo", "two-blocks"])
def test_inset_refusals(kw, shape):
    """An inset kernel streams the raw shard: it takes no x slab and no
    padded copy, and needs a block between the two it only reads."""
    with pytest.raises(ValueError, match="inset kernel"):
        StreamingStencil(shape, 1, 1, lambda t, e, s: {}, {"f": (1,)},
                         bx=2, by=8, interpret=True, x_inset=True, **kw)


#: sha256 of the jaxpr text (``pallas_call`` equation, kernel and index
#: maps included; no source locations, which the lowered module's
#: bytecode carries) of four unsplit kernels, recorded on the parent of
#: PR 43 (874860b) with x64 off
_UNSPLIT_JAXPRS = {
    ("wrap", 2): "d6831ae35c203f84",
    ("wrap", 4): "cc58ac3bcf281a0f",
    ("slab", 2): "31a0bb63d1e0c8ae",
    ("slab", 4): "1d41539e4d5a405b",
}


@pytest.mark.parametrize("mode, nbx", list(_UNSPLIT_JAXPRS))
def test_unsplit_kernel_traces_as_before_the_inset(mode, nbx):
    """For every kernel that is not the split's interior the inset is a
    static zero: a one-chip (wrapped) kernel and a slab-fed one (x and
    y, as on ``(2, 2, 1)``), at two x-blocks and at four (both primes
    of the ring), built for the chip, trace to the program they traced
    to on the parent of PR 43, equation for equation. The lowered text
    itself holds the kernel as bytecode with this file's line numbers,
    so it is the jaxpr that is held."""
    import hashlib
    from pystella_tpu.ops.pallas_stencil import HY, LANE

    def body(taps, extras, scalars):
        fv = taps()
        lap = -6.0 * fv
        for d in range(3):
            for s in (-1, 1):
                off = [0, 0, 0]
                off[d] = s
                lap = lap + taps(*off)
        return {"lap": scalars["c"] * lap + extras["e"]}

    with jax.enable_x64(False):
        X = 4 * nbx
        st = StreamingStencil(
            (X, 32, LANE), 2, 1, body, {"lap": (2,)},
            extra_defs={"e": (2,)}, scalar_names=("c",),
            dtype=jnp.float32, bx=4, by=8, interpret=False,
            x_slab=mode == "slab", y_slab=mode == "slab")
        x = jnp.zeros((2, X, 32, LANE), jnp.float32)
        slabs = None
        if mode == "slab":
            slabs = [{"x": (jnp.zeros((2, 1, 32, LANE), jnp.float32),) * 2,
                      "y": (jnp.zeros((2, X, HY, LANE), jnp.float32),) * 2}]

        def call(x, slabs):
            return st(x, scalars={"c": 3.0}, extras={"e": x}, slabs=slabs)

        text = str(jax.make_jaxpr(call)(x, slabs))
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        _UNSPLIT_JAXPRS[mode, nbx]


@pytest.mark.parametrize("names, defs, match", [
    (("f",), {}, "windowed input"),
    (("g",), {}, "an extra and an output of that name"),
    (("d",), {"d": (3,)}, "leading shape"),
], ids=["a-window", "no-output-of-the-name", "another-shape"])
def test_streaming_in_place_refusals(names, defs, match):
    """What cannot be written in place is refused at construction: a
    window (its halo rows are read by the neighbouring programs), a name
    that is not both an extra and an output, an extra whose leading shape
    is not its output's. (The storage dtype goes by name, so it cannot
    differ: the bfloat16 carry above qualifies.)"""
    with pytest.raises(ValueError, match=match):
        StreamingStencil(
            (8, 16, 8), {"f": 2}, 1, lambda t, e, s: {}, {"f": (2,),
                                                          "d": (2,)},
            extra_defs={"d": (2,), "g": (2,), **defs}, bx=2, by=8,
            interpret=True, in_place=names)


@interpret_only
def test_streaming_sums_keep_their_order():
    """Float sums come in the order they always did: x-blocks added in
    program order into their y-block's tile, y-blocks finished in order
    outside the kernel. Rebuilt here in numpy, bit for bit."""
    F, N, bx, by = 1, 16, 4, 8
    rng = np.random.default_rng(11)
    f = rng.standard_normal((F, N, N, N))

    def body(taps, extras, scalars):
        return {"out": taps(), "s": [jnp.sum(taps()[0])]}

    st = StreamingStencil((N, N, N), F, 1, body, {"out": (F,)},
                          dtype=jnp.float64, bx=bx, by=by,
                          sum_defs={"s": 1})
    got = np.asarray(st(jnp.asarray(f))["s"])[0]
    total = 0
    for j in range(N // by):
        tile = None
        for i in range(N // bx):
            blk = np.asarray(jnp.sum(jnp.asarray(
                f[0, i * bx:(i + 1) * bx, j * by:(j + 1) * by])))
            tile = blk if tile is None else tile + blk
        total = total + tile
    assert got == total


@interpret_only
def test_finitedifferencer_auto_fallback_odd_grid():
    """Grids with no feasible pallas blocking silently use the halo path
    (code-review regression: 12^3 / 4^3 grids with default mode)."""
    import jax
    import pystella_tpu as ps

    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    fd = ps.FiniteDifferencer(decomp, 2, 0.3, mode="pallas")
    for n in (12, 4):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((n, n, n)))
        out = np.asarray(fd.lap(x))
        ref = _numpy_lap(np.asarray(x)[None], _lap_coefs[2], 0.3)[0]
        assert out.shape == (n, n, n)
        assert np.max(np.abs(out - ref)) / np.max(np.abs(ref)) < 1e-12


@interpret_only
def test_finitedifferencer_pallas_sharded_x():
    """x-sharded lattice through the pallas x_halo path (code-review
    regression: out_specs axis count)."""
    import jax
    import pystella_tpu as ps

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    decomp = ps.DomainDecomposition((2, 1, 1), devices=jax.devices()[:2])
    fd = ps.FiniteDifferencer(decomp, 2, 0.3, mode="pallas")
    rng = np.random.default_rng(1)
    xh = rng.standard_normal((2, 16, 16, 16))
    x = decomp.shard(xh)
    out = np.asarray(fd.lap(x))
    ref = _numpy_lap(xh, _lap_coefs[2], 0.3)
    assert np.max(np.abs(out - ref)) / np.max(np.abs(ref)) < 1e-12
    g = np.asarray(fd.grad(x))
    assert g.shape == (2, 3, 16, 16, 16)


@interpret_only
@pytest.mark.parametrize("proc", [(1, 2, 1), (2, 2, 1)])
def test_finitedifferencer_pallas_sharded_2d(proc):
    """y- and xy-sharded lattices through the pallas y_halo path (the
    fused steppers' 2-D window machinery, reused by the FD operators)."""
    import jax
    import pystella_tpu as ps

    ndev = proc[0] * proc[1]
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    decomp = ps.DomainDecomposition(proc, devices=jax.devices()[:ndev])
    fd = ps.FiniteDifferencer(decomp, 2, 0.3, mode="pallas")
    rng = np.random.default_rng(2)
    xh = rng.standard_normal((2, 16, 16, 16))
    x = decomp.shard(xh)
    out = np.asarray(fd.lap(x))
    ref = _numpy_lap(xh, _lap_coefs[2], 0.3)
    assert np.max(np.abs(out - ref)) / np.max(np.abs(ref)) < 1e-12
    g = np.asarray(fd.grad(x))
    assert g.shape == (2, 3, 16, 16, 16)


@interpret_only
@pytest.mark.parametrize("shape", [(16, 16, 16), (8, 24, 12),
                                   (32, 32, 64)])
def test_resident_lap_matches_numpy(shape):
    """Whole-lattice-resident kernels (all-roll taps, no windows) match
    numpy on lattices the streaming kernels cannot compile for
    (Z % 128 != 0 — the wave-64^3-class small-lattice regime)."""
    from pystella_tpu.ops.pallas_stencil import ResidentStencil

    F, h = 2, 2
    dx = 0.37
    coefs = _lap_coefs[h]
    rng = np.random.default_rng(7)
    f = jnp.asarray(rng.standard_normal((F,) + shape))

    st = ResidentStencil(shape, F, h, _lap_body(coefs, dx),
                         {"lap": (F,)}, dtype=jnp.float64)
    out = np.asarray(st(f)["lap"])
    ref = _numpy_lap(np.asarray(f), coefs, dx)
    assert np.max(np.abs(out - ref)) / np.max(np.abs(ref)) < 1e-12


@interpret_only
def test_resident_extras_scalars_sums():
    """Extras, SMEM scalars, and lattice-sum outputs on the resident
    kernel (the energy-emitting fused-stage contract)."""
    from pystella_tpu.ops.pallas_stencil import ResidentStencil

    F, N = 2, 12
    rng = np.random.default_rng(8)
    f = jnp.asarray(rng.standard_normal((F, N, N, N)))
    g = jnp.asarray(rng.standard_normal((F, N, N, N)))

    def body(taps, extras, scalars):
        v = taps() * scalars["alpha"] + extras["g"]
        return {"out": v,
                "sums": [jnp.sum(v[i] * v[i]) for i in range(F)]}

    st = ResidentStencil((N, N, N), F, 1, body, {"out": (F,)},
                         extra_defs={"g": (F,)}, scalar_names=("alpha",),
                         dtype=jnp.float64, sum_defs={"sums": F})
    res = st(f, scalars={"alpha": 1.5}, extras={"g": g})
    ref = 1.5 * np.asarray(f) + np.asarray(g)
    assert np.allclose(np.asarray(res["out"]), ref)
    assert np.allclose(np.asarray(res["sums"]),
                       (ref * ref).sum(axis=(1, 2, 3)))


def test_resident_budget_guard():
    """Over-budget lattices are rejected with a clear error (callers fall
    back to the streaming or halo tiers)."""
    from pystella_tpu.ops.pallas_stencil import ResidentStencil

    with pytest.raises(ValueError, match="VMEM"):
        ResidentStencil((256, 256, 256), 4, 2,
                        lambda t, e, s: {"out": t()}, {"out": (4,)},
                        dtype=jnp.float32)


@interpret_only
def test_finitedifferencer_resident_small_z():
    """FiniteDifferencer's pallas tier serves Z < 128 lattices through
    the resident kernel (VERDICT r3 #4: the 64^3 cliff) — grad and lap
    agree with the halo path."""
    import pystella_tpu as ps

    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    fd = ps.FiniteDifferencer(decomp, 2, 0.3, mode="pallas")
    fd_ref = ps.FiniteDifferencer(decomp, 2, 0.3, mode="halo")
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 64, 64, 64)))
    for name in ("lap", "grad"):
        got = np.asarray(getattr(fd, name)(x))
        ref = np.asarray(getattr(fd_ref, name)(x))
        assert np.max(np.abs(got - ref)) < 1e-11, name
