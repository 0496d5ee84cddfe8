"""Pallas-TPU *lowering* regression tests — run on CPU, no device.

An interpret-mode pass says nothing about Mosaic acceptance: the
sum-output block spec once compiled fine interpreted and was rejected on
the TPU by the Pallas TPU lowering ("last two dimensions of your block
shape must be divisible by (8, 128) or equal the array's"). That check —
and the rest of the op-support surface of the Pallas TPU lowering — runs
CLIENT-side at trace/lower time, so ``jax.jit(f).trace(x).lower(
lowering_platforms=("tpu",))`` exercises it from a CPU host with no
chip. These tests lower every kernel family for TPU, so a client-side
rejection is caught before chip time is spent.

(What this cannot catch: Mosaic/XLA *compile* failures on the device
side — scoped-VMEM overflows, HBM OOM. Those budgets are gated in Python
and validated on the chip by chip_smoke.py and the benchmark.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pystella_tpu as ps
from pystella_tpu.ops.pallas_stencil import (
    LANE, ResidentStencil, StreamingStencil)


def lower_tpu(fn, *args):
    """Lower ``fn(*args)`` for the TPU platform (no execution)."""
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def _lap_body(taps, extras, scalars):
    fv = taps()
    lap = -6.0 * fv
    for d in range(3):
        for s in (-1, 1):
            off = [0, 0, 0]
            off[d] = s
            lap = lap + taps(*off)
    return {"lap": lap}


def test_streaming_ring_lowers():
    st = StreamingStencil((16, 16, LANE), 1, 1, _lap_body, {"lap": (1,)},
                          dtype=jnp.float32, bx=4, by=8, interpret=False)
    f = jnp.zeros((1, 16, 16, LANE), jnp.float32)
    lower_tpu(lambda x: st(x), f)


def _has_aligned_dynamic_offset(st, *args):
    """Does the kernel hold a ``multiple_of``: the declaration Mosaic
    needs for a dynamic sublane DMA offset (the kernel travels in the
    lowered text as bytecode, so the jaxpr is asked)?"""
    return "multiple_of" in str(jax.make_jaxpr(lambda *a: st(*a))(*args))


def test_streaming_sums_lower():
    """The per-y-block sum-accumulator tile (the block shape the first
    hardware session rejected, pre-fix) on a 2-D grid with middle
    y-blocks: their window offset ``j * by - HY`` is dynamic and has to
    reach Mosaic declared 8-aligned."""
    def body(taps, extras, scalars):
        fv = taps()
        out = _lap_body(taps, extras, scalars)
        out["sums"] = ([jnp.sum(fv[i] * fv[i]) for i in range(2)]
                       + [jnp.sum(out["lap"][0])])
        return out

    st = StreamingStencil((16, 32, LANE), 2, 1, body, {"lap": (2,)},
                          dtype=jnp.float32, bx=4, by=8,
                          sum_defs={"sums": 3}, interpret=False)
    assert st.grid == (4, 4)
    f = jnp.zeros((2, 16, 32, LANE), jnp.float32)
    lowered = lower_tpu(lambda x: st(x), f)
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert _has_aligned_dynamic_offset(st, f)


@pytest.mark.parametrize("mode", ["x", "y", "slab-x", "slab-y", "slab-xy",
                                  "inset"])
def test_streaming_halo_variants_lower(mode):
    """The halo variants on a 2-D grid with ``nby > 2``: with ``y_halo``
    every y-block's window is one piece at the dynamic ``j * by``; the
    slab-fed kernels stream the unpadded shard (the middle y-blocks'
    piece at the dynamic ``j * by - HY``) and take slab operands; the
    inset kernel (the overlap split's interior) streams it too, over a
    grid two x-blocks short."""
    from pystella_tpu.ops.pallas_stencil import HY
    h = 1
    slab = mode.startswith("slab-")
    xs, ys = "x" in mode[-2:], "y" in mode[-2:]
    st = StreamingStencil(
        (16, 32, LANE), 1, h, _lap_body, {"lap": (1,)},
        dtype=jnp.float32, bx=4, by=8, interpret=False,
        x_halo=(mode == "x"), y_halo=(mode == "y"),
        x_slab=slab and xs, y_slab=slab and ys, x_inset=mode == "inset")
    if mode == "inset":
        assert (st.grid, st.halo) == ((4, 2), ("inset", "wrap"))
    else:
        assert st.grid == (4, 4)
        assert st.halo == tuple(
            ("slab" if slab else "padded") if on else "wrap"
            for on in (xs, ys))
    shape = ((1, 16, 32, LANE) if slab or mode == "inset"
             else (1, 16 + 2 * h, 32, LANE) if mode == "x"
             else (1, 16, 32 + 16, LANE))
    x = jnp.zeros(shape, jnp.float32)
    slabs = [{}]
    if slab and xs:
        slabs[0]["x"] = (jnp.zeros((1, h, 32, LANE), jnp.float32),) * 2
    if slab and ys:
        slabs[0]["y"] = (jnp.zeros((1, 16, HY, LANE), jnp.float32),) * 2
    def call(x, slabs):
        return st(x, slabs=slabs)

    lowered = lower_tpu(call, x, slabs)
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert "multiple_of" in str(jax.make_jaxpr(call)(x, slabs))


def test_resident_rolls_lower():
    st = ResidentStencil((16, 16, 64), 1, 1, _lap_body, {"lap": (1,)},
                         dtype=jnp.float32, interpret=False)
    f = jnp.zeros((1, 16, 16, 64), jnp.float32)
    lower_tpu(lambda x: st(x), f)


def _preheat_stepper(grid_shape, cls=None, interpret=False, **kw):
    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])

    def potential(f):
        return 0.5 * 1.2e-2 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    sector = ps.ScalarSector(2, potential=potential)
    dx = (5.0 / grid_shape[0],) * 3
    if cls is None:
        return ps.FusedScalarStepper(
            sector, decomp, grid_shape, dx, 2, dtype=jnp.float32,
            dt=np.float32(0.01), interpret=interpret, **kw), decomp
    gw = ps.TensorPerturbationSector([sector])
    return ps.FusedPreheatStepper(
        sector, gw, decomp, grid_shape, dx, 2, dtype=jnp.float32,
        dt=np.float32(0.01), interpret=interpret, **kw), decomp


def _scalar_state(grid_shape, rng):
    return {
        "f": jnp.asarray(
            0.1 * rng.standard_normal((2,) + grid_shape), jnp.float32),
        "dfdt": jnp.asarray(
            0.01 * rng.standard_normal((2,) + grid_shape), jnp.float32),
    }


def test_fused_pair_step_lowers():
    grid_shape = (16, 16, LANE)
    stepper, _ = _preheat_stepper(grid_shape)
    state = _scalar_state(grid_shape, np.random.default_rng(1))
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    lower_tpu(lambda st: stepper.step(st, 0.0, stepper.dt, args), state)


def test_fused_pair_step_is_one_call_per_kernel():
    """A kernel writes its blocks where the output lives: the step
    program of a pair step over four y-blocks holds one custom call per
    kernel call (two stage pairs and the odd fifth stage; it was one
    per y-slab) and no op that puts y-slabs together into a lattice
    array."""
    import re
    grid_shape = (16, 32, LANE)
    stepper, _ = _preheat_stepper(grid_shape, bx=4, by=8, pair_bx=4,
                                  pair_by=8)
    assert stepper._pair_st.grid == (4, 4)
    state = _scalar_state(grid_shape, np.random.default_rng(1))
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    text = lower_tpu(
        lambda st: stepper.step(st, 0.0, stepper.dt, args),
        state).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 3
    lattice = "x".join(str(n) for n in grid_shape) + "xf32>"
    joins = [line for line in text.splitlines()
             if re.search(r"stablehlo\.(dynamic_update_slice|concatenate)",
                          line)
             and line.rstrip().endswith(lattice)]
    assert not joins, joins[:3]


def test_coupled_pair_chunk_lowers():
    """The energy-coupled deferred-drag pair path (esums kernels) — the
    config that failed Mosaic in the first round-5 hardware session."""
    grid_shape = (16, 16, LANE)
    stepper, _ = _preheat_stepper(grid_shape)
    state = _scalar_state(grid_shape, np.random.default_rng(2))
    assert stepper._ensure_coupled_pair_calls() is not None
    stepper._ensure_energy_call()

    def chunk(st):
        return stepper._coupled_pair_impl(
            st, t=0.0, dt=stepper.dt, a=jnp.float32(1.0),
            adot=jnp.float32(0.1), nsteps=2,
            grid_size=float(np.prod(grid_shape)), mpl=1.0)

    lower_tpu(chunk, state)


def test_gw_bf16_carry_lowers():
    """The 512^3-fits-one-chip GW configuration in miniature: bf16
    carries."""
    grid_shape = (16, 16, LANE)
    stepper, _ = _preheat_stepper(grid_shape, cls="gw",
                                  carry_dtype=jnp.bfloat16)
    rng = np.random.default_rng(3)
    state = _scalar_state(grid_shape, rng)
    state["hij"] = jnp.zeros((6,) + grid_shape, jnp.float32)
    state["dhijdt"] = jnp.zeros((6,) + grid_shape, jnp.float32)
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    lower_tpu(lambda st: stepper.step(st, 0.0, stepper.dt, args), state)


def test_multigrid_smoother_lowers():
    from pystella_tpu.multigrid import NewtonIterator

    decomp = ps.DomainDecomposition((1, 1, 1), devices=jax.devices()[:1])
    f_sym = ps.Field("f")
    problems = {f_sym: (ps.Field("lap_f") - f_sym + f_sym**3,
                        ps.Field("rho"))}
    solver = NewtonIterator(decomp, problems, halo_shape=1, omega=2 / 3,
                            dtype=np.float32)
    n = 16
    lvl_grid = (n, n, LANE)
    levels = type("L", (), {})  # placeholder; use the solver's API below
    from pystella_tpu.multigrid import FullApproximationScheme
    mg = FullApproximationScheme(solver=solver, halo_shape=1)
    lvls = mg._make_levels(decomp, lvl_grid, 1.0 / n, 1)
    aux_struct = solver._aux_struct({})
    fn = solver._pallas_level("smooth", lvls[0], decomp, jnp.float32,
                              aux_struct)
    if fn is None:
        pytest.skip("level does not admit the pallas smoother tier")
    fstack = jnp.zeros((1,) + lvl_grid, jnp.float32)
    # _pallas_level caches a jitted program from the unknowns' and the
    # sources' (nf, X, Y, Z) stacks to a stack; trace it for TPU
    lower_tpu(lambda a, b: fn(a, b, (), jnp.int32(2)), fstack, fstack)


def test_chunk4_multi_step_lowers():
    """The depth-4 whole-RK-chunk kernel (on no default path): window
    halo 2h, four composed stages per HBM pass."""
    grid_shape = (16, 16, LANE)
    stepper, _ = _preheat_stepper(grid_shape, chunk_stages=4)
    assert stepper._chunk_call is not None
    state = _scalar_state(grid_shape, np.random.default_rng(4))
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    lower_tpu(lambda st: stepper._multi_step_impl(
        st, 2, 0.0, stepper.dt, args, {}), state)


def test_x_sharded_overlap_step_lowers(make_decomp):
    """An x-sharded (2,1,1) step: the interior + x-shell launches of
    ``OverlapStreamingStencil`` inside ``shard_map``."""
    decomp = make_decomp((2, 1, 1))
    grid_shape = (16, 16, LANE)

    def potential(f):
        return 0.5 * 1.2e-2 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2

    stepper = ps.FusedScalarStepper(
        ps.ScalarSector(2, potential=potential), decomp, grid_shape,
        (5.0 / 16,) * 3, 2, dtype=jnp.float32, dt=np.float32(0.01),
        interpret=False, overlap=True)
    rng = np.random.default_rng(5)
    state = {k: decomp.shard(
        (0.1 * rng.standard_normal((2,) + grid_shape)).astype(np.float32))
        for k in ("f", "dfdt")}
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    lowered = lower_tpu(
        lambda st: stepper.step(st, 0.0, stepper.dt, args), state)
    # the split really is in the program, not the padded fallback
    assert "halo_overlap_interior" in lowered.as_text(debug_info=True)


#: the binning programs of the two output cells: (num_bins, outer,
#: local lattice, weighted) of coupled-run's spectra (two scalars in
#: k-space), its histogram (counts of rho) and -gws' GW spectrum
BINNING_SHAPES = [
    pytest.param(444, (2,), (512, 512, 257), True, id="spectra-2x512x512x257"),
    pytest.param(1000, (), (512, 512, 512), False, id="histogram-512^3"),
    pytest.param(334, (6,), (384, 384, 193), True, id="gw-6x384x384x193")]


@pytest.mark.parametrize("num_bins,outer,lattice,weighted", BINNING_SHAPES)
def test_bincount_kernel_lowers(num_bins, outer, lattice, weighted):
    """The one-hot contraction behind every histogram and spectrum, at
    the cells' shapes: one Mosaic call (an NT ``dot_general`` on
    bfloat16 one-hots, a ragged last block masked in the kernel), with
    x64 on as the suite runs (its grid indices must stay i32)."""
    from pystella_tpu.ops.histogram import _onehot_bincount
    nouter = int(np.prod(outer, dtype=np.int64))
    n = int(np.prod(lattice))
    b = jax.ShapeDtypeStruct((nouter, n), jnp.int32)
    w = jax.ShapeDtypeStruct((nouter, n), jnp.float32)
    lowered = lower_tpu(
        lambda *args: _onehot_bincount(
            args[0], args[1] if weighted else None, num_bins, False),
        *((b, w) if weighted else (b,)))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert "pallas_bincount" in lowered.as_text(debug_info=True)
    out, = lowered.out_info if isinstance(lowered.out_info, tuple) else (
        lowered.out_info,)
    assert out.shape[1] == nouter * num_bins
    assert out.dtype == (jnp.float32 if weighted else jnp.int32)
