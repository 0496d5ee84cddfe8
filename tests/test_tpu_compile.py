"""Compile every main-path kernel family FOR the chip, without the chip.

``tests/test_tpu_lowering.py`` stops at the client-side Pallas lowering;
what the device side of the compiler rejects — a vector layout Mosaic
cannot realize, a scoped-VMEM or HBM overflow — used to be learned only
on the chip. libtpu can compile against a *description* of a TPU slice
(``jax.experimental.topologies``: a compile-only client, no device), so
these tests take the real programs through Mosaic and XLA:TPU for a
v5e 2x2 host: on the (2, 2, 1) mesh at the real 512**3, on one chip at
512 x 128 x 512 (the same kernels and blockings over a quarter of the
y-slabs; the chip smoke runs the full size). They prove a program
compiles and fits HBM; they say nothing about what it computes or how
fast (``chip_smoke.py`` and the benchmark do, on the chip). Tier-1 runs
the coupled chunk on the mesh — every energy-emitting kernel, with x-
and y-halo windows; the rest is ``slow``-marked: run the whole file
(``-m "slow or not slow"``, under a minute) after touching a kernel.

Found here first (PR 21): the coupled pair kernel's ``(F,)`` energy sums
— "Mosaic failed to compile TPU kernel: Invalid output layout" on a
multi-axis ``vector.multi_reduction`` to a 1-D vector.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import pystella_tpu as ps

#: (mesh, lattice) pairs — see the module docstring
ONE_CHIP = pytest.param((1, 1, 1), (512, 128, 512),
                        marks=pytest.mark.slow)
MESH = ((2, 2, 1), (512, 512, 512))
#: the four-chip cell's own lattice (``preheat-mesh4-f32``): 512**3 a chip
MESH_CELL = pytest.param((2, 2, 1), (1024, 1024, 512),
                         marks=pytest.mark.slow)


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a compile-only v5e 2x2 topology."""
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to test
        pytest.skip(f"no compile-only TPU topology: {type(e).__name__}: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def no_x64():
    """The chip runs without 64-bit mode (the suite turns it on): under
    it the kernels' grid indices trace as i64, which Mosaic refuses."""
    with jax.enable_x64(False):
        yield


def compile_tpu(fn, *args, donate=()):
    """XLA:TPU + Mosaic compile of ``fn`` for the devices ``args``'
    shardings name. A program over the chip's HBM fails here too
    ("Ran out of memory in memory space hbm"), so passing means it
    fits. Returns the compiled executable (its ``as_text()`` is the
    optimized HLO a TPU trace names its rows from)."""
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


def _preheat(devices, proc_shape, grid, donate=True, h=2):
    """The flagship model as the example builds it, abstract state."""
    ndev = int(np.prod(proc_shape))
    decomp = ps.DomainDecomposition(proc_shape, devices=devices[:ndev])
    mphi, gsq = 1.20e-6, 2.5e-7

    def potential(f):
        return (mphi**2 / 2 * f[0]**2
                + gsq / 2 * f[0]**2 * f[1]**2) / mphi**2

    dx = tuple(5.0 / n for n in grid)
    stepper = ps.FusedScalarStepper(
        ps.ScalarSector(2, potential=potential), decomp, grid, dx, h,
        dtype=jnp.float32, dt=np.float32(0.1 * min(dx)), donate=donate,
        interpret=False)
    state = {k: jax.ShapeDtypeStruct((2,) + grid, jnp.float32,
                                     sharding=decomp.sharding(1))
             for k in ("f", "dfdt")}
    scalar = jax.ShapeDtypeStruct(
        (), jnp.float32, sharding=NamedSharding(decomp.mesh, P()))
    return stepper, state, scalar


#: compiled HLO text of the coupled chunk per (mesh, lattice): compiled
#: once, read by the compile test and by the name test
_COUPLED_HLO = {}
#: the coupled pairs' ``block_choice`` events of that build, same key
_COUPLED_BLOCKS = {}


def _coupled_chunk_hlo(v5e, proc_shape, grid, h=2, nsteps=1):
    from test_kernel_choice import _watch_events
    key = (proc_shape, grid, h, nsteps)
    if key not in _COUPLED_HLO:
        with _watch_events() as seen:
            stepper, state, scalar = _preheat(v5e, proc_shape, grid, h=h)
            assert stepper._ensure_coupled_pair_calls() is not None
            stepper._ensure_energy_call()
        _COUPLED_BLOCKS[key] = [d for d in seen.of("block_choice")
                                if d["kernel"] == "coupled_pair"]

        def chunk(st, a, adot):
            return stepper._coupled_pair_impl(
                st, t=0.0, dt=stepper.dt, a=a, adot=adot, nsteps=nsteps,
                grid_size=float(np.prod(grid)), mpl=1.0)

        _COUPLED_HLO[key] = compile_tpu(
            chunk, state, scalar, scalar, donate=0).as_text()
    return _COUPLED_HLO[key]


@pytest.mark.parametrize("grid", [
    (512, 128, 512),
    pytest.param((512, 512, 512), marks=pytest.mark.slow)],
    ids=["512x128x512", "512x512x512"])
def test_h4_coupled_chunk_compiles(v5e, grid):
    """``preheat-h4-f32``'s four-step coupled chunk (``--halo-shape 4
    --chunk-steps 4``): ten deferred-drag pair kernels with 25-tap
    Laplacians at ``bx = 4``, the smallest x block a radius of 4 takes,
    and half the y block of the h = 2 cells. Mosaic's VMEM account is
    the question (the model's "three window-sized temporaries a stage"
    was calibrated on 13-tap bodies): a refusal shows here, off the
    chip. At 512^3 the blocks are the cell's own
    (``test_kernel_choice.py``'s rows); at Y = 128 the same kernels
    over a quarter of the y-slabs."""
    import re
    from test_kernel_choice import _CELL_KERNELS
    hlo = _coupled_chunk_hlo(v5e, (1, 1, 1), grid, h=4, nsteps=4)
    names = _custom_call_names(hlo)
    kinds = [re.sub(r"\.\d+$", "", n) for n in names]
    assert kinds.count("pallas_stencil_coupled_pair") == 10, kinds
    assert set(kinds) == {"pallas_stencil_coupled_pair"}, kinds
    built = _COUPLED_BLOCKS[((1, 1, 1), grid, 4, 4)]
    assert [(d["h"], d["taps"]) for d in built] == [(4, 50)] * 2
    assert {d["bx"] for d in built} == {4}
    if grid == (512, 512, 512):
        recorded = [blocks for c, kernel, _, blocks, *_ in _CELL_KERNELS
                    if (c, kernel) == ("preheat-h4-f32", "coupled_pair")]
        assert [(d["bx"], d["by"]) for d in built] == recorded


@pytest.mark.parametrize("proc_shape,grid", [ONE_CHIP, MESH, MESH_CELL])
def test_coupled_chunk_compiles(v5e, proc_shape, grid):
    """One coupled step = two deferred-drag pair kernels (normal-in and
    deferred-in variants) + the single-stage energy kernel for the odd
    fifth stage: every energy-emitting kernel of the main path, with the
    in-trace Friedmann integration between them. On the mesh the
    kernels are slab-fed (the shards as the window operands, one low and
    one high ``h``-row x slab and ``HY``-row y slab beside them), and no
    operand of any of them is a padded copy of a window."""
    import re
    from test_kernel_choice import _CELL_KERNELS
    hlo = _coupled_chunk_hlo(v5e, proc_shape, grid)
    assert hlo
    # what Mosaic took is the blocking the cells build
    # (``test_kernel_choice.py``'s rows): a budget whose choice the
    # compiler refuses at a cell's size fails here, off the chip
    config = ("preheat-512-f32" if proc_shape == (1, 1, 1)
              else "preheat-mesh4-f32")
    recorded = [blocks for c, kernel, _, blocks, *_ in _CELL_KERNELS
                if (c, kernel) == (config, "coupled_pair")]
    built = _COUPLED_BLOCKS[(proc_shape, grid, 2, 1)]
    assert len(recorded) == 2
    assert [(d["bx"], d["by"]) for d in built] == recorded
    assert {d["source"] for d in built} == {"heuristic"}
    if proc_shape == (1, 1, 1):
        return
    local = tuple(n // p for n, p in zip(grid, proc_shape))
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    padded = f"[2,{local[0] + 4},{local[1] + 16},{local[2]}]"
    # a kernel's windows share their slabs: C is their components, 2 each
    x_slab = re.compile(rf"\[\d+,2,{local[1]},{local[2]}\]")
    y_slab = re.compile(rf"\[\d+,{local[0]},8,{local[2]}\]")
    for ln in calls:
        operands = ln.split(" custom-call(", 1)[1]
        assert padded not in operands, ln[:200]
        assert len(x_slab.findall(operands)) == 2, ln[:200]
        assert len(y_slab.findall(operands)) == 2, ln[:200]


def _computations(hlo):
    """Optimized HLO text by computation: name -> its instructions'
    lines; the entry computation also under ``"ENTRY"``."""
    import re
    computations = {}
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%([\w.]+) \(.*\{$", line)
        if head:
            lines = computations[head.group(2)] = []
            if head.group(1):
                computations["ENTRY"] = lines
        elif line.startswith("  "):
            lines.append(line)
    return computations


def _custom_call_names(hlo):
    """Names of the Mosaic custom calls in optimized HLO text."""
    import re
    return re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)


def test_coupled_chunk_kernels_are_named_by_kind(v5e):
    """What a TPU trace will call the chunk's kernels: every Mosaic
    custom call of the compiled (2,2,1) chunk is named after its kind
    (``%pallas_stencil_coupled_pair.N``, ``%pallas_stencil_energy.N``),
    none bare ``%pallas_stencil.N`` or ``%tpu_custom_call.N`` — so
    ``benchmark/kernels/pallas_stencil.json`` still matches each and the
    breakdown splits by kind."""
    import re
    names = _custom_call_names(_coupled_chunk_hlo(v5e, *MESH))
    assert names
    kinds = {re.sub(r"\.\d+$", "", n) for n in names}
    assert kinds == {"pallas_stencil_coupled_pair",
                     "pallas_stencil_energy"}, sorted(kinds)


#: the slab cell's own lattice (``preheat-mesh4x-f32``): 512**3 a chip
#: on ``(4, 1, 1)``, the one mesh on which the overlap split runs
SLAB = ((4, 1, 1), (2048, 512, 512))
#: compiled ``multi_step(4)`` of that cell per ``overlap``: HLO text,
#: the compiler's memory account, the ``overlap_plan`` events
_SLAB_CHUNK = {}


def _slab_chunk(v5e, monkeypatch, overlap):
    """``jit_multi_step_4`` of ``FusedScalarStepper(donate=True)`` on the
    slab mesh, as the ``fixed_bg`` loop body dispatches it; ``overlap``
    ``None`` is the run's own default (the suite pins it off in the
    environment, so the variable is taken away)."""
    from test_kernel_choice import _watch_events
    if overlap not in _SLAB_CHUNK:
        monkeypatch.delenv("PYSTELLA_HALO_OVERLAP", raising=False)
        proc_shape, grid = SLAB
        decomp = ps.DomainDecomposition(proc_shape, devices=v5e[:4])
        mphi, gsq = 1.20e-6, 2.5e-7

        def potential(f):
            return (mphi**2 / 2 * f[0]**2
                    + gsq / 2 * f[0]**2 * f[1]**2) / mphi**2

        dx = (5.0 / 512,) * 3
        with _watch_events() as seen:
            stepper = ps.FusedScalarStepper(
                ps.ScalarSector(2, potential=potential), decomp, grid, dx,
                2, dtype=jnp.float32, dt=np.float32(0.1 * dx[0]),
                donate=True, interpret=False, overlap=overlap)
        state = {k: jax.ShapeDtypeStruct((2,) + grid, jnp.float32,
                                         sharding=decomp.sharding(1))
                 for k in ("f", "dfdt")}
        compiled = stepper._multi_jit(4).trace(
            state, t=np.float32(0.0), dt=stepper.dt,
            rhs_args={"a": np.float32(1.0), "hubble": np.float32(0.5)},
            rhs_seq={}).lower(lowering_platforms=("tpu",)).compile()
        mem = compiled.memory_analysis()
        _SLAB_CHUNK[overlap] = (
            compiled.as_text(),
            (mem.argument_size_in_bytes, mem.temp_size_in_bytes),
            {d["kernel"]: d for d in seen.of("overlap_plan")})
    return _SLAB_CHUNK[overlap]


@pytest.mark.parametrize("overlap", [
    None, pytest.param(False, marks=pytest.mark.slow)],
    ids=["split", "single"])
def test_slab_chunk_compiles_and_what_it_holds(v5e, monkeypatch, overlap):
    """``preheat-mesh4x-f32.fixed-bg``'s 4-step chunk at the cell's own
    lattice, through Mosaic and XLA:TPU for the v5e, on both paths: the
    default (``auto`` is ON for a sharded mesh, so the ten pair calls
    are thirty launches: the interior, the ring kernel over the shard
    with its grid inset to rows 2 ... 510, ``bx`` 2, and two pre-padded
    shells at (2, 512, 512), ``bx = h``, under the 100-MB VMEM limit)
    and ``overlap=False`` (ten slab-fed single launches). Neither had
    been compiled for the chip before PR 42. Kept: the compiler's
    account of a chip's share. The state is 2.15 GB of arguments on
    both; the temporaries are 7.61 GB with the split and 10.74 GB
    without it. Re-recorded in PR 43: before it the interior was a
    pre-padded kernel of lattice (508, 512, 512), XLA materialised
    each output's three pieces and their concatenation
    (``pad_maximum_fusion``, four lattice arrays a pair call, 29 a
    chunk) and the extra sliced to the interior's rows, and the
    temporaries were 11.67 GB. Now nothing the size of the lattice
    stands between the launches: the shells' two rows go into the
    interior's outputs by ``dynamic-update-slice`` fusions over those
    rows alone (two an output that is read again: 76), and the only
    ``pad`` / ``maximum`` fusions left make the shells' six-row
    inputs."""
    import re
    hlo, (arguments, temporaries), plans = _slab_chunk(v5e, monkeypatch,
                                                       overlap)
    assert hlo.startswith("HloModule jit_multi_step_4"), hlo[:60]
    kinds = [re.sub(r"\.\d+$", "", n) for n in _custom_call_names(hlo)]
    one = 2 * 512**3 * 4            # one two-field array on a chip
    assert arguments == pytest.approx(2 * one, rel=1e-3)
    # every instruction of the entry computation that makes an array
    # the size of a chip's lattice, but the kernels: name and opcode
    made = re.findall(r"^  (?:ROOT )?%(\S+) = f32\[2,512,512,512\]\S* "
                      r"(?!custom-call|get-tuple-element|parameter)"
                      r"([\w-]+)\(", hlo[hlo.index("\nENTRY "):], re.M)
    if overlap is None:
        assert plans["pair"]["path"] == "split"
        assert plans["pair"]["interior"]["lattice"] == [508, 512, 512]
        assert (plans["pair"]["interior"]["bx"],
                plans["pair"]["shell"]["bx"]) == (2, 2)
        assert plans["pair"]["interior"]["by"] == 128
        # the ring reads every window row once, with its y halos: what
        # the single launch moves (the pre-padded interior: 3 x 1.125)
        assert plans["pair"]["interior"]["halo"] == "inset"
        assert plans["pair"]["interior"]["reread"] == pytest.approx(
            (6 * 1.125 + 2 + 8) / 16)
        # 3 windows' 12 rows, an extra's and 4 outputs' 4 rows, of two
        # fields, read and written: 0.23 GB a call (it was 10.9)
        assert plans["pair"]["stitch"] == "in_place"
        assert plans["pair"]["stitch_bytes"] == 2 * 2 * (
            3 * 12 + 5 * 4) * 512 * 512 * 4
        assert kinds.count("pallas_stencil_pair_interior") == 10, kinds
        assert kinds.count("pallas_stencil_pair_shell") == 20, kinds
        # the parent's 11.67 GB does not rise: it falls by four arrays
        assert 7.2e9 < temporaries < 8.0e9, temporaries
        # nothing lattice-sized is placed between the launches but the
        # first step's zeroed carry and the shells' rows going in
        dus = [n for n, op in made if op == "fusion"
               and "dynamic-update-slice" in n]
        assert len(dus) == 76, len(dus)
        assert [op for n, op in made if n not in dus] == ["broadcast"], \
            sorted(set(made))[:8]
        # ... and each of those writes its two rows and no more
        rows = re.findall(
            r"dynamic-update-slice\(%\S+, %(\S+?),", hlo)
        shapes = dict(re.findall(
            r"^  %(\S+) = (f32\[[\d,]+\])\S* parameter\(1\)", hlo, re.M))
        assert len(rows) == 76
        assert {shapes[r] for r in rows} == {"f32[2,2,512,512]"}
    else:
        assert (plans["pair"]["path"], plans["pair"]["reason"]) == (
            "single", "off")
        assert kinds.count("pallas_stencil_pair") == 10, kinds
        assert 10.3e9 < temporaries < 11.2e9, temporaries
        assert [op for _, op in made] == ["broadcast"], made
    assert len(set(kinds)) == (2 if overlap is None else 1), set(kinds)
    assert arguments + temporaries < 15.75 * 2**30


def test_slab_stage_program_keeps_its_extras_in_place(v5e, monkeypatch):
    """The stage-by-stage protocol on the slab mesh (upstream's loop on
    ``-proc 4 1 1``): the donating ``stage`` program takes the split
    too, and its interior writes the three donated extras in place.
    Their edge rows still hold the old values when the shells' slices
    are taken, and XLA orders it so without keeping a copy: the
    compiler accounts 3.22 GB of the 4.29 GB of arguments as aliased
    and 0.03 GB of temporaries (3.24 GB before PR 43, when every output
    was concatenated from three pieces), and the program holds no
    ``copy``, ``pad`` or ``concatenate`` the size of the lattice."""
    import re
    from unittest import mock
    from pystella_tpu.ops import fused
    monkeypatch.delenv("PYSTELLA_HALO_OVERLAP", raising=False)
    proc_shape, grid = SLAB
    programs = {}
    instrument_jit = fused._obs_memory.instrument_jit

    def recording(fn, label=None, **kw):
        programs.setdefault(label, []).append(
            instrument_jit(fn, label=label, **kw))
        return programs[label][-1]

    with mock.patch.object(fused._obs_memory, "instrument_jit", recording):
        stepper, state, scalar = _preheat(v5e, proc_shape, grid)
    assert stepper._scalar_st.in_place == ("dfdt", "kf", "kdfdt")
    stage = programs["fused.FusedScalarStepper.stage_call_sharded"][0]
    args = [state["f"]] + [scalar] * 5 + [state["f"]] * 3
    compiled = stage._jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    kinds = sorted(re.sub(r"\.\d+$", "", n) for n in _custom_call_names(hlo))
    assert kinds == ["pallas_stencil_stage_interior",
                     "pallas_stencil_stage_shell",
                     "pallas_stencil_stage_shell"], kinds
    one = 2 * 512**3 * 4
    assert mem.argument_size_in_bytes == pytest.approx(4 * one, rel=1e-3)
    assert mem.alias_size_in_bytes == 3 * one
    assert mem.temp_size_in_bytes < 0.1e9, mem.temp_size_in_bytes
    assert not re.findall(r"= f32\[2,5\d\d,512,512\]\S* "
                          r"(?:copy|pad|concatenate|maximum)\(", hlo)


def test_slab_chunk_kernels_are_named_by_kind(v5e, monkeypatch):
    """What a TPU trace will call the slab cell's kernels: every Mosaic
    custom call of the compiled ``(4, 1, 1)`` chunk is
    ``%pallas_stencil_pair_interior.N`` or ``%pallas_stencil_pair_shell.N``,
    none a plain ``%pallas_stencil_pair.N`` (which is what a single
    launch is, and what all three were before PR 42): so
    ``benchmark/kernels/pallas_stencil_pair_interior.json`` and
    ``..._pair_shell.json`` take them before ``pallas_stencil_pair.json``
    does, and ``pair_roofline`` reads nothing in that cell."""
    import re
    hlo, _, _ = _slab_chunk(v5e, monkeypatch, None)
    names = _custom_call_names(hlo)
    assert len(names) == 30
    kinds = {re.sub(r"\.\d+$", "", n) for n in names}
    assert kinds == {"pallas_stencil_pair_interior",
                     "pallas_stencil_pair_shell"}, sorted(kinds)
    # and under the scopes a host-side profile folds them by
    for scope in ("halo_overlap_exchange", "halo_overlap_interior",
                  "halo_overlap_shells"):
        assert scope in hlo, scope


@pytest.mark.slow
def test_derivs_kernels_are_named_by_kind(v5e, monkeypatch):
    """``FiniteDifferencer``'s lap and grad (``%tpu_custom_call.N`` in
    the PR 23 traces) compile to ``%pallas_stencil_lap`` /
    ``%pallas_stencil_grad`` inside programs called ``lap`` / ``grad``:
    on one chip each eagerly dispatched y-slab call, on the mesh the
    operator's one program."""
    import re
    from pystella_tpu.ops import pallas_stencil
    # the operators take no interpret= override: build for the chip
    monkeypatch.setattr(pallas_stencil, "_is_cpu", lambda: False)
    for proc_shape, grid in (((1, 1, 1), (512, 128, 512)), MESH):
        ndev = int(np.prod(proc_shape))
        decomp = ps.DomainDecomposition(proc_shape, devices=v5e[:ndev])
        fd = ps.FiniteDifferencer(decomp, 2, tuple(5.0 / n for n in grid),
                                  mode="pallas")
        x = jax.ShapeDtypeStruct((2,) + grid, jnp.float32,
                                 sharding=decomp.sharding(1))
        for name in ("lap", "grad"):
            op = fd._pallas_op(name, 2, jnp.dtype("float32"), False, grid)
            if ndev == 1:       # the stencil itself: its one program
                fn = op._program._jitted
            else:               # a closure over the sharded program
                fn = op.__defaults__[0]._jitted
            hlo = fn.trace(x).lower(
                lowering_platforms=("tpu",)).compile().as_text()
            assert hlo.startswith("HloModule jit_" + name), hlo[:60]
            kinds = {re.sub(r"\.\d+$", "", n)
                     for n in _custom_call_names(hlo)}
            assert kinds == {"pallas_stencil_" + name}, sorted(kinds)


@pytest.mark.slow
@pytest.mark.parametrize("proc_shape,grid", [ONE_CHIP, MESH])
def test_step_and_stage_loop_compile(v5e, proc_shape, grid):
    """``step()`` (pair kernels + the odd single stage) and the
    stage-by-stage protocol the upstream-style host loop drives."""
    stepper, state, _ = _preheat(v5e, proc_shape, grid)
    args = {"a": np.float32(1.0), "hubble": np.float32(0.5)}
    compile_tpu(lambda st: stepper._step_impl(st, 0.0, stepper.dt, args),
                state)

    def stage_loop(st):
        carry = st
        for s in range(stepper.num_stages):
            carry = stepper(s, carry, 0.0, a=np.float32(1.0),
                            hubble=np.float32(0.5))
        return carry

    compile_tpu(stage_loop, state)


def _stage_programs(devices, proc_shape, grid, donate):
    """``(name, compiled)`` for the two per-stage programs of the flagship
    stepper, ``jit_FusedScalarStepper_stage0`` and ``..._stage``, with the
    arguments ``Stepper.__call__`` hands them (the host loop's float64
    background scalars)."""
    stepper, state, _ = _preheat(devices, proc_shape, grid, donate=donate)
    stepper._ensure_stage_jits()
    tail = (0.0, stepper.dt,
            {"a": np.float64(1.0), "hubble": np.float64(0.5)})
    for name, fn, args in (
            ("stage0", stepper._jit_stage0,
             stepper._split_carry((state, {}))),
            ("stage", stepper._jit_stage,
             (1,) + stepper._split_carry((state, state)))):
        compiled = fn.trace(*args, *tail).lower(
            lowering_platforms=("tpu",)).compile()
        assert compiled.as_text().startswith(
            "HloModule jit_FusedScalarStepper_" + name + ",")
        yield name, compiled


STAGE_MESHES = [((1, 1, 1), (512, 128, 512)), MESH]


@pytest.mark.parametrize("proc_shape,grid", STAGE_MESHES,
                         ids=["one-chip", "mesh"])
def test_stage_programs_are_one_kernel_in_place(v5e, proc_shape, grid):
    """A per-stage program of a ``donate=True`` stepper, as
    ``Stepper.__call__`` builds it, is its kernel and nothing else: no
    lattice-shaped ``copy``; the Mosaic call carries
    ``output_to_operand_aliasing`` for its three extras (``dfdt``, ``kf``,
    ``kdfdt``); every donated parameter's ``input_output_alias`` entry
    names the output the kernel writes over that very parameter (jax
    pairs by position, so the fresh ``f`` has to come last); ``f`` is not
    donated; and the temporaries stay under one lattice array (stage 0's
    two zero registers are outputs). It fails on the parent of PR 37,
    where all four arrays were donated to a kernel without aliases and
    XLA put ``%copy.15``-``%copy.18`` (``copy(%carry_0___f__)`` ...,
    4.29 GB at 512**3, 12.7 ms) in front of the stage kernel and
    ``%copy.9``, ``%copy.10`` in front of stage 0's, with 4.30 GB of
    temporaries."""
    import re
    local = tuple(n // p for n, p in zip(grid, proc_shape))
    array = "f32[2,{},{},{}]".format(*local)
    donated = {"stage0": 1, "stage": 3}
    for name, compiled in _stage_programs(v5e, proc_shape, grid, True):
        hlo = compiled.as_text()
        copies = re.findall(
            rf"%([\w.]+) = {re.escape(array)}\S* copy\(", hlo)
        assert not copies, (name, copies)
        entry = _computations(hlo)["ENTRY"]
        params = {m.group(1): int(m.group(2)) for m in (
            re.match(r"\s*%([\w.]+) = .* parameter\((\d+)\)", ln)
            for ln in entry) if m}
        (call,) = [ln for ln in entry if "tpu_custom_call" in ln]
        kernel = re.match(r"\s*%(pallas_stencil_stage\.\d+) = ", call)
        assert kernel, call[:120]
        operands = re.findall(
            r"%([\w.\-]+)", call.split(" custom-call(", 1)[1].split(")", 1)[0])
        written = {int(out): operands[int(op)] for out, op in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}\)",
            re.search(r"output_to_operand_aliasing=\{(.*?)\}, \w+=",
                      call).group(1))}
        assert len(written) == 3, (name, written)
        # the kernel's window operand is a parameter nobody donated
        pairs = {int(out): int(par) for out, par in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", hlo.split("\n", 1)[0])}
        assert len(pairs) == donated[name], (name, pairs)
        assert params[operands[0]] not in pairs.values(), (name, pairs)
        elements = {m.group(1): int(m.group(2)) for m in (
            re.match(r"\s*%([\w.\-]+) = .* get-tuple-element\(%"
                     + re.escape(kernel.group(1)) + r"\), index=(\d+)", ln)
            for ln in entry) if m}
        (root,) = [ln for ln in entry if ln.lstrip().startswith("ROOT ")]
        results = re.findall(r"%([\w.\-]+)", root.split(" tuple(", 1)[1])
        for out, par in pairs.items():
            over = written[elements[results[out]]]
            assert params[over] == par, (name, out, par, over)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 4 * 2 * int(np.prod(local)), (
            name, mem.temp_size_in_bytes)


def test_undonated_stage_programs_copy_and_alias_nothing(v5e):
    """Built with ``donate=False`` the same two programs hold no lattice
    ``copy`` either, and no alias: the kernel declares none (fed buffers
    its program does not own, an in-place kernel costs a copy of each)
    and the module pairs no parameter with an output."""
    import re
    proc_shape, grid = STAGE_MESHES[0]
    for name, compiled in _stage_programs(v5e, proc_shape, grid, False):
        hlo = compiled.as_text()
        assert not re.findall(r"= f32\[2,[\d,]+\]\S* copy\(", hlo), name
        assert "output_to_operand_aliasing" not in hlo, name
        assert "input_output_alias" not in hlo.split("\n", 1)[0], name


def _gw_chunk(devices, carry_dtype, nsteps, proc_shape=(1, 1, 1),
              grid=(384, 384, 384)):
    """The ``-gws`` coupled chunk of ``preheat-gw-f32`` (one chip, 384**3,
    ``donate=True``) or, on ``proc_shape`` (2, 2, 1) at (768, 768, 384),
    of ``preheat-gw-mesh4-f32``, as ``coupled_multi_step`` jits it, and
    its abstract arguments."""
    stepper_s, state, scalar = _preheat(devices, proc_shape, grid)
    decomp = stepper_s.decomp
    stepper = ps.FusedPreheatStepper(
        stepper_s.sector, ps.TensorPerturbationSector([stepper_s.sector]),
        decomp, grid, tuple(5.0 / 384 for _ in grid), 2, dtype=jnp.float32,
        dt=stepper_s.dt, donate=True, interpret=False,
        carry_dtype=carry_dtype)
    for k in ("hij", "dhijdt"):
        state[k] = jax.ShapeDtypeStruct((6,) + grid, jnp.float32,
                                        sharding=decomp.sharding(1))
    with pytest.warns(UserWarning, match="32 window components"):
        assert stepper._ensure_coupled_pair_calls() is None
    stepper._ensure_energy_call()
    fn = stepper._coupled_jit(nsteps, float(np.prod(grid)), 1.0, False)
    return fn.trace(state, t=0.0, dt=stepper.dt, a=scalar,
                    adot=scalar).lower(lowering_platforms=("tpu",))


@pytest.mark.slow
def test_gw_chunk_at_384_and_what_the_carries_take(v5e):
    """What ``benchmark/configs/preheat-gw-f32.json`` says of memory: at
    384**3 the 4-step coupled chunk of the scalar + tensor system
    compiles for one v5e chip (20 single-stage energy kernels in
    ``jit_coupled_multi_step_4``: the deferred pair finds no blocking
    there) and holds 14.5 GB with the configuration's float32 RK
    carries, 10.9 GB with bfloat16 ones (``--carry-dtype bfloat16``:
    every stage then moves a quarter fewer bytes); a chunk of one step
    with float32 carries is refused for HBM."""
    import re
    held = {}
    for carry in (jnp.bfloat16, None):
        compiled = _gw_chunk(v5e, carry, 4).compile()
        hlo = compiled.as_text()
        assert hlo.startswith("HloModule jit_coupled_multi_step_4"), hlo[:60]
        names = _custom_call_names(hlo)
        assert len(names) == 20
        assert {re.sub(r"\.\d+$", "", n) for n in names} == {
            "pallas_stencil_energy"}
        mem = compiled.memory_analysis()
        held[carry] = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 10.5e9 < held[jnp.bfloat16] < 11.5e9, held
    assert 14e9 < held[None] < 15.75 * 2**30, held
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        _gw_chunk(v5e, None, 1).compile()


def test_gw_mesh_chunk_at_384_a_chip(v5e):
    """What ``benchmark/configs/preheat-gw-mesh4-f32.json`` says of
    memory, and what keeps the cell's kernel shares readable: on the
    ``(2, 2, 1)`` mesh at (768, 768, 384), 384**3 a chip, the 4-step
    coupled ``-gws`` chunk compiles for the v5e; it is 20 single-stage
    energy kernels and no other custom call (the deferred pair finds no
    blocking at 384**3 on the mesh either), fed by exchanged slabs on
    both sharded axes (``block_choice``: ``halo`` slab, slab, with the
    bytes a call moves), on the single launch (``overlap_plan``: a
    kernel with sums keeps it); 20 ``all-reduce`` (the energy sums of a
    stage in one), no ``all-gather``; arguments and temporaries
    between 14.0 GB and the 15.75 GiB the compiler allows a chip. In
    every kernel instruction ``custom_call_target`` stands inside the
    first 1,600 characters: ``benchmark/trace_reduce.py`` keeps that
    much of an instruction's text (``NAME_CHARS``) and tells a Mosaic
    kernel by the mark, so past it ``energy_roofline``,
    ``stencil_kernel_roofline`` and ``kernel_ms_per_step`` would read
    nothing in the cell."""
    import os
    import re
    from unittest import mock
    from test_kernel_choice import _watch_events
    # under the halo-overlap policy's own default, which is what a cell
    # runs under (the suite pins the policy off in the environment)
    with _watch_events() as seen, mock.patch.dict(os.environ):
        os.environ.pop("PYSTELLA_HALO_OVERLAP", None)
        compiled = _gw_chunk(v5e, None, 4, (2, 2, 1),
                             (768, 768, 384)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_coupled_multi_step_4"), hlo[:60]
    names = _custom_call_names(hlo)
    assert len(names) == 20
    assert {re.sub(r"\.\d+$", "", n) for n in names} == {
        "pallas_stencil_energy"}
    assert len(re.findall(r"custom-call\(", hlo)) == 20
    assert len(re.findall(r" all-reduce(?:-start)?\(", hlo)) == 20
    assert "all-gather" not in hlo
    assert re.search(r" collective-permute(?:-start)?\(", hlo)
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 14.0e9 < held < 15.75 * 2**30, held
    (energy,) = [d for d in seen.of("block_choice")
                 if d["kernel"] == "energy"]
    assert energy["halo"] == ["slab", "slab"]
    # eight window components (f, hij), two rows of an x and a y face,
    # float32, there and back
    assert energy["slab_bytes"] == 2 * 2 * (2 * 384 * 384) * 8 * 4
    (plan,) = [d for d in seen.of("overlap_plan")
               if d["kernel"] == "energy"]
    assert (plan["path"], plan["reason"]) == ("single", "sums")
    kernels = [ln for ln in hlo.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(kernels) == 20
    for ln in kernels:
        assert ln.lstrip().index("custom_call_target") < 1600


#: the binning programs of the two output cells on one chip:
#: coupled-run's spectra (two scalars in k-space), its histogram (counts
#: of rho), -gws' GW spectrum; and the same two on the (2,2,1) mesh at
#: 512**3 a chip. (mesh, program name, num_bins, outer, lattice, weighted)
BINNING = [
    pytest.param((1, 1, 1), "spectra_bin", 444, (2,), (512, 512, 257), True,
                 id="spectra-2x512x512x257"),
    pytest.param((1, 1, 1), "histogram_bincount", 1000, (),
                 (512, 512, 512), False, id="histogram-512^3"),
    pytest.param((1, 1, 1), "spectra_bin", 334, (6,), (384, 384, 193), True,
                 id="gw-6x384x384x193"),
    pytest.param((2, 2, 1), "spectra_bin", 888, (2,), (1024, 1024, 257),
                 True, id="mesh-spectra-2x1024x1024x257"),
    pytest.param((2, 2, 1), "histogram_bincount", 1000, (),
                 (1024, 1024, 512), False, id="mesh-histogram")]


@pytest.mark.parametrize(
    "proc_shape,program,num_bins,outer,lattice,weighted", BINNING)
def test_bincount_programs_compile(v5e, proc_shape, program, num_bins,
                                   outer, lattice, weighted):
    """The one-hot contraction as the output branch dispatches it, at
    the cells' shapes: the program carries the owner's name and one
    Mosaic call named ``%pallas_bincount.N`` (what the benchmark's
    breakdown keys its rows by; not ``pallas_stencil*``, which a kernel
    file would claim), no collective (the partials leave each device
    unreduced), and holds nothing the size of lattice x ``Hi``: its
    temporaries are the two operands laid out in rows (none at all
    where the lattice's own rows are lane-aligned)."""
    import re
    from pystella_tpu.ops.histogram import _bincount_fn
    ndev = int(np.prod(proc_shape))
    decomp = ps.DomainDecomposition(proc_shape, devices=v5e[:ndev])
    owner = "spectra" if program == "spectra_bin" else "histogram"
    fn = _bincount_fn(decomp, outer, num_bins, weighted, owner=owner)
    operand = jax.ShapeDtypeStruct(outer + lattice, jnp.int32,
                                   sharding=decomp.sharding(len(outer)))
    args = (operand,) + ((jax.ShapeDtypeStruct(
        operand.shape, jnp.float32, sharding=operand.sharding),)
        if weighted else ())
    compiled = fn._jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_" + program), hlo[:60]
    names = _custom_call_names(hlo)
    assert [re.sub(r"\.\d+$", "", n) for n in names] == ["pallas_bincount"]
    assert "scatter" not in hlo
    assert not re.search(r"all-(gather|reduce|to-all)|collective-permute",
                         hlo)
    mem = compiled.memory_analysis()
    # operands in tiled layout (the 257- and 193-wide rows are padded to
    # whole lane tiles), relaid once each; lattice x Hi would be 4-16x
    assert mem.temp_size_in_bytes <= 2 * mem.argument_size_in_bytes
    if lattice[-1] % 128 == 0:
        assert mem.temp_size_in_bytes < 2**20


#: the levels of ``multigrid-512-f32``'s default cycle; the finest in
#: tier-1, the rest ``slow``
MG_LEVELS = [512] + [pytest.param(n, marks=pytest.mark.slow)
                     for n in (256, 128, 64, 32, 16, 8)]


def _mg_level_compiler(v5e, monkeypatch, n):
    """``compile(kind)`` for the float32 two-unknown programs of one
    level of ``multigrid-512-f32`` (``NewtonIterator._pallas_level``:
    Poisson + Helmholtz, h = 1) on one v5e chip."""
    from pystella_tpu.multigrid import NewtonIterator
    from pystella_tpu.multigrid.relax import LevelSpec
    from pystella_tpu.ops import pallas_stencil
    # the solver takes no interpret= override: build for the chip
    monkeypatch.setattr(pallas_stencil, "_is_cpu", lambda: False)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=v5e[:1])
    solver = NewtonIterator(
        decomp,
        {ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho")),
         ps.Field("f2"): (ps.Field("lap_f2") - ps.Field("f2"),
                          ps.Field("rho2"))},
        halo_shape=1, dtype=np.float32, smoother="pallas",
        fixed_parameters=dict(omega=1 / 2))
    level = LevelSpec((n,) * 3, (10.0 / n,) * 3, False)
    x = jax.ShapeDtypeStruct((2,) + (n,) * 3, jnp.float32,
                             sharding=decomp.sharding(1))
    nu = jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=NamedSharding(decomp.mesh, P()))

    def compile(kind):
        fn = solver._pallas_level(kind, level, decomp, jnp.dtype("float32"),
                                  ())
        assert fn is not None, f"{kind} at {n}^3 fell to the XLA path"
        return fn._jitted.trace(x, x, (), nu).lower(
            lowering_platforms=("tpu",)).compile()
    return compile


@pytest.mark.parametrize("n", MG_LEVELS)
def test_multigrid_level_programs_compile(v5e, monkeypatch, n):
    """What ``benchmark/configs/multigrid-512-f32.json`` needs of a chip:
    the float32 two-unknown smooth, residual and tau programs of a level
    compile for one v5e chip and fit its 15.75 GB beside the cell's four
    resident 512**3 arrays; each is one program named after the level
    (``jit_pallas_<kind>_<n>_<n>_<n>``) whose Mosaic calls are named
    after the kernel's kind where the level streams (Z a multiple of 128:
    ``%pallas_stencil_mg_<kind>.N``, what the benchmark's kernel files
    match) and ``%pallas_resident_stencil.N`` below."""
    import re
    compile = _mg_level_compiler(v5e, monkeypatch, n)
    resident = 4 * 4 * 512**3  # the seeded f, f2 and rho, rho2 of the cell
    for kind in ("smooth", "residual", "tau"):
        compiled = compile(kind)
        hlo = compiled.as_text()
        assert hlo.startswith(f"HloModule jit_pallas_{kind}_{n}_{n}_{n}"), \
            hlo[:60]
        kinds = {re.sub(r"\.\d+$", "", name)
                 for name in _custom_call_names(hlo)}
        assert kinds == {f"pallas_stencil_mg_{kind}" if n % 128 == 0
                         else "pallas_resident_stencil"}, sorted(kinds)
        mem = compiled.memory_analysis()
        held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes)
        assert held + resident < 15.75 * 2**30, held


@pytest.mark.parametrize(
    "n", [512, pytest.param(256, marks=pytest.mark.slow),
          pytest.param(128, marks=pytest.mark.slow)])
def test_multigrid_smooth_loop_copies_no_lattice(v5e, monkeypatch, n):
    """A streaming level's smooth program holds no lattice-shaped ``copy``
    inside its ``while`` body, and at most one in the whole module. The
    sweep loop's carry is the ``(2, n, n, n)`` stack of unknowns, and the
    kernel cannot write the buffer it reads: with one kernel call an
    iteration (the parent of PR 33, on which this test fails: it finds
    ``%copy.9`` in ``%wide.region_0.1.sunk``) XLA copies the carry before
    every kernel call, 2.15 GB and 3.2 ms beside a 4.84-ms kernel at
    512**3. With two calls an iteration the body is two kernel calls
    whose buffers alternate; each still takes two lattice operands
    (unknowns, sources) and gives one output, which is what the
    benchmark's byte count reads from the instruction. Since PR 44 the
    program's parameter is that stack itself and the first sweeps are
    taken out of it in front of the loop by a conditional all of whose
    branches compute: the whole module holds no lattice-shaped ``copy``.
    Since PR 52 a call of the loop is the two-sweep kernel (both stacks
    as windows: still two lattice operands and one output, under the
    same name), an iteration four sweeps, and the conditional has the
    four branches of ``nu mod 4``."""
    import re
    hlo = _mg_level_compiler(v5e, monkeypatch, n)("smooth").as_text()
    bodies = re.findall(r" while\(.*\bbody=%([\w.]+)", hlo)
    assert len(bodies) == 1, bodies
    computations = _computations(hlo)
    lattice = rf"f32\[2,{n},{n},{n}\]"
    copies = {name: [ln.split(" = ")[0].strip() for ln in lines
                     if re.search(rf"= {lattice}\S* copy\(", ln)]
              for name, lines in computations.items()}
    assert not copies[bodies[0]], (bodies[0], copies[bodies[0]])
    assert sum(map(len, copies.values())) <= 1, copies
    calls = [ln for ln in computations[bodies[0]]
             if "tpu_custom_call" in ln]
    assert len(calls) == 2, calls
    for ln in calls:
        assert re.search(r"%pallas_stencil_mg_smooth\.\d+ = "
                         rf"{lattice}\S* custom-call\(", ln), ln[:200]
        operands = re.search(
            r"operand_layout_constraints=\{(.*?)\}, \w+=", ln).group(1)
        assert re.findall(r"f32\[[\d,]*\]", operands) \
            == [f"f32[2,{n},{n},{n}]"] * 2, operands


@pytest.mark.parametrize("kind", ["residual", "smooth"])
def test_multigrid_level_program_writes_no_lattice_but_its_kernels(
        v5e, monkeypatch, kind):
    """The level-0 residual and smooth programs of ``multigrid-512-f32``
    take the kernels' own ``(2, 512, 512, 512)`` stacks and return one:
    nothing in the compiled v5e module writes an array of the lattice's
    size but the Mosaic calls, round them only the plumbing of the
    ``cond`` and the ``while`` (parameters, tuples and their elements).
    On PR 44's parent, whose programs took the unknowns and sources by
    name, this fails on ``%pad_maximum_fusion`` (the two stacks, twice
    2.15 GB of traffic) and ``%slice_bitcast_fusion`` (the unstack):
    47 ms a cycle round a 24-ms residual kernel, five times a cycle."""
    import re
    hlo = _mg_level_compiler(v5e, monkeypatch, 512)(kind).as_text()
    plumbing = {"parameter", "tuple", "get-tuple-element", "while",
                "conditional", "bitcast"}
    written = set()  # (the entry computation is listed twice)
    for lines in _computations(hlo).values():
        for ln in lines:
            m = re.match(
                r"\s*(?:ROOT )?%([\w.\-]+) = (\(?[^=]*?\)?) ([\w\-]+)\(", ln)
            if m and re.search(r"f32\[(2,)?512,512,512\]", m.group(2)):
                written.add((m.group(1), m.group(3)))
    kernels = [name for name, op in written if op == "custom-call"]
    # a smooth: the four branches of nu mod 4 (two calls, one, one, two)
    # and the loop body's two (PR 52; 3 + 2 with one sweep a call)
    assert len(kernels) == (1 if kind == "residual" else 8), kernels
    assert all(name.startswith(f"pallas_stencil_mg_{kind}.")
               for name in kernels), kernels
    others = [(name, op) for name, op in written
              if op != "custom-call" and op not in plumbing]
    assert not others, others


@pytest.mark.parametrize("lead", [(), (2,)])
def test_restriction_takes_no_strided_slice_and_no_padded_copy(v5e, lead):
    """``FullWeighting().apply_local`` at 512**3 float32, the program
    ``multigrid-512-f32.vcycle`` runs six times a pair of levels: no
    stride-2 ``slice`` along either minor axis (on the chip a relayout of
    the ``(8, 128)`` tiles, not a copy: 117 of a V-cycle's 755 ms before
    PR 41, ``jit_transfer_FullWeighting_local/slice``), no periodic pad
    of the block written out (``f32[514,514,514]``), the two minor axes as
    dot fusions, temporaries under 0.6 GB an array (1,025 MB with the
    padded strided slices, on which this test fails). The same of the
    ``(2, 512, 512, 512)`` stack the walk restricts since PR 44 (``lead``):
    the leading axis rides through the split and both contractions, and
    brings no copy of its own."""
    import re
    from pystella_tpu.multigrid import FullWeighting
    decomp = ps.DomainDecomposition((1, 1, 1), devices=v5e[:1])
    x = jax.ShapeDtypeStruct(lead + (512,) * 3, jnp.float32,
                             sharding=decomp.sharding(len(lead)))
    compiled = compile_tpu(FullWeighting().apply_local, x)
    hlo = compiled.as_text()
    assert "514,514,514]" not in hlo
    assert not re.search(r"= f32\[[\d,]*\]\S* copy\(", hlo)
    for ln in hlo.splitlines():
        strides = re.search(r"\bslice\(.*slice=\{(.*?)\}", ln)
        if strides:
            y, z = re.findall(r"\[\d+:\d+(?::(\d+))?\]", strides.group(1))[-2:]
            assert (y or "1", z or "1") == ("1", "1"), ln.strip()[:200]
    dots = [ln for ln in hlo.splitlines()
            if " fusion(" in ln and "kind=kOutput" in ln]
    pre = "".join(f"{n}," for n in lead)
    assert [re.search(r"= (f32\[[\d,]*\])", ln).group(1) for ln in dots] \
        == [f"f32[{pre}256,256,512]", f"f32[{pre}256,256,256]"], dots
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.6e9 * int(np.prod(lead))


# -- the ``--halo-shape 0`` programs (``preheat-spectral-f32``) --------------

#: the cell's lattice (``slow``, 45 s) and in tier-1 a sixteenth of it
#: (12 s): the same programs, scopes and constants
SPECTRAL_GRIDS = [(256, 128, 256),
                  pytest.param((512, 512, 512), marks=pytest.mark.slow)]
#: what ``benchmark/configs/preheat-spectral-f32.json`` quotes under
#: ``assumed`` for 512**3, in bytes: (arguments, outputs, temporaries) of
#: a stage program (stages 1-4, undonated) and of ``spectral_lap``
SPECTRAL_QUOTED = {"stage": (4294968832, 4294967808, 1074419200),
                   "lap": (1073741824, 1073741824, 3775612416)}


def _spectral(devices, grid, monkeypatch, proc_shape=(1, 1, 1),
              tier="dft"):
    """The example's ``--halo-shape 0`` branch on described chips (one,
    or a mesh of ``proc_shape``): transform with the inverse by matrix
    products (``tier``: ``ps.DFT``, or ``PencilFFT`` as ``make_dft``
    picks it for a mesh), collocator, generic
    ``LowStorageRK54(full_rhs)``, abstract state."""
    from pystella_tpu.fourier.pencil import PencilFFT

    # a transform places its momenta with device_put, which a described
    # device refuses: here they become constants of the programs
    def constant(self, mu, values, sharded=True):
        return np.asarray(values).reshape(
            [-1 if i == mu else 1 for i in range(3)])

    monkeypatch.setattr(ps.DomainDecomposition, "axis_array", constant)
    monkeypatch.setattr(PencilFFT, "k_axis_array", constant)
    ndev = int(np.prod(proc_shape))
    decomp = ps.DomainDecomposition(proc_shape, devices=devices[:ndev])
    lattice = ps.Lattice(grid, tuple(5.0 * n / 512 for n in grid)
                         if ndev > 1 else (5.0,) * 3, dtype=np.float32)
    kw = dict(grid_shape=grid, dtype=np.float32, real_inverse="matmul")
    fft = (ps.make_dft(decomp, scheme="pencil", **kw) if tier == "pencil"
           else ps.DFT(decomp, **kw))
    derivs = ps.SpectralCollocator(fft, lattice.dk)
    mphi, gsq = 1.20e-6, 2.5e-7

    def potential(f):
        return (mphi**2 / 2 * f[0]**2
                + gsq / 2 * f[0]**2 * f[1]**2) / mphi**2

    rhs = ps.compile_rhs_dict(ps.ScalarSector(2, potential=potential).rhs_dict)
    stepper = ps.LowStorageRK54(
        lambda state, t, a, hubble: rhs(
            state, t, lap_f=derivs.lap(state["f"]), a=a, hubble=hubble),
        dt=0.1 * min(lattice.dx))
    stepper._ensure_stage_jits()
    x = jax.ShapeDtypeStruct((2,) + grid, jnp.float32,
                             sharding=decomp.sharding(1))
    return decomp, fft, derivs, stepper, x


def _spectral_programs(derivs, stepper, x):
    """``{kind: (jitted function, arguments, HLO module name)}`` of the
    stage program (stages 1-4, undonated) and ``spectral_lap``."""
    state = {"f": x, "dfdt": x}
    args = {"a": np.float64(1.0), "hubble": np.float64(0.1)}
    return {
        "stage": (stepper._jit_stage, (1, (state, state), 0.0, stepper.dt,
                                       args), "jit_LowStorageRK54_stage"),
        "lap": (derivs._lap, (x,), "jit_spectral_lap")}


def _largest_constant(hlo):
    import re
    return max(
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"= \w+\[([\d,]+)\]\S* constant\(", hlo))


@pytest.mark.parametrize("grid", SPECTRAL_GRIDS)
def test_spectral_stage_and_lap_compile(v5e, monkeypatch, grid):
    """What ``benchmark/configs/preheat-spectral-f32.json`` needs of a
    chip: a stage program of the generic stepper whose right-hand side
    takes ``lap f`` by transforms, not donated (as the example builds it),
    and the collocator's own ``spectral_lap`` compile for one v5e chip
    and fit its 15.75 GB; at the cell's lattice with the bytes the
    configuration quotes. Each is named (``jit_LowStorageRK54_stage``,
    ``jit_spectral_lap``: what a trace keys its rows by), carries the
    collocator's three scopes, and holds no constant of the lattice's
    size (``kx^2 + ky^2 + kz^2`` folded into one cost 269 MB a program
    and minutes of compile time at 512**3: PR 34)."""
    _, _, derivs, stepper, x = _spectral(v5e, grid, monkeypatch)
    for kind, (fn, fn_args, name) in _spectral_programs(
            derivs, stepper, x).items():
        compiled = fn.trace(*fn_args).lower(
            lowering_platforms=("tpu",)).compile()
        hlo = compiled.as_text()
        assert hlo.startswith("HloModule " + name), hlo[:60]
        for scope in ("spectral_forward", "spectral_symbol",
                      "spectral_inverse"):
            assert scope in hlo, (kind, scope)
        assert "fft_transpose" not in hlo, kind     # one chip: none
        assert _largest_constant(hlo) <= 512 * 512, kind
        mem = compiled.memory_analysis()
        held = (mem.argument_size_in_bytes, mem.output_size_in_bytes,
                mem.temp_size_in_bytes)
        assert sum(held) < 15.75 * 2**30, (kind, held)
        if grid == (512, 512, 512):
            for got, quoted in zip(held, SPECTRAL_QUOTED[kind]):
                assert abs(got - quoted) <= 0.02 * quoted, (kind, held)


def _assert_handed_in_stage_has_no_transform(derivs, stepper, x, ndev=1):
    """The stage program (stages 1-4) the generic stepper dispatches when
    the loop's energy has just taken ``derivs.lap`` of the carry's ``f``
    (``pystella_tpu/handoff.py``): the Laplacian is one more argument,
    and what is left is the right-hand side and the RK update: the
    module's name is the other variant's, no scope of the collocator, no
    transform and nothing between chips; five lattice arrays in, and of
    temporaries the right-hand side's components before their stack (one
    array's bytes at most, which the other variant holds too: XLA makes
    two fusions of a stage's arithmetic). The dispatch held the only
    reference to the Laplacian, so the program may write over it, and
    an output takes its place: the program then holds what the one that
    takes its own Laplacian holds, and no array more."""
    from pystella_tpu import handoff
    state = {"f": x, "dfdt": x}
    carry = (state, state)
    leaf = next(i for i, (path, _) in enumerate(
        jax.tree_util.tree_flatten_with_path(carry)[0])
        if jax.tree_util.keystr(path) == "[0]['f']")
    handed = handoff.HandedIn(x, leaf, derivs._last_lap.serial)
    compiled = stepper._jit_stage.trace(
        1, carry, 0.0, stepper.dt,
        {"a": np.float64(1.0), "hubble": np.float64(0.1)}, handed).lower(
            lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_LowStorageRK54_stage"), hlo[:60]
    for absent in ("spectral_forward", "spectral_symbol",
                   "spectral_inverse", "fft_transpose", "fft_stage",
                   "convolution", "fft(", "all-to-all",
                   "collective-permute", "all-gather"):
        assert absent not in hlo, absent
    mem = compiled.memory_analysis()
    one = int(np.prod(x.shape)) * x.dtype.itemsize // ndev
    assert mem.argument_size_in_bytes >= 5 * one
    assert mem.argument_size_in_bytes <= 5 * one + 4096
    assert mem.temp_size_in_bytes < 1.001 * one, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == one
    assert derivs._last_lap.offered(x) is None       # nothing left on offer


@pytest.mark.parametrize("grid", SPECTRAL_GRIDS)
def test_spectral_stage_with_laplacian_handed_in_compile(v5e, monkeypatch,
                                                         grid):
    """Beside ``test_spectral_stage_and_lap_compile`` (nothing handed in:
    unchanged): the one-chip cell's other stage program."""
    _, _, derivs, stepper, x = _spectral(v5e, grid, monkeypatch)
    _assert_handed_in_stage_has_no_transform(derivs, stepper, x)


#: what ``benchmark/configs/preheat-spectral-mesh4-f32.json`` quotes under
#: ``assumed`` for (1024, 1024, 512) on ``(2, 2, 1)``, bytes a chip:
#: (arguments, outputs, temporaries) of a stage program (stages 1-4,
#: undonated) and of ``spectral_lap``, on either transform tier, and the
#: ``all-to-all`` / ``collective-permute`` instructions of each
SPECTRAL_MESH_QUOTED = {
    "dft": {"stage": (4294968832, 4294967808, 1617936384),
            "lap": (1073741824, 1073741824, 4298089472),
            "collectives": (12, 4)},
    "pencil": {"stage": (4294968832, 4294967808, 3233752576),
               "lap": (1073741824, 1073741824, 3222322176),
               "collectives": (16, 0)}}
#: the cell's lattice (``slow``, 45 s a tier) and a sixty-fourth of it
#: (30-50 s a tier: the same programs, scopes, mesh), in tier-1 on the
#: tier the example's mesh run takes
SPECTRAL_MESH_CASES = [
    ((256, 256, 128), "pencil"),
    pytest.param((256, 256, 128), "dft", marks=pytest.mark.slow),
    pytest.param((1024, 1024, 512), "pencil", marks=pytest.mark.slow),
    pytest.param((1024, 1024, 512), "dft", marks=pytest.mark.slow)]


@pytest.mark.parametrize("grid,tier", SPECTRAL_MESH_CASES)
def test_spectral_mesh_stage_and_lap_compile(v5e, monkeypatch, grid, tier):
    """What ``benchmark/configs/preheat-spectral-mesh4-f32.json`` needs
    of a four-chip host: on the ``(2, 2, 1)`` mesh the undonated stage
    program and ``spectral_lap`` compile for the v5e and fit a chip, on
    the declarative tier (``ps.DFT``) and on ``PencilFFT``; each is
    named, carries the collocator's three scopes and, round what goes
    between chips and what stays on one, ``fft_transpose`` and
    ``fft_stage``; no transform gathers (no ``all-gather``: every stage
    holds a quarter of a field) and none holds a constant of the
    lattice's size; at the cell's lattice with the bytes and the
    collectives the configuration quotes."""
    import re
    _, fft, derivs, stepper, x = _spectral(v5e, grid, monkeypatch,
                                           proc_shape=(2, 2, 1), tier=tier)
    assert fft.scheme == {"dft": "pencil", "pencil": "pencil-a2a"}[tier]
    assert fft.transpose_plan() == (
        (3, 3) if tier == "dft" else (2, 2)) + (
        grid[0] * grid[1] * (grid[2] // 2 + 1) * 8 // 4,)
    quoted = SPECTRAL_MESH_QUOTED[tier]
    for kind, (fn, fn_args, name) in _spectral_programs(
            derivs, stepper, x).items():
        compiled = fn.trace(*fn_args).lower(
            lowering_platforms=("tpu",)).compile()
        hlo = compiled.as_text()
        assert hlo.startswith("HloModule " + name), hlo[:60]
        for scope in ("spectral_forward", "spectral_symbol",
                      "spectral_inverse", "fft_transpose", "fft_stage"):
            assert scope in hlo, (kind, scope)
        assert "all-gather" not in hlo, kind
        # the inverse's cos / sin matrices of an axis are the largest
        assert _largest_constant(hlo) <= max(grid) ** 2, kind
        collectives = tuple(
            len(re.findall(rf" {op}(?:-start)?\(", hlo))
            for op in ("all-to-all", "collective-permute"))
        assert collectives[0] > 0, kind
        mem = compiled.memory_analysis()
        held = (mem.argument_size_in_bytes, mem.output_size_in_bytes,
                mem.temp_size_in_bytes)
        assert sum(held) < 15.75 * 2**30, (kind, held)
        if grid == (1024, 1024, 512):
            assert collectives == quoted["collectives"], (kind, collectives)
            for got, want in zip(held, quoted[kind]):
                assert abs(got - want) <= 0.02 * want, (kind, held)


@pytest.mark.parametrize("grid", [
    (256, 256, 128),
    pytest.param((1024, 1024, 512), marks=pytest.mark.slow)])
def test_spectral_mesh_stage_with_laplacian_handed_in_compile(
        v5e, monkeypatch, grid):
    """Beside ``test_spectral_mesh_stage_and_lap_compile``: the mesh
    cell's other stage program, on ``PencilFFT`` (what the cell runs),
    holds no ``all-to-all``: a chip's shard of the Laplacian comes in."""
    _, _, derivs, stepper, x = _spectral(v5e, grid, monkeypatch,
                                         proc_shape=(2, 2, 1), tier="pencil")
    _assert_handed_in_stage_has_no_transform(derivs, stepper, x, ndev=4)


def test_spectral_collocator_refuses_xlas_inverse_for_a_tpu(v5e,
                                                            monkeypatch):
    """On a TPU's devices a collocator built on a real transform whose
    inverse is XLA's is refused with the option's name; with the inverse
    by matrix products it is built (``_spectral`` above)."""
    decomp, fft, _, _, _ = _spectral(v5e, (32, 32, 128), monkeypatch)
    bad = ps.DFT(decomp, grid_shape=fft.grid_shape, dtype=np.float32)
    with pytest.raises(ValueError, match='real_inverse="matmul"'):
        ps.SpectralCollocator(bad, (1.0, 1.0, 1.0))
