"""Which kernel and blocking a run gets, and from where.

A kernel's blocking has two sources: the constructor's pins
(``bx``/``by``, ``pair_bx``/``pair_by``, ``chunk_bx``/``chunk_by``) or
``choose_blocks``; the tier is what fits. The first test holds the
steppers and operators the benchmark's cells build to the kernels the
ledger's numbers were measured with: construction only, at the cells'
own shapes (read from ``benchmark/configs/*.json``), no lattice
allocated. The expected values were recorded from commit 66181e3, before
the autotune table, ``PYSTELLA_FORCE_BLOCKS``, ``PYSTELLA_CHUNK_STAGES``
and the budget variables went; a change to ``choose_blocks`` that moves
one of them moves a cell, and belongs in a PR that times it on the chip.
"""

import functools
import json
import os
import warnings
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pystella_tpu as ps
from pystella_tpu.obs import events
from pystella_tpu.ops import pallas_stencil as psten

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TPU_SESSION = jax.default_backend() == "tpu"
_XKW = {"interpret": True} if _TPU_SESSION else {}


def _devs(n):
    return (jax.devices("cpu") if _TPU_SESSION else jax.devices())[:n]


class _watch_events:
    """Collect the records emitted over a ``with`` block."""

    def __enter__(self):
        self.records = []
        self._log = events.get_log()
        self._log.subscribe(self.records.append)
        return self

    def __exit__(self, *exc):
        self._log.unsubscribe(self.records.append)

    def of(self, kind):
        return [r["data"] for r in self.records if r["kind"] == kind]


# -- the cells -------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _built(name):
    """:func:`_built_cell` under the halo-overlap policy's own default,
    which is what a cell runs under (the suite pins the policy off in
    the environment): on for a sharded mesh."""
    with mock.patch.dict(os.environ):
        os.environ.pop("PYSTELLA_HALO_OVERLAP", None)
        return _built_cell(name)


def _built_cell(name):
    """What a cell of configuration ``name`` builds, as its family does
    (``benchmark/families/*.py``): the stepper with ``tableau, dtype, dt,
    donate`` and nothing else, the kernels the coupled chunk adds, and
    the ``lap`` and ``grad`` operators of the feedback and the outputs.
    Compiled-mode kernels (``interpret=False``), never called."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    if cfg.get("family") == "multigrid":
        return _built_multigrid(cfg)
    grid = tuple(cfg["grid_shape"])
    proc = tuple(cfg["proc_shape"])
    ndev = int(np.prod(proc))
    if ndev > len(_devs(ndev)):
        return None
    dtype = np.dtype(cfg["dtype"])
    h = int(cfg["halo_shape"])
    decomp = ps.DomainDecomposition(proc, devices=_devs(ndev))
    lattice = ps.Lattice(grid, tuple(cfg["box_dim"]), dtype=dtype)
    mphi, gsq = cfg["mphi"], cfg["gsq"]

    def potential(f):
        return (mphi**2 / 2 * f[0]**2
                + gsq / 2 * f[0]**2 * f[1]**2) / mphi**2

    sector = ps.ScalarSector(cfg["nscalars"], potential=potential)
    kw = dict(tableau=getattr(ps, cfg["stepper"]), dtype=dtype,
              dt=cfg["kappa"] * min(lattice.dx), donate=True,
              interpret=False)
    with _watch_events() as seen, warnings.catch_warnings(record=True) \
            as warned:
        warnings.simplefilter("always")
        if cfg.get("gravitational_waves"):
            stepper = ps.FusedPreheatStepper(
                sector, ps.TensorPerturbationSector([sector]), decomp,
                grid, lattice.dx, h, carry_dtype=cfg.get("carry_dtype"),
                **kw)
        else:
            stepper = ps.FusedScalarStepper(sector, decomp, grid,
                                            lattice.dx, h, **kw)
        # what coupled_multi_step builds at its first call
        coupled_pair = stepper._ensure_coupled_pair_calls()
        stepper._ensure_energy_call()
    choices = {}
    for d in seen.of("block_choice"):
        choices.setdefault(d["kernel"], []).append(d)

    # the operators' kernels emit no event: watch them being built
    class Recorded(psten.StreamingStencil):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            pinned = (kwargs.get("bx") is not None
                      or kwargs.get("by") is not None)
            choices.setdefault(self.kind, []).append({
                "kernel": self.kind, "stencil": "StreamingStencil",
                "bx": self.bx, "by": self.by, "grid": list(self.grid),
                "halo": list(self.halo), "in_place": list(self.in_place),
                "reread": self.reread,
                "source": "explicit" if pinned else "heuristic"})

    derivs = ps.FiniteDifferencer(decomp, h, lattice.dx)
    with mock.patch.object(psten, "StreamingStencil", Recorded):
        for op in ("lap", "grad"):
            derivs._pallas_op(op, cfg["nscalars"], dtype, False, grid)
    return {"choices": choices, "tier": stepper.kernel_tier_report(),
            "coupled_pair": coupled_pair,
            "warnings": [str(w.message) for w in warned]}


def _built_multigrid(cfg):
    """What the multigrid cell's solver builds for each level of its
    default cycle (``benchmark/families/multigrid.py``: ``NewtonIterator``
    with the configuration's problems, the Pallas smoother a TPU gets),
    as its ``mg_level_plan`` events say: compiled-mode kernels, never
    called."""
    from pystella_tpu.multigrid import NewtonIterator
    from pystella_tpu.multigrid.relax import LevelSpec
    grid = tuple(cfg["grid_shape"])
    dx = cfg["box_dim"][0] / grid[0]
    decomp = ps.DomainDecomposition((1, 1, 1), devices=_devs(1))
    solver = NewtonIterator(
        decomp,
        {ps.Field("f"): (ps.Field("lap_f"), ps.Field("rho")),
         ps.Field("f2"): (ps.Field("lap_f2") - ps.Field("f2"),
                          ps.Field("rho2"))},
        halo_shape=cfg["halo_shape"], dtype=np.dtype(cfg["dtype"]),
        smoother="pallas", fixed_parameters=dict(omega=cfg["omega"]))
    with _watch_events() as seen, \
            mock.patch.object(psten, "_is_cpu", lambda: False):
        for i in range(cfg["depth"] + 1):
            level = LevelSpec(tuple(n >> i for n in grid),
                              (dx * 2 ** i,) * 3, False)
            for kind in ("smooth", "residual", "tau"):
                assert solver._pallas_level(
                    kind, level, decomp, jnp.dtype(cfg["dtype"]), ())
    return {"plans": seen.of("mg_level_plan")}


#: (configuration, kernel, which build of that kind, (bx, by), grid,
#: source). For the multigrid cell the build is the level (one
#: ``mg_level_plan`` a level, whichever of its three kernels came first),
#: and the source the tier where that is not ``streaming``. `energy` is "explicit" because the stepper hands it the
#: stage kernel's blocking; ``None`` for a kernel that fits no blocking.
_CELL_KERNELS = [
    ("preheat-512-f32", "stage", 0, (2, 256), (2, 256), "heuristic"),
    ("preheat-512-f32", "pair", 0, (2, 128), (4, 256), "heuristic"),
    # the chunk's first pair (state in) and the rest (deferred drag in)
    ("preheat-512-f32", "coupled_pair", 0, (2, 128), (4, 256),
     "heuristic"),
    ("preheat-512-f32", "coupled_pair", 1, (2, 128), (4, 256),
     "heuristic"),
    ("preheat-512-f32", "energy", 0, (2, 256), (2, 256), "explicit"),
    ("preheat-512-f32", "lap", 0, (2, 256), (2, 256), "heuristic"),
    ("preheat-512-f32", "grad", 0, (2, 256), (2, 256), "heuristic"),
    # 512^3 per chip on (2, 2, 1): the same kernels and blocks, their
    # edges from slabs (`halo`, below) where one chip wraps
    ("preheat-mesh4-f32", "stage", 0, (2, 256), (2, 256), "heuristic"),
    ("preheat-mesh4-f32", "coupled_pair", 0, (2, 128), (4, 256),
     "heuristic"),
    ("preheat-mesh4-f32", "coupled_pair", 1, (2, 128), (4, 256),
     "heuristic"),
    ("preheat-mesh4-f32", "lap", 0, (2, 256), (2, 256), "heuristic"),
    # -gws at 384^3: 32 components a stage (no 256 divides 384)
    ("preheat-gw-f32", "stage", 0, (2, 128), (3, 192), "heuristic"),
    ("preheat-gw-f32", "energy", 0, (2, 128), (3, 192), "explicit"),
    ("preheat-gw-f32", "pair", 0, (2, 64), (6, 192), "heuristic"),
    # the deferred pair's 32 window components fit no blocking: the
    # coupled chunk runs `energy`, five calls a step (PERF.md section 4)
    ("preheat-gw-f32", "coupled_pair", 1, None, None, None),
    ("preheat-gw-f32", "lap", 0, (2, 128), (3, 192), "heuristic"),
    ("preheat-gw-f32", "grad", 0, (2, 128), (3, 192), "heuristic"),
    # the multigrid cell's levels as the chip run built them (PR 32):
    # 512^3, 256^3 and 128^3 stream, 64^3 ... 8^3 (Z < 128) are resident
    ("multigrid-512-f32", "mg_smooth", 0, (1, 256), (2, 512), "heuristic"),
    ("multigrid-512-f32", "mg_smooth", 1, (1, 256), (1, 256), "heuristic"),
    ("multigrid-512-f32", "mg_smooth", 2, (1, 128), (1, 128), "heuristic"),
    ("multigrid-512-f32", "mg_smooth", 3, None, None, "resident"),
    ("multigrid-512-f32", "mg_smooth", 6, None, None, "resident"),
    # the two-sweep kernel a streaming level's smooth runs (PR 52): four
    # window components (unknowns and sources) 2h wide, so bx = 2
    ("multigrid-512-f32", "mg_smooth_pair", 0, (2, 256), None, "heuristic"),
    ("multigrid-512-f32", "mg_smooth_pair", 1, (2, 256), None, "heuristic"),
    ("multigrid-512-f32", "mg_smooth_pair", 2, (2, 128), None, "heuristic"),
    # --halo-shape 4 at 512^3 (PR 40): a radius of 4 has no smaller x
    # block than 4 to take, the ring is twice as deep and the y block
    # half the h = 2 cells'; as the chip run built them
    ("preheat-h4-f32", "stage", 0, (4, 128), (4, 128), "heuristic"),
    ("preheat-h4-f32", "pair", 0, (4, 64), (8, 128), "heuristic"),
    ("preheat-h4-f32", "coupled_pair", 0, (4, 64), (8, 128),
     "heuristic"),
    ("preheat-h4-f32", "coupled_pair", 1, (4, 64), (8, 128),
     "heuristic"),
    ("preheat-h4-f32", "energy", 0, (4, 128), (4, 128), "explicit"),
    ("preheat-h4-f32", "lap", 0, (4, 256), (2, 128), "heuristic"),
    ("preheat-h4-f32", "grad", 0, (4, 256), (2, 128), "heuristic"),
    # 512^3 per chip on the slab mesh (4, 1, 1) (PR 42): the one-chip
    # kernels with x slabs, and beside each kernel without sums the two
    # the overlap split launches in its place: the interior over rows
    # 2 ... 510 (PR 43: the ring kernel over the shard, its grid inset
    # by an x-block of h rows at either end; before, a pre-padded
    # kernel of lattice (508, 512, 512)) and the pre-padded h-row
    # shell, both at bx = h and the whole kernel's y block
    # (`_SPLIT_PARTS` holds their x edges and re-read); the coupled
    # pairs emit sums and stay whole
    ("preheat-mesh4x-f32", "stage", 0, (2, 256), (2, 256), "heuristic"),
    ("preheat-mesh4x-f32", "stage_interior", 0, (2, 256), (2, 254),
     "split"),
    ("preheat-mesh4x-f32", "stage_shell", 0, (2, 256), (2, 1), "split"),
    ("preheat-mesh4x-f32", "pair", 0, (2, 128), (4, 256), "heuristic"),
    ("preheat-mesh4x-f32", "pair_interior", 0, (2, 128), (4, 254),
     "split"),
    ("preheat-mesh4x-f32", "pair_shell", 0, (2, 128), (4, 1), "split"),
    ("preheat-mesh4x-f32", "coupled_pair", 0, (2, 128), (4, 256),
     "heuristic"),
    ("preheat-mesh4x-f32", "coupled_pair", 1, (2, 128), (4, 256),
     "heuristic"),
    ("preheat-mesh4x-f32", "lap", 0, (2, 256), (2, 256), "heuristic"),
    ("preheat-mesh4x-f32", "lap_interior", 0, (2, 256), (2, 254),
     "explicit"),
    ("preheat-mesh4x-f32", "lap_shell", 0, (2, 256), (2, 1), "explicit"),
]


#: the split's kernels: where the x edges of the window come from and
#: the modelled bytes moved over ideal bytes (``block_choice.reread``).
#: The interior's is the whole kernel's (the ring reads every row once:
#: `pair` 1.047 where the pre-padded interior said 1.89); a shell reads
#: every window row (bx + 2h) / bx = 3 times
_SPLIT_PARTS = {
    "stage_interior": ("inset", (2 * 1.0625 + 14) / 16),
    "stage_shell": ("padded", (2 * 3 * 1.0625 + 14) / 16),
    "pair_interior": ("inset", (6 * 1.125 + 10) / 16),
    "pair_shell": ("padded", (6 * 3 * 1.125 + 10) / 16),
    "lap_interior": ("inset", (2 * 1.0625 + 2) / 4),
    "lap_shell": ("padded", (2 * 3 * 1.0625 + 2) / 4),
}


@pytest.mark.parametrize(
    "config, kernel, nth, blocks, grid, source", _CELL_KERNELS,
    ids=[f"{c}-{k}{n or ''}" for c, k, n, *_ in _CELL_KERNELS])
def test_cells_get_the_kernels_the_ledger_measured(config, kernel, nth,
                                                   blocks, grid, source):
    built = _built(config)
    if built is None:
        pytest.skip(f"{config} needs more devices than this host has")
    if "plans" in built:
        assert len(built["plans"]) == 7
        d = built["plans"][nth]
        assert d["grid_shape"] == [512 >> nth] * 3 and d["kernel"] == "smooth"
        assert d["smoother"] == "pallas" and d["dtype"] == "float32"
        if kernel == "mg_smooth_pair":
            assert d["tier"] == "streaming" and d["sweeps_per_pass"] == 2
            assert (d["pair_bx"], d["pair_by"]) == blocks
            assert d["pair_reason"] is None
        elif blocks is None:
            assert (d["tier"], d["stencil"]) == (source, "ResidentStencil")
            assert d["bx"] is d["by"] is d["grid"] is None
            assert d["sweeps_per_pass"] == 1
            assert d["pair_bx"] is d["pair_by"] is d["pair_reason"] is None
        else:
            assert (d["tier"], d["stencil"]) == ("streaming",
                                                 "StreamingStencil")
            assert (d["bx"], d["by"]) == blocks
            assert tuple(d["grid"]) == grid
        assert d["reason"] is None
        return
    # the tier multi_step dispatches: five pair kernels per two steps
    assert built["tier"]["tier"] == "pair"
    assert built["tier"]["kernels_per_2_steps"] == {"pair": 5}
    assert built["tier"]["chunk_depth"] is None
    made = built["choices"].get(kernel, [])
    if blocks is None:
        assert len(made) == nth
        assert built["coupled_pair"] is None
        assert any("coupled pair kernels unavailable" in w
                   and "32 window components fits the 24 MB" in w
                   for w in built["warnings"]), built["warnings"]
        return
    if kernel == "coupled_pair":
        assert built["coupled_pair"] is not None
    d = made[nth]
    assert d["stencil"] == "StreamingStencil"
    assert (d["bx"], d["by"]) == blocks
    assert tuple(d["grid"]) == grid
    assert d["source"] == source
    # where the window's (x, y) edges come from follows from the mesh
    # the stepper or operator was built on, and from nothing else
    if kernel.endswith(("_interior", "_shell")):
        x_edges, moved = _SPLIT_PARTS[kernel]
        assert d["halo"] == [x_edges, "wrap"]
        assert d["reread"] == moved
        if x_edges == "inset":
            whole = built["choices"][kernel.removesuffix("_interior")]
            assert d["reread"] == whole[0]["reread"]
    else:
        assert d["halo"] == {"preheat-mesh4-f32": ["slab", "slab"],
                             "preheat-mesh4x-f32": ["slab", "wrap"]}.get(
                                 config, ["wrap", "wrap"])
    # the per-stage protocol's kernel writes its extras in place (the
    # families build with donate=True); no kernel of a chunk, and no
    # operator, does (PR 37)
    stage_extras = ["dfdt", "kf", "kdfdt"] + (
        ["dhijdt", "khij", "kdhijdt"] if config == "preheat-gw-f32" else [])
    assert d["in_place"] == (
        stage_extras if kernel.startswith("stage") else [])


# -- the budget, the tier figure and the re-read ---------------------------

#: ``choose_blocks``' model arguments ``(n_comp, lattice, h, itemsize,
#: n_extra, n_out)`` of the kernels the cells build
_MODELS = {
    "stage": (2, (512,) * 3, 2, 4, 6, 8),
    "pair": (6, (512,) * 3, 2, 4, 2, 8),
    "coupled_pair": (8, (512,) * 3, 2, 4, 0, 8),
    "lap": (2, (512,) * 3, 2, 4, 0, 2),
    "gw-energy": (8, (384,) * 3, 2, 4, 24, 32),
    "gw-coupled_pair": (32, (384,) * 3, 2, 4, 0, 32),
    "mg_smooth": (2, (512,) * 3, 1, 4, 2, 2),
    # preheat-h4-f32: the same arrays at radius 4
    "h4-stage": (2, (512,) * 3, 4, 4, 6, 8),
    "h4-pair": (6, (512,) * 3, 4, 4, 2, 8),
    "h4-coupled_pair": (8, (512,) * 3, 4, 4, 0, 8),
    "h4-lap": (2, (512,) * 3, 4, 4, 0, 2),
}


@pytest.mark.parametrize("kernel, by, moved, ideal", [
    ("stage", 64, 16.5, 16),
    ("pair", 32, 19, 16),
    ("coupled_pair", 32, 20, 16),
    ("gw-energy", 16, 72, 64),
    ("mg_smooth", 256, 6.125, 6),
    # the h = 4 cell's blocks: half the y block, twice the re-read
    ("h4-stage", 128, 16.25, 16),
    ("h4-pair", 64, 17.5, 16),
    ("h4-coupled_pair", 64, 18, 16),
])
def test_reread_counts_every_windows_y_halo(kernel, by, moved, ideal):
    """The bytes a call moves over the bytes its roofline counts, in
    passes over one lattice array: the rows of ISSUE 39's table (the
    blockings the cells had under the 24-MB budget), by hand."""
    n_comp, _, _, _, n_extra, n_out = _MODELS[kernel]
    assert n_comp + n_extra + n_out == ideal
    assert psten.reread(n_comp, n_extra, n_out, by) == moved / ideal
    # a larger y block re-reads less, and never less than nothing
    assert (psten.reread(n_comp, n_extra, n_out, 2 * by)
            < psten.reread(n_comp, n_extra, n_out, by))
    assert psten.reread(n_comp, n_extra, n_out, 2 * by) > 1


@pytest.mark.parametrize("kernel", sorted(_MODELS))
def test_a_larger_budget_never_gives_a_smaller_y_block(kernel):
    model = _MODELS[kernel]
    bys = []
    for mb in (24, 32, 48, 72, 96, 128):
        try:
            bys.append(psten.choose_blocks(*model, budget=mb * 2**20)[1])
        except ValueError:
            bys.append(0)
    assert bys == sorted(bys), bys
    if kernel == "gw-coupled_pair":
        # the trap of ISSUE 39: from 32 MB up the -gws deferred pair
        # "fits" at by = 8, and the cell's kernel kind would change ...
        assert bys[:2] == [0, 8]
        # ... so without a budget the tier figure decides that it exists
        # nowhere, whatever the blocking figure admits
        assert psten.feasible_blocks(*model)
        with pytest.raises(ValueError, match="fits the 24 MB"):
            psten.choose_blocks(*model)
        return
    # a kernel that exists is blocked under the blocking figure
    assert psten.TIER_BUDGET_BYTES < psten.BLOCK_BUDGET_BYTES
    assert psten.choose_blocks(*model) == psten.choose_blocks(
        *model, budget=psten.BLOCK_BUDGET_BYTES)


# -- pins, refusals, events ------------------------------------------------

_GRID = (16, 16, 16)


def _potential(f):
    return 0.5 * 1.2e-2 * f[0] ** 2 + 0.125 * f[0] ** 2 * f[1] ** 2


def _scalar(**kw):
    sector = ps.ScalarSector(2, potential=_potential)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=_devs(1))
    return ps.FusedScalarStepper(sector, decomp, _GRID, (0.3,) * 3, 2,
                                 dtype=jnp.float32, **_XKW, **kw)


def _gw(**kw):
    sector = ps.ScalarSector(2, potential=_potential)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=_devs(1))
    return ps.FusedPreheatStepper(
        sector, ps.TensorPerturbationSector([sector]), decomp, _GRID,
        (0.3,) * 3, 2, dtype=jnp.float32, **_XKW, **kw)


def _state(rng, names=("f", "dfdt"), ncomp=2):
    return {n: jnp.asarray(
        0.1 * rng.standard_normal((ncomp,) + _GRID).astype(np.float32))
        for n in names}


@pytest.mark.parametrize("kind, common, pins, attr", [
    ("stage", {"pair_stages": False}, {"bx": 4, "by": 8}, "_scalar_st"),
    ("pair", {}, {"pair_bx": 4, "pair_by": 8}, "_pair_st"),
    ("chunk", {"chunk_stages": 4}, {"chunk_bx": 4, "chunk_by": 8},
     "_chunk_st"),
], ids=["stage", "pair", "chunk"])
def test_pins_beat_the_heuristic_and_say_so(kind, common, pins, attr):
    """A pinned blocking is the one built, the event says ``explicit``,
    and blocking never enters the mathematics: two steps are bit-equal
    to the heuristic build's."""
    with _watch_events() as seen:
        heuristic = _scalar(**common)
    assert {d["source"] for d in seen.of("block_choice")} == {"heuristic"}
    chosen = getattr(heuristic, attr)
    assert (chosen.bx, chosen.by) != (4, 8)
    with _watch_events() as seen:
        pinned = _scalar(**common, **pins)
    st = getattr(pinned, attr)
    assert (st.bx, st.by) == (4, 8)
    sources = {d["kernel"]: d["source"] for d in seen.of("block_choice")}
    assert sources.pop(kind) == "explicit"
    assert set(sources.values()) <= {"heuristic"}

    args = {"a": np.float32(1.2), "hubble": np.float32(0.3)}
    results = []
    for stepper in (heuristic, pinned):
        out = stepper.multi_step(_state(np.random.default_rng(31)), 2,
                                 0.0, np.float32(0.01), args)
        results.append({k: np.asarray(v) for k, v in out.items()})
    for name in ("f", "dfdt"):
        assert np.array_equal(*(r[name] for r in results)), \
            f"{name}: a pinned blocking changed the numbers"


@pytest.mark.parametrize("build", [_scalar, _gw], ids=["scalar", "gw"])
def test_unknown_constructor_argument_is_refused(build):
    """A misspelt pin is an error, not silently the heuristic."""
    with pytest.raises(TypeError, match="pair_bby"):
        build(pair_bby=8)


@pytest.mark.parametrize("build, names", [
    (_scalar, ("f", "dfdt")),
    (_gw, ("f", "dfdt", "hij", "dhijdt")),
], ids=["scalar", "gw"])
def test_events_carry_what_the_benchmark_prints(build, names):
    """``benchmark/run.py`` prints ``kernel <kernel>: <stencil> (bx, by)
    = (<bx>, <by>) from <source>`` of every ``block_choice`` and ``tier
    at <entrypoint>: <tier>, <kernels_per_2_steps> per 2 steps`` of
    every ``kernel_tier``."""
    rng = np.random.default_rng(5)
    with _watch_events() as seen:
        stepper = build()
        state = _state(rng, names[:2])
        for n in names[2:]:
            state.update(_state(rng, (n,), 6))
        stepper.multi_step(state, 1, 0.0, np.float32(0.01),
                           {"a": np.float32(1.0),
                            "hubble": np.float32(0.1)})
    choices = seen.of("block_choice")
    assert {d["kernel"] for d in choices} == {"stage", "pair"}
    for d in choices:
        assert {"kernel", "stencil", "bx", "by", "grid", "halo", "in_place",
                "reread", "source", "local_shape", "label", "h",
                "taps"} <= set(d)
        # the radius, and a 13-tap Laplacian a fused stage at h = 2
        assert d["h"] == 2
        assert d["taps"] == {"stage": 13, "pair": 26}[d["kernel"]]
        assert d["source"] in ("explicit", "heuristic")
        assert d["halo"] == ["wrap", "wrap"]
        assert d["in_place"] == []   # built without donate=True
        assert d["grid"] == [16 // d["by"], 16 // d["bx"]]
        # the windowed share of the call's arrays, read with its y halo
        assert 1 < d["reread"] <= (d["by"] + 2 * psten.HY) / d["by"]
    tiers = seen.of("kernel_tier")
    assert [d["entrypoint"] for d in tiers] == ["multi_step"]
    for d in tiers:
        assert {"entrypoint", "tier", "kernels_per_2_steps",
                "chunk_depth", "bytes_per_step", "local_shape",
                "label"} <= set(d)
        assert d["tier"] == "pair"
    for d in choices + tiers:
        assert not any("autotune" in key for key in d)


@pytest.mark.parametrize("smoother, tiers", [
    ("pallas", ["streaming", "streaming", "streaming"]),
    ("xla", ["xla", "xla", "xla"]),
])
def test_level_plans_carry_what_the_benchmark_prints(smoother, tiers):
    """``benchmark/families/multigrid.py`` prints ``level <grid_shape>:
    <tier> (bx, by) = (<bx>, <by>), grid <grid>`` (or the reason, for a
    level on the XLA path) of every ``mg_level_plan``, and counts a level
    on the XLA path under the Pallas smoother as a fallback: one event a
    level of a cycle, whatever kernels of it were built."""
    from pystella_tpu.multigrid import (
        FullApproximationScheme, NewtonIterator)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=_devs(1))
    solver = NewtonIterator(
        decomp, {ps.Field("f"): (ps.Field("lap_f") - ps.Field("f"),
                                 ps.Field("rho"))},
        halo_shape=1, dtype=np.float32, smoother=smoother, omega=1 / 2)
    mg = FullApproximationScheme(solver=solver, halo_shape=1)
    rng = np.random.default_rng(9)
    f, rho = (jnp.asarray(rng.random((32,) * 3), jnp.float32)
              for _ in range(2))
    with _watch_events() as seen:
        for _ in range(2):
            mg(decomp, dx0=0.3, f=f, rho=rho)
    plans = seen.of("mg_level_plan")
    assert [d["grid_shape"] for d in plans] == [[32] * 3, [16] * 3, [8] * 3]
    assert [d["tier"] for d in plans] == tiers
    for d in plans:
        assert {"grid_shape", "local_shape", "tier", "stencil", "bx", "by",
                "grid", "reason", "kernel", "dtype", "smoother",
                "label", "sweeps_per_pass", "pair_bx", "pair_by",
                "pair_reason"} <= set(d)
        assert d["smoother"] == smoother and d["dtype"] == "float32"
        if d["tier"] == "streaming":
            n = d["grid_shape"][0]
            assert d["grid"] == [n // d["by"], n // d["bx"]]
            assert d["reason"] is None
            assert (d["sweeps_per_pass"], d["pair_bx"], d["pair_by"],
                    d["pair_reason"]) == (2, 2, n, None)
        else:
            assert d["bx"] is None and d["reason"] == "smoother='xla'"
            assert d["sweeps_per_pass"] == 1 and d["pair_reason"] is None
    assert len(seen.of("mg_cycle")) == 2


def test_a_stray_table_or_variable_changes_nothing(tmp_path, monkeypatch):
    """A winner table left in the working directory and the variables
    that once overrode the choice are not read: the blocking, the tier
    and the VMEM request are the heuristic's and the constants'."""
    clean = _scalar()
    budgeted = psten.choose_blocks(6, (512, 512, 512), 2, 4, 2, 8)
    (tmp_path / "bench_results").mkdir()
    (tmp_path / "bench_results" / "autotune_cpu.json").write_text(
        json.dumps({"version": 1, "entries": {"0" * 16: {
            "components": {"kind": "fused_scalar"},
            "winner": {"bx": 4, "by": 8, "chunk": 4}}}}))
    monkeypatch.chdir(tmp_path)
    for name, value in [
            ("PYSTELLA_AUTOTUNE", "1"),
            ("PYSTELLA_AUTOTUNE_DIR", str(tmp_path / "bench_results")),
            ("PYSTELLA_FORCE_BLOCKS", "4,8"),
            ("PYSTELLA_CHUNK_STAGES", "4"),
            ("PYSTELLA_BLOCK_BUDGET_MB", "1"),
            ("PYSTELLA_VMEM_LIMIT_MB", "48")]:
        monkeypatch.setenv(name, value)
    with _watch_events() as seen:
        strayed = _scalar()
    for attr in ("_scalar_st", "_pair_st"):
        assert ((getattr(strayed, attr).bx, getattr(strayed, attr).by)
                == (getattr(clean, attr).bx, getattr(clean, attr).by))
    assert strayed._chunk_call is None
    assert {d["source"] for d in seen.of("block_choice")} == {"heuristic"}
    assert strayed.kernel_tier_report() == clean.kernel_tier_report()
    assert psten.choose_blocks(6, (512, 512, 512), 2, 4, 2, 8) == budgeted
    assert psten.TIER_BUDGET_BYTES == 24 * 2**20
    assert psten.BLOCK_BUDGET_BYTES == psten.VMEM_LIMIT_BYTES
    assert (psten._compiler_params(False).vmem_limit_bytes
            == psten.VMEM_LIMIT_BYTES == 100 * 2**20)
    assert psten._compiler_params(True) is None  # interpret mode
