"""Streaming Pallas-TPU stencil kernels.

The TPU-native equivalent of the reference's local-memory-prefetch stencil
kernels (/root/reference/pystella/stencil.py:36-143, esp. the
``StreamingStencil`` that marches a prefetch window along one axis,
stencil.py:113-143). XLA's fusion handles elementwise maps well but
materializes relayouts for shifted slices on the tiled (sublane, lane)
dimensions, so high-order finite-difference operators run far below HBM
bandwidth; these kernels recover it.

Design (chosen by microbenchmark on TPU v5e):

- Arrays are ``(C, X, Y, Z)`` with lattice axes trailing. ``Z`` (the lane
  dimension) is kept whole in VMEM; z-shifts are in-register lane rolls with
  free periodic wrap. ``Y`` (sublane) is split into blocks ``by`` with an
  8-aligned halo window. A kernel is ONE ``pallas_call`` with grid
  ``(Y // by, X // bx)``, x innermost: each program writes its
  ``(bx, by, Z)`` block where it lives in the full-lattice output, so
  nothing is left to assemble. The y-window's DMA offset is
  ``j * by - HY``, which Mosaic takes as provably sublane-aligned
  (``pl.multiple_of``). ``X`` (untiled) is streamed: within one y-block
  the programs advance ``bx`` rows at a time; a persistent VMEM ring of
  4 x-blocks holds the stencil window and each program DMAs only its
  one new block — amplification ~1, contiguous descriptors, issued one
  program ahead (double buffering). The ring is primed anew at the
  first x-block of every y-block.
- Periodic wrap: x via block-index modulo, y via piecewise DMAs at the
  first and last y-block (chosen in-kernel by ``pl.when``), z via the
  lane roll.
- On a sharded axis (``x_slab`` / ``y_slab``) the window operand is
  still the unpadded local shard and the ring still streams it, one new
  ``bx``-row DMA a program. Only the edge source differs: ring block
  ``-1`` and block ``nbx`` are the ``h`` rows of a low / high *x slab*
  operand (the neighbour's last / first rows) where the one-chip kernel
  takes the shard's own last / first block, and the first and last
  y-block take their ``HY``-row piece from a low / high *y slab*
  ``(C, X, HY, Z)`` where the one-chip kernel takes the wrapped piece.
  The slabs are thin arrays the ``shard_map`` body fills by ``ppermute``
  (:meth:`StreamingStencil.halo_slabs`); nothing the size of a window
  is copied. An axis that is not sharded wraps locally, per axis.
- ``x_halo=True`` / ``y_halo=True`` is the older sharded variant, kept
  for the two ``h``-row shells of :class:`OverlapStreamingStencil` and
  for ``multigrid/relax.py``: the input is a *pre-padded copy* of the
  window (``h`` rows in x, ``HY`` in y, ``pad_with_halos``), and with
  ``x_halo`` each program DMAs its own ``bx + 2h`` rows (no ring:
  ``1 + 2h/bx`` reads of every row).
- ``x_inset=True`` is the interior of that split: the same ring over
  the raw shard, its grid short of the first and the last x-block, so
  no edge is ever taken (ring blocks ``0`` and ``nbx - 1`` are in the
  shard) and the launch waits for no collective. Its outputs are still
  the full local lattice; the two edge blocks' rows are left unwritten
  for the shells' rows to be put into.

The kernel body is arbitrary traced JAX: finite-difference taps, fused
Runge-Kutta stage updates (see :mod:`pystella_tpu.ops.fused`), multigrid
smoothers. On CPU backends the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import (
    in_jax_trace, kernel_scope, trace_scope)

__all__ = ["StreamingStencil", "ResidentStencil", "OverlapStreamingStencil",
           "OverlapInfeasible", "Taps", "HY", "LANE",
           "choose_blocks", "feasible_blocks", "reread", "sharded_halo",
           "lap_from_taps", "grad_from_taps", "memo_taps",
           "VMEM_LIMIT_BYTES", "BLOCK_BUDGET_BYTES", "TIER_BUDGET_BYTES"]

#: aligned y-halo width (one sublane tile); must be >= the stencil radius
HY = 8

#: Mosaic lane-tile width: the windowed HBM->VMEM ``async_copy`` requires
#: the trailing (lane) dimension of every slice to be a multiple of 128,
#: even when the slice spans the whole axis (measured on v5e: a
#: ``(C, bx, by, 64)`` window DMA fails to compile with "Slice shape along
#: dimension 3 must be aligned to tiling (128)"). Compiled kernels
#: therefore require ``Z % LANE == 0``; callers fall back to the XLA halo
#: path for smaller lattices.
LANE = 128

_RING = 4  # x-block ring slots: 3 live + 1 in flight

#: Scoped-VMEM limit requested from Mosaic for every compiled stencil
#: kernel (``CompilerParams(vmem_limit_bytes=...)``). XLA's *default*
#: scoped limit is 16 MB (measured on v5e: the 25 MB wave-64^3 resident
#: kernel compiled fine in interpret mode but Mosaic rejected it with
#: "Scoped allocation with size 25.40M and limit 16.00M exceeded scoped
#: vmem limit"), far below the 128 MB of physical VMEM; 100 MB leaves
#: headroom for Mosaic's own scratch.
VMEM_LIMIT_BYTES = 100 * 2**20

#: VMEM figure :func:`choose_blocks` fits a streaming kernel's window
#: ring, pipelined extras/outputs and compute temporaries into when it
#: picks the *blocking* of a kernel that exists: the limit the kernels
#: compile under. The model is the conservative side of that: for the
#: cells' kernels Mosaic accepts a limit of 0.67-0.76 of what the model
#: counts (bisected off the chip, PR 39: `stage` (2, 256) 42 MB for a
#: modelled 55.6, the ``-gws`` `energy` (2, 128) 65 for 85.9), so what
#: fits here compiles. A larger y block is fewer bytes: every windowed
#: array is DMA'd ``(by + 2 * HY) / by`` times (:func:`reread`), and
#: the kernels are HBM-bound. The 24 MB this replaced dated from the
#: 16 MB default limit. A change here is a change to every cell: time
#: it on the chip first (``tests/test_kernel_choice.py`` holds what the
#: cells build).
BLOCK_BUDGET_BYTES = VMEM_LIMIT_BYTES

#: VMEM figure that decides whether a kernel kind *exists* (a blocking
#: of it fits at all): the budget the tiers were measured under. At
#: 384^3 it is what sends the ``-gws`` run to the single-stage
#: ``energy`` kernel: the deferred pair's 32 window components fit no
#: blocking in 24 MB, and from 32 MB up they would at ``by = 8``. It
#: goes, and :data:`BLOCK_BUDGET_BYTES` decides both, with the PR that
#: lets that pair exist (ROADMAP speed item 3): the cell's
#: ``energy_roofline`` has to read whichever kind runs first.
TIER_BUDGET_BYTES = 24 * 2**20


def _compiler_params(interpret):
    """Mosaic compiler params for compiled kernels (None in interpret
    mode — TPU-specific params are meaningless there)."""
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def sharded_halo(h, px, py):
    """Halo widths for ``pad_with_halos`` feeding the PRE-PADDED
    (``x_halo`` / ``y_halo``) window kernels, which the multigrid
    smoothers and :class:`OverlapStreamingStencil`'s shells still
    build; the
    fused steppers and ``FiniteDifferencer`` hand a sharded kernel its
    shard and thin slabs instead (``x_slab`` / ``y_slab``,
    :meth:`StreamingStencil.halo_slabs`), whose y slab keeps the same
    ``HY``-aligned layout. x pads with the stencil radius ``h``, but
    sharded y MUST pad with the 8-aligned ``HY`` window width — an ``h``-wide y pad
    would put the window DMAs on misaligned sublane offsets, which
    Mosaic rejects (and interpret mode would read wrong halo rows).
    Callers pass ``exchange=(h, h, 0)`` alongside so only the ``h``
    semantically-read rows ride the interconnect; the ``HY - h``
    alignment rows are local zeros (the stencil taps reach at most
    ``h``, so they are never read — ICI bytes drop 4x for h=2 while
    the buffer layout stays Mosaic-clean)."""
    return (h if px > 1 else 0, HY if py > 1 else 0, 0)


def _is_cpu():
    return jax.default_backend() == "cpu"


def _rem(a, m):
    """int32-safe modulo for grid indices (x64 mode promotes literals)."""
    return jax.lax.rem(jnp.asarray(a, jnp.int32), jnp.int32(m))


def choose_blocks(n_comp, lattice_shape, h, itemsize, n_extra, n_out,
                  budget=None, win_halo=None, stages=1):
    """Pick ``(bx, by)`` fitting the VMEM budget: the window ring, the
    double-buffered extra inputs / outputs, and ~3 window-sized compute
    temporaries per fused stage.

    Preference: the largest feasible ``by``, then the *smallest*
    feasible ``bx >= h``. The y block decides how much a kernel reads
    twice: a windowed array is DMA'd ``(by + 2 * HY) / by`` times
    (:func:`reread`), and the kernels are HBM-bound, so the default
    budget (:data:`BLOCK_BUDGET_BYTES`) is sized for the
    :data:`VMEM_LIMIT_BYTES` the kernels compile under. Timed on a v5e
    (PR 39, the cells' loops): no kernel kind loses at a larger y
    block; a 512^3 coupled step takes 46.9 / 41.2 / 39.3 ms at ``by`` =
    32 / 64 / 128, a pair step 38.5 / 35.7 / 34.5, the ``-gws`` step at
    384^3 125.4 / 116.2 / 114.2 / 113.9 at 16 / 32 / 64 / 128, ``lap``
    4.06 / 3.60 at 128 / 256. Small x-blocks keep the ring slots cheap
    and pipeline best ((2,128) beat every bx>=4 blocking at 128^3):
    that is about ``bx``, not ``by``, and it dates from the padded
    layouts (before PR 26). A radius of 4 has no smaller ``bx`` than 4
    to take; its kernels at 512^3 (PR 40, the v5e, share of 819 GB/s in
    the bytes they move): ``stage`` (4, 128) 81.1 %, ``pair`` (4, 64)
    79.1, ``coupled_pair`` (4, 64) 78.5, where the radius-2 kernels at
    (2, 256) and (2, 128) read 81-82; ``lap`` (4, 256) 50.3 against
    (2, 256)'s 76.2. ``bx`` itself has not been varied at one radius.

    Without a ``budget`` two figures are asked: whether the kernel
    exists at all under :data:`TIER_BUDGET_BYTES` (a ``ValueError``
    where it does not, and the caller takes its next tier), then its
    blocking under :data:`BLOCK_BUDGET_BYTES`. An explicit ``budget``
    decides both.

    ``win_halo`` is the assembled window's halo width (defaults to the
    stencil radius ``h``); temporal-blocking chunk kernels pass
    ``ceil(depth/2) * h`` — each stage pair composed in-register reaches
    one radius further into the window — together with ``stages``, which
    scales the compute-temporary share of the model (composed stages
    keep ~3 extra window-sized live values each)."""
    tier_budget = TIER_BUDGET_BYTES if budget is None else budget
    wh = h if win_halo is None else int(win_halo)
    if wh < h:
        raise ValueError(f"win_halo {wh} below stencil radius {h}")
    if wh > HY:
        raise ValueError(
            f"win_halo {wh} (ceil(stages / 2) * h: {stages} stage(s) at "
            f"stencil radius {h}) exceeds the aligned y-halo width HY = "
            f"{HY}: no feasible streaming blocking (shrink the chunk "
            "depth or use the pair/single-stage kernels)")
    X, Y, Z = lattice_shape
    model = (n_comp, lattice_shape, h, itemsize, n_extra, n_out)
    feasible = feasible_blocks(*model, budget=tier_budget,
                               win_halo=win_halo, stages=stages)
    if feasible and budget is None:
        # the kernel exists: block it for the limit it compiles under
        feasible = feasible_blocks(
            *model, budget=max(BLOCK_BUDGET_BYTES, tier_budget),
            win_halo=win_halo, stages=stages)
    if not feasible:
        if Y % 8:
            # the streaming kernel's y-block math assumes by >= the 8-aligned
            # halo width, so lattices whose Y is not a multiple of 8 have no
            # feasible blocking at all — say so clearly (callers like
            # FiniteDifferencer catch this and take the halo path)
            raise ValueError(
                f"lattice y extent {Y} is not a multiple of 8: no feasible "
                "pallas/fused streaming-stencil blocking; use the halo-"
                "exchange operators (FiniteDifferencer mode='halo') or the "
                "generic steppers instead")
        # NO blocking fits the budget even at the (bx_min, 8) floor: say so
        # rather than hand back a config Mosaic's VMEM allocator will
        # reject at compile time (observed: the 24-window stage-pair
        # kernel at 512^3 — callers degrade to single-stage kernels)
        raise ValueError(
            f"no (bx, by) blocking of lattice {lattice_shape} with "
            f"{n_comp} window components fits the "
            f"{tier_budget / 2**20:.0f} MB "
            "VMEM budget; split the kernel (fewer window components) or "
            "use the halo-exchange / generic path")
    return feasible[0]


def feasible_blocks(n_comp, lattice_shape, h, itemsize, n_extra, n_out,
                    budget=None, win_halo=None, stages=1):
    """Every ``(bx, by)`` the :func:`choose_blocks` VMEM model admits
    under ``budget`` (default :data:`BLOCK_BUDGET_BYTES`),
    heuristic-preferred order first: what a blocking experiment picks
    its pins from."""
    if budget is None:
        budget = BLOCK_BUDGET_BYTES
    wh = h if win_halo is None else int(win_halo)
    if wh < h or wh > HY:
        return []
    X, Y, Z = lattice_shape
    out = []
    for by in (256, 128, 64, 32, 16, 8):
        if by > Y or Y % by:
            continue
        for bx in (1, 2, 4, 8, 16):
            if bx > X or X % bx or bx < wh:
                continue
            byw = by + 2 * HY
            win = n_comp * _RING * bx * byw * Z * itemsize
            temps = (3 * int(stages) * n_comp * (bx + 2 * wh) * byw * Z
                     * itemsize)
            io = 2 * (n_extra + n_out) * bx * by * Z * itemsize
            if win + temps + io <= budget:
                out.append((bx, by))
    return out


def reread(n_comp, n_extra, n_out, by, x_reads=1.0):
    """Modelled bytes a streaming kernel call really moves over the
    ideal count (every lattice input once, every output once), in
    passes over one lattice array: each of the ``n_comp`` windowed
    components is DMA'd with its ``HY``-row y halos, ``(by + 2 * HY) /
    by`` times (the x ring reads every row once; a pre-padded
    ``x_halo`` kernel has no ring and reads every row ``x_reads`` =
    ``(bx + 2h) / bx`` times), the extras and outputs once. What a
    ``*_roofline`` of ideal bytes has to be multiplied by to say which
    share of the HBM peak the kernel reaches in the bytes it moves."""
    ideal = n_comp + n_extra + n_out
    return (n_comp * x_reads * (by + 2 * HY) / by + n_extra + n_out) / ideal


class Taps:
    """Stencil-tap accessor handed to kernel bodies.

    ``taps(sx, sy, sz)`` returns the windowed field shifted by the given
    static offsets, shaped ``(C, bx, by, Z)``. ``|sx| <= wh`` (the
    window halo width — the stencil radius ``h`` for single/pair
    kernels, ``ceil(depth/2) * h`` for temporal-blocking chunk
    kernels), ``|sy| <= HY``; ``sz`` may only be nonzero alone
    (axis-aligned centered-difference taps); z wraps periodically
    (whole axis in VMEM), x/y shifts read the window halo."""

    def __init__(self, w, h, bx, by, Z, interpret, wh=None):
        self._w = w
        self._h, self._bx, self._by, self._Z = h, bx, by, Z
        self._wh = h if wh is None else wh
        self._interpret = interpret
        self._cache = {}

    def __call__(self, sx=0, sy=0, sz=0):
        key = (sx, sy, sz)
        if key in self._cache:
            return self._cache[key]
        wh, bx, by, Z = self._wh, self._bx, self._by, self._Z
        if sz != 0:
            if sx or sy:
                raise ValueError("taps must be axis-aligned")
            out = self.roll(self(), sz)
        else:
            out = self._w[:, wh + sx:wh + sx + bx,
                          HY + sy:HY + sy + by, :]
        self._cache[key] = out
        return out

    def roll(self, arr, sz):
        """Periodic z-shift of a *computed* ``(C, bx, by, Z)`` block with
        the same lowering as z taps (in-register lane roll when compiled;
        used by bodies that take stencil taps of derived quantities, e.g.
        the stage-pair kernel's Laplacian of the intermediate field)."""
        if self._interpret:
            return jnp.roll(arr, -sz, axis=3)
        # int32 shift: under x64 a bare python int traces as i64, which
        # tpu.dynamic_rotate rejects (caught by tests/test_tpu_lowering.py)
        return pltpu.roll(arr, jnp.int32((self._Z - sz) % self._Z), 3)

    def grown(self, m):
        """Taps of the block grown by ``m`` rows on either side in x and
        to the whole window in y: ``(C, bx + 2m, by + 2 * HY, Z)``
        blocks, for a body evaluated once where a second stencil will
        read it (``multigrid/relax.py``'s two-sweep kernel). Axis-aligned
        offsets up to ``wh - m`` in x (slices of the window's untiled
        axis); a y offset is a sublane roll of the offset-0 block, whose
        wrapped rows land in the window's outermost ``|sy|`` rows, so of
        a radius-``h`` body's result the rows within ``HY - h`` of the
        block are good: what :meth:`over` reads, ``m <= HY - h``."""
        wh, bx = self._wh, self._bx
        if not 0 < m <= wh:
            raise ValueError(f"cannot grow a block by {m} rows inside a "
                             f"window halo of {wh}")

        def block(sx, sy):
            if sy:
                if sx:
                    raise ValueError("taps must be axis-aligned")
                if self._interpret:
                    return jnp.roll(taps(), -sy, axis=2)
                byw = self._by + 2 * HY  # an int32 shift, as in roll
                return pltpu.roll(taps(), jnp.int32((byw - sy) % byw), 2)
            if abs(sx) > wh - m:
                raise ValueError(f"x offset {sx} leaves the window")
            return self._w[:, wh - m + sx:wh + m + sx + bx]

        taps = memo_taps(block, self.roll)
        return taps

    def over(self, block, m):
        """The :class:`Taps` of a computed block shaped as :meth:`grown`
        gives them: a window of halo ``m`` for the next stencil."""
        return Taps(block, self._h, self._bx, self._by, self._Z,
                    self._interpret, wh=m)


def memo_taps(compute_xy, roll):
    """A taps-like view from an (sx, sy) -> block expression: memoized
    per offset, z offsets as in-register rolls of the offset-0 block
    (what :class:`Taps` lowers its own z offsets to)."""
    cache = {}

    def taps(sx=0, sy=0, sz=0):
        key = (sx, sy, sz)
        if key in cache:
            return cache[key]
        if sz != 0:
            if sx or sy:
                raise ValueError("taps must be axis-aligned")
            out = roll(taps(), sz)
        else:
            out = compute_xy(sx, sy)
        cache[key] = out
        return out
    return taps


def _sum_tile(terms, shape, dtype):
    """A zero 2-D tile of ``shape`` with scalar ``terms[t]`` at
    ``[t, 0]``, composed from iota selects: Mosaic lays out neither a
    1-D vector nor a stack of scalars (the v5e compiler rejects the
    ``(nt,)`` result of a multi-axis reduction with "Invalid output
    layout"), so block sums travel as scalars and meet the output ref as
    a whole 2-D tile."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    tile = jnp.zeros(shape, dtype)
    for t, term in enumerate(terms):
        tile = jnp.where((row == t) & (col == 0),
                         jnp.asarray(term, dtype), tile)
    return tile


def lap_from_taps(taps, coefs, inv_dx2):
    """Laplacian from centered-difference taps: ``coefs`` maps offset ->
    coefficient (offset 0 included), ``inv_dx2`` is ``1/dx**2`` per axis."""
    acc = coefs[0] * sum(inv_dx2) * taps()
    for s, c in coefs.items():
        if s == 0:
            continue
        acc += c * inv_dx2[0] * (taps(s) + taps(-s))
        acc += c * inv_dx2[1] * (taps(0, s) + taps(0, -s))
        acc += c * inv_dx2[2] * (taps(0, 0, s) + taps(0, 0, -s))
    return acc


def grad_from_taps(taps, coefs, inv_dx):
    """Per-axis first derivatives from antisymmetric centered taps; returns
    a list of three ``(C, bx, by, Z)`` blocks."""
    grads = []
    for d in range(3):
        acc = 0
        for s, c in coefs.items():
            plus = [0, 0, 0]
            plus[d] = s
            minus = [0, 0, 0]
            minus[d] = -s
            acc = acc + c * inv_dx[d] * (taps(*plus) - taps(*minus))
        grads.append(acc)
    return grads


class RollTaps:
    """Taps accessor for :class:`ResidentStencil`: the whole lattice is a
    VMEM value, every shift is a periodic in-register roll along any of
    the three trailing axes (memoized per offset). Matches the
    :class:`Taps` indexing convention: ``taps(s)[..., i, ...] ==
    f[..., i + s, ...]`` with periodic wrap."""

    def __init__(self, w, interpret):
        self._w = w
        self._interpret = interpret
        self._cache = {}

    def _roll1(self, arr, s, axis):
        if s == 0:
            return arr
        if self._interpret:
            return jnp.roll(arr, -s, axis)
        n = arr.shape[axis]
        # int32 shift: see Taps.roll
        return pltpu.roll(arr, jnp.int32((n - s) % n), axis)

    def __call__(self, sx=0, sy=0, sz=0):
        key = (sx, sy, sz)
        if key in self._cache:
            return self._cache[key]
        out = self._roll1(self._roll1(self._roll1(
            self._w, sx, 1), sy, 2), sz, 3)
        self._cache[key] = out
        return out

    def roll(self, arr, sz):
        """Periodic z-shift of a computed block (same contract as
        :meth:`Taps.roll`)."""
        return self._roll1(arr, sz, 3)


class ResidentStencil:
    """Whole-lattice-resident Pallas kernels for small lattices.

    The streaming kernels require ``Z % 128 == 0`` (lane-aligned window
    DMAs); below that the XLA fallback ran at ~5% of the fused path
    (wave-64**3, doc/performance.md). Here the full ``(C, X, Y, Z)``
    arrays are pallas_call inputs placed in VMEM (no grid, no windows,
    no DMA choreography), stencil taps are periodic in-register rolls on
    all three axes, and the body — the same body the streaming kernels
    take — runs once over the whole lattice: one HBM read + one write
    per array with zero relayouts. Feasible whenever all inputs,
    outputs, and ~3 body temporaries fit the VMEM ``budget``.

    Interface-compatible with :class:`StreamingStencil` (``__call__``,
    ``out_defs``/``sum_defs``, scalars via SMEM) so fused steppers and
    ``FiniteDifferencer`` can select it per lattice shape.
    """

    def __init__(self, lattice_shape, win_defs, h, body, out_defs,
                 extra_defs=None, scalar_names=(), dtype=jnp.float32,
                 interpret=None, sum_defs=None, budget=64 * 2**20,
                 dtypes=None, stages=1):
        self.lattice_shape = X, Y, Z = tuple(int(s) for s in lattice_shape)
        if not isinstance(win_defs, dict):
            win_defs = {"f": int(win_defs)}
        self.win_defs = {k: int(v) for k, v in win_defs.items()}
        self.single_window = len(self.win_defs) == 1
        self.h = int(h)
        self.body = body
        self.out_defs = {k: tuple(v) for k, v in dict(out_defs).items()}
        self.sum_defs = {k: int(v) for k, v in dict(sum_defs or {}).items()}
        self.extra_defs = {k: tuple(v)
                           for k, v in dict(extra_defs or {}).items()}
        self.scalar_names = tuple(scalar_names)
        self.dtype = jnp.zeros((), dtype).dtype
        self.dtypes = {k: jnp.zeros((), v).dtype
                       for k, v in dict(dtypes or {}).items()}
        self.interpret = _is_cpu() if interpret is None else interpret

        nwin = sum(self.win_defs.values())
        nio = (nwin + sum(int(np.prod(s)) if s else 1
                          for s in self.extra_defs.values())
               + sum(int(np.prod(s)) if s else 1
                     for s in self.out_defs.values()))
        # RollTaps memoizes every distinct (sx, sy, sz) offset, so a
        # radius-h centered-difference body materializes up to 2h
        # shifted whole-lattice copies per axis per window stack (plus
        # the partial-roll intermediates x->xy->xyz composition makes):
        # budget ~(6h + 2) whole-lattice temporaries per window
        # component rather than a flat 3, so the Python-level gate
        # fires before Mosaic's VMEM allocator rejects the kernel with
        # no fallback (ADVICE r4). Multi-stage (temporal-blocking)
        # bodies memoize a comparable set of composed whole-lattice
        # values per fused stage — the ``stages`` factor.
        ntemp = (6 * self.h + 2) * max(1, int(stages))
        need = (nio + ntemp * nwin) * X * Y * Z * self.dtype.itemsize
        if need > budget:
            raise ValueError(
                f"resident stencil on lattice {self.lattice_shape} with "
                f"{nio} lattice arrays (+~{ntemp} tap temps per window "
                f"component at radius {self.h}) needs ~"
                f"{need / 2**20:.0f} MB VMEM > the {budget / 2**20:.0f} MB "
                "budget; use the streaming kernels or the halo path")
        # compile-ledger attribution: an eagerly-dispatched resident
        # kernel's Mosaic/XLA build is a real cold-start cost
        self._call = _obs_memory.InstrumentedJit(
            self._build(),
            label=f"pallas.resident{tuple(self.lattice_shape)}")

    def _build(self):
        nw, ns = len(self.win_defs), len(self.scalar_names)
        ne, no = len(self.extra_defs), len(self.out_defs)
        X, Y, Z = self.lattice_shape

        def kernel(*refs):
            f_refs = refs[:nw]
            scalar_refs = refs[nw:nw + ns]
            extra_refs = refs[nw + ns:nw + ns + ne]
            out_refs = refs[nw + ns + ne:]
            taps = {n: RollTaps(r[...], self.interpret)
                    for n, r in zip(self.win_defs, f_refs)}
            if self.single_window:
                taps = next(iter(taps.values()))
            scalars = {n: r[0]
                       for n, r in zip(self.scalar_names, scalar_refs)}
            extras = {n: r[...]
                      for n, r in zip(self.extra_defs, extra_refs)}
            outs = self.body(taps, extras, scalars)
            for n, ref in zip(self.out_defs, out_refs[:no]):
                ref[...] = outs[n].astype(ref.dtype)
            for n, ref in zip(self.sum_defs, out_refs[no:]):
                ref[...] = _sum_tile(outs[n], ref.shape, ref.dtype)

        def whole(lead):
            shape = tuple(lead) + self.lattice_shape
            return pl.BlockSpec(shape, lambda n=len(shape): (0,) * n)

        in_specs = [whole((C,)) for C in self.win_defs.values()]
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)
                     for _ in self.scalar_names]
        in_specs += [whole(lead) for lead in self.extra_defs.values()]
        out_specs = [whole(lead) for lead in self.out_defs.values()]
        out_shapes = [jax.ShapeDtypeStruct(lead + self.lattice_shape,
                                           self.dtypes.get(n, self.dtype))
                      for n, lead in self.out_defs.items()]
        for nt in self.sum_defs.values():
            out_specs.append(pl.BlockSpec((nt, 1), lambda: (0, 0)))
            out_shapes.append(jax.ShapeDtypeStruct((nt, 1), self.dtype))
        return pl.pallas_call(
            kernel,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            interpret=self.interpret,
            compiler_params=_compiler_params(self.interpret),
        )

    def __call__(self, f, scalars=None, extras=None):
        """Apply to the full-lattice input(s); same contract as
        :meth:`StreamingStencil.__call__` (sum outputs reduced to
        ``(nterms,)``)."""
        scalars = scalars or {}
        extras = extras or {}
        win_args = ([f[n] for n in self.win_defs] if isinstance(f, dict)
                    else [f])
        scalar_args = [jnp.asarray(scalars[n], self.dtype).reshape(1)
                       for n in self.scalar_names]
        extra_args = [extras[n] for n in self.extra_defs]
        with trace_scope("pallas_resident_stencil"):
            res = self._call(*win_args, *scalar_args, *extra_args)
        out = {}
        names = list(self.out_defs) + list(self.sum_defs)
        for n, arr in zip(names, res):
            out[n] = arr.reshape(-1) if n in self.sum_defs else arr
        return out


class StreamingStencil:
    """Builds and calls streaming-window Pallas stencil kernels.

    One ``pallas_call`` per kernel, grid ``(Y // by, X // bx)`` with x
    innermost: every program writes its ``(*lead, bx, by, Z)`` block
    straight into the full-lattice output.

    :arg lattice_shape: local interior ``(X, Y, Z)``.
    :arg win_defs: dict name -> leading component count, one entry per
        *windowed* (haloed) input; a bare int means a single input named
        ``"f"``.
    :arg h: stencil radius (<= HY).
    :arg body: ``body(taps, extras, scalars) -> dict`` mapping each output
        name to a ``(*lead, bx, by, Z)`` block. With several windowed
        inputs ``taps`` is a dict name -> :class:`Taps`.
    :arg out_defs: dict output name -> leading shape tuple.
    :arg extra_defs: dict input name -> leading shape tuple; same-lattice
        unhaloed arrays, pipelined blockwise.
    :arg scalar_names: names of runtime scalars (handed to the body).
    :arg x_slab: sharded x, the main path: the window operand is the
        unpadded shard and the ring's two edge blocks come from thin
        slab operands (``slabs`` of :meth:`__call__`,
        :meth:`halo_slabs`) instead of the periodic wrap.
    :arg y_slab: sharded y likewise: the first and last y-block take
        their ``HY``-row halo piece from a ``(C, X, HY, Z)`` slab, in
        which the neighbour's ``h`` rows sit against the shard (the
        last rows of the low slab, the first of the high one) and the
        rest is never read. With both, the corners (x slab rows at y
        halo rows) are NOT fetched: the window holds stale VMEM there,
        so bodies may take axis-aligned taps only (every fused body
        and every ``FiniteDifferencer`` operator does; a widened
        ``win_halo`` composes diagonal taps and is refused).
    :arg x_halo: the older sharded variant: the input x-axis is a copy
        pre-padded with ``h`` halo rows, and every program DMAs its own
        ``bx + 2h`` rows (no ring). Not combined with the slab modes.
        Built by the overlap split's shells and ``multigrid/relax.py``.
    :arg x_inset: the overlap split's interior
        (:class:`OverlapStreamingStencil` asks for it through
        :meth:`with_lattice`; nothing else does): the ring kernel over
        grid ``(Y // by, X // bx - 2)``, program ``i`` doing x-block
        ``i + 1``. Blocks ``0`` and ``X // bx - 1`` are only ever read,
        as the ring's first and last, so x neither wraps nor takes a
        slab, every window row is DMA'd once, and the extras and
        outputs stay arrays of the whole ``(X, Y, Z)`` whose two edge
        blocks no program touches: an output's rows there are
        unwritten (an ``in_place`` output keeps its extra's). Not
        combined with ``x_slab`` / ``x_halo``.
    :arg y_halo: the input y-axis is pre-padded with ``HY`` (8) halo rows
        per side (sharded y): each y-block window is one contiguous
        8-aligned DMA piece from the padded input, no in-kernel wrap.
        The pad is ``HY`` rather than the stencil radius ``h`` so every
        sublane DMA offset stays tile-aligned (the mesh halo exchange
        moves 8 rows instead of ``h`` — a few percent extra ICI bytes
        for guaranteed Mosaic-clean windows).
    :arg sum_defs: dict name -> term count: lattice-summed outputs. The
        body returns a sequence of ``nterms`` scalar block sums per name
        (scalars, not a vector — see :func:`_sum_tile`); each
        grid program adds its partial into the ``(nt_pad8, LANE)``
        accumulator tile of its y-block, revisited across the
        (sequential) x programs, and :meth:`__call__` finishes the
        reduction over y-blocks outside the kernel — deterministic
        summation order (program order is fixed), one tile writeback
        per y-block. This is how fused RK stages emit energy reductions
        of their input state for free (the whole state is already in
        VMEM).
    :arg in_place: names of extras the kernel writes over: each is
        paired with the output of its name through
        ``pallas_call(input_output_aliases=...)``, so a caller that owns
        the extra's buffer (a donated argument, a temporary) gets the
        output in it and nothing is copied. Safe for exactly these
        arrays: an extra and its output share one ``BlockSpec``, program
        ``(j, i)`` has block ``(i, j)`` in VMEM before it writes that
        block back, and no other program reads it. A window is read
        through its halo by the neighbouring programs, so a window's
        name is refused, as is a name with no output of its name or
        whose leading shape is not its output's (the storage dtype goes
        by name, ``dtypes``, so it is the same on both sides). A
        caller that does NOT own the buffer pays a copy of it: declare
        what is donated and nothing else.
    :arg kind: what the kernel is, for traces: the call is dispatched
        under ``obs.scope.kernel_scope(kind)``
        (``pallas_stencil_<kind>``), the name a TPU trace gives its
        HLO instruction, and applied eagerly it is one program named
        after the kind (``jit_<kind>``). ``None`` keeps the bare
        ``pallas_stencil``.
    """

    def __init__(self, lattice_shape, win_defs, h, body, out_defs,
                 extra_defs=None, scalar_names=(), dtype=jnp.float32,
                 bx=None, by=None, x_halo=False, y_halo=False,
                 interpret=None, sum_defs=None, dtypes=None,
                 win_halo=None, stages=1, kind=None, x_slab=False,
                 y_slab=False, in_place=(), x_inset=False):
        if h > HY:
            raise ValueError(f"stencil radius {h} exceeds aligned halo {HY}")
        #: what the kernel is (``"pair"``, ``"lap"`` ...; ``None``: not
        #: said) and the scope its call is dispatched under: the name
        #: its HLO instruction carries in a TPU trace
        self.kind = kind
        self._scope = kernel_scope(kind)
        #: fused-stage count of the body (1 single, 2 pair, >=4 chunk):
        #: scales the compute-temporary share of the default-blocking
        #: VMEM model — composed stages keep extra window-sized values
        #: live
        self.stages = max(1, int(stages))
        #: assembled window halo width: the stencil radius for
        #: single/pair kernels; temporal-blocking chunk kernels widen it
        #: to ``ceil(depth/2) * h`` so composed deeper-stage taps stay
        #: in-window (the recompute-for-traffic trade of
        #: doc/performance.md "Temporal blocking")
        self.wh = int(h if win_halo is None else win_halo)
        if self.wh < int(h):
            raise ValueError(
                f"win_halo {self.wh} below stencil radius {h}")
        if self.wh > HY:
            raise ValueError(
                f"win_halo {self.wh} (ceil(stages / 2) * h: "
                f"{self.stages} stage(s) at stencil radius {h}) exceeds "
                f"the aligned y-halo width HY = {HY}: the y-window pad "
                "cannot cover the composed-stage taps; use a shallower "
                "chunk or the pair kernels")
        self.lattice_shape = X, Y, Z = tuple(int(s) for s in lattice_shape)
        if not isinstance(win_defs, dict):
            win_defs = {"f": int(win_defs)}
        self.win_defs = {k: int(v) for k, v in win_defs.items()}
        self.single_window = len(self.win_defs) == 1
        self.h = int(h)
        self.body = body
        self.out_defs = {k: tuple(v) for k, v in dict(out_defs).items()}
        self.sum_defs = {k: int(v) for k, v in dict(sum_defs or {}).items()}
        self.extra_defs = {k: tuple(v)
                           for k, v in dict(extra_defs or {}).items()}
        self.scalar_names = tuple(scalar_names)
        # canonicalize (f64 -> f32 when x64 is disabled) so out_shapes and
        # in-kernel values agree
        self.dtype = jnp.zeros((), dtype).dtype
        #: per-array dtype overrides (windowed inputs / extras / outputs)
        #: for mixed precision, e.g. bfloat16 RK carries riding f32 state
        #: (the fused steppers' ``carry_dtype``). Bodies see the storage
        #: dtype in taps/extras (jnp promotion upcasts against the f32
        #: scalars); outputs are cast to their storage dtype on write.
        self.dtypes = {k: jnp.zeros((), v).dtype
                       for k, v in dict(dtypes or {}).items()}
        for n in in_place:
            if n in self.win_defs:
                raise ValueError(
                    f"in_place {n!r} is a windowed input: its halo rows "
                    "are read by the neighbouring programs, so it cannot "
                    "be written where it is read")
            if n not in self.extra_defs or n not in self.out_defs:
                raise ValueError(
                    f"in_place {n!r} needs an extra and an output of that "
                    f"name (extras {list(self.extra_defs)}, outputs "
                    f"{list(self.out_defs)})")
            if self.extra_defs[n] != self.out_defs[n]:
                raise ValueError(
                    f"in_place {n!r}: the extra's leading shape "
                    f"{self.extra_defs[n]} is not its output's "
                    f"{self.out_defs[n]}")
        #: the extras written in place, in ``extra_defs`` order
        self.in_place = tuple(n for n in self.extra_defs if n in in_place)
        #: lattice-sized arrays a call moves: windowed components, extra
        #: inputs, outputs (what the VMEM model and ``reread`` count)
        self.n_arrays = (sum(self.win_defs.values()),) + tuple(
            sum(int(np.prod(s)) if s else 1 for s in defs.values())
            for defs in (self.extra_defs, self.out_defs))
        if bx is None or by is None:
            n_comp, n_extra, n_out = self.n_arrays
            cbx, cby = choose_blocks(
                n_comp, self.lattice_shape, self.h, self.dtype.itemsize,
                n_extra, n_out, win_halo=self.wh, stages=self.stages)
            bx = bx if bx is not None else cbx
            by = by if by is not None else cby
        if X % bx or Y % by:
            raise ValueError(
                f"block ({bx},{by}) must divide lattice ({X},{Y})")
        if bx < self.wh and X // bx > 1:
            raise ValueError(
                f"bx={bx} must be >= the window halo {self.wh} (ring "
                "slots supply the halo rows)")
        self.bx, self.by = int(bx), int(by)
        self.x_halo = bool(x_halo)
        self.y_halo = bool(y_halo)
        self.x_slab = bool(x_slab)
        self.y_slab = bool(y_slab)
        #: x-blocks at either end of the shard that no program does (the
        #: overlap split's interior: 1; every other kernel: 0)
        self.x_inset = int(bool(x_inset))
        if self.x_inset and (self.x_slab or self.x_halo
                             or X // self.bx < 3):
            raise ValueError(
                "an inset kernel streams the raw shard (no x slab, no "
                f"padded copy) and needs three x-blocks of {self.bx} in "
                f"{X} rows: its first and last are only read")
        #: the kernel's grid: y-blocks, then x-blocks (x innermost)
        self.grid = (Y // self.by, X // self.bx - 2 * self.x_inset)
        if (self.x_slab or self.y_slab) and (self.x_halo or self.y_halo):
            raise ValueError(
                "slab edges and pre-padded windows do not combine: a "
                "sharded kernel takes either its shard and slabs or a "
                "padded copy")
        if self.x_slab and self.y_slab and self.wh != self.h:
            raise ValueError(
                f"win_halo {self.wh} beyond the stencil radius {self.h} "
                "composes diagonal taps, and an xy-sharded kernel leaves "
                "the window's corners unfetched")
        self.interpret = _is_cpu() if interpret is None else interpret
        if not self.interpret and Z % LANE:
            raise ValueError(
                f"compiled streaming stencils require the z axis to be a "
                f"multiple of the {LANE}-lane tile (got Z={Z}): Mosaic "
                f"rejects windowed DMAs with unaligned lane slices; use "
                f"the halo/roll path (or interpret mode) for this lattice")
        self._call = self._build()
        # applied eagerly (on one chip FiniteDifferencer's operators
        # are) the stencil is one program named after its kind, so a
        # trace shows jit_lap; a caller's program traces _apply directly
        self._program = _obs_memory.instrument_jit(
            self._apply,
            label=f"pallas.streaming{tuple(self.lattice_shape)}",
            name=kind or "stencil")

    # -- construction ------------------------------------------------------

    def _each_y_case(self, j, stream):
        """Run ``stream(pieces)`` for the y-window of y-block ``j`` (a
        grid index): ``pieces`` are the ``(src_y0, dst_y0, n)`` DMA
        pieces of the window; the slab-fed kernel's are ``(src, src_y0,
        dst_y0, n)`` with ``src`` ``None`` for the window operand,
        ``"lo"`` / ``"hi"`` for its y slab. With ``y_halo`` it is one
        contiguous piece
        of the HY-padded input. Otherwise the first and last y-block
        wrap periodically at the global y edges (two static pieces
        each) or, with ``y_slab``, take that ``HY``-row piece from the
        slab; a middle block is one piece at the dynamic, 8-aligned
        ``j * by - HY``; ``pl.when`` picks the case in-kernel."""
        Y = self.lattice_shape[1]
        by, byw = self.by, self.by + 2 * HY
        nby = Y // by

        def at(off):
            # int32: under x64 a raw program_id product lowers as i64,
            # which tpu.memref_slice rejects (test_tpu_lowering)
            return pl.multiple_of(
                jnp.asarray(j, jnp.int32) * jnp.int32(by) + jnp.int32(off),
                HY)

        if self.y_halo:
            return stream([(None, at(0), 0, byw)])
        if self.y_slab:
            low, high = ("lo", 0, 0, HY), ("hi", 0, by + HY, HY)
        else:
            low, high = (None, Y - HY, 0, HY), (None, 0, by + HY, HY)
        if nby == 1:
            return stream([low, (None, 0, HY, Y), high])
        cases = [
            (j == 0, [low, (None, 0, HY, by + HY)]),
            (j == nby - 1, [(None, Y - by - HY, 0, by + HY), high])]
        if nby > 2:
            cases.append(((j > 0) & (j < nby - 1),
                          [(None, at(-HY), 0, byw)]))
        for cond, pieces in cases:
            pl.when(cond)(lambda pieces=pieces: stream(pieces))

    def _make_specs(self):
        """(in_specs, out_specs, out_shapes) shared by both kernel modes:
        program ``(j, i)`` reads and writes block ``(i, j)`` of the
        full-lattice extras and outputs (``(i + 1, j)`` with
        ``x_inset``)."""
        X, Y, Z = self.lattice_shape
        bx, by = self.bx, self.by
        o = self.x_inset

        def block_spec(lead):
            nlead = len(lead)
            # an inset kernel's program i does x-block i + o; every
            # other kernel's index map holds no addition
            return pl.BlockSpec(
                tuple(lead) + (bx, by, Z),
                lambda j, i: (0,) * nlead + (i + o if o else i, j, 0))

        in_specs = [pl.BlockSpec(memory_space=pl.ANY)
                    for _ in range(len(self.win_defs) + self._nslabs)]
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)
                     for _ in self.scalar_names]
        in_specs += [block_spec(lead) for lead in self.extra_defs.values()]
        out_specs = [block_spec(lead) for lead in self.out_defs.values()]
        out_shapes = [
            jax.ShapeDtypeStruct(lead + (X, Y, Z),
                                 self.dtypes.get(n, self.dtype))
            for n, lead in self.out_defs.items()]
        for nt in self.sum_defs.values():
            # One (nt_pad8, LANE) accumulator tile per y-block, REVISITED
            # by that y-block's x programs (index map constant in i; the
            # terms live in lane 0).
            # Mosaic requires an output block's trailing two dims to be
            # (8, 128)-aligned or equal to the array's (measured on v5e:
            # a per-program (nt, 1, 1) block over (nt, nbx, 1) partials
            # fails to compile), so per-program partial columns are out;
            # the revisited block stays VMEM-resident across the
            # sequential x programs and each adds its block sum —
            # deterministic (TPU grids are sequential) and written back
            # to HBM once per y-block.
            ntp = -(-nt // HY) * HY
            out_specs.append(pl.BlockSpec((ntp, LANE), lambda j, i: (j, 0)))
            out_shapes.append(
                jax.ShapeDtypeStruct((self.grid[0] * ntp, LANE), self.dtype))
        return in_specs, out_specs, out_shapes

    @property
    def _slab_keys(self):
        """The slab operands of one group of windows, in operand
        order."""
        return ((("x", "lo"), ("x", "hi")) if self.x_slab else ()) + (
            (("y", "lo"), ("y", "hi")) if self.y_slab else ())

    @property
    def _slab_groups(self):
        """The windows whose slabs travel in one operand, stacked along
        the component axis in ``win_defs`` order: those of one storage
        dtype (all of them, but for reduced-precision carries). One
        ``ppermute`` a face moves a group, and the kernel's operand
        list stays short (the instruction's text is what a trace names
        the kernel by)."""
        groups = {}
        for n in self.win_defs:
            groups.setdefault(self.dtypes.get(n, self.dtype), []).append(n)
        return list(groups.values())

    @property
    def _nslabs(self):
        return len(self._slab_groups) * len(self._slab_keys)

    @property
    def slab_bytes(self):
        """The bytes the ``ppermute``s of :meth:`halo_slabs` move a call
        and chip: every window component's ``wh`` rows of each sharded
        axis' face, both directions (what leaves the chip: the zeros a
        y slab is grown by stay local); 0 without slab edges."""
        X, Y, Z = self.lattice_shape
        rows = (Y * Z if self.x_slab else 0) + (X * Z if self.y_slab else 0)
        return 2 * self.wh * rows * sum(
            n * self.dtypes.get(k, self.dtype).itemsize
            for k, n in self.win_defs.items())

    @property
    def halo(self):
        """Where the (x, y) edges of the window come from: ``"wrap"``
        (the local periodic wrap), ``"slab"`` (thin halo-slab operands),
        ``"padded"`` (a pre-padded copy of the window) or, in x,
        ``"inset"`` (the shard's own first and last block, which the
        kernel only reads)."""
        x, y = ("slab" if slab else "padded" if padded else "wrap"
                for slab, padded in ((self.x_slab, self.x_halo),
                                     (self.y_slab, self.y_halo)))
        return ("inset" if self.x_inset else x, y)

    @property
    def reread(self):
        """The call's modelled real-over-ideal bytes (:func:`reread`)
        at the kernel's own blocks."""
        x_reads = (self.bx + 2 * self.wh) / self.bx if self.x_halo else 1.0
        return reread(*self.n_arrays, self.by, x_reads)

    def _unpack_refs(self, refs):
        """``(f_refs, slab_refs, scalar_refs, extra_refs, out_refs, wins,
        sem)``; ``slab_refs`` is per window its group's dict ``(axis,
        side) -> ref`` (empty without slab edges) and the slice of the
        slabs' component axis that is this window's."""
        nw, ns, ne = (len(self.win_defs), len(self.scalar_names),
                      len(self.extra_defs))
        no = len(self.out_defs) + len(self.sum_defs)
        f_refs = refs[:nw]
        keys = self._slab_keys
        slab_refs = {}
        for g, names in enumerate(self._slab_groups):
            group = dict(zip(keys, refs[nw + g * len(keys):]))
            c0 = 0
            for n in names:
                slab_refs[n] = (group, pl.ds(c0, self.win_defs[n]))
                c0 += self.win_defs[n]
        slab_refs = [slab_refs[n] for n in self.win_defs]
        refs = refs[:nw] + refs[nw + self._nslabs:]
        scalar_refs = refs[nw:nw + ns]
        extra_refs = refs[nw + ns:nw + ns + ne]
        out_refs = refs[nw + ns + ne:nw + ns + ne + no]
        wins, sem = refs[-nw - 1:-1], refs[-1]
        return (f_refs, slab_refs, scalar_refs, extra_refs, out_refs, wins,
                sem)

    def _run_body(self, ws, scalar_refs, extra_refs, out_refs):
        X, Y, Z = self.lattice_shape
        taps = {n: Taps(w, self.h, self.bx, self.by, Z, self.interpret,
                        wh=self.wh)
                for n, w in zip(self.win_defs, ws)}
        if self.single_window:
            taps = next(iter(taps.values()))
        scalars = {n: r[0] for n, r in zip(self.scalar_names, scalar_refs)}
        extras = {n: r[...] for n, r in zip(self.extra_defs, extra_refs)}
        outs = self.body(taps, extras, scalars)
        nlat = len(self.out_defs)
        for n, ref in zip(self.out_defs, out_refs[:nlat]):
            ref[...] = outs[n].astype(ref.dtype)
        i = pl.program_id(1)
        for n, ref in zip(self.sum_defs, out_refs[nlat:]):
            self._accumulate_sums(ref, outs[n], self.sum_defs[n], i)

    @staticmethod
    def _accumulate_sums(ref, terms, nt, i):
        """Add this program's ``nt`` block sums into its y-block's
        revisited ``(nt_pad8, LANE)`` accumulator tile (terms in lane
        0), initialised at the y-block's first x program."""
        if len(terms) != nt:
            raise ValueError(f"body returned {len(terms)} sum terms, "
                             f"sum_defs declares {nt}")
        tile = _sum_tile(terms, ref.shape, ref.dtype)

        @pl.when(i == 0)
        def _():
            ref[...] = tile

        @pl.when(i > 0)
        def _():
            ref[...] = ref[...] + tile

    def _pallas_call(self, kernel, win_rows):
        """The one ``pallas_call`` of ``kernel`` over the ``(j, i)`` grid,
        with a ``win_rows``-row VMEM window per windowed input."""
        Z = self.lattice_shape[2]
        in_specs, out_specs, out_shapes = self._make_specs()
        # operands: windows, slabs, scalars, then the extras
        first_extra = (len(self.win_defs) + self._nslabs
                       + len(self.scalar_names))
        extras, outs = list(self.extra_defs), list(self.out_defs)
        return pl.pallas_call(
            kernel,
            grid=self.grid,
            input_output_aliases={
                first_extra + extras.index(n): outs.index(n)
                for n in self.in_place},
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            scratch_shapes=[
                pltpu.VMEM((C, win_rows, self.by + 2 * HY, Z),
                           self.dtypes.get(n, self.dtype))
                for n, C in self.win_defs.items()
            ] + [pltpu.SemaphoreType.DMA((2,))],
            interpret=self.interpret,
            compiler_params=_compiler_params(self.interpret),
        )

    def _build(self):
        if self.x_halo:
            return self._build_xhalo()
        h, bx = self.wh, self.bx
        nbx = self.lattice_shape[0] // bx
        # the x-blocks the programs do are o ... nbx - 1 - o, the ring
        # holds o - 1 ... nbx - o: past the shard's ends where o is 0
        # (the wrap, or the x slabs), inside it for an inset kernel
        o = self.x_inset
        R = _RING

        def kernel(*refs):
            f_refs, slab_refs, scalar_refs, extra_refs, out_refs, wins, \
                sem = self._unpack_refs(refs)
            j, p = pl.program_id(0), pl.program_id(1)
            i = p + o if o else p   # the x-block this program does

            def stream(ypieces):
                """Bring x-block i's window of this y-block into the
                ring; the ring is primed anew at every y-block's first
                program."""
                def dmas(blk, slot):
                    # a block of the window operand fills its slot. With
                    # x slabs ring block -1 is the low slab and block
                    # nbx the high one (static indices: a traced one is
                    # kept inside the shard by its pl.when), whose h
                    # rows land where the neighbouring block reads them:
                    # the slot's last rows (low) or first
                    xe = None
                    if self.x_slab and isinstance(blk, int):
                        xe = "lo" if blk < 0 else "hi" if blk >= nbx else None
                    b = blk if o else _rem(blk + nbx, nbx)
                    out = []
                    for f_ref, (slabs, comps), win in zip(
                            f_refs, slab_refs, wins):
                        for ye, sy0, dy0, n in ypieces:
                            if xe is None:
                                src = slabs["y", ye] if ye else f_ref
                                rows = (pl.ds(b * bx, bx),
                                        pl.ds(slot * bx, bx))
                            elif ye:
                                continue  # a corner: never read
                            else:
                                src = slabs["x", xe]
                                off = bx - h if xe == "lo" else 0
                                rows = (pl.ds(0, h),
                                        pl.ds(slot * bx + off, h))
                            lead = slice(None) if src is f_ref else comps
                            out.append(pltpu.make_async_copy(
                                src.at[lead, rows[0], pl.ds(sy0, n), :],
                                win.at[:, rows[1], pl.ds(dy0, n), :],
                                sem.at[_rem(slot, 2)]))
                    return out

                def start(blk, slot):
                    for d in dmas(blk, slot):
                        d.start()

                def wait(blk, slot):
                    for d in dmas(blk, slot):
                        d.wait()

                if nbx - 2 * o <= 2:
                    # all blocks (o-1..nbx-o) fit in the ring: fetch at
                    # the first program
                    @pl.when(p == 0)
                    def _():
                        for blk in range(o - 1, nbx - o + 1):
                            start(blk, (blk + R) % R)
                            wait(blk, (blk + R) % R)
                else:
                    @pl.when(p == 0)
                    def _():
                        for blk in (o - 1, o, o + 1):
                            start(blk, (blk + R) % R)
                            wait(blk, (blk + R) % R)
                        start(o + 2, (o + 2) % R)

                    @pl.when(p > 0)
                    def _():
                        if self.x_slab:
                            # the block after the shard's last is the
                            # high slab: started by the last program
                            # but one, awaited by the last
                            @pl.when(i < nbx - 1)
                            def _():
                                wait(i + 1, _rem(i + 1, R))

                            @pl.when(i == nbx - 1)
                            def _():
                                wait(nbx, nbx % R)

                            @pl.when(i < nbx - 2)
                            def _():
                                start(i + 2, _rem(i + 2, R))

                            @pl.when(i == nbx - 2)
                            def _():
                                start(nbx, nbx % R)
                        else:
                            wait(i + 1, _rem(i + 1, R))

                            @pl.when(i < nbx - 1 - o)
                            def _():
                                start(i + 2, _rem(i + 2, R))

            self._each_y_case(j, stream)

            sl = [_rem(i + db + R, R) for db in (-1, 0, 1)]
            ws = []
            for win in wins:
                prev = win[:, pl.ds(sl[0] * bx + bx - h, h), :, :]
                cur = win[:, pl.ds(sl[1] * bx, bx), :, :]
                nxt = win[:, pl.ds(sl[2] * bx, h), :, :]
                ws.append(jnp.concatenate([prev, cur, nxt], axis=1))
            self._run_body(ws, scalar_refs, extra_refs, out_refs)

        return self._pallas_call(kernel, R * bx)

    def _build_xhalo(self):
        """Sharded-x variant: input rows are pre-padded ``(C, X+2wh, Y,
        Z)``; each program DMAs its own haloed window (double-buffered
        within a y-block)."""
        h, bx = self.wh, self.bx
        bxw = bx + 2 * h
        nbx = self.grid[1]

        def kernel(*refs):
            f_refs, _, scalar_refs, extra_refs, out_refs, wins, sem = \
                self._unpack_refs(refs)
            j, i = pl.program_id(0), pl.program_id(1)
            slot = _rem(i, 2)

            def stream(ypieces):
                def dmas(ii, buf):
                    # int32 starts: under x64 a raw program_id product
                    # lowers as i64, which tpu.memref_slice rejects
                    # (test_tpu_lowering)
                    x0 = jnp.asarray(ii, jnp.int32) * jnp.int32(bx)
                    # _rem also canonicalizes python-int slots to i32: a
                    # bare python index on the semaphore ref lowers as
                    # i64 under x64
                    return [pltpu.make_async_copy(
                        f_ref.at[:, pl.ds(x0, bxw), pl.ds(sy0, n), :],
                        win.at[:, pl.ds(buf * bxw, bxw), pl.ds(dy0, n), :],
                        sem.at[_rem(buf, 2)])
                        for f_ref, win in zip(f_refs, wins)
                        for _, sy0, dy0, n in ypieces]

                @pl.when(i == 0)
                def _():
                    for d in dmas(0, 0):
                        d.start()

                for d in dmas(i, slot):
                    d.wait()

                if nbx > 1:
                    @pl.when(i < nbx - 1)
                    def _():
                        for d in dmas(i + 1, _rem(i + 1, 2)):
                            d.start()

            self._each_y_case(j, stream)

            ws = [win[:, pl.ds(slot * bxw, bxw), :, :] for win in wins]
            self._run_body(ws, scalar_refs, extra_refs, out_refs)

        return self._pallas_call(kernel, 2 * bxw)

    def with_lattice(self, lattice_shape, bx=None, by=None, padded=False,
                     kind=None, inset=False):
        """A new :class:`StreamingStencil` sharing this one's body,
        definitions, dtypes and halo mode, built for a different local
        lattice shape — how :class:`OverlapStreamingStencil` derives the
        interior and shell kernels from the full-block kernel, each
        under a ``kind`` of its own (this kernel's without one). It
        asks for the shell ``padded`` (a slab-fed axis becomes a
        pre-padded one) and for the interior ``inset``: this kernel's
        own lattice, the x axis neither slab-fed nor padded, the grid
        short of the first and last x-block (``x_inset``). Raises
        ``ValueError`` when the new shape admits no feasible
        blocking."""
        return StreamingStencil(
            lattice_shape, self.win_defs, self.h, self.body,
            self.out_defs, extra_defs=self.extra_defs,
            scalar_names=self.scalar_names, dtype=self.dtype,
            bx=bx, by=by, x_inset=inset,
            x_halo=not inset and (self.x_halo or (padded and self.x_slab)),
            y_halo=self.y_halo or (padded and self.y_slab),
            x_slab=self.x_slab and not padded and not inset,
            y_slab=self.y_slab and not padded,
            interpret=self.interpret, sum_defs=self.sum_defs,
            dtypes=self.dtypes, win_halo=self.wh, stages=self.stages,
            kind=kind or self.kind, in_place=self.in_place)

    # -- invocation --------------------------------------------------------

    def halo_slabs(self, decomp, f):
        """The ``slabs`` of :meth:`__call__` for the local shard(s) ``f``
        (as ``__call__`` takes them), filled over the mesh: the
        ``h``-row faces the neighbours owe, the windows' stacked along
        the component axis and moved by one ``ppermute`` a face
        (``decomp.exchange_slabs``: what ``pad_with_halos`` moves over
        the interconnect, without the padded copy), the y pair padded
        with local zeros to the ``HY`` rows a window piece has. MUST be
        called inside a ``shard_map`` over ``decomp``'s mesh."""
        wins = f if isinstance(f, dict) else {next(iter(self.win_defs)): f}
        slabs = []
        for names in self._slab_groups:
            group = [wins[n] for n in names]
            slabs.append({})
            if self.x_slab:
                slabs[-1]["x"] = decomp.exchange_slabs(group, 0, self.wh)
            if self.y_slab:
                slabs[-1]["y"] = decomp.exchange_slabs(group, 1, self.wh,
                                                       pad_to=HY)
        return slabs

    def __call__(self, f, scalars=None, extras=None, slabs=None):
        """Apply to the windowed input(s) ``f`` — a single array (shape
        ``(n_comp, X, Y, Z)``, or x-padded ``(n_comp, X+2h, Y, Z)`` with
        ``x_halo``) or a dict name -> array matching ``win_defs``. A
        slab-fed kernel also takes ``slabs``, as :meth:`halo_slabs`
        makes them: a list with, for each group of windows of one
        storage dtype (one group, but for reduced-precision carries),
        ``{"x": (low, high), "y": (low, high)}`` for its sharded axes,
        shapes ``(n_comp, h, Y, Z)`` and ``(n_comp, X, HY, Z)`` with the
        group's windows stacked along ``n_comp``. Returns a dict of
        named full-lattice outputs."""
        scalars = scalars or {}
        extras = extras or {}
        if isinstance(f, dict):
            win_args = [f[n] for n in self.win_defs]
        else:
            win_args = [f]
        for group in slabs if self._nslabs else ():
            for axis, side in self._slab_keys:
                win_args.append(group[axis][side == "hi"])
        scalar_args = [jnp.asarray(scalars[n], self.dtype).reshape(1)
                       for n in self.scalar_names]
        extra_args = [extras[n] for n in self.extra_defs]
        # inside someone's program the call is traced as it is;
        # dispatched eagerly it goes as the named program
        apply = self._apply if in_jax_trace() else self._program
        return apply(*win_args, *scalar_args, *extra_args)

    def _apply(self, *args):
        """The call under this kernel's dispatch scope (entered here,
        because a scope round an eager dispatch does not reach the
        program), and the sums finished over the y-blocks."""
        with trace_scope(self._scope):
            res = self._call(*args)
        nlat = len(self.out_defs)
        out = dict(zip(self.out_defs, res))
        for n, tiles in zip(self.sum_defs, res[nlat:]):
            # each y-block's tile already holds the sum over its x
            # programs; finish over the y-blocks in order and strip the
            # (nt_pad8, LANE) tile padding
            nt = self.sum_defs[n]
            ntp = tiles.shape[0] // self.grid[0]
            out[n] = sum(tiles[jj * ntp:jj * ntp + nt, 0]
                         for jj in range(self.grid[0]))
        return out


class OverlapInfeasible(ValueError):
    """Why a kernel keeps its single launch: ``reason`` is the word an
    ``overlap_plan`` event carries (``sums``, ``y_sharded``,
    ``unsharded``, ``thin``, ``blocking``)."""

    def __init__(self, reason, message):
        super().__init__(message)
        self.reason = reason


class OverlapStreamingStencil:
    """Interior + x-shell split of a streaming stencil kernel for
    communication/computation overlap on x-sharded lattices.

    The single launch makes the whole kernel wait on the ``ppermute``d
    x slabs (a custom call waits for all of its operands). Here the
    full-block kernel is rebuilt (same body, same definitions —
    :meth:`StreamingStencil.with_lattice`) as three launches over an x
    partition of the local block:

    - *interior*: the ring kernel over the RAW local block with its
      grid inset by one x-block of ``bx = h`` rows at either end
      (``x_inset``): rows ``h ... X - h``, every window row DMA'd once
      as in the single launch, no dependence on the collectives, so it
      runs while they are in flight. Its outputs are the full local
      lattice, the ``h`` rows at either end left unwritten;
    - two *x shells*, lattice ``(h, Y, Z)`` with ``bx = h``, the
      pre-padded ``x_halo`` variant: their inputs are ``concat(slab,
      first/last 2h local rows)``, computed once the slabs land.

    The shells' ``h``-row outputs are put into the interior's outputs in
    place (``dynamic_update_slice``: the interior's result is a buffer
    XLA owns), so nothing the size of the lattice is copied round the
    launches; the extras go to the interior whole (its index maps skip
    the edge blocks) and to the shells as ``h``-row slices. An extra
    the kernel writes in place (``in_place``) is aliased by the
    interior alone: its edge rows still hold the old values when the
    shells' slices are taken, which comes first in program order.
    Bit-exact with the single launch: every output element sees
    identical tap offsets and per-element arithmetic (blocking never
    enters the math).

    The two kernels carry kinds of their own, ``<kind>_interior`` and
    ``<kind>_shell`` (``interior`` / ``shell`` for a kernel of no
    stated kind), so a TPU trace names their launches
    ``%pallas_stencil_<kind>_interior.N`` / ``..._shell.N`` and tells
    them from a single launch of the kind; :attr:`plan` says what was
    built.

    Feasibility (:class:`OverlapInfeasible`, a ``ValueError``,
    otherwise — callers fall back to the single launch, :meth:`plan_for`
    does it for them and says so in an event): x-sharded windows only
    (``x_slab`` or ``x_halo`` set, y whole — an h-thin y shell has no
    legal sublane blocking), no ``sum_defs`` (the region split would
    change the deterministic reduction order), ``X >= 3h`` so an
    interior exists, and x-blocks of ``h`` rows at the full-block
    kernel's ``by`` (``blocking``: ``h`` has to divide ``X`` and be the
    window's halo).
    """

    def __init__(self, st, h):
        from pystella_tpu.parallel.overlap import MIN_INTERIOR_FACTOR
        if st.sum_defs:
            raise OverlapInfeasible(
                "sums",
                "sum outputs: the interior/shell split would change the "
                "deterministic reduction order")
        if st.y_halo or st.y_slab:
            raise OverlapInfeasible(
                "y_sharded",
                "overlap split supports x-sharded windows only (an "
                "h-thin y shell has no legal sublane blocking)")
        if not (st.x_halo or st.x_slab):
            raise OverlapInfeasible(
                "unsharded",
                "overlap split supports x-sharded windows only")
        X, Y, Z = st.lattice_shape
        self.h = int(h)
        if X < MIN_INTERIOR_FACTOR * self.h:
            raise OverlapInfeasible(
                "thin",
                f"local x extent {X} thinner than "
                f"{MIN_INTERIOR_FACTOR}*h: no interior to hide the "
                "transfer behind")
        self.st = st

        def part(name):
            return name if st.kind is None else f"{st.kind}_{name}"

        # both at x-blocks of h rows: the rows the interior's inset
        # leaves are then exactly the shells'
        try:
            self.st_interior = st.with_lattice(
                st.lattice_shape, bx=self.h, by=st.by, inset=True,
                kind=part("interior"))
            self.st_shell = st.with_lattice(
                (self.h, Y, Z), bx=self.h, by=st.by, padded=True,
                kind=part("shell"))
        except ValueError as e:
            raise OverlapInfeasible("blocking", str(e)) from e

    @classmethod
    def plan_for(cls, st, h, enabled=True, label=None):
        """The split of the full-block kernel ``st`` on a sharded mesh
        where the policy asks for it (``enabled``:
        :func:`pystella_tpu.parallel.overlap.enabled`) and the kernel
        admits it, else ``None`` (the caller keeps the single launch);
        either way one ``overlap_plan`` event says which, for ``label``
        (the consumer's class): ``path`` ``"split"`` with :attr:`plan`'s
        fields, or ``"single"`` with the ``reason`` (``off``: the
        policy; ``sums``, ``y_sharded``, ``thin``, ``blocking``:
        :class:`OverlapInfeasible`'s)."""
        split, plan = None, {"path": "single", "reason": "off"}
        if enabled:
            try:
                split = cls(st, h)
                plan = split.plan
            except OverlapInfeasible as e:
                plan["reason"] = e.reason
        _events.emit("overlap_plan", kernel=st.kind,
                     local_shape=list(st.lattice_shape), label=label,
                     **plan)
        return split

    @property
    def stitch_bytes(self):
        """Ideal bytes, read plus written, of the copies one call still
        places round its three launches: every window's two shell
        inputs concatenated from a slab and ``2h`` local rows, every
        lattice extra's two ``h``-row slices for the shells, every
        output's two ``h``-row updates. XLA may fuse or elide some of
        them; a kernel writes none of it, and nothing the size of the
        lattice is among them."""
        st, h = self.st, self.h
        Y, Z = st.lattice_shape[1:]

        def rows(name, lead, n):
            comps = int(np.prod(lead)) if lead else 1
            return comps * n * Y * Z * st.dtypes.get(name, st.dtype).itemsize

        copied = sum(rows(n, (c,), 2 * 3 * h)
                     for n, c in st.win_defs.items())
        copied += sum(rows(n, lead, 2 * h)
                      for defs in (st.extra_defs, st.out_defs)
                      for n, lead in defs.items())
        return 2 * copied

    @property
    def plan(self):
        """What the split built, as an ``overlap_plan`` event carries
        it: per kernel the lattice its launch updates, blocking, grid,
        where its x edges come from (``halo``: the interior's
        ``"inset"``, the shells' ``"padded"``) and modelled ``reread``
        (the interior's is the single launch's: the ring reads every
        row once); how the pieces meet (``stitch``: ``"in_place"``) and
        :attr:`stitch_bytes`."""
        def built(st):
            return {"kernel": st.kind,
                    "lattice": [st.grid[1] * st.bx, *st.lattice_shape[1:]],
                    "bx": st.bx, "by": st.by, "grid": list(st.grid),
                    "halo": st.halo[0], "reread": st.reread}
        return {"path": "split", "interior": built(self.st_interior),
                "shell": built(self.st_shell), "stitch": "in_place",
                "stitch_bytes": self.stitch_bytes}

    @staticmethod
    def _slice_x(tree, s, e):
        if tree is None:
            return None
        out = {}
        for n, a in tree.items():
            nd = getattr(a, "ndim", 0)
            if nd < 3:
                out[n] = a
            else:
                out[n] = lax.slice_in_dim(a, s, e, axis=nd - 3)
        return out

    def __call__(self, f, decomp, scalars=None, extras=None):
        """Run the three launches inside a ``shard_map`` body. ``f`` is
        the RAW (unpadded) local window input — a single ``(C, X, Y,
        Z)`` array or a dict matching ``win_defs``; ``decomp`` issues
        the slab ``ppermute``s. Returns the same dict of full-block
        outputs as the single ``StreamingStencil.__call__``."""
        h = self.h
        X = self.st.lattice_shape[0]
        single = not isinstance(f, dict)
        wins = {"f": f} if single else f

        def xsl(a, s, e):
            return lax.slice_in_dim(a, s, e, axis=a.ndim - 3)

        def put(a, rows, x0):
            return lax.dynamic_update_slice_in_dim(a, rows, x0,
                                                   axis=a.ndim - 3)

        with trace_scope("halo_overlap"):
            # slab ppermutes first: program order hands the scheduler
            # the dependence-free interior launch to hide them behind
            with trace_scope("halo_overlap_exchange"):
                slabs = {n: decomp.exchange_slabs(a, 0, h)
                         for n, a in wins.items()}
            with trace_scope("halo_overlap_shells"):
                # the shells' h rows of every extra, taken before the
                # interior's call: an extra it writes in place holds
                # the old values in its edge rows only until then
                low_extras = self._slice_x(extras, 0, h)
                high_extras = self._slice_x(extras, X - h, X)
            with trace_scope("halo_overlap_interior"):
                out = self.st_interior(f, scalars=scalars, extras=extras)
            with trace_scope("halo_overlap_shells"):
                low_in = {n: lax.concatenate(
                    [slabs[n][0], xsl(a, 0, 2 * h)],
                    dimension=a.ndim - 3) for n, a in wins.items()}
                high_in = {n: lax.concatenate(
                    [xsl(a, X - 2 * h, X), slabs[n][1]],
                    dimension=a.ndim - 3) for n, a in wins.items()}
                low_out = self.st_shell(
                    low_in["f"] if single else low_in, scalars=scalars,
                    extras=low_extras)
                high_out = self.st_shell(
                    high_in["f"] if single else high_in, scalars=scalars,
                    extras=high_extras)
                # the interior's outputs are whole-lattice buffers of
                # its own with these rows unwritten: filled in place
                out = {n: put(put(a, low_out[n], 0), high_out[n], X - h)
                       for n, a in out.items()}
        return out
