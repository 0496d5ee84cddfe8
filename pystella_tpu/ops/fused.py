"""Fully-fused Pallas Runge-Kutta stages for Klein-Gordon-form systems.

The reference's hot loop executes, per RK stage, a stencil kernel
(Laplacian) followed by an elementwise RK-stage kernel
(/root/reference/examples/scalar_preheating.py:258-266, step.py:482-488) —
two full passes over HBM plus a materialized Laplacian. On TPU the entire
stage fits in one streaming Pallas kernel: each lattice block is read once,
the finite-difference Laplacian is computed from the in-VMEM window, the
Klein-Gordon right-hand side (including the symbolic ``dV/df`` evaluated
in-register) and the 2N-storage Runge-Kutta update are applied, and the four
state arrays are written back — one read + one write of the state for the
whole stage.

:class:`FusedScalarStepper` goes one further by default (``pair_stages``):
``step()`` runs *two* consecutive stages per kernel. The intermediate
field is a pointwise axpy of (f, kf, dfdt), so the second stage's
Laplacian composes from the same ring windows at offsets ``<= h`` — no
wider halos, and the per-stage HBM traffic halves again (the measured
512**3 hot loop went from ~141 to ~89 ms/step on v5e). The pairing is
bit-exact against two single-stage kernels (same arithmetic sequence;
``tests/test_fused.py::test_pair_stages_match_single_stages``).

Two steppers:

- :class:`FusedScalarStepper` — ``ScalarSector`` systems
  (``f'' = lap f - 2 H f' - a^2 dV/df``, reference sectors.py:117-131).
- :class:`FusedPreheatStepper` — adds ``TensorPerturbationSector``
  gravitational waves (``h_ij'' = lap h_ij - 2 H h_ij' + 16 pi S_ij``,
  sectors.py:183-204); the tensor source's field gradients are computed
  from the same VMEM window as the scalar Laplacian.

Both expose the :class:`~pystella_tpu.step.Stepper` interface (``step`` /
per-stage ``__call__`` / ``stage``) with a ``(state, k)`` carry, and accept
any low-storage tableau class (``LowStorageRK54`` etc.).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from pystella_tpu import field as _field
from pystella_tpu import step as _step
from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs import metrics as _metrics
from pystella_tpu.obs.scope import host_span, trace_scope
from pystella_tpu.ops.derivs import (
    _grad_coefs, _lap_coefs, stencil_radius)
from pystella_tpu.ops.pallas_stencil import (
    ResidentStencil, StreamingStencil,
    grad_from_taps as _grad_from_taps, lap_from_taps as _lap_from_taps,
    memo_taps as _memo_taps,
)

__all__ = ["FusedScalarStepper", "FusedPreheatStepper", "CARRY_SCOPE"]

#: The registered carry-quantization point. Every ``carry_dtype`` downcast
#: the steppers emit is wrapped in this named scope, so the dataflow lint
#: tier (``pystella_tpu.lint.dataflow``) can tell a sanctioned RK-carry
#: quantization from an accidental mid-chain precision loss: a float
#: narrowing whose HLO scope path does not pass through this scope is a
#: POLICY_BF16_ACC32 violation.
CARRY_SCOPE = "carry_quantize"


def _carry_cast(x, dtype):
    """The ONE sanctioned narrowing: cast ``x`` to the carry dtype
    under the :data:`CARRY_SCOPE` named scope, so the lowered module's
    convert carries the scope path the dataflow lint tier keys on."""
    with jax.named_scope(CARRY_SCOPE):
        return x.astype(dtype)


def _quantize_carries(body, dtypes):
    """Wrap a stage ``body`` so its carry-named outputs are cast to the
    carry dtype via :func:`_carry_cast`. The stencil kernel's own
    ``astype(ref.dtype)`` on store then becomes an identity, and every
    f32->bf16 convert in the lowered module is scope-annotated."""
    def wrapped(taps, extras, scalars):
        outs = dict(body(taps, extras, scalars))
        for n, dt in dtypes.items():
            if n in outs:
                outs[n] = _carry_cast(outs[n], dt)
        return outs
    return wrapped


class FusedScalarStepper(_step.Stepper):
    """One-kernel-per-stage low-storage RK for a :class:`ScalarSector`.

    :arg sector: a :class:`~pystella_tpu.ScalarSector`.
    :arg decomp: :class:`~pystella_tpu.DomainDecomposition`; the lattice
        may be sharded along x and/or y (``proc_shape (px, py, 1)``) —
        each device pads its block with ``lax.ppermute`` halos and runs
        the fused kernel on its local block inside ``shard_map`` (the
        sharded-y window pad is the 8-aligned ``HY``, see
        :class:`~pystella_tpu.ops.pallas_stencil.StreamingStencil`).
        The z axis (the VMEM lane dimension) stays whole per device; use
        the generic steppers for z-sharded meshes.
    :arg grid_shape: the *global* lattice shape (divided over the mesh
        when sharded).
    :arg dx: lattice spacing (scalar or 3-tuple).
    :arg halo_shape: stencil radius ``h``.
    :arg tableau: a :class:`~pystella_tpu.LowStorageRKStepper` subclass
        providing ``_A``/``_B``/``_C`` and ``num_stages``.
    :arg bx, by: explicit blocking for the single-stage kernel (default:
        :func:`~pystella_tpu.ops.pallas_stencil.choose_blocks`).
    :arg pair_stages: when True (default) ``step()`` fuses consecutive
        stage pairs into one kernel each (see module docstring); the
        per-stage protocol (``stage()`` / ``__call__``) always runs
        single-stage kernels. Set False to force one kernel per stage in
        ``step()`` too.
    :arg pair_bx, pair_by: explicit blocking for the stage-pair kernel
        (its VMEM footprint is ~2x the single-stage kernel's, so it picks
        its own default blocking; ``bx``/``by`` do not apply to it).
    :arg chunk_stages: temporal-blocking chunk depth — an even number
        >= 4 of consecutive RK stages advanced by ONE kernel invocation
        while the lattice block stays in VMEM (``step()``/``multi_step``
        dispatch chunk kernels first, then pairs, then singles). Each
        composed stage pair widens the window halo by ``h`` (redundant
        halo-region recompute traded for eliminated HBM round trips —
        per-stage lattice traffic halves again vs the pair tier: 4 ->
        2 array transfers/stage for the scalar system). Bit-exact
        against the sequence of pair-stage kernels it replaces (the
        deeper intermediate fields compose through the identical
        per-element arithmetic the pair kernels materialize).
        ``0`` (default) keeps the pair tier. Sharded meshes, window
        halos beyond the 8-aligned y pad, and VMEM-infeasible shapes
        degrade to pair kernels with a ``kernel_fallback`` event (the
        pair tier's own fallbacks to single-stage/XLA below it are
        unchanged).
    :arg chunk_bx, chunk_by: explicit blocking for the chunk kernel.
    """

    #: chunk support (the scalar+GW subclass overrides: its chunk body
    #: is not implemented — requests degrade to the pair tier with a
    #: kernel_fallback event)
    _chunk_supported = True

    def __init__(self, sector, decomp, grid_shape, dx, halo_shape=2,
                 tableau=None, dtype=jnp.float32, bx=None, by=None,
                 dt=None, pair_stages=True, pair_bx=None, pair_by=None,
                 interpret=None, donate=False, resident=None,
                 carry_dtype=None, overlap=None,
                 chunk_stages=0, chunk_bx=None, chunk_by=None):
        tableau = tableau or _step.LowStorageRK54
        self._A = tableau._A
        self._B = tableau._B
        self._C = tableau._C
        self.num_stages = tableau.num_stages
        self.expected_order = tableau.expected_order
        self.dt = dt
        self.sector = sector
        self.decomp = decomp
        if decomp.proc_shape[2] != 1:
            raise NotImplementedError(
                "fused steppers support x/y sharding (proc_shape "
                "(px, py, 1)); the z axis is the VMEM lane dimension "
                "(kept whole per device) — use the generic LowStorageRK "
                "steppers with FiniteDifferencer for z-sharded meshes "
                "(pystella_tpu.advise_shapes lists which meshes keep "
                "the fused tier available)")
        self._px = decomp.proc_shape[0]
        self._py = decomp.proc_shape[1]
        # overlapped halo path: issue the slab ppermutes first, run the
        # interior kernel while they fly, stitch the x shells when the
        # halos land (bit-exact with the padded launch; see
        # pystella_tpu.parallel.overlap). Resolved once here: per-call
        # kwarg > PYSTELLA_HALO_OVERLAP > auto (on for sharded meshes).
        from pystella_tpu.parallel import overlap as _overlap
        self._overlap = _overlap.enabled(decomp, override=overlap)
        self.grid_shape = tuple(grid_shape)
        if np.isscalar(dx):
            dx = (dx,) * 3
        self.dx = tuple(float(d) for d in dx)
        self.h = stencil_radius(halo_shape, type(self).__name__)
        self.dtype = jnp.zeros((), dtype).dtype

        F = sector.nscalars
        self.F = F
        f = sector.f
        V = sector.potential(f)
        self._V = V
        self._dvdf = [_field.diff(V, f[i]) for i in range(F)]

        self.local_shape = decomp.rank_shape(self.grid_shape)
        self._pair_stages = bool(pair_stages) and self.num_stages >= 2
        self._pair_bx, self._pair_by = pair_bx, pair_by
        self._pair_call = None  # set by _build_kernels when pairing
        self._interpret = interpret
        self._resident = resident
        self._donate = bool(donate)
        # mixed-precision RK carries (e.g. jnp.bfloat16): the 2N-storage
        # k arrays are STORED at reduced precision while all in-kernel
        # arithmetic stays in ``dtype`` (taps promote; outputs cast on
        # write). Halves the carry half of the state footprint — the
        # difference between the 512**3 GW system fitting one chip
        # (~12.4 GB vs 16.5 GB f32, doc/performance.md "Memory") — at a
        # measured accuracy cost bounded by the carry quantization
        # (tests/test_fused.py::test_bf16_carry_accuracy; NOT for
        # convergence-order-critical runs).
        self._carry_dtype = (None if carry_dtype is None
                             else jnp.zeros((), carry_dtype).dtype)
        self._chunk_requested = int(chunk_stages or 0)
        if self._chunk_requested and (self._chunk_requested % 2
                                      or self._chunk_requested < 4):
            raise ValueError(
                f"chunk_stages must be an even number >= 4 (got "
                f"{self._chunk_requested}); depth 2 is the pair tier "
                "(pair_stages=True)")
        self._chunk_bx, self._chunk_by = chunk_bx, chunk_by
        self._chunk_call = None   # set by _maybe_build_chunk
        self._chunk_st = None
        self._chunk_depth = 0
        self._tier_emitted = set()  # entrypoints that reported their tier

        self._build_kernels(bx, by)
        self._maybe_build_chunk()

        # jitted whole-step (one XLA computation, all stages fused).
        # ``donate=True`` donates the input state buffers (halves the
        # eager-step peak-HBM footprint; the caller must not reuse the
        # state afterwards — see doc/performance.md "Memory").
        self._jit_step = _obs_memory.instrument_jit(
            self._step_impl, label=f"fused.{type(self).__name__}.step",
            donate_argnums=(0,) if donate else ())
        self._jit_multi = {}  # (nsteps, seq struct) -> jitted multi_step
        self._jit_coupled = {}  # (nsteps, grid_size, mpl, pair) -> jitted
        self._es_call = None  # lazily built energy-emitting stage kernel
        self._pes_call = None  # lazily built energy-emitting pair kernel
        self._pes_tried = False

    @property
    def _halo_kw(self):
        """Shared StreamingStencil kwargs: slab edges on the axes the
        mesh shards (the others wrap locally), and the interpret-mode
        override."""
        return {"x_slab": self._px > 1, "y_slab": self._py > 1,
                "interpret": self._interpret}

    #: array names that hold 2N-storage RK carries (reduced-precision
    #: storage candidates; subclasses extend)
    _carry_names = frozenset({"kf", "kdfdt", "kdfp"})

    def _emit_block_choice(self, kind, st, source):
        """The record of what a kernel build chose: ``source`` is
        ``"explicit"`` (pinned by the caller), ``"heuristic"``
        (``choose_blocks``) or ``"split"`` (the interior or shell
        kernel of the overlap split, ``kernel`` ``<kind>_interior`` /
        ``<kind>_shell``: the full-block kernel's ``by`` and ``bx =
        h`` for both); ``halo`` is where its (x, y) edges come from,
        ``"wrap"``, (a sharded axis) ``"slab"`` or, in x, the split's
        ``"inset"`` (the interior: the ring over the raw shard, the
        first and last x-block only read) and ``"padded"`` (the
        shells); a kernel with a ``"slab"`` edge also says
        ``slab_bytes``, what its ``ppermute``s move a call and chip
        (``StreamingStencil.slab_bytes``); ``in_place``
        names the extras it writes over (the per-stage protocol's
        ``stage`` kernel under ``donate=True``, else none); ``reread``
        is the modelled real-over-ideal byte ratio of a call at that
        ``by`` (``pallas_stencil.reread``; a resident kernel has
        none); ``h`` is the stencil radius and ``taps`` the shifted
        values a site and component's derivatives take in the body,
        ``6 h + 1`` for the Laplacian of each stage the kernel fuses
        (two in a pair kernel, whose ``stages`` says 1: that figure is
        the VMEM model's, which counts a pair's temporaries as one
        stage's): what the kernel's arithmetic scales with where its
        bytes do not."""
        stages = getattr(st, "stages", 1)
        whole = kind.removesuffix("_interior").removesuffix("_shell")
        fused = 2 if whole in ("pair", "coupled_pair") else stages
        halo = list(getattr(st, "halo", ("wrap", "wrap")))
        # what a slab-fed kernel's exchange moves between chips a call
        slab = {"slab_bytes": st.slab_bytes} if "slab" in halo else {}
        _events.emit(
            "block_choice", kernel=kind, **slab,
            stencil=type(st).__name__,
            bx=getattr(st, "bx", None), by=getattr(st, "by", None),
            grid=getattr(st, "grid", None),
            reread=getattr(st, "reread", None),
            win_halo=getattr(st, "wh", None),
            h=self.h, taps=fused * (6 * self.h + 1),
            stages=stages,
            halo=halo,
            in_place=list(getattr(st, "in_place", ())),
            source=source, local_shape=list(self.local_shape),
            label=type(self).__name__)

    def _build_stencil(self, win_defs, body, out_defs, extra_defs,
                       scalar_names, bx=None, by=None, sum_defs=None,
                       kind="stage", win_halo=None, stages=1,
                       in_place=()):
        """A stage kernel: streaming VMEM-ring windows when the lattice
        admits them, else (single-device) the whole-lattice-resident
        all-roll kernel — the Z < 128 small-lattice tier (VERDICT r3
        #4). ``resident=True``/``False`` at construction forces the
        choice. The blocking is the caller's ``bx``/``by`` or, without
        them, the ``choose_blocks`` heuristic's; a ``block_choice``
        event records which. ``in_place`` names the extras a streaming
        kernel writes over (the resident tier writes fresh outputs)."""
        dtypes = None
        if self._carry_dtype is not None:
            names = (set(win_defs) | set(extra_defs or {})
                     | set(out_defs)) & self._carry_names
            dtypes = {n: self._carry_dtype for n in names}
            out_carries = set(out_defs) & self._carry_names
            if out_carries:
                body = _quantize_carries(
                    body, {n: self._carry_dtype for n in out_carries})
        source = ("heuristic" if bx is None and by is None
                  else "explicit")
        common = dict(extra_defs=extra_defs, scalar_names=scalar_names,
                      dtype=self.dtype, sum_defs=sum_defs, dtypes=dtypes)
        streaming_error = None
        if not self._resident:
            try:
                st = StreamingStencil(
                    self.local_shape, win_defs, self.h, body, out_defs,
                    bx=bx, by=by, win_halo=win_halo, stages=stages,
                    kind=kind, in_place=in_place, **self._halo_kw,
                    **common)
                self._emit_block_choice(kind, st, source)
                return st
            except ValueError as e:
                # no resident fallback for sharded lattices (resident
                # taps assume LOCAL periodicity) or explicitly pinned
                # blockings (resident has no blocking to pin)
                if (self._resident is False or self._px > 1
                        or self._py > 1 or bx is not None
                        or by is not None):
                    raise
                streaming_error = e
        try:
            st = ResidentStencil(self.local_shape, win_defs, self.h, body,
                                 out_defs, interpret=self._interpret,
                                 stages=stages, **common)
        except ValueError as e:
            if streaming_error is None:
                raise
            # the streaming builder's reason is the one a caller can act
            # on (which kernel, how many window components); that a
            # lattice this size is not VMEM-resident either goes without
            # a size in megabytes
            raise ValueError(
                f"{kind} kernel: {streaming_error}; nor is the lattice "
                "small enough for the whole-lattice-resident tier") from e
        self._emit_block_choice(kind, st, source)
        return st

    def _try_pair_stencil(self, make):
        """Build the stage-pair kernel, degrading to single-stage kernels
        when no blocking of the (much wider) pair window fits the VMEM
        budget — e.g. the 24-window-component preheat pair at 512**3 —
        instead of handing Mosaic a config its allocator will reject.
        Explicitly pinned ``pair_bx``/``pair_by`` are honored verbatim
        (construction errors then propagate)."""
        try:
            return make()
        except ValueError as e:
            if self._pair_bx is not None or self._pair_by is not None:
                raise
            import warnings
            warnings.warn(
                f"stage-pair fusion disabled at stencil radius "
                f"{self.h} ({e}); step() will run single-stage fused "
                "kernels", stacklevel=3)
            self._pair_stages = False
            return None

    def _stage_in_place(self, extras):
        """What the per-stage protocol's ``stage`` kernel writes in
        place: all its extras where the stepper was built with
        ``donate=True`` (its per-stage programs then own those buffers
        and hand them to the kernel: no copy, :meth:`_make_call`), and
        none otherwise, because an aliased kernel fed buffers its
        program does not own costs a copy of each."""
        return tuple(extras) if self._donate else ()

    def _build_kernels(self, bx, by):
        """Construct this stepper's stage kernel(s). Subclasses override to
        build their own fused kernel instead (so they don't pay for — or
        keep alive — a scalar-only kernel they never call)."""
        F = self.F
        extras = {"dfdt": (F,), "kf": (F,), "kdfdt": (F,)}
        self._scalar_st = self._build_stencil(
            {"f": F}, self._scalar_body,
            {"f": (F,), "dfdt": (F,), "kf": (F,), "kdfdt": (F,)},
            extras, ("dt", "a", "hubble", "A", "B"), bx=bx, by=by,
            kind="stage", in_place=self._stage_in_place(extras))
        self._scalar_call = self._make_call(
            self._scalar_st, windows=("f",),
            extra_names=("dfdt", "kf", "kdfdt"))
        if self._pair_stages:
            # stage-pair kernel: two consecutive 2N stages per HBM pass.
            # f, dfdt and kf ride ring windows (their taps feed the
            # stage-2 Laplacian through the f1 axpy; window halos come
            # from neighboring ring slots, not extra HBM reads); kdfdt is
            # only ever read at offset 0, so it stays a blockwise-
            # pipelined extra (no halo ring, no x halo exchange). Net:
            # the lattice traffic per stage halves (8 -> 4 array
            # transfers). The intermediate field f1 is a pointwise axpy
            # of (f, kf, dfdt), so lap(f1) composes from the raw windows
            # at offsets <= h: no wider halos are needed. Blocking is
            # chosen independently of the single-stage kernel's (the pair
            # kernel's VMEM footprint is ~2x; explicit bx/by apply to the
            # single-stage kernel only — use pair_bx/pair_by to pin this
            # one).
            self._pair_st = self._try_pair_stencil(
                lambda: self._build_stencil(
                    {"f": F, "dfdt": F, "kf": F}, self._pair_body,
                    {"f": (F,), "dfdt": (F,), "kf": (F,), "kdfdt": (F,)},
                    {"kdfdt": (F,)},
                    ("dt", "a1", "hubble1", "A1", "B1",
                     "a2", "hubble2", "A2", "B2"),
                    bx=self._pair_bx, by=self._pair_by, kind="pair"))
            if self._pair_st is not None:
                self._pair_call = self._make_call(
                    self._pair_st,
                    windows=("f", "dfdt", "kf"), extra_names=("kdfdt",))

    def _make_call(self, st, windows, extra_names):
        """Wrap a StreamingStencil in a ``shard_map`` over the sharded
        mesh axes (the kernel streams each windowed input's own shard
        and takes its edges from ``ppermute``d ``h``-row slabs; no
        padded copy is made) or call it directly on an unsharded
        lattice.

        With ``donate=True`` (construction) the call is a program of
        its own that donates exactly the extras its kernel writes in
        place (``st.in_place``: all of the ``stage`` kernel's, none of
        any other kernel's) and nothing else. A windowed input is NOT
        donated: the kernel cannot write where its neighbours' halos
        are read, so its output is a fresh buffer, and the caller's
        array stays readable after the call. The program returns the
        in-place outputs first and the fresh ones after them: jax pairs
        a donated parameter with the first unpaired output of its shape
        and dtype, so this order gives each donated buffer the output
        the kernel writes over it and XLA places no copy round the
        kernel. By the compiler's account a 512**3 two-field stage
        program holds 5.37 GB that way (arguments 4.29 + the fresh
        ``f`` 1.07, no temporaries) where donating all four arrays to
        a kernel without aliases held 8.59 GB (4.29 of temporaries
        copied into before every stage; PR 37). Inside ``jit``-traced
        chunk drivers the inner donation is inlined away and the outer
        jit's own donation governs."""
        given = tuple(n for n in extra_names
                      if n in getattr(st, "in_place", ()))
        if self._px == 1 and self._py == 1:
            def call(win_arrays, scalars, extras):
                arg = (win_arrays[windows[0]] if len(windows) == 1
                       else win_arrays)
                return st(arg, scalars=scalars, extras=extras)
            if not self._donate:
                return call

            def program(win_arrays, scalars, given_extras, extras):
                outs = call(win_arrays, scalars, {**given_extras, **extras})
                return {n: outs.pop(n) for n in given}, outs
            program = _obs_memory.instrument_jit(
                program, label=f"fused.{type(self).__name__}.stage_call",
                donate_argnums=(2,))

            def donating(win_arrays, scalars, extras):
                written, fresh = program(
                    win_arrays, scalars, {n: extras[n] for n in given},
                    {n: v for n, v in extras.items() if n not in given})
                return {**written, **fresh}
            return donating

        from pystella_tpu.ops.pallas_stencil import OverlapStreamingStencil
        decomp = self.decomp
        out_names = (list(given)
                     + [n for n in st.out_defs if n not in given]
                     + list(st.sum_defs))
        scalar_names = st.scalar_names
        from jax.sharding import PartitionSpec as P

        # x-sharded stages take the interior/shell launch split where
        # the policy asks for it and the kernel admits it (one with sum
        # outputs keeps the single launch: the split would change the
        # deterministic reduction order); the event says which, and why
        ov = OverlapStreamingStencil.plan_for(
            st, self.h, enabled=self._overlap, label=type(self).__name__)
        if ov is not None:
            for part in (ov.st_interior, ov.st_shell):
                self._emit_block_choice(part.kind, part, "split")

        def body(*flat):
            nw = len(windows)
            ns = len(scalar_names)
            scalars = dict(zip(scalar_names, flat[nw:nw + ns]))
            extras = dict(zip(extra_names, flat[nw + ns:]))
            raw = dict(zip(windows, flat[:nw]))
            arg = raw[windows[0]] if nw == 1 else raw
            if ov is not None:
                outs = ov(arg, decomp, scalars=scalars, extras=extras)
            else:
                outs = st(arg, scalars=scalars, extras=extras,
                          slabs=st.halo_slabs(decomp, raw))
            for n in st.sum_defs:  # per-shard partials -> global sums
                outs[n] = decomp.psum(outs[n])
            return tuple(outs[n] for n in out_names)

        lat_spec = decomp.spec(1)
        in_specs = ((lat_spec,) * len(windows) + (P(),) * len(scalar_names)
                    + (lat_spec,) * len(extra_names))
        out_specs = (tuple(decomp.spec(1) for _ in st.out_defs)
                     + (P(),) * len(st.sum_defs))
        nw, ns = len(windows), len(scalar_names)
        donate = tuple(nw + ns + i for i, n in enumerate(extra_names)
                       if n in given)
        sharded = _obs_memory.instrument_jit(
            decomp.shard_map(body, in_specs, out_specs, check_vma=False),
            label=f"fused.{type(self).__name__}.stage_call_sharded",
            donate_argnums=donate)

        def call(win_arrays, scalars, extras):
            flat = ([win_arrays[n] for n in windows]
                    + [jnp.asarray(scalars[n], st.dtype).reshape(())
                       for n in scalar_names]
                    + [extras[n] for n in extra_names])
            res = sharded(*flat)
            return dict(zip(out_names, res))
        return call

    # -- kernel body -------------------------------------------------------

    def _scalar_body(self, taps, extras, scalars, energy=False):
        inv_dx2 = [1.0 / d**2 for d in self.dx]
        coefs = _lap_coefs[self.h]
        dt, a, hub = scalars["dt"], scalars["a"], scalars["hubble"]
        A, B = scalars["A"], scalars["B"]

        fint = taps()
        lap = _lap_from_taps(taps, coefs, inv_dx2)
        dfdt, kf, kdf = extras["dfdt"], extras["kf"], extras["kdfdt"]

        dV = self._dV(fint, a, hub)

        rhs_f = dfdt
        rhs_df = lap - 2 * hub * dfdt - a * a * dV

        kf2 = A * kf + dt * rhs_f
        f2 = fint + B * kf2
        kdf2 = A * kdf + dt * rhs_df
        df2 = dfdt + B * kdf2
        outs = {"f": f2, "dfdt": df2, "kf": kf2, "kdfdt": kdf2}
        if energy:
            outs["esums"] = self._esums(fint, dfdt, lap, a, hub)
        return outs

    def _esums(self, fv, dfdt, lap, a, hub):
        """Raw energy sums of a stage's ENTRY state, from values already
        in VMEM (free bandwidth-wise): per component ``sum(dfdt**2)`` and
        ``sum(-f * lap f)`` (the reducers' integration-by-parts gradient
        energy, sectors.py reducers), plus ``sum(V(f))`` — the inputs of
        :func:`~pystella_tpu.models.sectors.get_rho_and_p` up to the
        ``1/(2 a**2)`` combine factors applied by the coupled driver."""
        kin = [jnp.sum(dfdt[i] * dfdt[i]) for i in range(self.F)]
        grad = [jnp.sum(-fv[i] * lap[i]) for i in range(self.F)]
        env = {"f": fv, "a": a, "hubble": hub}
        pot = jnp.sum(jnp.broadcast_to(
            jnp.asarray(_field.evaluate(self._V, env), fv.dtype),
            fv.shape[1:]))
        # scalars, one per term: Mosaic cannot lay out the (F,) vector a
        # multi-axis reduction would produce (pallas_stencil._sum_tile)
        return kin + grad + [pot]

    def _dV(self, fv, a, hub):
        env = {"f": fv, "a": a, "hubble": hub}
        return jnp.stack([
            jnp.broadcast_to(
                jnp.asarray(_field.evaluate(e, env), fv.dtype),
                fv.shape[1:])
            for e in self._dvdf])

    @staticmethod
    def _axpy_taps(t_y, t_k, t_dy, B, A, dt, y1):
        """Taps-like view of a 2N stage-updated array
        ``y1 = y + B*(A*k + dt*dy)`` without materializing its halo: x/y
        shifts compose from the raw windows at the same offsets (the
        identical arithmetic as slicing a materialized y1), z shifts are
        in-register rolls of the block value ``y1`` itself. Memoized like
        ``Taps`` so consumers sharing offsets (lap + grad) reuse the
        composed expressions."""
        cache = {}

        def taps(sx=0, sy=0, sz=0):
            key = (sx, sy, sz)
            if key in cache:
                return cache[key]
            if sz:
                if sx or sy:  # same contract as Taps.__call__
                    raise ValueError("taps must be axis-aligned")
                out = t_y.roll(y1, sz)
            elif sx == 0 and sy == 0:
                out = y1
            else:
                out = (t_y(sx, sy)
                       + B * (A * t_k(sx, sy) + dt * t_dy(sx, sy)))
            cache[key] = out
            return out
        return taps

    # -- whole-RK-chunk (temporal blocking) kernels ------------------------
    #
    # The pair kernel composes ONE intermediate field's taps from the
    # raw windows; the chunk kernel iterates that idea: every
    # post-stage array (f, dfdt, kf, kdfdt) becomes a lazily-evaluated,
    # memoized taps-like view composed from the pre-stage views by the
    # IDENTICAL per-element arithmetic the pair kernels apply — so a
    # depth-D kernel advances D stages in one HBM pass, bit-exact
    # against the sequence of pair kernels it replaces (a materialized
    # array's value at a shifted site is the same op tree the composed
    # view evaluates there; rolls are permutations and commute with
    # elementwise ops). The price is window width — stage j's Laplacian
    # reaches h further than stage j-2's, so the assembled window halo
    # is ceil(D/2)*h — and redundant halo-region recompute, which is
    # exactly the temporal-blocking trade (PAPERS.md arxiv 2309.04671):
    # per-stage lattice traffic drops from the pair tier's 4 array
    # transfers to 8/D (2 at depth 4).

    @staticmethod
    def _lap_at(t, roll, coefs, inv_dx2, sx, sy):
        """The Laplacian of a taps-like view at a shifted base offset:
        a shifted-taps adapter handed to THE :func:`ops.pallas_stencil.
        lap_from_taps` — chunk/pair bit-exactness needs the identical
        accumulation order, which sharing the function makes true by
        construction. The adapter's z taps are rolls of the shifted
        block, exactly what ``Taps`` lowers its z offsets to."""
        def shifted(a=0, b=0, c=0):
            if c:
                if a or b:
                    raise ValueError("taps must be axis-aligned")
                return roll(t(sx, sy), c)
            return t(sx + a, sy + b)
        return _lap_from_taps(shifted, coefs, inv_dx2)

    def _compose_scalar_stage(self, tf, tdf, tkf, tkdf, roll, dt, a,
                              hub, A, B):
        """One 2N-storage scalar stage as composed taps-like views —
        the arithmetic sequence of :meth:`_scalar_body` /
        :meth:`_scalar_pair_core`, evaluated lazily at any offset."""
        inv_dx2 = [1.0 / d**2 for d in self.dx]
        coefs = _lap_coefs[self.h]
        kf1 = _memo_taps(
            lambda sx, sy: A * tkf(sx, sy) + dt * tdf(sx, sy), roll)
        f1 = _memo_taps(
            lambda sx, sy: tf(sx, sy) + B * kf1(sx, sy), roll)
        kdf1 = _memo_taps(
            lambda sx, sy: A * tkdf(sx, sy) + dt * (
                self._lap_at(tf, roll, coefs, inv_dx2, sx, sy)
                - 2 * hub * tdf(sx, sy)
                - a * a * self._dV(tf(sx, sy), a, hub)), roll)
        df1 = _memo_taps(
            lambda sx, sy: tdf(sx, sy) + B * kdf1(sx, sy), roll)
        return f1, df1, kf1, kdf1

    def _chunk_body(self, taps, extras, scalars, depth):
        """``depth`` consecutive scalar stages in ONE pass over HBM.
        With reduced-precision carries, the composed carry views are
        quantized at every interior PAIR boundary — exactly where the
        pair-kernel sequence materializes (and therefore rounds) them —
        so the chunk stays bit-exact against that sequence in either
        precision mode."""
        tf, tdf = taps["f"], taps["dfdt"]
        tkf, tkdf = taps["kf"], taps["kdfdt"]
        roll = tf.roll
        dt = scalars["dt"]
        cd = self._carry_dtype
        for j in range(depth):
            i = j + 1
            tf, tdf, tkf, tkdf = self._compose_scalar_stage(
                tf, tdf, tkf, tkdf, roll, dt,
                scalars[f"a{i}"], scalars[f"hubble{i}"],
                scalars[f"A{i}"], scalars[f"B{i}"])
            if cd is not None and j % 2 == 1 and j < depth - 1:
                tkf = _memo_taps(
                    lambda sx, sy, t=tkf: _carry_cast(t(sx, sy), cd),
                    roll)
                tkdf = _memo_taps(
                    lambda sx, sy, t=tkdf: _carry_cast(t(sx, sy), cd),
                    roll)
        return {"f": tf(), "dfdt": tdf(), "kf": tkf(), "kdfdt": tkdf()}

    def _chunk_fallback(self, reason):
        """The first rung of the fallback ladder (chunk -> pair ->
        single -> XLA): log it — a silently-degraded tier is exactly
        what the roofline accounting must not hide."""
        import warnings
        to = "pair" if self._pair_call is not None else "single"
        warnings.warn(
            f"whole-RK-chunk fusion disabled ({reason}); step() will "
            f"run {to}-stage fused kernels", stacklevel=3)
        _events.emit("kernel_fallback", tier="chunk", to=to,
                     reason=str(reason),
                     local_shape=list(self.local_shape),
                     label=type(self).__name__)

    def _maybe_build_chunk(self):
        """Build the requested whole-RK-chunk kernel, degrading to the
        pair tier (``kernel_fallback`` event) for sharded meshes,
        window halos beyond the 8-aligned y pad, and VMEM-infeasible
        shapes. Explicitly pinned ``chunk_bx``/``chunk_by`` propagate
        construction errors instead (a pinned config must not silently
        change tiers)."""
        depth = self._chunk_requested
        if not depth:
            return
        if not self._chunk_supported:
            self._chunk_fallback(
                f"no chunk body for {type(self).__name__}")
            return
        if self._px > 1 or self._py > 1:
            # the halo exchange would have to move ceil(depth/2)*h-wide
            # slabs per chunk (and the overlap split does not compose
            # with composed-stage windows) — the sharded hot loop stays
            # on the pair tier
            self._chunk_fallback(
                f"sharded mesh ({self._px},{self._py}): chunk windows "
                "need ceil(depth/2)*h-wide halos")
            return
        if self._A[0] != 0 and depth > self.num_stages:
            self._chunk_fallback(
                f"tableau A[0] != 0: a depth-{depth} chunk would cross "
                "a step boundary whose k-carry reset is not a no-op")
            return
        F = self.F
        win_halo = (depth // 2) * self.h
        try:
            self._chunk_st = self._build_stencil(
                {"f": F, "dfdt": F, "kf": F, "kdfdt": F},
                lambda t, e, s: self._chunk_body(t, e, s, depth),
                {"f": (F,), "dfdt": (F,), "kf": (F,), "kdfdt": (F,)},
                {},
                ("dt",) + tuple(
                    f"{name}{i}" for i in range(1, depth + 1)
                    for name in ("a", "hubble", "A", "B")),
                bx=self._chunk_bx, by=self._chunk_by, kind="chunk",
                win_halo=win_halo, stages=depth)
        except ValueError as e:
            if self._chunk_bx is not None or self._chunk_by is not None:
                raise
            self._chunk_fallback(str(e))
            return
        self._chunk_call = self._make_call(
            self._chunk_st, windows=("f", "dfdt", "kf", "kdfdt"),
            extra_names=())
        self._chunk_depth = depth

    def _check_chunk(self, stages):
        if self._chunk_call is None:
            raise RuntimeError(
                "whole-RK-chunk fusion is not available on this "
                "stepper (chunk_stages unset/0, an infeasible shape, "
                "or a sharded mesh); use stage_pair()/stage()/step()")
        if len(stages) != self._chunk_depth:
            raise ValueError(
                f"stage_chunk takes exactly {self._chunk_depth} stage "
                f"indices (got {len(stages)})")
        for prev, cur in zip(stages, stages[1:]):
            if cur < prev and self._A[cur] != 0:
                raise ValueError(
                    f"cross-boundary chunking needs A[{cur}] == 0 so "
                    "the step-boundary k-carry reset is a no-op; this "
                    f"tableau has A[{cur}] = {self._A[cur]}")

    def stage_chunk(self, stages, carry, t, dt, rhs_args_seq):
        """Run the listed stages (``len == chunk_stages``) as ONE
        resident kernel invocation. ``rhs_args_seq`` supplies each
        stage's expansion scalars; stage indices may wrap to the next
        step exactly like :meth:`stage_pair` (gated on the wrapped
        stage's ``A == 0``)."""
        stages = list(stages)
        self._check_chunk(stages)
        state, k = carry
        scalars = {"dt": dt}
        for i, (s, ra) in enumerate(zip(stages, rhs_args_seq), 1):
            ra = ra or {}
            scalars[f"a{i}"] = ra.get("a", 1.0)
            scalars[f"hubble{i}"] = ra.get("hubble", 0.0)
            scalars[f"A{i}"] = self._A[s]
            scalars[f"B{i}"] = self._B[s]
        with trace_scope("chunk_stage"):
            outs = self._chunk_call(
                {"f": state["f"], "dfdt": state["dfdt"],
                 "kf": k["f"], "kdfdt": k["dfdt"]},
                scalars, {})
        return ({"f": outs["f"], "dfdt": outs["dfdt"]},
                {"f": outs["kf"], "dfdt": outs["kdfdt"]})

    # -- kernel-tier accounting (the roofline's dispatch record) -----------

    @staticmethod
    def _stencil_bytes(st):
        """Exact per-invocation HBM traffic of one streaming/resident
        kernel: every windowed/extra input is read once, every output
        written once — that is the design invariant of the Pallas tier,
        so this is a measurement of the kernel structure, not a guess."""
        sites = int(np.prod(st.lattice_shape))
        total = 0
        for name, comps in st.win_defs.items():
            total += comps * sites * st.dtypes.get(name,
                                                   st.dtype).itemsize
        for defs in (st.extra_defs, st.out_defs):
            for name, lead in defs.items():
                n = int(np.prod(lead)) if lead else 1
                total += n * sites * st.dtypes.get(name,
                                                   st.dtype).itemsize
        return total

    def kernel_tier_report(self):
        """Which kernel tier the hot loop (``multi_step``) dispatches
        and the per-step lattice traffic it implies — the record the
        ledger's roofline section reports per run. The consumption
        model mirrors ``_multi_step_impl`` over one even-step period
        (chunks first, then pairs, then singles, crossing step
        boundaries when ``A[0] == 0``)."""
        from pystella_tpu.ops.pallas_stencil import ResidentStencil \
            as _Res
        D = self._chunk_depth if self._chunk_call is not None else 0
        single_st = self._stage_st
        bytes_total = 0
        kernels = {}

        def consume(n):
            nonlocal bytes_total
            i = 0
            while D and i + D <= n:
                bytes_total += self._stencil_bytes(self._chunk_st)
                kernels["chunk"] = kernels.get("chunk", 0) + 1
                i += D
            while self._pair_call is not None and i + 1 < n:
                bytes_total += self._stencil_bytes(self._pair_st)
                kernels["pair"] = kernels.get("pair", 0) + 1
                i += 2
            while i < n:
                bytes_total += self._stencil_bytes(single_st)
                kernels["single"] = kernels.get("single", 0) + 1
                i += 1

        if self._A[0] == 0:
            consume(2 * self.num_stages)  # crossing step boundaries
        else:
            consume(self.num_stages)      # per-step k-carry reset
            consume(self.num_stages)
        if D:
            tier = ("resident-chunk"
                    if isinstance(self._chunk_st, _Res)
                    else "streaming-chunk")
        elif self._pair_call is not None:
            tier = "pair"
        else:
            tier = "single"
        return {
            "tier": tier,
            "chunk_depth": D or None,
            "kernels_per_2_steps": kernels,
            "bytes_per_step": bytes_total // 2,
            "local_shape": list(self.local_shape),
            "h": self.h,
        }

    def _emit_tier(self, entrypoint):
        """One ``kernel_tier`` event per (stepper, entrypoint), emitted
        at first dispatch — the ledger's record of the tier actually
        run, not merely built."""
        if entrypoint in self._tier_emitted:
            return
        self._tier_emitted.add(entrypoint)
        _events.emit("kernel_tier", entrypoint=entrypoint,
                     label=type(self).__name__,
                     **self.kernel_tier_report())

    def _scalar_pair_core(self, taps, extras, scalars):
        """Two consecutive 2N-storage scalar stages in one HBM pass;
        returns the four outputs plus the stage-1 field's composed taps
        (for subclasses that differentiate the intermediate field).
        (The energy-coupled pair variant lives in
        :meth:`_deferred_pair_core`.)"""
        tf, tdf, tkf = taps["f"], taps["dfdt"], taps["kf"]
        kdf0 = extras["kdfdt"]
        inv_dx2 = [1.0 / d**2 for d in self.dx]
        coefs = _lap_coefs[self.h]
        dt = scalars["dt"]
        a1, hub1 = scalars["a1"], scalars["hubble1"]
        A1, B1 = scalars["A1"], scalars["B1"]
        a2, hub2 = scalars["a2"], scalars["hubble2"]
        A2, B2 = scalars["A2"], scalars["B2"]

        # stage 1 on the block (identical arithmetic to _scalar_body)
        f0, df0 = tf(), tdf()
        lap_f = _lap_from_taps(tf, coefs, inv_dx2)
        kf1 = A1 * tkf() + dt * df0
        f1 = f0 + B1 * kf1
        kdf1 = A1 * kdf0 + dt * (lap_f - 2 * hub1 * df0
                                 - a1 * a1 * self._dV(f0, a1, hub1))
        df1 = df0 + B1 * kdf1

        f1_taps = self._axpy_taps(tf, tkf, tdf, B1, A1, dt, f1)
        lap_f1 = _lap_from_taps(f1_taps, coefs, inv_dx2)

        # stage 2 on the block
        kf2 = A2 * kf1 + dt * df1
        f2 = f1 + B2 * kf2
        kdf2 = A2 * kdf1 + dt * (lap_f1 - 2 * hub2 * df1
                                 - a2 * a2 * self._dV(f1, a2, hub2))
        df2 = df1 + B2 * kdf2
        outs = {"f": f2, "dfdt": df2, "kf": kf2, "kdfdt": kdf2}
        return outs, f1_taps

    def _pair_body(self, taps, extras, scalars):
        """Two consecutive 2N-storage RK stages in one pass over HBM."""
        outs, _ = self._scalar_pair_core(taps, extras, scalars)
        return outs

    # -- Stepper interface -------------------------------------------------

    def init_carry(self, state):
        import jax
        cd = self._carry_dtype
        k = jax.tree_util.tree_map(
            jnp.zeros_like if cd is None
            else (lambda x: jnp.zeros_like(x, dtype=cd)), state)
        return (state, k)

    def extract(self, carry):
        return carry[0]

    def current(self, carry):
        return carry[0]

    # -- the per-stage protocol's programs ---------------------------------

    @property
    def _stage_st(self):
        """The single-stage kernel the per-stage protocol calls."""
        return (getattr(self, "_scalar_st", None)
                or getattr(self, "_both_st", None))

    def _split_carry(self, carry):
        """``(kept, given)`` of a ``(state, k)`` carry: the arrays the
        stage kernel reads through a halo window (``f``; ``hij``), and
        the rest as ``(state without them, k)``, which it reads at
        offset 0 and, in place, writes over."""
        state, k = carry
        wins = self._stage_st.win_defs
        return ({n: state[n] for n in wins},
                ({n: v for n, v in state.items() if n not in wins}, k))

    @staticmethod
    def _join_carry(kept, given):
        state, k = given
        return ({**kept, **state}, k)

    def _ensure_stage_jits(self):
        """The per-stage programs of the reference-style driver loop,
        one kernel each and nothing else. They take the carry as
        :meth:`_split_carry` cuts it and return ``(given, kept)``, the
        fresh window outputs last; :meth:`_dispatch_stage` re-assembles
        the carry outside the program.

        Where the stage kernel writes its extras in place (a stepper
        built with ``donate=True``) ``given`` is donated and ``kept``
        is not, as :meth:`_make_call` says and for its reasons: each
        donated parameter is paired with the output the kernel writes
        over it, no lattice array is copied (12.7 ms in front of every
        stage kernel at 512**3 before PR 37, 57 ms of a 190-ms step),
        and by the compiler's account a stage program holds 5.37 GB
        there, not 8.59. The caller's ``state["f"]`` (and ``hij``)
        outlives the call; its ``dfdt`` and the carry it passes back in
        do not. Otherwise nothing is donated and the kernel declares no
        alias: no copy either, at two states' worth of memory."""
        if hasattr(self, "_jit_stage"):
            return
        cls = type(self).__name__
        donate = bool(getattr(self._stage_st, "in_place", ()))

        def stage(s, kept, given, t, dt, rhs_args):
            carry = self.stage(s, self._join_carry(kept, given), t, dt,
                               rhs_args)
            return self._split_carry(carry)[::-1]

        def stage0(kept, given, t, dt, rhs_args):
            state, _ = self._join_carry(kept, given)
            return stage(0, *self._split_carry(self.init_carry(state)),
                         t, dt, rhs_args)

        self._jit_stage = _obs_memory.instrument_jit(
            stage, label=f"step.{cls}.stage", static_argnums=0,
            donate_argnums=(2,) if donate else ())
        self._jit_stage0 = _obs_memory.instrument_jit(
            stage0, label=f"step.{cls}.stage0",
            donate_argnums=(1,) if donate else ())

    def _dispatch_stage(self, stage, state_or_carry, t, dt, rhs_args):
        self._ensure_stage_jits()
        if stage == 0:
            given, kept = self._jit_stage0(
                *self._split_carry((state_or_carry, {})), t, dt, rhs_args)
        else:
            given, kept = self._jit_stage(
                stage, *self._split_carry(state_or_carry), t, dt, rhs_args)
        return self._join_carry(kept, given)

    def _stage_scalars(self, s, dt, rhs_args):
        return {"dt": dt, "a": rhs_args.get("a", 1.0),
                "hubble": rhs_args.get("hubble", 0.0),
                "A": self._A[s], "B": self._B[s]}

    def stage(self, s, carry, t, dt, rhs_args):
        state, k = carry
        with trace_scope("fused_rk_stage"):
            outs = self._scalar_call(
                {"f": state["f"]},
                self._stage_scalars(s, dt, rhs_args),
                {"dfdt": state["dfdt"], "kf": k["f"], "kdfdt": k["dfdt"]})
        return ({"f": outs["f"], "dfdt": outs["dfdt"]},
                {"f": outs["kf"], "dfdt": outs["kdfdt"]})

    # -- energy-coupled stages (expansion ODE integrated on device) --------

    def _ensure_energy_call(self):
        """Build (lazily) the energy-emitting single-stage kernel: the
        stage kernel plus ``esums`` partial-sum outputs of its ENTRY
        state — same blocking, same arithmetic, zero extra HBM passes."""
        if self._es_call is None:
            F = self.F
            st = self._build_stencil(
                {"f": F},
                lambda t, e, s: self._scalar_body(t, e, s, energy=True),
                {"f": (F,), "dfdt": (F,), "kf": (F,), "kdfdt": (F,)},
                {"dfdt": (F,), "kf": (F,), "kdfdt": (F,)},
                ("dt", "a", "hubble", "A", "B"),
                bx=getattr(self._scalar_st, "bx", None),
                by=getattr(self._scalar_st, "by", None),
                sum_defs={"esums": 2 * F + 1}, kind="energy")
            self._es_call = self._make_call(
                st, windows=("f",), extra_names=("dfdt", "kf", "kdfdt"))
        return self._es_call

    def _stage_energy(self, s, carry, t, dt, rhs_args):
        """Like :meth:`stage`, additionally returning the raw energy sums
        of the stage's entry state (see :meth:`_esums`)."""
        state, k = carry
        with trace_scope("fused_rk_stage_energy"):
            outs = self._es_call(
                {"f": state["f"]},
                self._stage_scalars(s, dt, rhs_args),
                {"dfdt": state["dfdt"], "kf": k["f"], "kdfdt": k["dfdt"]})
        return (({"f": outs["f"], "dfdt": outs["dfdt"]},
                 {"f": outs["kf"], "dfdt": outs["kdfdt"]}), outs["esums"])

    def _pair_scalars(self, s, dt, rhs_args, rhs_args2=None, s2=None):
        s2 = s + 1 if s2 is None else s2
        args2 = rhs_args2 if rhs_args2 is not None else rhs_args
        return {"dt": dt,
                "a1": rhs_args.get("a", 1.0),
                "hubble1": rhs_args.get("hubble", 0.0),
                "A1": self._A[s], "B1": self._B[s],
                "a2": args2.get("a", 1.0),
                "hubble2": args2.get("hubble", 0.0),
                "A2": self._A[s2], "B2": self._B[s2]}

    def _check_pair(self, s, s2):
        """Validate a ``stage_pair`` request: pairing must be enabled, and
        a wrapped pairing (``s2 < s``, i.e. crossing a step boundary) is
        only sound when the tableau's stage-``s2`` carry scale is zero —
        the skipped per-step k-carry reset must be a no-op."""
        if self._pair_call is None:
            raise RuntimeError(
                "stage-pair fusion is not available on this stepper "
                "(pair_stages=False, a single-stage tableau, or no "
                "feasible pair-kernel blocking); use stage() or step()")
        if s2 < s and self._A[s2] != 0:
            raise ValueError(
                f"cross-boundary pairing needs A[{s2}] == 0 so the "
                f"step-boundary k-carry reset is a no-op; this tableau "
                f"has A[{s2}] = {self._A[s2]}")

    def stage_pair(self, s, carry, t, dt, rhs_args, rhs_args2=None,
                   s2=None):
        """Run stages ``s`` and ``s2`` (default ``s+1``) as one fused
        kernel. ``rhs_args2`` supplies second-stage expansion scalars
        when the caller advances them between stages (defaults to
        ``rhs_args``). ``s2`` may wrap to stage 0 of the NEXT step
        (every 2N tableau has A[0] == 0, so the k-carry reset at a step
        boundary is a no-op) — see :meth:`multi_step`."""
        self._check_pair(s, s + 1 if s2 is None else s2)
        state, k = carry
        with trace_scope("fused_rk_stage_pair"):
            outs = self._pair_call(
                {"f": state["f"], "dfdt": state["dfdt"], "kf": k["f"]},
                self._pair_scalars(s, dt, rhs_args, rhs_args2, s2),
                {"kdfdt": k["dfdt"]})
        return ({"f": outs["f"], "dfdt": outs["dfdt"]},
                {"f": outs["kf"], "dfdt": outs["kdfdt"]})

    def _step_impl(self, state, t, dt, rhs_args):
        carry = self.init_carry(state)
        s = 0
        D = self._chunk_depth if self._chunk_call is not None else 0
        while D and s + D <= self.num_stages:
            carry = self.stage_chunk(
                list(range(s, s + D)), carry, t, dt, [rhs_args] * D)
            s += D
        if self._pair_call is not None:
            while s + 1 < self.num_stages:
                carry = self.stage_pair(s, carry, t, dt, rhs_args)
                s += 2
        while s < self.num_stages:
            carry = self.stage(s, carry, t, dt, rhs_args)
            s += 1
        return self.extract(carry)

    def _multi_step_impl(self, state, nsteps, t, dt, rhs_args, rhs_seq):
        nstages = self.num_stages

        def args_at(i):
            """rhs_args for flat stage index ``i``: static values from
            ``rhs_args`` overlaid with the i-th entry of each per-stage
            sequence in ``rhs_seq``."""
            if not rhs_seq:
                return rhs_args
            return {**rhs_args, **{n: v[i] for n, v in rhs_seq.items()}}

        D = self._chunk_depth if self._chunk_call is not None else 0
        if ((self._pair_call is None and not D) or self._A[0] != 0):
            # no cross-boundary fusion possible: sequential steps, each
            # with its own k-carry reset (a tableau with A[0] != 0 NEEDS
            # the per-step zeros), chunking/pairing within the step
            # when possible
            for step in range(nsteps):
                carry = self.init_carry(state)
                s, base = 0, step * nstages
                while D and s + D <= nstages:
                    carry = self.stage_chunk(
                        list(range(s, s + D)), carry, t, dt,
                        [args_at(base + s + j) for j in range(D)])
                    s += D
                if self._pair_call is not None:
                    while s + 1 < nstages:
                        carry = self.stage_pair(
                            s, carry, t, dt, args_at(base + s),
                            rhs_args2=args_at(base + s + 1))
                        s += 2
                while s < nstages:
                    carry = self.stage(s, carry, t, dt, args_at(base + s))
                    s += 1
                state = self.extract(carry)
            return state
        carry = self.init_carry(state)
        flat = [s for _ in range(nsteps) for s in range(nstages)]
        i = 0
        # chunk/pair across step boundaries: the stage-0 update
        # multiplies the stale k-carry by A[0] == 0, so skipping the
        # per-step zero-reset is bit-exact
        while D and i + D <= len(flat):
            carry = self.stage_chunk(
                flat[i:i + D], carry, t, dt,
                [args_at(i + j) for j in range(D)])
            i += D
        while self._pair_call is not None and i + 1 < len(flat):
            carry = self.stage_pair(flat[i], carry, t, dt, args_at(i),
                                    rhs_args2=args_at(i + 1),
                                    s2=flat[i + 1])
            i += 2
        while i < len(flat):
            carry = self.stage(flat[i], carry, t, dt, args_at(i))
            i += 1
        return self.extract(carry)

    def _multi_jit(self, nsteps, rhs_seq=None, sentinel=None):
        """The cached jitted ``nsteps``-chunk executable (state arg
        donated). Factored out of :meth:`multi_step` so the IR audit
        (``pystella_tpu.lint``) can ``.lower()`` the exact dispatched
        computation without running it."""
        key = (int(nsteps), tuple(sorted(rhs_seq)) if rhs_seq else None,
               None if sentinel is None else id(sentinel))
        fn = self._jit_multi.get(key)
        if fn is None:
            import functools
            impl = functools.partial(self._multi_step_impl,
                                     nsteps=int(nsteps))
            if sentinel is not None:
                base_impl = impl

                def impl(state, t, dt, rhs_args, rhs_seq):
                    new = base_impl(state, t=t, dt=dt,
                                    rhs_args=rhs_args, rhs_seq=rhs_seq)
                    with trace_scope("sentinel"):
                        hv = sentinel.compute(new)
                    return new, hv
            fn = _obs_memory.instrument_jit(
                impl, label=f"fused.multi_step[{int(nsteps)}]",
                donate_argnums=0)
            self._jit_multi[key] = fn
        return fn

    def multi_step_fn(self, nsteps):
        """The fused chunk body as a pure ``(state, t, dt, rhs_args) ->
        state`` function (stage pairing across step boundaries, no
        ``rhs_seq``) — the single-member entry point the ensemble tier
        maps over a batch (:mod:`pystella_tpu.ensemble`). The Pallas
        kernels keep each member's per-stage arithmetic inside opaque
        ``pallas_call``\\ s, so a member mapped here is BIT-EXACT with
        the same member run through :meth:`multi_step` alone."""
        nsteps = int(nsteps)

        def fn(state, t, dt, rhs_args):
            return self._multi_step_impl(state, nsteps, t, dt,
                                         rhs_args, {})
        return fn

    def multi_step(self, state, nsteps, t=0.0, dt=None, rhs_args=None,
                   rhs_seq=None, sentinel=None):
        """Advance ``nsteps`` full RK steps as one jitted computation,
        pairing stages ACROSS step boundaries. For RK54's odd stage count
        this eliminates the single-stage kernel entirely: 10 stages per
        2 steps = 5 pair kernels, cutting lattice traffic another
        48 -> 40 transfers per 2 steps vs per-step pairing. Bit-exact
        vs ``nsteps`` sequential ``step()`` calls with the same
        per-stage scalars.

        Expansion scalars may evolve across the chunk: ``rhs_seq`` maps
        scalar names (``"a"``, ``"hubble"``) to arrays of per-stage
        values, one entry per flat stage (``nsteps * num_stages``),
        overlaying the static ``rhs_args``. A driver precomputes them on
        host from the Expansion ODE over the chunk (the background is a
        cheap scalar integration; see
        ``examples/scalar_preheating.py --chunk-steps``) — so the hot
        loop needs no per-stage host dispatch at all.

        The input ``state`` buffers are DONATED (this is the hot-loop
        driver; donation keeps peak HBM at one state + one carry) — do
        not reuse ``state`` after the call.

        With ``sentinel`` (a :class:`~pystella_tpu.obs.sentinel.
        Sentinel`), the chunk additionally computes the health vector of
        its FINAL state inside the same jitted computation (the
        sentinel's reductions piggyback on the chunk — no extra
        dispatch, no host sync) and returns ``(state, health_vector)``
        for asynchronous polling by a ``SentinelMonitor``."""
        dt = dt if dt is not None else self.dt
        nsteps = int(nsteps)
        if rhs_seq:
            rhs_seq = {n: jnp.asarray(v) for n, v in rhs_seq.items()}
            nflat = nsteps * self.num_stages
            for n, v in rhs_seq.items():
                if v.shape[0] != nflat:
                    raise ValueError(
                        f"rhs_seq[{n!r}] has {v.shape[0]} entries; need "
                        f"one per stage ({nsteps} steps x "
                        f"{self.num_stages} stages = {nflat})")
        fn = self._multi_jit(nsteps, rhs_seq, sentinel)
        _metrics.counter("steps").inc(nsteps)
        self._emit_tier("multi_step")
        with host_span("step_dispatch"):
            return fn(state, t=t, dt=dt, rhs_args=rhs_args or {},
                      rhs_seq=rhs_seq or {})

    def step(self, state, t=0.0, dt=None, rhs_args=None):
        dt = dt if dt is not None else self.dt
        _metrics.counter("steps").inc()
        self._emit_tier("step")
        with host_span("step_dispatch"):
            return self._jit_step(state, t, dt, rhs_args or {})

    # -- deferred-drag coupled pair kernels --------------------------------
    #
    # The energy-coupled stage-pair problem: the pair kernel needs the
    # second stage's expansion scalars at launch, but the exact
    # ``hubble2`` only exists after the first stage's global energy
    # reduction. The resolution is that ``hubble2`` enters the stage-2
    # update LINEARLY and ONLY through the Hubble-drag term (``a2``
    # never depends on rho at all: ``ka = A ka + dt adot; a += B ka``),
    # so the kernel can DEFER that one term: it outputs the stage-1
    # velocity ``df1`` and the drag-free stage-2 carry ``kdfp = A2 kdf1
    # + dt (lap f1 - a2^2 dV(f1))`` instead of the completed
    # ``(dfdt, kdfdt)``. The NEXT pair kernel — which by then holds the
    # exact ``hubble2`` (integrated between kernels from the TRUE
    # in-kernel energy sums) — completes ``kdf2 = kdfp - 2 dt hub2 df1;
    # df2 = df1 + B2 kdf2`` in-register while reconstructing its taps,
    # and the chunk end applies the same completion as one fused
    # elementwise op. Net: the pair-fused hot loop's HBM traffic with
    # EXACT per-stage Friedmann coupling (driver-loop parity to float
    # roundoff) — no predictor, no stale background anywhere.
    #
    # The deferral requires the potential (and, for the GW system, the
    # anisotropic stress) to not reference ``hubble`` symbolically —
    # checked at build time (:meth:`_hubble_free`); otherwise the
    # coupled chunk falls back to single-stage kernels.

    @property
    def _hubble_free(self):
        """True when the stage-2 non-drag terms are hubble-independent
        (the deferred-drag factorization's soundness condition)."""
        exprs = [self._V] + list(self._dvdf)
        return all("hubble" not in _field.field_names(e) for e in exprs)

    def _def_win_defs(self, in_deferred):
        F = self.F
        if in_deferred:
            return {"f": F, "dfp": F, "kdfp": F, "kf": F}, {}
        return {"f": F, "dfdt": F, "kf": F}, {"kdfdt": (F,)}

    def _def_out_defs(self):
        F = self.F
        return {"f": (F,), "dfp": (F,), "kf": (F,), "kdfp": (F,)}

    def _def_in_normal(self, carry):
        state, k = carry
        return ({"f": state["f"], "dfdt": state["dfdt"], "kf": k["f"]},
                {"kdfdt": k["dfdt"]})

    def _def_in_deferred(self, carry):
        state, k = carry
        return ({"f": state["f"], "dfp": state["dfdt"],
                 "kdfp": k["dfdt"], "kf": k["f"]}, {})

    def _def_out(self, outs):
        return ({"f": outs["f"], "dfdt": outs["dfp"]},
                {"f": outs["kf"], "dfdt": outs["kdfp"]})

    def _finalize_deferred(self, carry, dt, hubfix, B2p):
        """Complete the deferred stage-2 Hubble drag of a chunk's final
        pair with the (by now exact) ``hubfix``: one fused elementwise
        pass, the same arithmetic the next kernel would have applied."""
        state, k = carry
        # the background scalars are float64 under x64 whatever the
        # fields' dtype; meet the lattice arrays in THEIR dtype, as the
        # kernels' scalar operands do
        drag = jnp.asarray(2 * dt * hubfix, state["dfdt"].dtype)
        kdf = k["dfdt"] - drag * state["dfdt"]
        df = state["dfdt"] + B2p * kdf
        return ({"f": state["f"], "dfdt": df}, {"f": k["f"], "dfdt": kdf})

    @staticmethod
    def _completed_taps(tdfp, tkdfp, dt, hubfix, B2p):
        """Taps-like view of the previous pair's completed velocity
        ``df = dfp + B2p (kdfp - 2 dt hubfix dfp)``, composed in-register
        from the deferred windows (memoized per offset)."""
        cache = {}

        def taps(sx=0, sy=0, sz=0):
            key = (sx, sy, sz)
            if key not in cache:
                dfp = tdfp(sx, sy, sz)
                cache[key] = dfp + B2p * (tkdfp(sx, sy, sz)
                                          - 2 * dt * hubfix * dfp)
            return cache[key]
        return taps

    def _deferred_pair_core(self, taps, extras, scalars, in_deferred):
        """Scalar-system core of the deferred-drag coupled pair: the
        stage-pair arithmetic of :meth:`_scalar_pair_core` with (a) the
        incoming state optionally reconstructed from the previous pair's
        deferred representation and (b) the outgoing stage-2 drag
        deferred. Returns ``(outs, f1_taps, df1)`` for the GW subclass."""
        tf, tkf = taps["f"], taps["kf"]
        inv_dx2 = [1.0 / d**2 for d in self.dx]
        coefs = _lap_coefs[self.h]
        dt = scalars["dt"]
        a1, hub1 = scalars["a1"], scalars["hubble1"]
        A1, B1 = scalars["A1"], scalars["B1"]
        a2 = scalars["a2"]
        A2, B2 = scalars["A2"], scalars["B2"]

        if in_deferred:
            tdf = self._completed_taps(taps["dfp"], taps["kdfp"], dt,
                                       scalars["hubfix"], scalars["B2p"])
            kdf0 = (taps["kdfp"]() - 2 * dt * scalars["hubfix"]
                    * taps["dfp"]())
        else:
            tdf = taps["dfdt"]
            kdf0 = extras["kdfdt"]

        # stage 1 (identical arithmetic to _scalar_body, exact scalars)
        f0, df0 = tf(), tdf()
        lap_f = _lap_from_taps(tf, coefs, inv_dx2)
        kf1 = A1 * tkf() + dt * df0
        f1 = f0 + B1 * kf1
        kdf1 = A1 * kdf0 + dt * (lap_f - 2 * hub1 * df0
                                 - a1 * a1 * self._dV(f0, a1, hub1))
        df1 = df0 + B1 * kdf1

        f1_taps = self._axpy_taps(tf, tkf, tdf, B1, A1, dt, f1)
        lap_f1 = _lap_from_taps(f1_taps, coefs, inv_dx2)

        # stage 2: everything but the Hubble drag (deferred; a2 is
        # exact — its update never touches rho). dV/V evaluate with
        # hubble=None: the _hubble_free gate guarantees no lookup.
        kf2 = A2 * kf1 + dt * df1
        f2 = f1 + B2 * kf2
        kdfp = A2 * kdf1 + dt * (lap_f1 - a2 * a2 * self._dV(f1, a2, None))
        outs = {"f": f2, "dfp": df1, "kf": kf2, "kdfp": kdfp,
                "esums1": self._esums(f0, df0, lap_f, a1, hub1),
                "esums2": self._esums(f1, df1, lap_f1, a2, None)}
        return outs, f1_taps, df1

    def _deferred_body(self, taps, extras, scalars, in_deferred):
        outs, _, _ = self._deferred_pair_core(taps, extras, scalars,
                                              in_deferred)
        return outs

    def _build_coupled_pair_call(self, in_deferred):
        F = self.F
        win_defs, extra_defs = self._def_win_defs(in_deferred)
        scalar_names = ("dt", "a1", "hubble1", "A1", "B1", "a2",
                        "A2", "B2")
        if in_deferred:
            scalar_names += ("hubfix", "B2p")
        st = self._build_stencil(
            win_defs,
            lambda t, e, s: self._deferred_body(t, e, s, in_deferred),
            self._def_out_defs(), extra_defs, scalar_names,
            sum_defs={"esums1": 2 * F + 1, "esums2": 2 * F + 1},
            kind="coupled_pair")
        return self._make_call(st, windows=tuple(win_defs),
                               extra_names=tuple(extra_defs))

    def _ensure_coupled_pair_calls(self):
        """Build (lazily) the two deferred-drag coupled pair kernels
        (normal-repr input for a chunk's first pair, deferred-repr input
        for the rest). Returns None — and coupled chunks degrade to
        single-stage kernels — when pairing is disabled, the tableau's
        ``A[0] != 0`` (the cross-boundary k-carry reset would not be a
        no-op), the potential references ``hubble``, or no blocking of
        the wider deferred windows fits VMEM."""
        if self._pes_tried:
            return self._pes_call
        self._pes_tried = True
        if (not self._pair_stages or self._A[0] != 0
                or not self._hubble_free):
            return None
        try:
            self._pes_call = (self._build_coupled_pair_call(False),
                              self._build_coupled_pair_call(True))
        except ValueError as e:
            import warnings
            warnings.warn(
                f"deferred-drag coupled pair kernels unavailable at "
                f"stencil radius {self.h} ({e}); coupled_multi_step "
                "will run single-stage kernels", stacklevel=3)
            self._pes_call = None
        return self._pes_call

    # -- energy-coupled chunk driver ---------------------------------------

    def _combine_esums(self, es, a, grid_size):
        """Raw kernel-emitted energy sums -> (rho, p) with the CURRENT
        scale factor — the arithmetic of
        :func:`~pystella_tpu.models.sectors.get_rho_and_p` on the
        driver loop's per-stage ``compute_energy`` output."""
        F = self.F
        es = es.astype(a.dtype)
        inv = 1.0 / (2.0 * a * a * grid_size)
        kin = jnp.sum(es[:F]) * inv
        grad = jnp.sum(es[F:2 * F]) * inv
        pot = es[2 * F] / grid_size
        return kin + grad + pot, kin - grad / 3.0 - pot

    def _friedmann_stage(self, s, a, adot, ka, kadot, rho, p, dt, mpl):
        """One 2N-storage expansion-ODE stage on traced scalars (the
        arithmetic of :meth:`~pystella_tpu.Expansion.step`,
        reference expansion.py:101-157)."""
        addot = 4 * np.pi * a**3 / 3 / mpl**2 * (rho - 3 * p)
        ka = self._A[s] * ka + dt * adot
        kadot = self._A[s] * kadot + dt * addot
        return a + self._B[s] * ka, adot + self._B[s] * kadot, ka, kadot

    def _coupled_impl(self, state, t, dt, a, adot, nsteps, grid_size,
                      mpl):
        """``nsteps`` steps with the Friedmann background integrated
        in-trace, per-stage-exactly coupled: each stage kernel emits the
        energy sums of its entry state (the quantity the driver loop's
        per-stage ``compute_energy`` produces), which feed the matching
        expansion-ODE stage on traced scalars — the same arithmetic
        sequence as the reference-style driver
        (examples/scalar_preheating.py stage loop), with zero extra HBM
        passes and zero host round-trips."""
        carry = self.init_carry(state)
        ka = kadot = jnp.zeros_like(a)
        for _ in range(nsteps):
            for s in range(self.num_stages):
                if s == 0:  # fresh expansion k-carry each step, like the
                    ka = kadot = jnp.zeros_like(a)  # driver's Expansion
                hubble = adot / a
                carry, esums = self._stage_energy(
                    s, carry, t, dt, {"a": a, "hubble": hubble})
                # combine sums -> (rho, p) with the CURRENT a (matching
                # compute_energy(..., expand.a) in the driver loop), then
                # expansion stage s (k = A k + dt rhs; y += B k)
                rho, p = self._combine_esums(esums, a, grid_size)
                a, adot, ka, kadot = self._friedmann_stage(
                    s, a, adot, ka, kadot, rho, p, dt, mpl)
        return self.extract(carry), a, adot

    def _coupled_pair_impl(self, state, t, dt, a, adot, nsteps,
                           grid_size, mpl):
        """The pair-fused energy-coupled chunk, EXACT via deferred
        drag: each stage-pair kernel runs with exact scalars for its
        first stage (and the rho-independent ``a2``), defers the second
        stage's Hubble-drag term, and emits the TRUE energy sums of both
        stages' entry states; the Friedmann ODE advances on traced
        scalars between kernels from those sums, producing the exact
        ``hubble2`` that the NEXT kernel (or the chunk-end finalize)
        uses to complete the deferred update. Reproduces the per-stage
        driver loop to float roundoff — same arithmetic sequence up to
        re-association of one ``dt`` distribution — at the pair-fused
        hot loop's HBM traffic. Pairs cross step boundaries like
        :meth:`multi_step` (gated on ``A[0] == 0``); an odd trailing
        stage finalizes and runs the single-stage energy kernel."""
        calls = self._ensure_coupled_pair_calls()
        assert calls is not None  # coupled_multi_step gates on this
        call_normal, call_deferred = calls
        carry = self.init_carry(state)
        ka = kadot = jnp.zeros_like(a)
        ns = self.num_stages
        flat = [s for _ in range(nsteps) for s in range(ns)]
        deferred = False
        hubfix = None  # exact hub completing the pending deferred stage
        B2p = 0.0      # that stage's tableau B

        i = 0
        while i < len(flat):
            s = flat[i]
            if s == 0:
                ka = kadot = jnp.zeros_like(a)
            hub = adot / a
            if i + 1 >= len(flat):
                # odd trailing stage: complete the pending deferred
                # drag, then one exact single-stage energy kernel
                if deferred:
                    carry = self._finalize_deferred(carry, dt, hubfix,
                                                    B2p)
                    deferred = False
                carry, es = self._stage_energy(
                    s, carry, t, dt, {"a": a, "hubble": hub})
                rho, p = self._combine_esums(es, a, grid_size)
                a, adot, ka, kadot = self._friedmann_stage(
                    s, a, adot, ka, kadot, rho, p, dt, mpl)
                i += 1
                continue
            s2 = flat[i + 1]
            # a2 never touches rho: compute it exactly at launch (the
            # identical fma sequence as the post-kernel Friedmann
            # stage, so the two agree bitwise)
            a2 = a + self._B[s] * (self._A[s] * ka + dt * adot)
            scalars = {"dt": dt, "a1": a, "hubble1": hub, "a2": a2,
                       "A1": self._A[s], "B1": self._B[s],
                       "A2": self._A[s2], "B2": self._B[s2]}
            if deferred:
                scalars["hubfix"] = hubfix
                scalars["B2p"] = B2p
                wins, extras = self._def_in_deferred(carry)
                with trace_scope("fused_coupled_pair"):
                    outs = call_deferred(wins, scalars, extras)
            else:
                wins, extras = self._def_in_normal(carry)
                with trace_scope("fused_coupled_pair"):
                    outs = call_normal(wins, scalars, extras)
            carry = self._def_out(outs)
            deferred = True
            # exact background integration from the true esums
            rho, p = self._combine_esums(outs["esums1"], a, grid_size)
            a, adot, ka, kadot = self._friedmann_stage(
                s, a, adot, ka, kadot, rho, p, dt, mpl)
            if s2 == 0:
                ka = kadot = jnp.zeros_like(a)
            hubfix = adot / a  # exact hub entering stage s2
            B2p = self._B[s2]
            rho2, p2 = self._combine_esums(outs["esums2"], a, grid_size)
            a, adot, ka, kadot = self._friedmann_stage(
                s2, a, adot, ka, kadot, rho2, p2, dt, mpl)
            i += 2
        if deferred:
            carry = self._finalize_deferred(carry, dt, hubfix, B2p)
        return self.extract(carry), a, adot

    def _coupled_jit(self, nsteps, grid_size, mpl, pair, sentinel=None):
        """The cached jitted coupled-chunk executable (state donated;
        signature ``fn(state, t=, dt=, a=, adot=)``). Factored out of
        :meth:`coupled_multi_step` for the same reason as
        :meth:`_multi_jit` — the IR audit lowers it without running."""
        import functools
        key = (int(nsteps), float(grid_size), float(mpl), bool(pair),
               None if sentinel is None else id(sentinel))
        fn = self._jit_coupled.get(key)
        if fn is None:
            impl = self._coupled_pair_impl if pair else self._coupled_impl
            impl = functools.partial(impl, nsteps=int(nsteps),
                                     grid_size=float(grid_size),
                                     mpl=float(mpl))
            if sentinel is not None:
                base_impl = impl

                def impl(state, t, dt, a, adot):
                    new, a2, adot2 = base_impl(state, t=t, dt=dt, a=a,
                                               adot=adot)
                    with trace_scope("sentinel"):
                        hv = sentinel.compute(new, {"a": a2,
                                                    "adot": adot2})
                    return new, a2, adot2, hv
            fn = _obs_memory.instrument_jit(
                impl, label=f"fused.coupled_multi_step[{int(nsteps)}]",
                donate_argnums=0)
            self._jit_coupled[key] = fn
        return fn

    def coupled_multi_step(self, state, nsteps, expansion, t=0.0,
                           dt=None, grid_size=None, pair=None,
                           sentinel=None):
        """Advance ``nsteps`` steps as ONE jitted computation with the
        scale factor evolved self-consistently on device — the accurate
        fast path for expanding-background runs (``--chunk-steps`` with
        the default coupled mode in ``examples/scalar_preheating.py``).

        By default (``pair=None``) the chunk runs deferred-drag
        stage-PAIR kernels: the pair-fused hot loop's HBM traffic (the
        :meth:`multi_step` bench path) with EXACT per-stage Friedmann
        feedback — each kernel emits both stages' true entry-state
        energy sums and defers only the second stage's (linear)
        Hubble-drag term until its exact ``hubble`` exists (see
        :meth:`_coupled_pair_impl`; driver-loop parity to roundoff,
        tests/test_fused.py::test_coupled_pair_accuracy_vs_driver).
        ``pair=False`` forces the single-stage kernels (a global energy
        barrier per stage); ``pair=True`` requires the pair path and
        raises when it is unavailable (pairing disabled, ``A[0] != 0``,
        a ``hubble``-referencing potential, or no feasible blocking).
        ``expansion`` (an :class:`~pystella_tpu.Expansion`) provides the
        entry ``(a, adot)`` and is ADVANCED to the chunk end. The input
        ``state`` buffers are donated.

        With ``sentinel``, the chunk also computes the health vector of
        its final state in the same computation, with the chunk-end
        ``(a, adot)`` passed as the sentinel ``aux`` — so invariants
        like :meth:`~pystella_tpu.Expansion.constraint_residual` see
        the exact on-device background. Returns ``(state,
        health_vector)`` instead of ``state``."""
        import functools
        import jax
        dt = dt if dt is not None else self.dt
        nsteps = int(nsteps)
        if grid_size is None:
            grid_size = float(np.prod(self.grid_shape))
        mpl = float(expansion.mpl)
        if pair is None:
            pair = self._ensure_coupled_pair_calls() is not None
        elif pair and self._ensure_coupled_pair_calls() is None:
            raise RuntimeError(
                "pair=True but the deferred-drag coupled pair kernels "
                "are unavailable on this stepper (pair_stages=False, "
                "A[0] != 0, a hubble-referencing potential, or no "
                "feasible blocking)")
        self._ensure_energy_call()  # pair path's odd-tail stage uses it
        fn = self._coupled_jit(nsteps, grid_size, mpl, pair, sentinel)
        _metrics.counter("steps").inc(nsteps)
        with host_span("step_dispatch"):
            res = fn(state, t=t, dt=dt,
                     a=jnp.asarray(float(expansion.a)),
                     adot=jnp.asarray(float(expansion.adot)))
        state, a, adot = res[:3]
        # the chunk's own host sync: the background it ends on
        with host_span("step_fetch"):
            expansion.a = expansion.dtype.type(np.asarray(a))
            expansion.adot = expansion.dtype.type(np.asarray(adot))
        expansion.hubble = expansion.adot / expansion.a
        return state if sentinel is None else (state, res[3])


class FusedPreheatStepper(FusedScalarStepper):
    """Fused stages for the full preheating system: scalar fields plus
    transverse metric perturbations sourced by their anisotropic stress.

    Each stage is **one** Pallas kernel whose window covers both ``f`` and
    ``hij``: the scalar Laplacian, the gradient source terms, and the
    tensor Laplacian all come from the same VMEM ring, so the ``f`` window
    streams from HBM exactly once per stage (an earlier two-kernel design
    re-read it for the tensor source — ~1.5x the minimum traffic for the
    GW system). The f → hij coupling is one-way and uses the stage-entry
    ``f``, which is exactly what the shared window holds.

    :arg gw_sector: a :class:`~pystella_tpu.TensorPerturbationSector`.
    """

    _carry_names = frozenset({"kf", "kdfdt", "kdfp",
                              "khij", "kdhijdt", "kdhp"})

    #: the whole-RK-chunk body is scalar-only so far — a chunk_stages
    #: request here degrades to the pair tier (kernel_fallback event)
    _chunk_supported = False

    def __init__(self, sector, gw_sector, decomp, grid_shape, dx,
                 halo_shape=2, tableau=None, dtype=jnp.float32,
                 bx=None, by=None, dt=None, **kwargs):
        # set before super().__init__, which calls _build_kernels()
        self.gw_sector = gw_sector
        self.n_hij = gw_sector.hij.shape[0]

        # symbolic anisotropic-stress components S_ij in terms of dfdx
        from pystella_tpu.models.sectors import tensor_index
        self._sij = {}
        for i in range(1, 4):
            for j in range(i, 4):
                fld = tensor_index(i, j)
                self._sij[fld] = sum(
                    sec.stress_tensor(i, j, drop_trace=True)
                    for sec in gw_sector.sectors)

        super().__init__(sector, decomp, grid_shape, dx,
                         halo_shape=halo_shape, tableau=tableau,
                         dtype=dtype, bx=bx, by=by, dt=dt, **kwargs)

    def _build_kernels(self, bx, by):
        F, H = self.F, self.n_hij
        extras = {"dfdt": (F,), "kf": (F,), "kdfdt": (F,),
                  "dhijdt": (H,), "khij": (H,), "kdhijdt": (H,)}
        self._both_st = self._build_stencil(
            {"f": F, "hij": H}, self._preheat_body,
            {"f": (F,), "dfdt": (F,), "kf": (F,), "kdfdt": (F,),
             "hij": (H,), "dhijdt": (H,), "khij": (H,), "kdhijdt": (H,)},
            extras, ("dt", "a", "hubble", "A", "B"), bx=bx, by=by,
            kind="stage", in_place=self._stage_in_place(extras))
        self._both_call = self._make_call(
            self._both_st, windows=("f", "hij"),
            extra_names=("dfdt", "kf", "kdfdt",
                         "dhijdt", "khij", "kdhijdt"))
        if self._pair_stages:
            # stage-pair kernel for the full system: every array whose
            # stage-1 update is differentiated in stage 2 rides a ring
            # window (f/dfdt/kf feed lap+grad of f1; hij/dhijdt/khij feed
            # lap of h1); the k-derivative carries are offset-0 only and
            # stay blockwise extras
            self._pair_st = self._try_pair_stencil(
                lambda: self._build_stencil(
                    {"f": F, "dfdt": F, "kf": F,
                     "hij": H, "dhijdt": H, "khij": H}, self._pair_body,
                    {"f": (F,), "dfdt": (F,), "kf": (F,), "kdfdt": (F,),
                     "hij": (H,), "dhijdt": (H,), "khij": (H,),
                     "kdhijdt": (H,)},
                    {"kdfdt": (F,), "kdhijdt": (H,)},
                    ("dt", "a1", "hubble1", "A1", "B1",
                     "a2", "hubble2", "A2", "B2"),
                    bx=self._pair_bx, by=self._pair_by, kind="pair"))
            if self._pair_st is not None:
                self._pair_call = self._make_call(
                    self._pair_st,
                    windows=("f", "dfdt", "kf", "hij", "dhijdt", "khij"),
                    extra_names=("kdfdt", "kdhijdt"))

    @staticmethod
    def _gw_stage(h0, dh0, kh0, kdh0, lap_h, sij, A, B, dt, hub):
        """One 2N-storage tensor-sector stage (the identical arithmetic
        sequence everywhere it appears: single-stage body and both halves
        of the pair body)."""
        kh1 = A * kh0 + dt * dh0
        h1 = h0 + B * kh1
        kdh1 = A * kdh0 + dt * (lap_h - 2 * hub * dh0
                                + 16 * np.pi * sij)
        dh1 = dh0 + B * kdh1
        return h1, dh1, kh1, kdh1

    def _sij_eval(self, ftaps_like, a, hub, dtype, shape):
        """Evaluate the symbolic anisotropic-stress components from field
        gradients taken through ``ftaps_like`` (raw window taps or a
        composed intermediate-field view)."""
        inv_dx = [1.0 / d for d in self.dx]
        grads = _grad_from_taps(ftaps_like, _grad_coefs[self.h], inv_dx)
        dfdx = jnp.stack(grads, axis=1)  # (F, 3, bx, by, Z)
        env = {"dfdx": dfdx, "a": a, "hubble": hub}
        return jnp.stack([
            jnp.broadcast_to(
                jnp.asarray(_field.evaluate(self._sij[c], env), dtype),
                shape)
            for c in range(self.n_hij)])

    def _preheat_body(self, taps, extras, scalars, energy=False):
        ftaps, htaps = taps["f"], taps["hij"]

        # scalar-system update from the shared f window (inherited body;
        # the expansion couples to the scalar-sector energy only, so the
        # esums come from the f parts — reference driver semantics)
        souts = self._scalar_body(
            ftaps, {n: extras[n] for n in ("dfdt", "kf", "kdfdt")},
            scalars, energy=energy)

        inv_dx2 = [1.0 / d**2 for d in self.dx]
        lap_coefs = _lap_coefs[self.h]
        dt, a, hub = scalars["dt"], scalars["a"], scalars["hubble"]
        A, B = scalars["A"], scalars["B"]

        hint = htaps()
        lap_h = _lap_from_taps(htaps, lap_coefs, inv_dx2)
        sij = self._sij_eval(ftaps, a, hub, hint.dtype, hint.shape[1:])

        dh, kh, kdh = extras["dhijdt"], extras["khij"], extras["kdhijdt"]
        h2, dh2, kh2, kdh2 = self._gw_stage(
            hint, dh, kh, kdh, lap_h, sij, A, B, dt, hub)
        return {**souts,
                "hij": h2, "dhijdt": dh2, "khij": kh2, "kdhijdt": kdh2}

    def _pair_body(self, taps, extras, scalars):
        """Two consecutive stages of the full scalar+GW system in one
        pass over HBM (same composition rule as the scalar pair: the
        stage-1 fields are pointwise axpys of windowed arrays, so their
        Laplacians/gradients come from the same taps)."""
        souts, f1_taps = self._scalar_pair_core(taps, extras, scalars)

        th, tdh, tkh = taps["hij"], taps["dhijdt"], taps["khij"]
        kdh0 = extras["kdhijdt"]
        inv_dx2 = [1.0 / d**2 for d in self.dx]
        lap_coefs = _lap_coefs[self.h]
        dt = scalars["dt"]
        a1, hub1 = scalars["a1"], scalars["hubble1"]
        A1, B1 = scalars["A1"], scalars["B1"]
        a2, hub2 = scalars["a2"], scalars["hubble2"]
        A2, B2 = scalars["A2"], scalars["B2"]

        # stage 1 (identical arithmetic to _preheat_body)
        h0, dh0 = th(), tdh()
        lap_h = _lap_from_taps(th, lap_coefs, inv_dx2)
        sij1 = self._sij_eval(taps["f"], a1, hub1, h0.dtype, h0.shape[1:])
        h1, dh1, kh1, kdh1 = self._gw_stage(
            h0, dh0, tkh(), kdh0, lap_h, sij1, A1, B1, dt, hub1)

        h1_taps = self._axpy_taps(th, tkh, tdh, B1, A1, dt, h1)
        lap_h1 = _lap_from_taps(h1_taps, lap_coefs, inv_dx2)
        sij2 = self._sij_eval(f1_taps, a2, hub2, h0.dtype, h0.shape[1:])

        # stage 2
        h2, dh2, kh2, kdh2 = self._gw_stage(
            h1, dh1, kh1, kdh1, lap_h1, sij2, A2, B2, dt, hub2)
        return {**souts,
                "hij": h2, "dhijdt": dh2, "khij": kh2, "kdhijdt": kdh2}

    def stage_pair(self, s, carry, t, dt, rhs_args, rhs_args2=None,
                   s2=None):
        """Run stages ``s`` and ``s2`` (default ``s+1``) of the
        scalar+GW system as one fused kernel (see
        :meth:`FusedScalarStepper.stage_pair`)."""
        self._check_pair(s, s + 1 if s2 is None else s2)
        state, k = carry
        with trace_scope("fused_rk_stage_pair"):
            outs = self._pair_call(
                {"f": state["f"], "dfdt": state["dfdt"], "kf": k["f"],
                 "hij": state["hij"], "dhijdt": state["dhijdt"],
                 "khij": k["hij"]},
                self._pair_scalars(s, dt, rhs_args, rhs_args2, s2),
                {"kdfdt": k["dfdt"], "kdhijdt": k["dhijdt"]})
        return ({"f": outs["f"], "dfdt": outs["dfdt"],
                 "hij": outs["hij"], "dhijdt": outs["dhijdt"]},
                {"f": outs["kf"], "dfdt": outs["kdfdt"],
                 "hij": outs["khij"], "dhijdt": outs["kdhijdt"]})

    def stage(self, s, carry, t, dt, rhs_args):
        state, k = carry
        with trace_scope("fused_rk_stage"):
            outs = self._both_call(
                {"f": state["f"], "hij": state["hij"]},
                self._stage_scalars(s, dt, rhs_args),
                {"dfdt": state["dfdt"], "kf": k["f"], "kdfdt": k["dfdt"],
                 "dhijdt": state["dhijdt"], "khij": k["hij"],
                 "kdhijdt": k["dhijdt"]})
        new_state = {"f": outs["f"], "dfdt": outs["dfdt"],
                     "hij": outs["hij"], "dhijdt": outs["dhijdt"]}
        new_k = {"f": outs["kf"], "dfdt": outs["kdfdt"],
                 "hij": outs["khij"], "dhijdt": outs["kdhijdt"]}
        return (new_state, new_k)

    # -- deferred-drag coupled pair (scalar+GW) ----------------------------

    @property
    def _hubble_free(self):
        exprs = ([self._V] + list(self._dvdf)
                 + [self._sij[c] for c in range(self.n_hij)])
        return all("hubble" not in _field.field_names(e) for e in exprs)

    def _def_win_defs(self, in_deferred):
        F, H = self.F, self.n_hij
        if in_deferred:
            return ({"f": F, "dfp": F, "kdfp": F, "kf": F,
                     "hij": H, "dhp": H, "kdhp": H, "khij": H}, {})
        return ({"f": F, "dfdt": F, "kf": F,
                 "hij": H, "dhijdt": H, "khij": H},
                {"kdfdt": (F,), "kdhijdt": (H,)})

    def _def_out_defs(self):
        F, H = self.F, self.n_hij
        return {"f": (F,), "dfp": (F,), "kf": (F,), "kdfp": (F,),
                "hij": (H,), "dhp": (H,), "khij": (H,), "kdhp": (H,)}

    def _def_in_normal(self, carry):
        state, k = carry
        return ({"f": state["f"], "dfdt": state["dfdt"], "kf": k["f"],
                 "hij": state["hij"], "dhijdt": state["dhijdt"],
                 "khij": k["hij"]},
                {"kdfdt": k["dfdt"], "kdhijdt": k["dhijdt"]})

    def _def_in_deferred(self, carry):
        state, k = carry
        return ({"f": state["f"], "dfp": state["dfdt"],
                 "kdfp": k["dfdt"], "kf": k["f"],
                 "hij": state["hij"], "dhp": state["dhijdt"],
                 "kdhp": k["dhijdt"], "khij": k["hij"]}, {})

    def _def_out(self, outs):
        return ({"f": outs["f"], "dfdt": outs["dfp"],
                 "hij": outs["hij"], "dhijdt": outs["dhp"]},
                {"f": outs["kf"], "dfdt": outs["kdfp"],
                 "hij": outs["khij"], "dhijdt": outs["kdhp"]})

    def _finalize_deferred(self, carry, dt, hubfix, B2p):
        state, k = carry
        kdf = k["dfdt"] - 2 * dt * hubfix * state["dfdt"]
        kdh = k["dhijdt"] - 2 * dt * hubfix * state["dhijdt"]
        return ({"f": state["f"], "dfdt": state["dfdt"] + B2p * kdf,
                 "hij": state["hij"],
                 "dhijdt": state["dhijdt"] + B2p * kdh},
                {"f": k["f"], "dfdt": kdf,
                 "hij": k["hij"], "dhijdt": kdh})

    def _deferred_body(self, taps, extras, scalars, in_deferred):
        souts, f1_taps, _ = self._deferred_pair_core(
            taps, extras, scalars, in_deferred)

        th, tkh = taps["hij"], taps["khij"]
        inv_dx2 = [1.0 / d**2 for d in self.dx]
        lap_coefs = _lap_coefs[self.h]
        dt = scalars["dt"]
        a1, hub1 = scalars["a1"], scalars["hubble1"]
        A1, B1 = scalars["A1"], scalars["B1"]
        a2 = scalars["a2"]
        A2, B2 = scalars["A2"], scalars["B2"]

        if in_deferred:
            tdh = self._completed_taps(taps["dhp"], taps["kdhp"], dt,
                                       scalars["hubfix"], scalars["B2p"])
            kdh0 = (taps["kdhp"]() - 2 * dt * scalars["hubfix"]
                    * taps["dhp"]())
        else:
            tdh = taps["dhijdt"]
            kdh0 = extras["kdhijdt"]

        # tensor stage 1 (exact scalars; identical arithmetic to
        # _preheat_body)
        h0, dh0 = th(), tdh()
        lap_h = _lap_from_taps(th, lap_coefs, inv_dx2)
        sij1 = self._sij_eval(taps["f"], a1, hub1, h0.dtype, h0.shape[1:])
        h1, dh1, kh1, kdh1 = self._gw_stage(
            h0, dh0, tkh(), kdh0, lap_h, sij1, A1, B1, dt, hub1)

        h1_taps = self._axpy_taps(th, tkh, tdh, B1, A1, dt, h1)
        lap_h1 = _lap_from_taps(h1_taps, lap_coefs, inv_dx2)
        sij2 = self._sij_eval(f1_taps, a2, None, h0.dtype, h0.shape[1:])

        # tensor stage 2 with the Hubble drag deferred
        kh2 = A2 * kh1 + dt * dh1
        h2 = h1 + B2 * kh2
        kdhp = A2 * kdh1 + dt * (lap_h1 + 16 * np.pi * sij2)
        return {**souts, "hij": h2, "dhp": dh1, "khij": kh2,
                "kdhp": kdhp}

    def _ensure_energy_call(self):
        if self._es_call is None:
            F, H = self.F, self.n_hij
            st = self._build_stencil(
                {"f": F, "hij": H},
                lambda t, e, s: self._preheat_body(t, e, s, energy=True),
                {"f": (F,), "dfdt": (F,), "kf": (F,), "kdfdt": (F,),
                 "hij": (H,), "dhijdt": (H,), "khij": (H,),
                 "kdhijdt": (H,)},
                {"dfdt": (F,), "kf": (F,), "kdfdt": (F,),
                 "dhijdt": (H,), "khij": (H,), "kdhijdt": (H,)},
                ("dt", "a", "hubble", "A", "B"),
                bx=getattr(self._both_st, "bx", None),
                by=getattr(self._both_st, "by", None),
                sum_defs={"esums": 2 * F + 1}, kind="energy")
            self._es_call = self._make_call(
                st, windows=("f", "hij"),
                extra_names=("dfdt", "kf", "kdfdt",
                             "dhijdt", "khij", "kdhijdt"))
        return self._es_call

    def _stage_energy(self, s, carry, t, dt, rhs_args):
        state, k = carry
        with trace_scope("fused_rk_stage_energy"):
            outs = self._es_call(
                {"f": state["f"], "hij": state["hij"]},
                self._stage_scalars(s, dt, rhs_args),
                {"dfdt": state["dfdt"], "kf": k["f"], "kdfdt": k["dfdt"],
                 "dhijdt": state["dhijdt"], "khij": k["hij"],
                 "kdhijdt": k["dhijdt"]})
        new_state = {"f": outs["f"], "dfdt": outs["dfdt"],
                     "hij": outs["hij"], "dhijdt": outs["dhijdt"]}
        new_k = {"f": outs["kf"], "dfdt": outs["kdfdt"],
                 "hij": outs["khij"], "dhijdt": outs["kdhijdt"]}
        return ((new_state, new_k), outs["esums"])
