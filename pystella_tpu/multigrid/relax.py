"""Relaxation (smoothing) solvers for boundary-value problems L(f) = rho.

TPU-native counterpart of /root/reference/pystella/multigrid/relax.py:36-373.
The reference builds four loopy kernels per solver (stepper, residual,
lhs-correction, residual statistics) and ping-pongs ``f``/``tmp_f`` arrays
with a halo exchange per iteration. Here each of those becomes a jitted
function; the whole ``nu``-iteration smooth runs as ONE compiled
computation.

One array layout goes through all of it: a level's unknowns are one
``(nf, X, Y, Z)`` stack in ``f_to_rho_dict``'s order, and so are their
sources, a residual and a tau right-hand side. It is what the stencil
kernels take and give, so a level's programs pass stacks to one another
and none copies its operands in or its result out (at 512**3 a
``jnp.stack`` of two arrays is 3.1 ms beside a 4.8-ms kernel, and each of
a cycle's ~50 kernel programs made two and an unstack); the XLA bodies
index the leading axis, which fuses. ``smooth``, ``residual``, ``tau_rhs``
and ``__call__`` also take dicts by name, from callers outside a
multigrid walk: they stack at their own boundary (:meth:`stack`, one
program) and return by name (:meth:`unstack`).

On the XLA path a smooth is a ``lax.fori_loop`` whose body fuses
the stencil evaluation with the pointwise update, with ``lax.ppermute``
halo exchanges inside (via ``shard_map``) on sharded levels and
periodic-wrap pads on replicated (coarse) levels. On the Pallas path
(``smoother="pallas"``) a sweep is one stencil kernel, or on a streaming
unsharded level two sweeps are (the steppers' ``pair`` trade: both read
old values only, so one pass over HBM serves two), and the loop runs two
kernel calls an iteration, so that its carry's buffer and a temporary
alternate and XLA copies nothing (``RelaxationBase._pallas_level``).

Equations are specified as in the reference (``lhs_dict`` mapping unknown
:class:`~pystella_tpu.Field`\\ s to ``(lhs, rho)`` pairs), with one
TPU-first change: the Laplacian appears *symbolically* as
``Field("lap_<name>")`` and is supplied by the solver from the
order-``2h`` centered stencil, so the smoother's effective operator is
exactly consistent with :class:`~pystella_tpu.FiniteDifferencer`. The
Jacobi/Newton diagonal is ``diff(lhs, f) + diff(lhs, lap_f) * lap_diag``
where ``lap_diag = sum_d c_0 / dx_d**2`` is the stencil's center weight
(the chain-rule term the reference gets from symbolic stencil
differentiation, relax.py:341-349).
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from pystella_tpu import field as _field
from pystella_tpu.field import Field, Var, diff, evaluate
from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs import metrics as _metrics
from pystella_tpu.obs.scope import trace_scope
from pystella_tpu.ops.derivs import (
    SecondCenteredDifference, _apply_centered, _shifted)
from pystella_tpu.multigrid.transfer import periodic_pad

__all__ = ["LevelSpec", "RelaxationBase", "JacobiIterator", "NewtonIterator"]


@dataclasses.dataclass(frozen=True)
class LevelSpec:
    """Geometry of one multigrid level: global shape, spacing, and whether
    its arrays are sharded over the mesh (coarse levels whose local blocks
    would drop below the stencil halo are replicated instead — the
    level-dependent re-decomposition the reference gets by building a
    ``DomainDecomposition`` per level, multigrid/__init__.py:357-366)."""

    grid_shape: tuple
    dx: tuple
    sharded: bool


def _field_name(f):
    if isinstance(f, _field.Field):
        return f.name
    if isinstance(f, str):
        return f
    raise TypeError(f"lhs_dict keys must be Field or str, got {type(f)}")


#: (Linf, L2) residual norms of a stack, ``nf`` of each from ONE program
#: shared by every solver instance (eagerly: four norm ops an unknown a
#: smooth, each a device dispatch)
_residual_norms = _obs_memory.instrument_jit(
    lambda r: (jnp.max(jnp.abs(r), axis=(-3, -2, -1)),
               jnp.sqrt(jnp.mean(r * r, axis=(-3, -2, -1)))),
    label="mg.residual_norms")

#: the two layout copies: per-name arrays into the ``(nf, X, Y, Z)``
#: stack a level's programs pass on (cast on the way), and back
_stack = _obs_memory.instrument_jit(
    lambda arrays, dtype: jnp.stack([jnp.asarray(a, dtype) for a in arrays]),
    label="mg.stack", static_argnums=1)
_unstack = _obs_memory.instrument_jit(
    lambda stacked: tuple(stacked), label="mg.unstack")


def dispatch(fn, *args):
    """``fn(*args)``, counted (``mg_dispatches``): every program of a
    multigrid walk goes through here, and ``mg_cycle`` says how many."""
    _metrics.counter("mg_dispatches").inc()
    return fn(*args)


class RelaxationBase:
    """Base class for relaxation solvers (reference relax.py:36-320).

    :arg decomp: a :class:`~pystella_tpu.DomainDecomposition` (used for
        sharded levels; replicated levels need no communication).
    :arg lhs_dict: dict ``{Field(f): (lhs, rho)}``; ``lhs`` is a symbolic
        expression in ``Field(f)``, ``Field("lap_" + f)`` and any auxiliary
        names; ``rho`` must be a :class:`~pystella_tpu.Field`.
    :arg halo_shape: stencil radius ``h`` of the order-``2h`` Laplacian.
    :arg omega: relaxation damping factor (the reference passes it via
        ``fixed_parameters=dict(omega=...)``, which is also accepted).
    """

    def __init__(self, decomp, lhs_dict, halo_shape=1, omega=1.0,
                 dtype=None, smoother="auto", overlap=None, **kwargs):
        self.decomp = decomp
        self.halo_shape = int(halo_shape)
        # halo-overlap policy for sharded levels (resolved per level
        # decomp at compile time — coarse replicated levels never
        # communicate); None defers to PYSTELLA_HALO_OVERLAP / auto
        self._overlap_override = overlap
        self.omega = float(kwargs.pop("fixed_parameters", {}).get(
            "omega", omega))
        self.dtype = dtype
        if smoother == "auto":
            # the Pallas sweep kernels are the measured-fast tier on TPU;
            # on CPU they would run in interpret mode (orders of magnitude
            # slower than XLA) — tests opt in explicitly
            smoother = "pallas" if jax.default_backend() == "tpu" else "xla"
        if smoother not in ("pallas", "xla"):
            raise ValueError(f"unknown smoother {smoother}")
        self.smoother = smoother
        self.stencil = SecondCenteredDifference(self.halo_shape)

        self.f_to_rho_dict = {}
        #: per kind, per unknown: the relaxation update (``smooth``),
        #: ``rho - lhs`` (``residual``) and ``lhs`` (``tau``)
        self._exprs = {"smooth": {}, "residual": {}, "tau": {}}
        for f, (lhs, rho) in lhs_dict.items():
            name = _field_name(f)
            if not isinstance(rho, _field.Field):
                raise TypeError("rho must be a Field naming the source array")
            self.f_to_rho_dict[name] = rho.name
            fsym = f if isinstance(f, _field.Field) else Field(name)
            self._exprs["smooth"][name] = self.step_operator(fsym, lhs, rho)
            self._exprs["residual"][name] = rho - lhs
            self._exprs["tau"][name] = lhs
        self._compiled = {}
        self._planned = set()

    # -- subclass hook ------------------------------------------------------

    def step_operator(self, f, lhs, rho):
        """Symbolic relaxation update for unknown ``f`` (reference
        relax.py:140-150)."""
        raise NotImplementedError

    def _diagonal(self, f, lhs):
        """d lhs / d f including the Laplacian's center weight."""
        lap = Field("lap_" + f.name)
        return diff(lhs, f) + diff(lhs, lap) * Var("_lap_diag")

    # -- the layout's boundary ----------------------------------------------

    def stack(self, arrays, sources=False):
        """The ``(nf, X, Y, Z)`` stack of ``arrays`` by name, in
        ``f_to_rho_dict``'s order (``sources``: by the sources' names),
        cast to the solver's ``dtype``: one program, a new buffer."""
        keys = self.f_to_rho_dict.values() if sources else self.f_to_rho_dict
        _metrics.counter("mg_layout_copies").inc()
        return dispatch(_stack, tuple(arrays[k] for k in keys),
                        None if self.dtype is None else np.dtype(self.dtype))

    def unstack(self, stacked, sources=False):
        """A stack's arrays by name (:meth:`stack`'s inverse): one
        program."""
        keys = self.f_to_rho_dict.values() if sources else self.f_to_rho_dict
        _metrics.counter("mg_layout_copies").inc()
        return dict(zip(keys, dispatch(_unstack, stacked)))

    # -- local stencil + update ---------------------------------------------

    def _lap_from_padded(self, padded, dx):
        h = self.halo_shape
        la = padded.ndim - 3
        acc = None
        for d in range(3):
            y = padded
            for other in range(3):
                if other != d:
                    y = _shifted(y, la + other, 0, h)
            term = _apply_centered(y, la + d, self.stencil.coefs, h, 2,
                                   1 / dx[d] ** 2)
            acc = term if acc is None else acc + term
        return acc

    def _center(self, padded):
        """The unpadded block back out of a halo-padded one."""
        h = self.halo_shape
        la = padded.ndim - 3
        y = padded
        for d in range(3):
            y = _shifted(y, la + d, 0, h)
        return y

    def _lap_diag(self, dx):
        return float(sum(self.stencil.coefs[0] / d ** 2 for d in dx))

    def _update(self, kind, f, lap, other, aux, dx):
        """What ``kind`` makes of a block of the unknowns' stack ``f``,
        its Laplacian ``lap`` and ``other`` (the sources' stack; for
        ``tau`` the restricted fine residual, to which the coarse
        operator is added: the FAS right-hand side, reference
        lhs_correction, relax.py:202-214), pointwise: the one body of the
        XLA, the overlapped and the kernel paths."""
        env = {**aux, "omega": self.omega, "_lap_diag": self._lap_diag(dx)}
        for i, (n, r) in enumerate(self.f_to_rho_dict.items()):
            env[n], env["lap_" + n] = f[i], lap[i]
            if kind != "tau":
                env[r] = other[i]
        vals = jnp.stack([
            jnp.broadcast_to(jnp.asarray(evaluate(expr, env), f.dtype),
                             f.shape[1:])
            for expr in self._exprs[kind].values()])
        return other + vals if kind == "tau" else vals

    # -- compiled per-level operations --------------------------------------

    def _get_compiled(self, kind, level, nu=None, decomp=None):
        """The XLA program of a level's ``kind`` on stacks: the CPU
        default, and any level the kernels refuse. On a sharded level
        with the halo overlap on, each sweep issues the unknowns'
        ``ppermute``s first, computes the interior from local data while
        they fly and stitches the boundary shells once the halos land
        (``decomp.overlap_stencil``; bit-exact with the padded sweep:
        identical taps and per-element arithmetic)."""
        from pystella_tpu.parallel import overlap as _overlap
        decomp = decomp if decomp is not None else self.decomp
        use_overlap = (level.sharded
                       and _overlap.enabled(decomp,
                                            self._overlap_override))
        key = (kind, level, nu, decomp, use_overlap)
        cached = self._compiled.get(key)
        if cached is not None:
            return cached
        if kind not in self._exprs:
            raise ValueError(kind)

        pad_fn = (decomp.pad_with_halos if level.sharded
                  else periodic_pad)
        dx = level.dx
        halo = (self.halo_shape,) * 3

        if use_overlap:
            def apply(padded, ex):
                return self._update(
                    kind, self._center(padded),
                    self._lap_from_padded(padded, dx), ex["other"],
                    ex["aux"], dx)

            def sweep(f, other, aux):
                return decomp.overlap_stencil(
                    f, halo, apply, extras={"other": other, "aux": aux})
        else:
            def sweep(f, other, aux):
                lap = self._lap_from_padded(pad_fn(f, halo), dx)
                return self._update(kind, f, lap, other, aux, dx)

        if kind == "smooth":
            def body(f, rhos, aux):
                return lax.fori_loop(
                    0, nu, lambda _, f: sweep(f, rhos, aux), f)
        else:
            body = sweep

        if level.sharded:
            spec = decomp.spec(1)
            body = decomp.shard_map(body, (spec, spec, decomp.spec(0)), spec)
        fn = _obs_memory.instrument_jit(
            body, label=f"mg.{kind}{tuple(level.grid_shape)}")
        self._compiled[key] = fn
        return fn

    def _plan_level(self, kind, level, decomp, dtype, tier, st=None,
                    reason=None, pair=None, pair_reason=None):
        """One ``mg_level_plan`` event a level: which tier serves it
        (``streaming`` with its blocking, ``resident``, or ``xla`` with
        the reason), said when the first of its kernels is built. A
        level's smooth, residual and tau kernels have the same windows,
        extras and outputs, so what one of them gets the others get.
        ``pair`` is the level's two-sweep smooth kernel
        (``sweeps_per_pass`` 2, with its blocking) or, where a streaming
        level keeps one sweep a pass, ``pair_reason`` says why."""
        key = (level, decomp)
        if key in self._planned:
            return
        self._planned.add(key)
        proc = decomp.proc_shape if level.sharded else (1, 1, 1)
        grid = getattr(st, "grid", None)  # a streaming kernel's alone
        _events.emit(
            "mg_level_plan", grid_shape=list(level.grid_shape),
            local_shape=[n // p for n, p in zip(level.grid_shape, proc)],
            tier=tier, stencil=type(st).__name__ if st is not None else None,
            bx=getattr(st, "bx", None), by=getattr(st, "by", None),
            grid=list(grid) if grid else None, reason=reason, kernel=kind,
            sweeps_per_pass=2 if pair is not None else 1,
            pair_bx=getattr(pair, "bx", None),
            pair_by=getattr(pair, "by", None), pair_reason=pair_reason,
            dtype=str(jnp.dtype(dtype)), smoother=self.smoother,
            layout="stacked", label=type(self).__name__)

    # -- Pallas sweep tier ---------------------------------------------------

    def _aux_struct(self, aux):
        """Static routing of auxiliary arrays: lattice-shaped values ride
        the kernel's blockwise extras, scalars go to SMEM."""
        struct = []
        for k in sorted(aux):
            v = aux[k]
            ndim = getattr(v, "ndim", 0)
            struct.append((k, "lattice" if ndim >= 3 else "scalar"))
        return tuple(struct)

    def _pallas_level(self, kind, level, decomp, dtype, aux_struct):
        """A stencil-kernel pass for one level: ``smooth``, ``residual``
        or ``tau``, as a program ``fn(fstack, other, aux_args, nu)`` from
        the unknowns' ``(nf, X, Y, Z)`` stack and the sources' (for
        ``tau`` the restricted residual's) to a stack: the kernel's own
        operands and result, nothing stacked or sliced round it. Each
        sweep reads the unknowns once from HBM, computes the order-2h
        Laplacian from the VMEM window, evaluates the update pointwise,
        and writes once — the identical streaming pattern as the fused
        RK stages.

        A smooth is ``nu >= 1`` sweeps with ``nu`` a runtime ``int32``,
        so one compile serves every sweep count, and no buffer is ever
        both a kernel's operand and its result (the kernel cannot write
        where it still reads, and XLA resolves that by a copy of the
        whole stack beside the sweep: ``PERF.md`` section 6, PR 33).
        First a conditional takes the first sweeps out of the parameter
        into a new buffer: every branch computes, so none passes its
        operand through (a branch that did would have XLA copy the
        parameter before the conditional, whichever branch runs), and
        the parameter is only read, so it is not donated. Then a
        ``fori_loop`` runs TWO kernel calls an iteration on that buffer
        as its carry: the first writes a temporary, the second the
        carry's buffer, whose last reader has finished. The compiled v5e
        program holds no lattice-shaped ``copy``
        (``tests/test_tpu_compile.py``).

        On a streaming level that is not sharded a call of that loop is
        the two-sweep kernel (PR 52): a sweep reads old values only, so
        ``S(S(f))`` comes from one pass over HBM, out of windows two
        radii wide of the unknowns and of what else a sweep reads at a
        site, for the 3 ``nf`` lattice passes one sweep moves. An
        iteration is then four sweeps and the conditional a four-way
        switch on ``nu mod 4`` (one sweep, a pair, a pair after one, two
        pairs). Elsewhere (a sharded level, whose padded copy carries
        one radius; a resident level; a window the VMEM budget refuses:
        ``mg_level_plan`` says which) a call is one sweep, an iteration
        two, and the conditional takes the odd sweep or a first pair.

        Returns None when this level/mesh cannot take the kernel tier
        (z-sharded, sublane-infeasible sharded y, over-budget resident)
        — callers fall back to the XLA path."""
        from pystella_tpu.ops.pallas_stencil import (
            HY, ResidentStencil, StreamingStencil, lap_from_taps,
            sharded_halo)

        key = ("pallas", kind, level, decomp, str(dtype), aux_struct)
        if key in self._compiled:
            return self._compiled[key]

        nf = len(self.f_to_rho_dict)
        proc = decomp.proc_shape if level.sharded else (1, 1, 1)
        px, py, pz = proc
        local_shape = tuple(n // p for n, p in zip(level.grid_shape, proc))
        feasible = (pz == 1
                    and (py == 1 or (local_shape[1] >= HY
                                     and local_shape[1] % HY == 0)))
        coefs = self.stencil.coefs
        inv_dx2 = [1.0 / d**2 for d in level.dx]
        aux_lat = [k for k, kk in aux_struct if kk == "lattice"]
        aux_scal = [k for k, kk in aux_struct if kk == "scalar"]

        def body_of(kind):
            def body(taps, extras, scalars):
                aux = {**{k: extras[k] for k in aux_lat},
                       **{k: scalars[k] for k in aux_scal}}
                return {"out": self._update(
                    kind, taps(), lap_from_taps(taps, coefs, inv_dx2),
                    extras["rhos"], aux, level.dx)}
            return body

        body = body_of(kind)

        st = None
        reason = ("z-sharded mesh, or a sharded y no 8-row window "
                  "divides")
        if feasible:
            extra_defs = {"rhos": (nf,), **{k: () for k in aux_lat}}
            try:
                st = StreamingStencil(
                    local_shape, {"f": nf}, self.halo_shape, body,
                    {"out": (nf,)}, extra_defs=extra_defs,
                    scalar_names=tuple(aux_scal), dtype=dtype,
                    x_halo=(px > 1), y_halo=(py > 1), kind="mg_" + kind)
            except ValueError as e:
                reason = str(e)
                if px == 1 and py == 1:
                    try:
                        st = ResidentStencil(
                            local_shape, {"f": nf}, self.halo_shape,
                            body, {"out": (nf,)}, extra_defs=extra_defs,
                            scalar_names=tuple(aux_scal), dtype=dtype)
                    except ValueError as e2:
                        reason = f"{e}; {e2}"
        if st is None:
            self._plan_level(kind, level, decomp, dtype, "xla",
                             reason=reason)
            self._compiled[key] = None
            return None
        sharded = px > 1 or py > 1
        streaming = isinstance(st, StreamingStencil)
        # two sweeps a pass (the steppers' `pair` trade): sweep 1 once
        # over the block grown by h rows, from windows 2h wide of the
        # unknowns and of everything the sweep reads at a site (the
        # sources and lattice auxiliaries become windows too), then
        # sweep 2 from that block: the single body twice, so the two
        # calls' arithmetic and not an approximation of it, for one
        # call's 3 nf lattice passes. Built beside the level's first
        # kernel of any kind, whose plan says whether the smooth has it.
        pair, pair_reason = None, None
        if streaming and sharded:
            pair_reason = ("sharded level: its padded copy carries one "
                           "stencil radius in x")
        elif streaming:
            h = self.halo_shape
            sweep = body_of("smooth")

            def at_site(taps):
                return {"rhos": taps["rhos"](),
                        **{k: taps[k]()[0] for k in aux_lat}}

            def pair_body(taps, extras, scalars):
                wide = {k: t.grown(h) for k, t in taps.items()}
                first = sweep(wide["f"], at_site(wide), scalars)["out"]
                return sweep(taps["f"].over(first, h), at_site(taps),
                             scalars)

            try:
                pair = StreamingStencil(
                    local_shape,
                    {"f": nf, "rhos": nf, **{k: 1 for k in aux_lat}},
                    h, pair_body, {"out": (nf,)},
                    scalar_names=tuple(aux_scal), dtype=dtype,
                    win_halo=2 * h, stages=2, kind="mg_smooth")
            except ValueError as e:
                pair_reason = str(e)
        self._plan_level(
            kind, level, decomp, dtype,
            "streaming" if streaming else "resident", st=st, pair=pair,
            pair_reason=pair_reason)

        halo = sharded_halo(self.halo_shape, px, py)
        ov = None
        if sharded:
            from pystella_tpu.ops.pallas_stencil import (
                OverlapStreamingStencil)
            from pystella_tpu.parallel import overlap as _overlap
            # x-sharded sweeps overlap the slab ppermutes with the
            # interior kernel (bit-exact; infeasible shapes keep the
            # padded single launch, and the event says why)
            ov = OverlapStreamingStencil.plan_for(
                st, self.halo_shape,
                enabled=_overlap.enabled(decomp, self._overlap_override),
                label=type(self).__name__)

        def run(fstack, other, aux_args, nu):
            scalars = dict(zip(aux_scal, aux_args[len(aux_lat):]))
            extras = {"rhos": jnp.asarray(other, dtype),
                      **dict(zip(aux_lat, aux_args[:len(aux_lat)]))}

            def one(fst):
                if ov is not None:
                    return ov(fst, decomp, scalars=scalars,
                              extras=extras)["out"]
                fin = (decomp.pad_with_halos(
                    fst, halo, exchange=(self.halo_shape,) * 3)
                    if sharded else fst)
                return st(fin, scalars=scalars, extras=extras)["out"]

            if kind != "smooth":
                return one(fstack)

            if pair is None:
                call = one
            else:
                wins = {"rhos": extras["rhos"],
                        **{k: extras[k][None] for k in aux_lat}}

                def call(fst):
                    return pair({"f": fst, **wins}, scalars=scalars)["out"]

            def twice(fst):
                return call(call(fst))

            # the first sweeps out of the parameter by a switch on nu
            # modulo an iteration's sweeps, then two calls an iteration:
            # every branch computes, no buffer is both read and written,
            # and XLA copies nothing (the docstring says why)
            first = [twice, one]
            if pair is not None:
                first += [call, lambda fst: call(one(fst))]
            return lax.fori_loop(
                0, (nu - 1) // len(first), lambda _, fst: twice(fst),
                lax.switch(nu % len(first), first, fstack))

        if sharded:
            from jax.sharding import PartitionSpec as P
            spec = decomp.spec(1)
            in_specs = (spec, spec,
                        (decomp.spec(0),) * len(aux_lat)
                        + (P(),) * len(aux_scal), P())
            run = decomp.shard_map(run, in_specs, spec, check_vma=False)

        fn = _obs_memory.instrument_jit(
            run, label=f"mg.pallas_{kind}{tuple(level.grid_shape)}")
        self._compiled[key] = fn
        return fn

    def _level_program(self, kind, level, fs, other, aux, decomp, nu=None):
        """``kind`` of ``level`` on stacks: the kernel tier where the
        level admits it, else the XLA program."""
        decomp = decomp if decomp is not None else self.decomp
        if self.dtype is not None:
            aux = {k: jnp.asarray(v, self.dtype) for k, v in aux.items()}
        fn = None
        if self.smoother == "pallas":
            aux_struct = self._aux_struct(aux)
            fn = self._pallas_level(kind, level, decomp, fs.dtype,
                                    aux_struct)
        else:
            self._plan_level(kind, level, decomp, fs.dtype, "xla",
                             reason="smoother='xla'")
        if fn is None:
            return dispatch(self._get_compiled(kind, level, nu, decomp),
                            fs, other, aux)
        aux_args = tuple(aux[k] for k, kk in aux_struct if kk == "lattice")
        aux_args += tuple(aux[k] for k, kk in aux_struct if kk == "scalar")
        return dispatch(fn, fs, other, aux_args, np.int32(nu or 0))

    def smooth(self, level, fs, rhos, aux, iterations, decomp=None):
        """Run ``iterations`` relaxation sweeps; returns updated unknowns:
        by name from dicts by name, or, from the stacks a multigrid walk
        carries, a new stack (no operand is donated)."""
        iterations = int(iterations)
        if not iterations:
            return fs
        if isinstance(fs, dict):
            return self.unstack(self.smooth(
                level, self.stack(fs), self.stack(rhos, sources=True), aux,
                iterations, decomp))
        with trace_scope("mg_smooth"):
            return self._level_program("smooth", level, fs, rhos, aux,
                                       decomp, iterations)

    def residual(self, level, fs, rhos, aux, decomp=None):
        """``rho - L(f)`` per unknown (reference relax.py:216-223), by
        name or as a stack, as :meth:`smooth`."""
        if isinstance(fs, dict):
            return self.unstack(self.residual(
                level, self.stack(fs), self.stack(rhos, sources=True), aux,
                decomp))
        with trace_scope("mg_residual"):
            return self._level_program("residual", level, fs, rhos, aux,
                                       decomp)

    def tau_rhs(self, level, fs, restricted_resid, aux, decomp=None):
        """Coarse-level rho with FAS tau-correction: the restricted fine
        residual (by the unknowns' names) plus the coarse operator on the
        restricted unknowns, by the sources' names; or stack to stack.
        The kernel tier where the level admits it (the same kernel shape
        as ``residual``; VERDICT r4 #4), else the XLA halo-pad path."""
        if isinstance(fs, dict):
            return self.unstack(self.tau_rhs(
                level, self.stack(fs), self.stack(restricted_resid), aux,
                decomp), sources=True)
        return self._level_program("tau", level, fs, restricted_resid, aux,
                                   decomp)

    def error_arrays(self, level, fs, rhos, aux, decomp=None):
        """Residual norms as DEVICE arrays ``(Linf, L2)``, an entry an
        unknown in ``f_to_rho_dict``'s order, from one program — no host
        sync, so cycle drivers can record errors without serializing the
        device queue (they convert once at the end;
        multigrid/__init__.py)."""
        if isinstance(fs, dict):
            fs, rhos = self.stack(fs), self.stack(rhos, sources=True)
        return dispatch(_residual_norms,
                        self.residual(level, fs, rhos, aux, decomp))

    def named_errors(self, norms):
        """``{name: [Linf, L2]}`` in floats from :meth:`error_arrays`'s
        pair, fetched if it is still on the device."""
        linf, l2 = (np.asarray(a) for a in norms)
        return {n: [float(linf[i]), float(l2[i])]
                for i, n in enumerate(self.f_to_rho_dict)}

    def get_error(self, level, fs, rhos, aux, decomp=None):
        """L-infinity and L2 norms of the residual per unknown (reference
        relax.py:242-266)."""
        return self.named_errors(
            self.error_arrays(level, fs, rhos, aux, decomp))

    # -- standalone relaxation (reference __call__, relax.py:164-200) -------

    def __call__(self, decomp, iterations=100, dx=None, **arrays):
        """Relax for ``iterations`` sweeps on global arrays. Unknowns, rho,
        and auxiliary arrays are passed by keyword; returns the dict of
        updated unknowns."""
        if dx is None:
            raise ValueError("dx is required")
        if np.isscalar(dx):
            dx = (float(dx),) * 3
        fs = {n: arrays.pop(n) for n in self.f_to_rho_dict}
        rhos = {r: arrays.pop(r) for r in self.f_to_rho_dict.values()}
        first = next(iter(fs.values()))
        sharded = (decomp is not None
                   and any(p > 1 for p in decomp.proc_shape))
        level = LevelSpec(tuple(first.shape[-3:]), tuple(dx), sharded)
        return self.smooth(level, fs, rhos, arrays, iterations, decomp)


class JacobiIterator(RelaxationBase):
    """Damped Jacobi iteration for linear systems (reference
    relax.py:323-349): ``f <- (1-omega) f + omega D^{-1} (rho - (L-D) f)``.
    """

    def step_operator(self, f, lhs, rho):
        omega = Var("omega")
        D = self._diagonal(f, lhs)
        R_y = lhs - D * f  # valid for linear equations, as in the reference
        return (1 - omega) * f + omega * (rho - R_y) / D


class NewtonIterator(RelaxationBase):
    """Newton iteration for arbitrary (nonlinear) systems (reference
    relax.py:352-373): ``f <- f - omega (L(f) - rho) / (dL/df)``."""

    def step_operator(self, f, lhs, rho):
        omega = Var("omega")
        D = self._diagonal(f, lhs)
        return f - omega * (lhs - rho) / D
