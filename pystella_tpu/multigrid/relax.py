"""Relaxation (smoothing) solvers for boundary-value problems L(f) = rho.

TPU-native counterpart of /root/reference/pystella/multigrid/relax.py:36-373.
The reference builds four loopy kernels per solver (stepper, residual,
lhs-correction, residual statistics) and ping-pongs ``f``/``tmp_f`` arrays
with a halo exchange per iteration. Here each of those becomes a jitted
function; the whole ``nu``-iteration smooth runs as ONE compiled
computation. On the XLA path that is a ``lax.fori_loop`` whose body fuses
the stencil evaluation with the pointwise update, with ``lax.ppermute``
halo exchanges inside (via ``shard_map``) on sharded levels and
periodic-wrap pads on replicated (coarse) levels. On the Pallas path
(``smoother="pallas"``) a sweep is one stencil kernel and the loop runs
two sweeps an iteration, the odd sweep after it: a ``while``'s carry
and the kernel's operand cannot share a buffer (the kernel reads a window
of its input while it writes), so with one sweep an iteration XLA copies
the whole carry before every kernel call; with two, the first sweep
writes a temporary, the second writes the carry's buffer back, and
nothing is copied (``RelaxationBase._pallas_level``).

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


#: jitted (Linf, L2) residual norms — one executable shared by every
#: solver instance; the four eager norm ops per unknown per smooth would
#: each be a separate device dispatch (its cost on the chip: not measured)
_residual_norms = _obs_memory.instrument_jit(
    lambda rn: (jnp.max(jnp.abs(rn)), jnp.sqrt(jnp.mean(rn * rn))),
    label="mg.residual_norms")


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
        self.step_exprs = {}
        self.resid_exprs = {}
        self.lhs_exprs = {}
        for f, (lhs, rho) in lhs_dict.items():
            name = _field_name(f)
            if not isinstance(rho, _field.Field):
                raise TypeError("rho must be a Field naming the source array")
            self.f_to_rho_dict[name] = rho.name
            fsym = f if isinstance(f, _field.Field) else Field(name)
            self.step_exprs[name] = self.step_operator(fsym, lhs, rho)
            self.resid_exprs[name] = rho - lhs
            self.lhs_exprs[name] = lhs
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

    # -- local stencil + environment ---------------------------------------

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

    def _local_lap(self, x, dx, pad_fn):
        h = self.halo_shape
        return self._lap_from_padded(pad_fn(x, (h,) * 3), dx)

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

    def _env(self, fs, rhos, aux, dx, pad_fn):
        env = {**aux, **rhos, **fs}
        for n in fs:
            env["lap_" + n] = self._local_lap(fs[n], dx, pad_fn)
        env["omega"] = self.omega
        env["_lap_diag"] = self._lap_diag(dx)
        return env

    # -- compiled per-level operations --------------------------------------

    def _overlap_body(self, kind, level, decomp, nu=None):
        """The overlapped-halo variant of a sharded level's XLA body:
        per sweep, the unknowns' ``ppermute``s are issued first, the
        interior update is computed from local data while the
        collectives fly, and the boundary shells are stitched once
        halos land (``decomp.overlap_stencil``; bit-exact with the
        padded body — identical taps and per-element arithmetic)."""
        names = list(self.f_to_rho_dict)
        h = self.halo_shape
        halo = (h,) * 3
        dx = level.dx
        exprs = {"smooth": self.step_exprs, "residual": self.resid_exprs,
                 "tau": self.lhs_exprs}[kind]

        def apply(padded_fs, ex):
            env = {**ex.get("aux", {}), **ex.get("rhos", {})}
            env["omega"] = self.omega
            env["_lap_diag"] = self._lap_diag(dx)
            for n in names:
                p = padded_fs[n]
                env[n] = self._center(p)
                env["lap_" + n] = self._lap_from_padded(p, dx)
            if kind == "tau":
                return {self.f_to_rho_dict[n]:
                        ex["rr"][n] + evaluate(exprs[n], env)
                        for n in names}
            return {n: evaluate(exprs[n], env) for n in names}

        if kind == "smooth":
            def body(fs, rhos, aux):
                def it(_, fs):
                    return decomp.overlap_stencil(
                        fs, halo, apply,
                        extras={"rhos": rhos, "aux": aux})
                return lax.fori_loop(0, nu, it, fs)
        elif kind == "residual":
            def body(fs, rhos, aux):
                return decomp.overlap_stencil(
                    fs, halo, apply, extras={"rhos": rhos, "aux": aux})
        else:
            def body(fs, rr, aux):
                return decomp.overlap_stencil(
                    fs, halo, apply, extras={"rr": rr, "aux": aux})
        return body

    def _get_compiled(self, kind, level, nu=None, decomp=None):
        from pystella_tpu.parallel import overlap as _overlap
        decomp = decomp if decomp is not None else self.decomp
        use_overlap = (level.sharded
                       and _overlap.enabled(decomp,
                                            self._overlap_override))
        key = (kind, level, nu, decomp, use_overlap)
        cached = self._compiled.get(key)
        if cached is not None:
            return cached

        pad_fn = (decomp.pad_with_halos if level.sharded
                  else periodic_pad)
        dx = level.dx

        if use_overlap and kind in ("smooth", "residual", "tau"):
            body = self._overlap_body(kind, level, decomp, nu)
        elif kind == "smooth":
            def body(fs, rhos, aux):
                def it(_, fs):
                    env = self._env(fs, rhos, aux, dx, pad_fn)
                    return {n: evaluate(self.step_exprs[n], env)
                            for n in fs}
                return lax.fori_loop(0, nu, it, fs)
        elif kind == "residual":
            def body(fs, rhos, aux):
                env = self._env(fs, rhos, aux, dx, pad_fn)
                return {n: evaluate(self.resid_exprs[n], env) for n in fs}
        elif kind == "tau":
            # FAS coarse-grid right-hand side: restricted fine residual
            # plus the coarse operator applied to the restricted unknowns
            # (reference lhs_correction, relax.py:202-214)
            def body(fs, rr, aux):
                env = self._env(fs, {}, aux, dx, pad_fn)
                return {self.f_to_rho_dict[n]:
                        rr[n] + evaluate(self.lhs_exprs[n], env)
                        for n in fs}
        else:
            raise ValueError(kind)

        if level.sharded:
            spec = decomp.spec(0)
            body = decomp.shard_map(body, (spec, spec, spec), spec)
        fn = _obs_memory.instrument_jit(
            body, label=f"mg.{kind}{tuple(level.grid_shape)}")
        self._compiled[key] = fn
        return fn

    def _cast(self, arrays):
        if self.dtype is None:
            return arrays
        return {k: jnp.asarray(v, self.dtype) for k, v in arrays.items()}

    def _plan_level(self, kind, level, decomp, dtype, tier, st=None,
                    reason=None):
        """One ``mg_level_plan`` event a level: which tier serves it
        (``streaming`` with its blocking, ``resident``, or ``xla`` with
        the reason), said when the first of its kernels is built. A
        level's smooth, residual and tau kernels have the same windows,
        extras and outputs, so what one of them gets the others get."""
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
            grid=list(grid) if grid else None, reason=reason, kernel=kind, dtype=str(jnp.dtype(dtype)),
            smoother=self.smoother, label=type(self).__name__)

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
        or ``tau``. Each sweep reads the unknowns once from HBM, computes
        the order-2h Laplacian from the VMEM window, evaluates the update
        pointwise, and writes once — the identical streaming pattern as
        the fused RK stages.

        A smooth is ``nu`` such kernel calls with ``nu`` a runtime
        ``int32``, so one compile serves every sweep count: a
        ``fori_loop`` of ``nu // 2`` iterations of TWO sweeps each, then
        the odd sweep under a ``cond``. Two, because a ``while``'s carry
        is one buffer and the kernel cannot write where it still reads:
        with one sweep an iteration the carry would be both the kernel's
        operand and its result, and XLA resolves that by copying the
        carry before every call (a read and a write of the whole stack
        beside each sweep's own: ``PERF.md`` section 6, PR 33). With two,
        the first sweep writes a temporary and the second writes the
        carry's buffer, whose last reader has finished: the buffers
        alternate. The ``cond``'s branches return the unknowns unstacked,
        so neither passes its operand through and the odd sweep needs no
        copy either (``tests/test_tpu_compile.py`` holds both).

        Returns None when this level/mesh cannot take the kernel tier
        (z-sharded, sublane-infeasible sharded y, over-budget resident)
        — callers fall back to the XLA path."""
        from pystella_tpu.ops.pallas_stencil import (
            HY, ResidentStencil, StreamingStencil, lap_from_taps,
            sharded_halo)

        key = ("pallas", kind, level, decomp, str(dtype), aux_struct)
        if key in self._compiled:
            return self._compiled[key]

        names = list(self.f_to_rho_dict)
        nf = len(names)
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
        exprs = {"smooth": self.step_exprs,
                 "residual": self.resid_exprs,
                 "tau": self.lhs_exprs}[kind]

        def body(taps, extras, scalars):
            fs = taps()
            lap = lap_from_taps(taps, coefs, inv_dx2)
            env = {"omega": self.omega,
                   "_lap_diag": self._lap_diag(level.dx)}
            for i, n in enumerate(names):
                env[n] = fs[i]
                env["lap_" + n] = lap[i]
                if kind != "tau":
                    env[self.f_to_rho_dict[n]] = extras["rhos"][i]
            for k in aux_lat:
                env[k] = extras[k]
            for k in aux_scal:
                env[k] = scalars[k]
            vals = [jnp.broadcast_to(
                jnp.asarray(evaluate(exprs[n], env), fs.dtype),
                fs.shape[1:]) for n in names]
            if kind == "tau":
                # FAS coarse rho: restricted fine residual (riding the
                # "rhos" extras slot) + the coarse operator
                vals = [extras["rhos"][i] + v
                        for i, v in enumerate(vals)]
            return {"out": jnp.stack(vals)}

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
        self._plan_level(
            kind, level, decomp, dtype,
            "streaming" if isinstance(st, StreamingStencil) else "resident",
            st=st)

        halo = sharded_halo(self.halo_shape, px, py)
        sharded = px > 1 or py > 1
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

        def run(fstack, rhostack, aux_args, nu):
            scalars = dict(zip(aux_scal, aux_args[len(aux_lat):]))
            extras = {"rhos": rhostack,
                      **dict(zip(aux_lat, aux_args[:len(aux_lat)]))}

            def one(fst):
                if ov is not None:
                    return ov(fst, decomp, scalars=scalars,
                              extras=extras)["out"]
                fin = (decomp.pad_with_halos(
                    fst, halo, exchange=(self.halo_shape,) * 3)
                    if sharded else fst)
                return st(fin, scalars=scalars, extras=extras)["out"]

            def unstack(fst):
                return tuple(fst[i] for i in range(nf))

            if kind != "smooth":
                return unstack(one(fstack))
            # two sweeps an iteration: the carry's buffer and a temporary
            # alternate, and XLA copies nothing (the docstring says why)
            fstack = lax.fori_loop(
                0, nu // 2, lambda _, fst: one(one(fst)), fstack)
            return lax.cond(nu % 2 == 1, lambda fst: unstack(one(fst)),
                            unstack, fstack)

        if sharded:
            spec = decomp.spec(1)
            from jax.sharding import PartitionSpec as P
            in_specs = (spec, spec,
                        (spec,) * len(aux_lat) + (P(),) * len(aux_scal),
                        P())
            core = decomp.shard_map(run, in_specs, (decomp.spec(0),) * nf,
                                    check_vma=False)
        else:
            core = run

        def entry(f_list, rho_list, aux_args, nu):
            # stack/unstack INSIDE the jit: eager jnp.stack copies the
            # full lattice per call (~40 copies per 512^3 V-cycle); here
            # XLA fuses or aliases them into the kernel's input layout
            fstack = jnp.stack(f_list)
            rhostack = jnp.stack([jnp.asarray(r, dtype) for r in rho_list])
            return list(core(fstack, rhostack, aux_args, nu))

        fn = _obs_memory.instrument_jit(
            entry, label=f"mg.pallas_{kind}{tuple(level.grid_shape)}")
        self._compiled[key] = fn
        return fn

    def _try_pallas(self, kind, level, fs, rhos, aux, decomp, nu=0):
        names = list(self.f_to_rho_dict)
        dtype = jnp.result_type(fs[names[0]])
        if self.smoother != "pallas":
            self._plan_level(kind, level, decomp, dtype, "xla",
                             reason="smoother='xla'")
            return None
        aux_struct = self._aux_struct(aux)
        fn = self._pallas_level(kind, level, decomp, dtype, aux_struct)
        if fn is None:
            return None  # cheap: no stacking before the feasibility gate
        f_list = tuple(fs[n] for n in names)
        rho_list = tuple(rhos[self.f_to_rho_dict[n]] for n in names)
        aux_args = tuple(aux[k] for k, kk in aux_struct
                         if kk == "lattice")
        aux_args += tuple(aux[k] for k, kk in aux_struct
                          if kk == "scalar")
        out = fn(f_list, rho_list, aux_args, jnp.int32(nu))
        return {n: out[i] for i, n in enumerate(names)}

    def smooth(self, level, fs, rhos, aux, iterations, decomp=None):
        """Run ``iterations`` relaxation sweeps; returns updated unknowns."""
        decomp = decomp if decomp is not None else self.decomp
        iterations = int(iterations)
        fs, rhos, aux = self._cast(fs), self._cast(rhos), self._cast(aux)
        with trace_scope("mg_smooth"):
            res = self._try_pallas("smooth", level, fs, rhos, aux, decomp,
                                   nu=iterations)
            if res is not None:
                return res
            return self._get_compiled(
                "smooth", level, iterations, decomp)(fs, rhos, aux)

    def residual(self, level, fs, rhos, aux, decomp=None):
        """``rho - L(f)`` per unknown (reference relax.py:216-223)."""
        decomp = decomp if decomp is not None else self.decomp
        fs, rhos, aux = self._cast(fs), self._cast(rhos), self._cast(aux)
        with trace_scope("mg_residual"):
            res = self._try_pallas("residual", level, fs, rhos, aux, decomp)
            if res is not None:
                return res
            return self._get_compiled("residual", level, None, decomp)(
                fs, rhos, aux)

    def tau_rhs(self, level, fs, restricted_resid, aux, decomp=None):
        """Coarse-level rho with FAS tau-correction. Takes the Pallas
        stencil tier when the level admits it (the same kernel shape as
        ``residual``; VERDICT r4 #4), else the XLA halo-pad path."""
        decomp = decomp if decomp is not None else self.decomp
        fs = self._cast(fs)
        rr = self._cast(restricted_resid)
        aux = self._cast(aux)
        res = self._try_pallas(
            "tau", level, fs,
            {self.f_to_rho_dict[n]: rr[n] for n in fs}, aux, decomp)
        if res is not None:
            return {self.f_to_rho_dict[n]: res[n] for n in res}
        return self._get_compiled("tau", level, None, decomp)(fs, rr, aux)

    def error_arrays(self, level, fs, rhos, aux, decomp=None):
        """Residual norms as DEVICE scalars — no host sync, so cycle
        drivers can record errors without serializing the device queue
        (they convert once at the end; multigrid/__init__.py)."""
        r = self.residual(level, fs, rhos, aux, decomp)
        return {n: list(_residual_norms(rn)) for n, rn in r.items()}

    def get_error(self, level, fs, rhos, aux, decomp=None):
        """L-infinity and L2 norms of the residual per unknown (reference
        relax.py:242-266)."""
        return {n: [float(a), float(b)] for n, (a, b) in
                self.error_arrays(level, fs, rhos, aux, decomp).items()}

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
