"""Geometric multigrid solvers on sharded 3-D lattices.

TPU-native counterpart of /root/reference/pystella/multigrid/__init__.py.
Cycles are the same ``(level, iterations)`` walks; the Full Approximation
Scheme and linear multigrid keep the reference's transfer semantics
(restrict unknowns + tau-corrected right-hand side going down,
correction-interpolation going up, multigrid/__init__.py:244-283) but are
*functional*: a cycle maps input arrays to output arrays, and every
per-level operation is a jitted XLA computation.

The walk's layout: a level's unknowns are ONE ``(nf, X, Y, Z)`` array, the
stack the stencil kernels take (``multigrid/relax.py``), in the order of
the solver's ``f_to_rho_dict``; so are its sources, a residual, a
correction and a tau right-hand side. ``__call__`` stacks the caller's
unknowns and sources on entry and unstacks the finest unknowns on return
(three layout copies a cycle, ``mg_cycle.layout_copies``); between them
every program takes stacks and gives stacks, and a transfer, a norm or an
add runs once a level and not once a name. One program donates an operand:
the correction's add writes over the level's unknowns, a stack the walk owns.
Auxiliary arrays stay by name.

Level placement: fine levels run sharded over the device mesh (halo
exchange by ``lax.ppermute`` inside ``shard_map``); once a level's local
block would fall below the stencil/transfer halo, that level and all
coarser ones are computed replicated (every device redundantly owns the
whole coarse grid — cheaper than communicating 8**3 points). This replaces
the reference's per-level ``DomainDecomposition`` rebuild
(multigrid/__init__.py:357-366).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs import metrics as _metrics
from pystella_tpu.obs.scope import host_span, trace_scope
from pystella_tpu.multigrid.relax import (
    LevelSpec, RelaxationBase, JacobiIterator, NewtonIterator, dispatch)
from pystella_tpu.multigrid.transfer import (
    RestrictionBase, FullWeighting, Injection,
    InterpolationBase, LinearInterpolation, CubicInterpolation,
    periodic_pad, _run_local)

__all__ = [
    "mu_cycle", "v_cycle", "w_cycle", "f_cycle",
    "FullApproximationScheme", "MultiGridSolver",
    "RelaxationBase", "JacobiIterator", "NewtonIterator",
    "RestrictionBase", "FullWeighting", "Injection",
    "InterpolationBase", "LinearInterpolation", "CubicInterpolation",
    "LevelSpec", "periodic_pad",
]


#: a level's unknowns plus the interpolated correction, written over the
#: unknowns' stack (the walk's own: it made it or a program returned it),
#: so the sum takes no third lattice-sized buffer at the cycle's fullest
_correct = _obs_memory.instrument_jit(jnp.add, label="mg.correct",
                                      donate_argnums=0)


def mu_cycle(mu, i, nu1, nu2, max_depth):
    """Generic recursive mu-cycle as a list of ``(level, iterations)``
    (reference multigrid/__init__.py:55-80). Level ``i`` has ``2**i`` fewer
    points per axis than the finest grid."""
    if i == max_depth:
        return [(i, nu2)]
    x = mu_cycle(mu, i + 1, nu1, nu2, max_depth)
    return [(i, nu1)] + x + x[1:] * (mu - 1) + [(i, nu2)]


def v_cycle(nu1, nu2, max_depth):
    """V-cycle (reference multigrid/__init__.py:83-105)."""
    return mu_cycle(1, 0, nu1, nu2, max_depth)


def w_cycle(nu1, nu2, max_depth):
    """W-cycle (reference multigrid/__init__.py:108-131)."""
    return mu_cycle(2, 0, nu1, nu2, max_depth)


def _updown(i, j, k, nu1, nu2):
    down = [(a, nu1) for a in range(i, j)]
    up = [(a, nu2) for a in range(j, k - 1, -1)]
    return down + up


def f_cycle(nu1, nu2, max_depth):
    """F-cycle (reference multigrid/__init__.py:140-166)."""
    cycle = _updown(0, max_depth, max_depth - 1, nu1, nu2)
    for top in range(max_depth - 1, 0, -1):
        cycle += _updown(top + 1, max_depth, top - 1, nu1, nu2)
    return cycle


class FullApproximationScheme:
    """Nonlinear multigrid via the Full Approximation Scheme (reference
    multigrid/__init__.py:169-439).

    :arg solver: a :class:`RelaxationBase` subclass instance
        (:class:`JacobiIterator` or :class:`NewtonIterator`).
    :arg halo_shape: stencil/transfer halo width; defaults to the solver's.
    :arg Restrictor: defaults to :class:`FullWeighting`.
    :arg Interpolator: defaults to :class:`LinearInterpolation`.
    :arg defer_errors: error-norm materialization. ``True`` keeps the
        per-smooth residual norms as device scalars until the cycle end
        (one batched fetch — an eager per-smooth ``float()`` is a host
        sync that drains the device queue; what the ~24 syncs of a
        V-cycle cost on the chip is not measured); ``False``
        materializes eagerly. Default ``None`` auto-selects: deferred on
        accelerator backends, eager on CPU (where deferring across a
        3-axis virtual mesh was measured to abort XLA's CPU runtime).

    Unknown keyword arguments raise ``TypeError`` (a misspelled
    ``defer_errors`` silently changing sync behavior is exactly the kind
    of contamination the event log exists to catch).

    Call with the fine decomposition, the fine grid spacing, an optional
    cycle, and all arrays by keyword; returns ``(errors, unknowns)`` where
    ``errors`` is the reference's list of ``(level, {name: [Linf, L2]})``
    entries and ``unknowns`` the updated solution arrays (functional — the
    inputs are not mutated).
    """

    def __init__(self, solver, halo_shape=None, **kwargs):
        self.solver = solver
        self.halo_shape = (int(halo_shape) if halo_shape is not None
                           else solver.halo_shape)
        Restrictor = kwargs.pop("Restrictor", FullWeighting)
        self.restrictor = Restrictor(halo_shape=self.halo_shape)
        Interpolator = kwargs.pop("Interpolator", LinearInterpolation)
        self.interpolator = Interpolator(halo_shape=self.halo_shape)
        #: error-norm materialization (the class docstring): None = auto
        self._defer_errors = kwargs.pop("defer_errors", None)
        if kwargs:
            raise TypeError(
                f"{type(self).__name__}() got unexpected keyword "
                f"argument(s): {', '.join(sorted(kwargs))}")

    # -- level geometry -----------------------------------------------------

    def _make_levels(self, decomp, grid_shape, dx0, depth):
        if np.isscalar(dx0):
            dx0 = (float(dx0),) * 3
        dx0 = tuple(float(d) for d in dx0)
        # minimum local block so every halo pad (Laplacian h, restriction
        # pad, interpolation pad) fits, and restriction's fine block is even
        min_block = max(self.halo_shape, self.restrictor.pad,
                        self.interpolator.pad, 2)
        levels = []
        for i in range(depth + 1):
            shape_i = tuple(n >> i for n in grid_shape)
            if any(n << i != g for n, g in zip(shape_i, grid_shape)):
                raise ValueError(
                    f"grid {grid_shape} not divisible by 2**{i} for "
                    f"multigrid depth {depth}")
            sharded = any(p > 1 for p in decomp.proc_shape) and all(
                n % p == 0 and n // p >= min_block and (n // p) % 2 == 0
                for n, p in zip(shape_i, decomp.proc_shape))
            # once a level is replicated all coarser ones are too
            if levels and not levels[-1].sharded:
                sharded = False
            levels.append(LevelSpec(
                shape_i, tuple(d * 2 ** i for d in dx0), sharded))
        return levels

    # -- transfers ----------------------------------------------------------

    def _replicate(self, decomp, x):
        return jax.device_put(
            x, NamedSharding(decomp.mesh, P(*(None,) * x.ndim)))

    def _restrict(self, decomp, lf, lc, x):
        """Restrict ``x`` (a stack, or an auxiliary array) from (fine)
        level ``lf`` to (coarse) ``lc``: one cached program either way
        (``_run_local``: under ``shard_map`` where the coarse level is
        sharded, else on the whole replicated array)."""
        if lf.sharded and not lc.sharded:
            x = self._replicate(decomp, x)
        return dispatch(_run_local, self.restrictor, x,
                        decomp if lc.sharded else None)

    def _interpolate(self, decomp, lc, lf, x):
        """Interpolate ``x`` from (coarse) level ``lc`` to (fine) ``lf``."""
        out = dispatch(_run_local, self.interpolator, x,
                       decomp if lc.sharded else None)
        if lf.sharded and not lc.sharded:
            out = jax.device_put(out, decomp.sharding(out.ndim - 3))
        return out

    # -- cycle steps (reference transfer_down/transfer_up/smooth) -----------

    def transfer_down(self, decomp, levels, i, unknowns, rhos, aux):
        """Restrict unknowns and build the tau-corrected coarse rho
        (reference multigrid/__init__.py:244-267)."""
        solver = self.solver
        fine, coarse = levels[i - 1], levels[i]
        unknowns[i] = self._restrict(decomp, fine, coarse, unknowns[i - 1])
        r_fine = solver.residual(fine, unknowns[i - 1], rhos[i - 1],
                                 aux[i - 1], decomp)
        rhos[i] = solver.tau_rhs(
            coarse, unknowns[i], self._restrict(decomp, fine, coarse, r_fine),
            aux[i], decomp)

    def transfer_up(self, decomp, levels, i, unknowns, rhos, aux):
        """Correct the finer level ``i`` by the coarse-grid change
        (reference multigrid/__init__.py:269-283): the correction is the
        smoothed coarse solution minus the restricted fine one, and is
        interpolated up and added."""
        corr = dispatch(
            jnp.subtract, unknowns[i + 1],
            self._restrict(decomp, levels[i], levels[i + 1], unknowns[i]))
        unknowns[i] = dispatch(
            _correct, unknowns[i],
            self._interpolate(decomp, levels[i + 1], levels[i], corr))

    def smooth(self, levels, i, nu, unknowns, rhos, aux, decomp=None):
        """Relax level ``i`` for ``nu`` sweeps, recording errors before and
        after (reference multigrid/__init__.py:285-302). On accelerator
        backends the norms stay device arrays until the cycle end
        (``__call__`` materializes them once) — eager per-smooth
        fetches serialize the device queue. On CPU they materialize
        eagerly (deferring across a 3-axis virtual mesh was measured to
        abort XLA's CPU runtime)."""
        solver = self.solver
        defer = (self._defer_errors if self._defer_errors is not None
                 else jax.default_backend() != "cpu")

        def norms():
            errs = solver.error_arrays(levels[i], unknowns[i], rhos[i],
                                       aux[i], decomp)
            return i, errs if defer else jax.device_get(errs)

        before = norms()
        unknowns[i] = solver.smooth(levels[i], unknowns[i], rhos[i],
                                    aux[i], nu, decomp)
        return [before, norms()]

    def _materialize_errors(self, errors):
        """The reference's ``(level, {name: [Linf, L2]})`` record from
        the walk's ``(level, (Linf, L2))`` pairs, deferred device arrays
        among them fetched by ONE batched ``device_get`` of the whole
        record — a fetch an entry would still pay a device round trip
        each, defeating the deferral."""
        return [(i, self.solver.named_errors(norms))
                for i, norms in jax.device_get(errors)]

    # -- entry point --------------------------------------------------------

    def __call__(self, decomp, dx0=None, cycle=None, **kwargs):
        solver = self.solver
        unknowns0 = {n: kwargs.pop(n) for n in solver.f_to_rho_dict}
        rhos0 = {r: kwargs.pop(r)
                 for r in solver.f_to_rho_dict.values()}
        aux0 = kwargs
        grid_shape = tuple(next(iter(unknowns0.values())).shape[-3:])
        if dx0 is None:
            raise ValueError("dx0 is required")

        if cycle is None:
            depth = max(1, int(np.log2(min(grid_shape) / 8)))
            cycle = v_cycle(25, 50, depth)
        depth = max(i for i, _ in cycle)

        levels = self._make_levels(decomp, grid_shape, dx0, depth)

        aux = {0: aux0}
        for i in range(1, depth + 1):
            aux[i] = {k: self._restrict(decomp, levels[i - 1], levels[i], v)
                      for k, v in aux[i - 1].items()}
        dispatched, copies = (_metrics.counter(c) for c in (
            "mg_dispatches", "mg_layout_copies"))
        dispatches0, copies0 = dispatched.value, copies.value
        unknowns = {0: solver.stack(unknowns0)}
        rhos = {0: solver.stack(rhos0, sources=True)}

        # host spans of the walk: each goes round dispatches only (a
        # smooth with its two error norms, a transfer), and the one
        # fetch of the cycle's norms is the one place the host waits
        with _metrics.timer("mg_cycle_s"), trace_scope("mg_cycle"):
            with host_span("mg_smooth"):
                errors = self.smooth(levels, 0, cycle[0][1], unknowns,
                                     rhos, aux, decomp)
            previous = 0
            for i, nu in cycle[1:]:
                if i == previous + 1:
                    with host_span("mg_transfer_down"):
                        self.transfer_down(decomp, levels, i, unknowns,
                                           rhos, aux)
                elif i == previous - 1:
                    with host_span("mg_transfer_up"):
                        self.transfer_up(decomp, levels, i, unknowns,
                                         rhos, aux)
                else:
                    raise ValueError(
                        "consecutive levels must be spaced by one")
                with host_span("mg_smooth"):
                    errors += self.smooth(levels, i, nu, unknowns, rhos,
                                          aux, decomp)
                previous = i
            solution = solver.unstack(unknowns[0])
            with host_span("mg_errors_fetch"):
                materialized = self._materialize_errors(errors)
        _metrics.counter("mg_cycles").inc()
        _metrics.counter("mg_smooths").inc(len(cycle))
        final = materialized[-1][1] if materialized else {}
        _events.emit("mg_cycle", depth=depth, grid_shape=grid_shape,
                     nsmooths=len(cycle), final_errors=final,
                     dispatches=dispatched.value - dispatches0,
                     layout_copies=copies.value - copies0)
        return materialized, solution


class MultiGridSolver(FullApproximationScheme):
    """Linear (correction-scheme) multigrid (reference
    multigrid/__init__.py:442-478). The coarse equation is ``L e = R r``
    with a zero initial guess for the correction ``e`` (the reference omits
    the zeroing — its noted slow convergence, __init__.py:463 — so this
    implementation adds it); going up, the correction is interpolated and
    added to the finer solution."""

    def transfer_down(self, decomp, levels, i, unknowns, rhos, aux):
        r_fine = self.solver.residual(levels[i - 1], unknowns[i - 1],
                                      rhos[i - 1], aux[i - 1], decomp)
        rhos[i] = self._restrict(decomp, levels[i - 1], levels[i], r_fine)
        unknowns[i] = dispatch(jnp.zeros_like, rhos[i])

    def transfer_up(self, decomp, levels, i, unknowns, rhos, aux):
        unknowns[i] = dispatch(
            _correct, unknowns[i],
            self._interpolate(decomp, levels[i + 1], levels[i],
                              unknowns[i + 1]))
