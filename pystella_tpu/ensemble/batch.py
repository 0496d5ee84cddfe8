"""Batched member stepping: vmapped/mapped wrappers over the steppers.

The single-run steppers (:class:`~pystella_tpu.Stepper`,
:class:`~pystella_tpu.FusedScalarStepper`) advance ONE lattice per
call. :class:`EnsembleStepper` turns any of them into a population
engine: a batch of ``size`` members lives as ONE pytree whose leaves
carry a leading member axis, per-member parameters (couplings, dt,
time, IC draws) enter as batched pytree leaves, and the whole batch
advances as one jitted computation — one trace, one compile, no
re-trace per member.

Two batching tiers, chosen by ``via``:

``"vmap"``
    ``jax.vmap`` of the stepper's step body — the XLA tier. The
    partitioner sees the whole batched program, so on an
    ``(ensemble, x, y, z)`` mesh (:func:`~pystella_tpu.ensemble_mesh`)
    the member axis shards over the ensemble devices and each member's
    stencils/reductions stay shard-local. Member results agree with
    sequential single-member runs to a few ulp (vmap changes XLA fusion
    boundaries, not the math).
``"map"``
    ``jax.lax.map`` over the member axis — the fused-Pallas tier. The
    member body is traced ONCE at single-member shapes, so the Mosaic
    kernels run exactly as built (``pallas_call`` needs no batching
    rule) and member results are BIT-EXACT with sequential runs. The
    loop is sequential per device; use it for packed (spatially
    unsharded) members where throughput comes from the kernels, not
    from cross-member parallelism inside one device.

``via="auto"`` picks ``"map"`` for fused steppers (anything carrying a
Pallas chunk body — detected via the ``_multi_step_impl`` marker) and
``"vmap"`` otherwise.

Per-member arguments: ``t`` and ``dt`` may be scalars (shared) or
``(size,)`` arrays; ``rhs_args`` leaves may be scalars or arrays with a
leading ``size`` axis. :meth:`EnsembleStepper.batch_args` normalizes
everything to batched leaves before the dispatch.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from pystella_tpu.obs import memory as _obs_memory
from pystella_tpu.obs.scope import trace_scope

__all__ = ["EnsembleStepper", "repack_members"]


def repack_members(batch, decomp):
    """Re-place a batched ``(members, ...)`` state pytree onto a
    DIFFERENT ensemble decomposition — the member-axis repack of a
    re-mesh (:mod:`pystella_tpu.resilience.remesh`): the member count
    is unchanged, but the ensemble device extent shrank, so ``E``
    members over ``D'`` surviving devices land as ``E / D'`` per mesh
    slice. The new extent must divide the member count
    (``shard_members`` raises otherwise — the planner's member-axis
    shrink rule guarantees it picks such an extent). Checkpointed
    batches take the equivalent zero-copy path through
    ``Checkpointer.restore(mesh=new_decomp)`` instead; this is the
    in-memory repack for a batch that survived in host or device
    buffers."""
    import jax as _jax
    return _jax.tree_util.tree_map(decomp.shard_members, batch)


class EnsembleStepper:
    """Drive ``size`` members of a base stepper as one batched program.

    :arg stepper: any :class:`~pystella_tpu.Stepper` (including the
        fused Pallas steppers).
    :arg size: member count of every batch this wrapper dispatches.
    :arg decomp: optional ensemble-aware
        :class:`~pystella_tpu.DomainDecomposition` (built over an
        :func:`~pystella_tpu.ensemble_mesh`); when given,
        :meth:`stack` places batches with the member axis over the
        ensemble devices.
    :arg via: ``"vmap"`` | ``"map"`` | ``"auto"`` (see module
        docstring).
    :arg donate: donate the input batch buffers to each dispatch
        (the batch is rebound ``batch = step(batch)`` in driver loops;
        off by default because the eviction path re-reads slots).
    """

    def __init__(self, stepper, size, decomp=None, via="auto",
                 donate=False):
        self.stepper = stepper
        self.size = int(size)
        if self.size < 1:
            raise ValueError(f"ensemble size must be >= 1, got {size}")
        self.decomp = decomp
        if via == "auto":
            # fused steppers carry Pallas bodies (their chunked
            # _multi_step_impl); lax.map keeps those single-member
            via = "map" if hasattr(stepper, "_multi_step_impl") \
                else "vmap"
        if via not in ("vmap", "map"):
            raise ValueError(f"unknown batching tier {via!r}")
        self.via = via
        self._donate = bool(donate)
        self._jits = {}        # (kind, nsteps, sentinel-id) -> jitted
        self._write_jit = None

    # -- batch construction -------------------------------------------------

    def batch_args(self, tree):
        """Normalize an argument pytree to batched leaves: leaves whose
        leading axis is already ``size`` pass through, everything else
        is broadcast to a leading member axis. (A per-member SCALAR
        parameter is therefore a ``(size,)`` array, never a bare list.)
        """
        def go(x):
            x = jnp.asarray(x)
            if x.ndim >= 1 and x.shape[0] == self.size:
                return x
            return jnp.broadcast_to(x, (self.size,) + x.shape)
        return jax.tree_util.tree_map(go, tree)

    def stack(self, states):
        """One batched state pytree from ``size`` member states
        (stacked along a new leading axis and, with an ensemble
        ``decomp``, placed member-axis-over-ensemble-devices)."""
        states = list(states)
        if len(states) != self.size:
            raise ValueError(f"need {self.size} member states, "
                             f"got {len(states)}")
        if self.decomp is not None and self.decomp.ensemble_axis is not None:
            # stack on HOST and let shard_members device_put straight
            # to the batched sharding: jnp.stack would commit the whole
            # population to the default device first, which OOMs for
            # exactly the spatially-sharded large-lattice case the
            # ensemble mesh exists for (the sharded batch fits the
            # mesh; one device's copy of all of it does not)
            batched = jax.tree_util.tree_map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *states)
            return self.place(batched)
        batched = jax.tree_util.tree_map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
        return batched

    def place(self, batched):
        """Apply the ensemble mesh placement to an already-batched
        state (no-op without a ``decomp``)."""
        if self.decomp is None or self.decomp.ensemble_axis is None:
            return batched
        return jax.tree_util.tree_map(self.decomp.shard_members, batched)

    def take_member(self, batched, index):
        """Host copy of member ``index``'s state (forces a sync — use
        at retire/checkpoint points, not in the hot loop)."""
        return jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a[index])), batched)

    # -- the batched bodies -------------------------------------------------

    def _member_fn(self, nsteps):
        if nsteps == 1:
            return lambda st, t, dt, ra: self.stepper._step_impl(
                st, t, dt, ra)
        return self.stepper.multi_step_fn(nsteps)

    def _spmd_axis_name(self):
        """The ensemble mesh-axis name for ``jax.vmap``'s
        ``spmd_axis_name``: member bodies containing ``shard_map``s
        (halo-mode stencils) then treat the batched member axis as
        SHARDED over the ensemble devices instead of replicating it —
        without this, vmap-of-shard_map would all-gather every member
        onto every ensemble slice."""
        if (self.decomp is not None
                and self.decomp.ensemble_axis is not None
                and self.decomp.ensemble_devices > 1):
            return self.decomp.ensemble_axis
        return None

    def _batched_impl(self, nsteps):
        """The batched chunk body ``(batch, t_vec, dt_vec, rhs_args) ->
        batch`` under the selected tier."""
        member = self._member_fn(int(nsteps))
        if self.via == "vmap":
            spmd = self._spmd_axis_name()

            def run(batch, t, dt, rhs_args):
                with trace_scope("ensemble_step"):
                    return jax.vmap(member, spmd_axis_name=spmd)(
                        batch, t, dt, rhs_args)
        else:
            def run(batch, t, dt, rhs_args):
                with trace_scope("ensemble_step"):
                    return jax.lax.map(lambda a: member(*a),
                                       (batch, t, dt, rhs_args))
        return run

    def _get_jit(self, nsteps, sentinel=None, aux_arg=False):
        key = (int(nsteps), None if sentinel is None else id(sentinel),
               bool(aux_arg))
        fn = self._jits.get(key)
        if fn is not None:
            return fn
        run = self._batched_impl(nsteps)
        if sentinel is None:
            impl = run
        elif aux_arg:
            def impl(batch, t, dt, rhs_args, aux):
                new = run(batch, t, dt, rhs_args)
                with trace_scope("sentinel"):
                    hm = sentinel.compute_members(new, aux)
                return new, hm
        else:
            def impl(batch, t, dt, rhs_args):
                new = run(batch, t, dt, rhs_args)
                with trace_scope("sentinel"):
                    hm = sentinel.compute_members(new)
                return new, hm
        label = (f"ensemble.step_{self.via}[{self.size}x{int(nsteps)}]"
                 + (".health" if sentinel is not None else ""))
        fn = _obs_memory.instrument_jit(
            impl, label=label,
            donate_argnums=(0,) if self._donate else ())
        self._jits[key] = fn
        return fn

    def health_jit(self, sentinel):
        """The cached jitted step+health executable for ``sentinel`` —
        also the IR-audit entry point (``pystella_tpu.lint`` lowers it
        to prove the member-axis health reductions fuse into the
        batched step module). Signature: ``(batch, t_vec, dt_vec,
        rhs_args, aux) -> (batch, health_matrix)``."""
        return self._get_jit(1, sentinel, aux_arg=True)

    # -- dispatch ------------------------------------------------------------

    def _norm(self, t, dt, rhs_args):
        dt = dt if dt is not None else self.stepper.dt
        if dt is None:
            raise ValueError("no dt: pass dt= or construct the base "
                             "stepper with one")
        return (self.batch_args(t), self.batch_args(dt),
                self.batch_args(rhs_args or {}))

    def step(self, batch, t=0.0, dt=None, rhs_args=None):
        """Advance every member one full RK step; one jitted batched
        dispatch. ``t``/``dt`` scalars or ``(size,)`` arrays;
        ``rhs_args`` leaves scalar or member-batched."""
        t, dt, rhs_args = self._norm(t, dt, rhs_args)
        return self._get_jit(1)(batch, t, dt, rhs_args)

    def multi_step(self, batch, nsteps, t=0.0, dt=None, rhs_args=None,
                   sentinel=None):
        """Advance every member ``nsteps`` steps as one jitted chunk
        (the fused tier pairs stages across step boundaries inside
        each member, exactly as its single-run ``multi_step`` does).
        With ``sentinel`` (a :class:`~pystella_tpu.obs.sentinel.
        Sentinel` built for ONE member's state), additionally returns
        the ``(size, len(vector))`` health MATRIX of the new batch,
        computed inside the same computation — per-member numerics
        observability with no extra dispatch and no host sync."""
        t, dt, rhs_args = self._norm(t, dt, rhs_args)
        return self._get_jit(int(nsteps), sentinel)(batch, t, dt,
                                                    rhs_args)

    def step_with_health(self, batch, sentinel, t=0.0, dt=None,
                         rhs_args=None, aux=None):
        """One step + the member-axis health matrix, in one jitted
        computation (``aux`` leaves scalar or member-batched)."""
        t, dt, rhs_args = self._norm(t, dt, rhs_args)
        aux = self.batch_args(aux or {})
        return self.health_jit(sentinel)(batch, t, dt, rhs_args, aux)

    # -- eviction / slot management -----------------------------------------

    def write_member(self, batch, index, member_state):
        """Overwrite slot ``index`` of the batch with ``member_state``
        (the evict-and-resample write, traced once: the slot index is a
        device scalar, so refilling ANY slot reuses one compiled
        program — no recompile, no shape change, the rest of the batch
        untouched)."""
        if self._write_jit is None:
            def impl(b, idx, m):
                return jax.tree_util.tree_map(
                    lambda ba, ma: jax.lax.dynamic_update_index_in_dim(
                        ba, ma.astype(ba.dtype), idx, 0), b, m)
            self._write_jit = _obs_memory.instrument_jit(
                impl, label="ensemble.write_member")
        member_state = jax.tree_util.tree_map(jnp.asarray, member_state)
        with trace_scope("ensemble_evict"):
            return self._write_jit(batch, jnp.asarray(index, jnp.int32),
                                   member_state)
