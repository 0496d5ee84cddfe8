"""In-graph numerics health sentinels with asynchronous host polling.

:class:`~pystella_tpu.HealthMonitor`'s original design put a blocking
host sync on the step critical path every N steps: one ``isfinite``
reduction per field, each forced to host before the next step could be
issued. This module is the replacement underneath it — always-on
numerics telemetry with **no forced sync**:

- :class:`Sentinel` computes a compact per-step **health vector**
  (schema v1: per field ``finite`` / ``max_abs`` / ``rms``, plus
  model-level invariant scalars — energy components, Friedmann
  constraint residual) as pure traceable jnp, so it runs *inside* the
  compiled step (``Stepper.step_with_health``,
  ``FusedScalarStepper.multi_step(..., sentinel=...)``) or as one tiny
  fused dispatch right after it (:meth:`SentinelMonitor.observe`). The
  vector is a few dozen bytes; XLA fuses its reductions with the step's
  final writes.
- :class:`SentinelMonitor` is the asynchronous consumer: the driver
  pushes each step's (device-resident) health vector and polls. A poll
  only converts vectors **at least** ``every`` steps behind the newest
  push — values whose computation retired long ago — so the driver loop
  always runs ``>= every`` steps ahead of any device->host transfer and
  the dispatch pipeline never drains. ``flush()`` drains everything
  (end of run, pre-checkpoint).

On a tripped sentinel (non-finite field, magnitude bound, or an
invariant leaving its declared bounds) the monitor emits a ``diverged``
run event carrying the *actual* offending step, hands its ring-buffer
history to the configured :class:`~pystella_tpu.obs.forensics.
ForensicSink` (last-K health vectors, per-field stats history, recent
event-log window, environment fingerprint, last-good-checkpoint
pointer), and raises :class:`SimulationDiverged`.

Host-side cost is accounted in the ``sentinel`` metrics timer; the
ledger reports it as a percentage of step time (``numerics``
section in ``perf_report.json``) and a tier-1 test pins it under 2% of
the smoke payload's step time.
"""

from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _memory
from pystella_tpu.obs import metrics as _metrics
from pystella_tpu.obs.scope import host_span

__all__ = ["HEALTH_SCHEMA_VERSION", "Sentinel", "SentinelMonitor",
           "SimulationDiverged"]

#: health-vector layout version (doc/observability.md "Numerics health")
HEALTH_SCHEMA_VERSION = 1

#: per-field statistics, in slot order
FIELD_STATS = ("finite", "max_abs", "rms")


class SimulationDiverged(RuntimeError):
    """Raised when the numerics health check fails: non-finite values,
    a magnitude bound exceeded, or an invariant outside its declared
    bounds. ``step`` is the step the offending state was produced at
    (not the step the check ran at); ``bad_fields`` names the offending
    fields and/or invariants."""

    def __init__(self, step, bad_fields, problems=None):
        self.step = step
        self.bad_fields = tuple(bad_fields)
        self.problems = tuple(problems or ())
        detail = ("; ".join(self.problems) if self.problems
                  else ", ".join(self.bad_fields))
        super().__init__(
            f"numerics health check failed at step {step}: {detail}")


def _max_abs_and_mean_sq(x):
    """``(max|x|, mean(x^2))`` as ONE variadic reduction — a single
    pass over the array instead of two separate reduce ops (XLA does
    not fuse independent reductions over the same input; measured ~1.5x
    on the CPU backend, and on TPU one pass means the health stats ride
    a single read of the state the step just wrote)."""
    x = jnp.asarray(x)
    # reduce over the ORIGINAL axes — an earlier ravel()-then-reduce
    # formulation forced the SPMD partitioner to all-gather every
    # sharded field before the 1-D reshape (a full per-field lattice
    # transfer per health vector), which the IR-tier lint's collective
    # audit caught the first time it ran; the multi-axis reduce keeps
    # the pass shard-local with one tiny scalar all-reduce at the end
    ax = jnp.abs(x)
    sq = jnp.square(x)
    zero = jnp.zeros((), ax.dtype)
    mx, s = jax.lax.reduce(
        (ax, sq), (zero, zero),
        lambda acc, v: (jnp.maximum(acc[0], v[0]), acc[1] + v[1]),
        tuple(range(ax.ndim)))
    return mx, s / x.size


def _leaf_name(path):
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def named_leaves(state):
    """``{dotted-path-name: leaf}`` for a state pytree (the field-naming
    convention shared with :class:`~pystella_tpu.HealthMonitor`)."""
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    return {_leaf_name(path): leaf for path, leaf in leaves}


class Sentinel:
    """Compact per-step health vector of a state pytree (schema v1).

    :arg fields: iterable of state leaf names (dotted paths, see
        :func:`named_leaves`); stored sorted.
    :arg invariants: optional ``{name: fn}`` of model-level invariant
        scalars — each ``fn(state, aux)`` must be traceable jnp
        returning a scalar (``aux`` is the driver-supplied dict of
        background scalars, e.g. ``{"a": ..., "adot": ...}``; may be
        empty). Typical producers:
        :meth:`pystella_tpu.ScalarSector.energy_means` and
        :meth:`pystella_tpu.Expansion.constraint_residual`.
    :arg dtype: output vector dtype (default float32 — the vector is
        telemetry, not arithmetic).

    Layout: for each field name in sorted order, three slots ``finite``
    (1.0 iff every element is finite), ``max_abs``, ``rms``; then one
    slot per invariant in sorted name order. The finite flag derives
    from the reductions themselves (a single NaN/Inf poisons
    ``max_abs``/``rms``), so each field costs one read fused into two
    reductions — no separate ``isfinite`` pass.
    """

    def __init__(self, fields, invariants=None, dtype=jnp.float32):
        self.fields = tuple(sorted(str(f) for f in fields))
        if not self.fields:
            raise ValueError("Sentinel needs at least one field name")
        self.invariants = dict(sorted((invariants or {}).items()))
        self.dtype = jnp.zeros((), dtype).dtype
        self._jit = None

    @classmethod
    def for_state(cls, state, invariants=None, **kwargs):
        """Build from a concrete state pytree's leaf names."""
        return cls(named_leaves(state), invariants, **kwargs)

    @property
    def size(self):
        return len(FIELD_STATS) * len(self.fields) + len(self.invariants)

    @property
    def slot_names(self):
        """Flat slot names, e.g. ``["dfdt.finite", "dfdt.max_abs",
        "dfdt.rms", "f.finite", ..., "constraint"]``."""
        out = [f"{f}.{s}" for f in self.fields for s in FIELD_STATS]
        return out + list(self.invariants)

    # -- the traceable core -------------------------------------------------

    def compute(self, state, aux=None):
        """The health vector of ``state`` — pure traceable jnp, callable
        inside any jitted step. ``aux`` is forwarded to the invariant
        functions."""
        leaves = named_leaves(state)
        missing = [f for f in self.fields if f not in leaves]
        if missing:
            raise KeyError(f"state has no leaves {missing}; sentinel "
                           f"was built for fields {list(self.fields)}")
        parts = []
        for name in self.fields:
            x = leaves[name]
            max_abs, mean_sq = _max_abs_and_mean_sq(x)
            # the flag derives from the reductions — no extra pass —
            # but each leg covers a specific failure: a NaN element
            # always poisons the SUM as NaN (the max alone is not
            # sufficient — XLA max-reductions may drop NaN per IEEE
            # maxNum, which is exactly how the pre-sentinel smoke
            # payload ran NaN for five rounds unnoticed), and an inf
            # element always poisons the MAX. mean_sq == +inf with a
            # finite max is merely x*x overflowing the field dtype
            # (legitimate large-but-finite data, e.g. f32 beyond
            # ~1.8e19) and must NOT read as divergence — so the sum
            # leg only vetoes on NaN.
            finite = jnp.isfinite(max_abs) & ~jnp.isnan(mean_sq)
            parts += [finite.astype(self.dtype),
                      max_abs.astype(self.dtype),
                      jnp.sqrt(mean_sq).astype(self.dtype)]
        aux = aux or {}
        for name, fn in self.invariants.items():
            parts.append(jnp.asarray(fn(state, aux), self.dtype)
                         .reshape(()))
        return jnp.stack(parts)

    def compute_jit(self, state, aux=None):
        """Jitted :meth:`compute` — one tiny fused dispatch, returning a
        device array (NO host sync)."""
        if self._jit is None:
            self._jit = _memory.instrument_jit(
                self.compute, label="sentinel.health_vector")
        return self._jit(state, aux or {})

    def compute_members(self, states, aux=None):
        """The member-axis generalization of :meth:`compute` for the
        ensemble tier (:mod:`pystella_tpu.ensemble`): ``states`` is a
        batched state pytree whose leaves carry a leading member axis,
        and the result is a ``(members, size)`` health MATRIX — row i
        is exactly the vector :meth:`compute` would produce for member
        i. Pure traceable jnp (a ``vmap`` of the single-run reductions,
        so each member's pass stays shard-local on a member-sharded
        mesh), callable inside any jitted ensemble step. ``aux`` leaves
        must be batched to the member axis too (or the dict empty)."""
        if aux:
            return jax.vmap(self.compute)(states, aux)
        return jax.vmap(lambda st: self.compute(st, {}))(states)

    def decode_members(self, matrix):
        """Host decode of a ``(members, size)`` health matrix — one
        :meth:`decode` dict per row. The single device->host transfer
        for a matured ensemble health check."""
        m = np.asarray(matrix)
        if m.ndim != 2 or m.shape[1] != self.size:
            raise ValueError(
                f"ensemble health matrix has shape {m.shape}; schema "
                f"v{HEALTH_SCHEMA_VERSION} for this sentinel needs "
                f"(members, {self.size})")
        return [self.decode(row) for row in m]

    # -- host-side decode and checks ----------------------------------------

    def decode(self, vector):
        """Device vector (or numpy array) -> ``{"fields": {name:
        {"finite": bool, "max_abs": float, "rms": float}}, "invariants":
        {name: float}}``. This is the one device->host transfer; on a
        matured vector the computation retired long ago, so it does not
        stall the pipeline."""
        v = np.asarray(vector)
        if v.shape != (self.size,):
            raise ValueError(f"health vector has shape {v.shape}; "
                             f"schema v{HEALTH_SCHEMA_VERSION} for this "
                             f"sentinel needs ({self.size},)")
        ns = len(FIELD_STATS)
        fields = {}
        for i, name in enumerate(self.fields):
            fin, mx, rms = (float(v[ns * i + j]) for j in range(ns))
            fields[name] = {"finite": bool(fin == 1.0), "max_abs": mx,
                            "rms": rms}
        base = ns * len(self.fields)
        invariants = {name: float(v[base + i])
                      for i, name in enumerate(self.invariants)}
        return {"fields": fields, "invariants": invariants}

    def problems(self, decoded, max_abs=None, invariant_bounds=None):
        """Health-check a decoded vector: returns ``(bad_names,
        descriptions)`` — non-finite fields, fields over the ``max_abs``
        magnitude bound, and invariants outside their declared
        ``invariant_bounds`` ``{name: (lo, hi)}`` (either bound may be
        ``None``). Empty lists mean healthy."""
        bad, why = [], []
        for name, st in decoded["fields"].items():
            if not st["finite"]:
                bad.append(name)
                why.append(f"{name}: non-finite values "
                           f"(max_abs={st['max_abs']})")
            elif max_abs is not None and st["max_abs"] > max_abs:
                bad.append(name)
                why.append(f"{name}: |max| {st['max_abs']:.6g} exceeds "
                           f"bound {max_abs:.6g}")
        for name, val in decoded["invariants"].items():
            if not np.isfinite(val):
                bad.append(name)
                why.append(f"invariant {name}: non-finite ({val})")
                continue
            lo, hi = (invariant_bounds or {}).get(name, (None, None))
            if (lo is not None and val < lo) or \
                    (hi is not None and val > hi):
                bad.append(name)
                why.append(f"invariant {name}: {val:.6g} outside "
                           f"bounds ({lo}, {hi})")
        return bad, why


class SentinelMonitor:
    """Asynchronous consumer of per-step health vectors.

    The driver calls :meth:`observe` (compute + enqueue, one tiny
    dispatch, no sync) or :meth:`push` (enqueue a vector an in-graph
    step already produced — ``Stepper.step_with_health`` /
    ``multi_step(..., sentinel=...)``) once per step/chunk, then
    :meth:`poll`. A poll converts only vectors at least ``every`` steps
    behind the newest push, so the driver loop always runs ``>= every``
    steps ahead of any host transfer; :meth:`flush` drains everything.

    :arg sentinel: the :class:`Sentinel` that produced the vectors.
    :arg every: minimum step lag before a vector is host-converted.
    :arg history: ring-buffer capacity of decoded vectors (the forensic
        bundle's last-K history).
    :arg max_abs: optional per-field magnitude bound.
    :arg invariant_bounds: optional ``{name: (lo, hi)}`` invariant
        bounds; leaving them triggers the same trip path as a NaN.
    :arg emit_steps: emit one ``health`` run event per checked vector
        (the smoke bench does; leave off for chatty-averse runs —
        drivers can emit coarser ``health`` events themselves).
    :arg forensics: optional
        :class:`~pystella_tpu.obs.forensics.ForensicSink`; on a trip it
        receives the ring-buffer history before
        :class:`SimulationDiverged` is raised.
    :arg metrics_prefix: prefix for this monitor's metric names. The
        defaults — the ``sentinel`` timer and ``health_checks`` counter
        — feed the ledger's ``numerics`` section (sentinel overhead %
        of step time), so an AUXILIARY monitor running beside the main
        one (e.g. the resilience supervisor's) must use its own names
        (``"supervised"`` -> ``supervised_sentinel`` /
        ``supervised_health_checks``) to keep that section honest,
        exactly like the ensemble tier's ``ensemble_sentinel``.
    """

    def __init__(self, sentinel, every=50, history=64, max_abs=None,
                 invariant_bounds=None, emit_steps=False, label="",
                 forensics=None, metrics_prefix=""):
        self.sentinel = sentinel
        self.every = int(every)
        self.max_abs = max_abs
        self.invariant_bounds = dict(invariant_bounds or {})
        self.emit_steps = bool(emit_steps)
        self.label = label
        self.forensics = forensics
        prefix = f"{metrics_prefix}_" if metrics_prefix else ""
        self._timer_name = prefix + "sentinel"
        self._counter_name = prefix + "health_checks"
        self._pending = collections.deque()   # (step, device vector)
        self.history = collections.deque(maxlen=int(history))
        #: newest step pushed (None before the first push)
        self.newest_step = None
        #: highest step actually health-checked (None before the first)
        self.checked_through = None

    @property
    def pending_steps(self):
        """Steps enqueued but not yet host-checked (newest last)."""
        return [s for s, _ in self._pending]

    def observe(self, step, state, aux=None):
        """Compute the health vector of ``state`` (one tiny jitted
        dispatch, NO host sync) and enqueue it for ``step``."""
        with host_span("sentinel_observe"), \
                _metrics.timer(self._timer_name):
            self.push(step, self.sentinel.compute_jit(state, aux))

    def push(self, step, vector):
        """Enqueue a health vector an in-graph step already produced."""
        step = int(step)
        self._pending.append((step, vector))
        self.newest_step = step

    def poll(self):
        """Check every pending vector at least ``every`` steps behind
        the newest push; younger vectors are never touched, so the
        device queue stays ``>= every`` steps ahead of the host.
        Returns the number of vectors checked; raises
        :class:`SimulationDiverged` on the first unhealthy one."""
        n = 0
        while (self._pending and self.newest_step is not None
                and self._pending[0][0] <= self.newest_step
                - self.every):
            self._check_one(*self._pending.popleft())
            n += 1
        return n

    def flush(self):
        """Drain the queue unconditionally (end of run, or immediately
        before trusting the current state — e.g. a checkpoint save).
        Returns the number of vectors checked."""
        n = 0
        while self._pending:
            self._check_one(*self._pending.popleft())
            n += 1
        return n

    def discard(self):
        """Drop every pending (unchecked) vector WITHOUT checking it —
        the recovery path: after a fault rolls the run back, the queue
        describes the corrupted trajectory about to be replayed, and
        checking it would re-trip on history. Returns the number of
        vectors dropped."""
        n = len(self._pending)
        self._pending.clear()
        return n

    def check_sync(self, step, state, aux=None):
        """Synchronous one-off check of ``state`` at ``step`` (the
        legacy :class:`~pystella_tpu.HealthMonitor` contract; does not
        disturb the async queue). Raises on failure, returns the
        decoded vector otherwise."""
        with _metrics.timer(self._timer_name):
            vector = self.sentinel.compute_jit(state, aux)
        return self._check_one(int(step), vector)

    def _check_one(self, step, vector):
        # the "sentinel" timer covers the sentinel machinery (decode —
        # the one host transfer — plus the checks); event-log JSONL
        # writes are I/O of the telemetry sink, not sentinel cost, and
        # stay outside it like every other event emission
        with host_span("sentinel_poll"), \
                _metrics.timer(self._timer_name):
            decoded = self.sentinel.decode(vector)
            bad, why = self.sentinel.problems(
                decoded, max_abs=self.max_abs,
                invariant_bounds=self.invariant_bounds)
        self.checked_through = (step if self.checked_through is None
                                else max(self.checked_through, step))
        _metrics.counter(self._counter_name).inc()
        self.history.append({"step": step, **decoded})
        if self.emit_steps:
            _events.emit("health", step=step, label=self.label, **decoded)
        if bad:
            # the forensic record a checkpointed run resumes from:
            # which fields/invariants went bad, and exactly when —
            # written BEFORE the raise so it survives an unhandled crash
            offending = next((n for n in bad
                              if n in self.sentinel.invariants), None)
            _events.emit("diverged", step=step, fields=bad,
                         max_abs=self.max_abs, problems=why,
                         offending_invariant=offending, label=self.label)
            if self.forensics is not None:
                self.forensics.write(
                    step=step, reason="; ".join(why), bad_fields=bad,
                    offending_invariant=offending,
                    history=list(self.history))
            raise SimulationDiverged(step, bad, why)
        return decoded
