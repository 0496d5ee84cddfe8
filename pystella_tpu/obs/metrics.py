"""Lightweight metrics registry: counters, gauges, timers.

Host-side telemetry for driver loops — cheap enough to update every
step, structured enough to aggregate across a multi-host fleet. Three
metric kinds:

- :class:`Counter` — monotonically-increasing event counts (steps
  taken, halo exchanges, V-cycles, compile events). Hosts sum.
- :class:`Gauge` — last-set values (ms/step, site-updates/s, peak HBM
  bytes) with a per-gauge cross-host reduction (``mean``/``max``/
  ``min``/``sum``).
- :class:`Timer` — duration accumulator with an exponential moving
  average; exports ``<name>.count`` / ``<name>.total_s`` (summed across
  hosts) and ``<name>.ema_ms`` (averaged).

:meth:`MetricsRegistry.aggregate` gathers every host's snapshot through
:func:`pystella_tpu.parallel.multihost.all_gather_hosts` and reduces, so
host 0 can report fleet-wide numbers; on a single-process run (tests,
one chip) it degrades to the local snapshot. Counting caveat: counters
incremented inside jit-traced code count *traces*, not executions —
increment from host-level entry points (``step()``, the cycle driver)
for true counts; traced increments are a static proxy only.

Thread-safety contract (a reader may take
:meth:`MetricsRegistry.snapshot` from another thread while the driver
loop updates): every metric a registry creates shares the registry's
re-entrant lock, each update (``inc``/``set``/``observe``) is one
atomic section under it, and ``snapshot`` holds the same lock across
ALL exports — a reader can never observe a Timer between its ``count``
bump and its ``total_s`` accumulation, or a half-updated EMA. A metric
constructed standalone gets its own lock.
"""

from __future__ import annotations

import threading
import time

import numpy as np

__all__ = ["Counter", "Gauge", "Timer", "MetricsRegistry",
           "counter", "gauge", "timer", "registry"]

_REDUCERS = {"sum": np.sum, "mean": np.mean, "max": np.max, "min": np.min}


class Counter:
    """Monotonic event count; cross-host reduction: sum."""

    def __init__(self, name, _lock=None):
        self.name = name
        self.value = 0
        self._lock = _lock if _lock is not None else threading.RLock()

    def inc(self, n=1):
        with self._lock:
            self.value += n
            return self.value

    def export(self):
        with self._lock:
            return {self.name: (float(self.value), "sum")}


class Gauge:
    """Last-set value; cross-host reduction per ``reduce``."""

    def __init__(self, name, reduce="mean", _lock=None):
        if reduce not in _REDUCERS:
            raise ValueError(f"unknown reduction {reduce!r}; "
                             f"choose from {sorted(_REDUCERS)}")
        self.name = name
        self.reduce = reduce
        self.value = float("nan")
        self._lock = _lock if _lock is not None else threading.RLock()

    def set(self, value):
        with self._lock:
            self.value = float(value)
            return self.value

    def export(self):
        with self._lock:
            return {self.name: (self.value, self.reduce)}


class Timer:
    """Duration accumulator with an EMA of the per-call milliseconds.

    Use as a context manager (``with registry.timer("halo"): ...``) or
    feed observed seconds via :meth:`observe`.
    """

    def __init__(self, name, ema_alpha=0.2, _lock=None):
        self.name = name
        self.ema_alpha = float(ema_alpha)
        self.count = 0
        self.total_s = 0.0
        self.ema_ms = float("nan")
        self._lock = _lock if _lock is not None else threading.RLock()

    def observe(self, seconds):
        with self._lock:
            self.count += 1
            self.total_s += seconds
            ms = seconds * 1e3
            self.ema_ms = (ms if self.count == 1 else
                           self.ema_alpha * ms
                           + (1.0 - self.ema_alpha) * self.ema_ms)
            return self.ema_ms

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.observe(time.perf_counter() - self._t0)

    def export(self):
        with self._lock:
            return {f"{self.name}.count": (float(self.count), "sum"),
                    f"{self.name}.total_s": (self.total_s, "sum"),
                    f"{self.name}.ema_ms": (self.ema_ms, "mean")}


class MetricsRegistry:
    """Name -> metric map with get-or-create accessors and multihost
    aggregation. Metric accessors are idempotent (the same name returns
    the same object), so hot-loop call sites need no setup phase."""

    def __init__(self):
        self._metrics = {}
        # re-entrant: _exports holds it while each metric's export()
        # re-enters; metrics created here share it so an update and a
        # snapshot serialize against each other (module docstring)
        self._lock = threading.RLock()

    def _get(self, name, factory, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = factory()
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {cls.__name__}")
            return m

    def counter(self, name):
        return self._get(name, lambda: Counter(name, _lock=self._lock),
                         Counter)

    def gauge(self, name, reduce="mean"):
        return self._get(name,
                         lambda: Gauge(name, reduce, _lock=self._lock),
                         Gauge)

    def timer(self, name, ema_alpha=0.2):
        return self._get(name,
                         lambda: Timer(name, ema_alpha,
                                       _lock=self._lock),
                         Timer)

    def reset(self):
        with self._lock:
            self._metrics.clear()

    # -- snapshots and aggregation ----------------------------------------

    def _exports(self):
        """Sorted flat exports ``{key: (value, reduce_op)}`` — sorted so
        every host's snapshot vector lines up positionally for the
        cross-host gather (all hosts must register the same metrics,
        which lockstep SPMD drivers do by construction). Held under the
        registry lock end to end, so the whole vector is one consistent
        cut even while another thread updates."""
        with self._lock:
            flat = {}
            for m in self._metrics.values():
                flat.update(m.export())
        return dict(sorted(flat.items()))

    def snapshot(self):
        """Local values as ``{name: float}`` (sorted by name); one
        consistent cut under the registry lock (module docstring)."""
        return {k: v for k, (v, _) in self._exports().items()}

    def reduce_snapshots(self, snapshots):
        """Reduce a sequence of per-host ``{name: value}`` snapshots
        into one fleet-wide dict using each metric's reduction. Exposed
        separately from :meth:`aggregate` so the reduction semantics are
        testable without a multi-host cluster.

        NaN entries are dropped before reducing: gauges are deliberately
        pre-registered at NaN on every host (so the snapshot vectors
        line up) and hosts cross their report cadences at different wall
        times — one not-yet-reported host must not turn the fleet-wide
        mean into NaN. A metric no host has set yet stays NaN."""
        ops = {k: op for k, (_, op) in self._exports().items()}
        out = {}
        for k in ops:
            vals = [s[k] for s in snapshots if k in s]
            finite = [v for v in vals if not np.isnan(v)]
            if finite:
                out[k] = float(_REDUCERS[ops[k]](finite))
            elif vals:
                out[k] = float("nan")
        return out

    def aggregate(self):
        """Fleet-wide reduced values: gathers every host's snapshot via
        :func:`~pystella_tpu.parallel.multihost.all_gather_hosts` and
        applies each metric's reduction; identical to :meth:`snapshot`
        on a single-process run."""
        from pystella_tpu.parallel.multihost import all_gather_hosts
        snap = self.snapshot()
        names = list(snap)
        stacked = all_gather_hosts(np.array([snap[n] for n in names]
                                            or [0.0]))
        if not names:
            return {}
        return self.reduce_snapshots(
            [dict(zip(names, row)) for row in stacked])


#: process-default registry (what the in-tree instrumentation uses)
_default = MetricsRegistry()


def registry():
    """The process-default :class:`MetricsRegistry`."""
    return _default


def counter(name):
    return _default.counter(name)


def gauge(name, reduce="mean"):
    return _default.gauge(name, reduce)


def timer(name, ema_alpha=0.2):
    return _default.timer(name, ema_alpha)
