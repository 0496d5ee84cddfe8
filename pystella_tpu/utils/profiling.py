"""Benchmark/profiling helpers.

The reference has no profiling subsystem; its mechanism is a warmup+average
timing harness used by every test's ``__main__`` benchmark
(/root/reference/test/common.py:41-56) plus per-kernel events. The analogs
here: :func:`timer` (blocks on device completion via
``jax.block_until_ready``), and ``jax.profiler`` for full TPU traces.
"""

from __future__ import annotations

import collections
import time

import jax

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import metrics as _metrics

__all__ = ["timer", "trace", "StepTimer"]


def timer(kernel, ntime=200, nwarmup=2, reps=1, min_over_rounds=None):
    """Average milliseconds per call of ``kernel()`` (a thunk returning jax
    arrays), with warmup; mirrors /root/reference/test/common.py:41-56.

    ``min_over_rounds=R`` (an int > 1) instead runs R such timed rounds
    and returns the MINIMUM of the per-round averages: the noise
    floor, not the scheduler's bad luck."""
    result = None
    for _ in range(nwarmup):
        result = kernel()
    jax.block_until_ready(result)

    rounds = 1 if not min_over_rounds else max(1, int(min_over_rounds))
    best = None
    for _ in range(rounds):
        start = time.perf_counter()
        for _ in range(ntime):
            for _ in range(reps):
                result = kernel()
        jax.block_until_ready(result)
        elapsed = time.perf_counter() - start
        ms = elapsed / ntime / reps * 1000
        best = ms if best is None else min(best, ms)
    return best


class trace:
    """Context manager around ``jax.profiler`` producing a TensorBoard/
    Perfetto trace of everything inside (kernel timelines, HBM traffic,
    ICI collectives) — the TPU upgrade over the reference's per-kernel
    ``pyopencl.Event`` timing (/root/reference/pystella/elementwise.py:
    322-326).

    Usage::

        with ps.trace("/tmp/trace"):
            state = stepper.step(state, t, dt, args)
            jax.block_until_ready(state)
    """

    def __init__(self, logdir, create_perfetto_link=False):
        self.logdir = str(logdir)
        self.create_perfetto_link = create_perfetto_link

    def __enter__(self):
        jax.profiler.start_trace(
            self.logdir, create_perfetto_link=self.create_perfetto_link)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


class StepTimer:
    """Rolling ms/step + steps/s telemetry for driver loops (the
    reference's every-30-seconds console line,
    /root/reference/examples/scalar_preheating.py:272-276, which reports
    the lifetime average; here the rate covers only the last reporting
    window so one-time jit compilation does not skew steady-state
    numbers).

    Call :meth:`tick` once per step; it returns a ``(ms_per_step,
    steps_per_s)`` tuple every ``report_every`` seconds and ``None``
    otherwise.

    The metrics registry's ``step`` :class:`~pystella_tpu.obs.metrics.
    Timer` is the single timing accumulator: every tick's inter-step
    duration is observed there (count, total seconds, per-step EMA), and
    the window report is derived from its deltas rather than kept in
    parallel here. Each report additionally sets the ``ms_per_step`` /
    ``steps_per_s`` gauges (the fleet-aggregatable export) and emits a
    ``kind="step_timer"`` run event.

    Per-step wall times are also retained in :attr:`samples_ms` (a
    bounded deque, newest last) for
    :class:`~pystella_tpu.obs.ledger.PerfLedger` distribution analysis;
    with ``emit_steps=True`` each tick also emits a ``kind="step_time"``
    run event — the ledger's preferred per-step record (``--profile``'d
    example runs enable it; leave it off for
    million-step production runs where one event per step is too chatty).

    :arg report_every: seconds between window reports.
    :arg emit_steps: emit a ``step_time`` event on every tick.
    :arg sample_capacity: per-step samples retained in
        :attr:`samples_ms`.
    """

    def __init__(self, report_every=30.0, emit_steps=False,
                 sample_capacity=4096):
        self.report_every = float(report_every)
        self.emit_steps = bool(emit_steps)
        self.samples_ms = collections.deque(maxlen=int(sample_capacity))
        # the clock starts at the FIRST tick, not at construction, so
        # timing covers steps 2..N and excludes the first step's jit
        # compilation
        self.last_tick = None
        self.last_report = None
        self.steps = 0
        # register the metrics NOW: SPMD hosts construct StepTimer in
        # lockstep but cross report_every at slightly different wall
        # times, and aggregate() requires every host to export the same
        # metric set (values stay NaN until the first report)
        _metrics.gauge("ms_per_step")
        _metrics.gauge("steps_per_s")
        self._timer = _metrics.timer("step")
        self._count_at_report = self._timer.count
        self._total_at_report = self._timer.total_s

    def tick(self):
        self.steps += 1
        now = time.perf_counter()
        if self.last_tick is None:
            self.last_tick = now
            self.last_report = now
            self._count_at_report = self._timer.count
            self._total_at_report = self._timer.total_s
            return None
        elapsed = now - self.last_tick
        self.last_tick = now
        self._timer.observe(elapsed)  # the one accumulator
        self.samples_ms.append(elapsed * 1e3)
        if self.emit_steps:
            _events.emit("step_time", step=self.steps, ms=elapsed * 1e3)
        if now - self.last_report < self.report_every:
            return None
        window_steps = self._timer.count - self._count_at_report
        window_s = self._timer.total_s - self._total_at_report
        self.last_report = now
        self._count_at_report = self._timer.count
        self._total_at_report = self._timer.total_s
        ms = window_s * 1e3 / window_steps
        _metrics.gauge("ms_per_step").set(ms)
        _metrics.gauge("steps_per_s").set(1e3 / ms)
        _events.emit("step_timer", step=self.steps, ms_per_step=ms,
                     steps_per_s=1e3 / ms)
        return ms, 1e3 / ms
