"""Budget-aware retry/backoff with transient-vs-deterministic triage.

Three rules, as a tested library:

- **deterministic failures must not be retried** — a ``ValueError``, an
  ``INVALID_ARGUMENT`` from the runtime: re-running it burns the budget
  to fail identically;
- **fast failures are deterministic in disguise** — an "attempt" that
  dies in seconds never reached the slow part; a tight crash loop
  (misconfiguration, import error) must trip a consecutive-fast-failure
  limit instead of eating the whole budget;
- **slow transient failures are worth retrying for as long as the
  budget lasts** — a device link that drops and comes back is weather,
  not a bug.

Pieces:

- :func:`classify_exception` — ``"transient"`` or ``"deterministic"``
  for an exception, by type and by the status markers transport errors
  carry (``UNAVAILABLE``, ``DEADLINE_EXCEEDED``, connection resets,
  ...). Unknown errors classify **deterministic**: retrying an
  unrecognized failure mode is how budgets disappear.
- :class:`RetryPolicy` / :class:`Retrier` — jittered exponential
  backoff under attempt/wall budgets, with the fast-failure counter.
  The :class:`Retrier` is outcome-driven (``note_failure`` returns a
  retry/stop decision), so callers that deal in return codes and
  callers that deal in exceptions (the supervisor) share one policy
  engine.
- :func:`retry_call` — the exception-driven wrapper:
  ``retry_call(dial, policy=...)`` retries transients with backoff and
  re-raises deterministics immediately.

This module is **stdlib-only and free of package imports** so a
jax-free supervisor process can load it by file, exactly like
``pystella_tpu/config.py`` and ``obs/events.py``.
Event emission is therefore dependency-injected: pass ``emit=`` (an
``obs.events.emit``-shaped callable) to get ``retry_wait`` /
``retry_stop`` telemetry; the default is silent.
"""

from __future__ import annotations

import dataclasses
import random
import time

__all__ = ["RetryPolicy", "Retrier", "classify_exception", "retry_call",
           "TRANSIENT_MARKERS", "DETERMINISTIC_MARKERS"]


#: substrings (upper-cased comparison) that mark an error message as a
#: transport/availability failure worth retrying. The gRPC/absl status
#: names cover JaxRuntimeError from a dying device link; the rest are
#: socket-level spellings.
TRANSIENT_MARKERS = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "DEADLINE EXCEEDED", "ABORTED",
    "CANCELLED", "CONNECTION RESET", "CONNECTION REFUSED",
    "CONNECTION CLOSED", "SOCKET CLOSED", "BROKEN PIPE",
    "FAILED TO CONNECT", "UNREACHABLE", "TRANSPORT", "PREEMPT",
    "DEVICE OR RESOURCE BUSY", "TEMPORARILY", "TIMED OUT", "TIMEOUT",
    "HEARTBEAT", "DATA_LOSS", "DATA LOSS",
)

#: markers that force the deterministic verdict even when a transient
#: marker also matches (e.g. "timeout" appearing inside an argument
#: dump of an INVALID_ARGUMENT error)
DETERMINISTIC_MARKERS = (
    "INVALID_ARGUMENT", "INVALID ARGUMENT", "NOT_FOUND", "NOT FOUND",
    "UNIMPLEMENTED", "FAILED_PRECONDITION", "FAILED PRECONDITION",
    "PERMISSION_DENIED", "OUT_OF_RANGE", "ALREADY_EXISTS",
)

#: exception type names that are transient by construction (name-based
#: so jax/grpc need not be importable here)
_TRANSIENT_TYPE_NAMES = frozenset({
    "TimeoutError", "ConnectionError", "ConnectionResetError",
    "ConnectionRefusedError", "ConnectionAbortedError", "BrokenPipeError",
    "InterruptedError", "RpcError", "AioRpcError",
})

#: exception type names whose MESSAGE decides (runtime errors carry the
#: status string; a bare RuntimeError with no marker is deterministic)
_MESSAGE_TYPE_NAMES = frozenset({
    "XlaRuntimeError", "JaxRuntimeError", "RuntimeError", "OSError",
    "IOError", "InternalError", "FatalError", "DeviceLossError",
})

#: exception types that are always deterministic: program bugs, not
#: environment weather
_DETERMINISTIC_TYPES = (ValueError, TypeError, KeyError, IndexError,
                        AttributeError, AssertionError, NotImplementedError,
                        ArithmeticError, ImportError, SyntaxError)


def classify_exception(exc):
    """``"transient"`` (worth retrying) or ``"deterministic"`` (must not
    be retried) for an exception instance.

    Classification order: hard-deterministic python types first (a
    ``ValueError`` stays deterministic whatever its message), then
    deterministic status markers (``INVALID_ARGUMENT`` beats an
    incidental ``timeout`` in the same message), then transient types
    (``TimeoutError``, connection errors), then transient markers in
    the message of runtime/OS error types. Anything unrecognized is
    **deterministic** — optimistic retries of unknown failures eat
    whole budgets.
    """
    if isinstance(exc, _DETERMINISTIC_TYPES):
        return "deterministic"
    names = {t.__name__ for t in type(exc).__mro__}
    msg = str(exc).upper()
    if any(m in msg for m in DETERMINISTIC_MARKERS):
        return "deterministic"
    if names & _TRANSIENT_TYPE_NAMES:
        return "transient"
    if names & _MESSAGE_TYPE_NAMES or isinstance(exc, Exception):
        if any(m in msg for m in TRANSIENT_MARKERS):
            return "transient"
    return "deterministic"


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Backoff/budget parameters for a :class:`Retrier`.

    :arg base_s: first backoff in seconds.
    :arg factor: exponential growth per failure (1.0 = constant).
    :arg max_s: backoff ceiling.
    :arg jitter: symmetric jitter as a fraction of the computed backoff
        (0.1 -> +-10%); decorrelates a fleet of retriers hammering one
        coordinator.
    :arg max_attempts: attempt ceiling (``None`` = unbounded; the wall
        budget still applies).
    :arg budget_s: total wall budget across attempts and backoffs
        (``None`` = unbounded). The retrier stops when the NEXT backoff
        would land beyond it — it never sleeps into a dead budget.
    :arg fast_failure_s: attempts failing faster than this count as
        *fast* (they never reached the slow transport).
    :arg max_fast_failures: consecutive fast failures allowed before
        the retrier stops (a tight crash loop is deterministic in
        disguise); a slow failure resets the streak.
    """

    base_s: float = 1.0
    factor: float = 2.0
    max_s: float = 60.0
    jitter: float = 0.1
    max_attempts: int | None = None
    budget_s: float | None = None
    fast_failure_s: float | None = None
    max_fast_failures: int | None = 3


class Retrier:
    """Outcome-driven retry engine: callers report each failure with
    :meth:`note_failure` and get a ``("retry" | "stop", reason)``
    decision back; :meth:`wait` sleeps the jittered backoff.

    :arg policy: a :class:`RetryPolicy`.
    :arg clock: monotonic-seconds callable (injectable for tests).
    :arg sleep: sleep callable (injectable for tests).
    :arg rng: ``random.Random`` for jitter (seedable for tests).
    :arg emit: optional ``obs.events.emit``-shaped callable receiving
        ``retry_wait`` / ``retry_stop`` events.
    :arg label: caller tag carried on emitted events.
    """

    def __init__(self, policy=None, clock=time.monotonic,
                 sleep=time.sleep, rng=None, emit=None, label=""):
        self.policy = policy or RetryPolicy()
        self._clock = clock
        self._sleep = sleep
        self._rng = rng or random.Random()
        self._emit = emit
        self.label = label
        self.failures = 0
        self.consecutive_fast = 0
        self.started = clock()
        #: reason the retrier stopped ("" while it is still willing)
        self.stop_reason = ""

    # -- derived state -----------------------------------------------------

    def elapsed_s(self):
        return self._clock() - self.started

    def backoff_s(self):
        """The next backoff (jittered, clipped): grows from ``base_s``
        by ``factor`` per recorded failure."""
        p = self.policy
        raw = p.base_s * (p.factor ** max(0, self.failures - 1))
        raw = min(raw, p.max_s)
        if p.jitter:
            raw *= 1.0 + p.jitter * (2.0 * self._rng.random() - 1.0)
        return max(0.0, raw)

    # -- the decision ------------------------------------------------------

    def note_failure(self, kind="transient", duration_s=None, error=None):
        """Record one failed attempt; returns ``(decision, reason)``
        where ``decision`` is ``"retry"`` or ``"stop"``.

        :arg kind: ``"transient"`` or ``"deterministic"`` (use
            :func:`classify_exception`, an rc mapping, ...).
        :arg duration_s: how long the attempt ran (feeds the
            fast-failure streak).
        :arg error: the failure itself, for telemetry only.
        """
        p = self.policy
        self.failures += 1
        if str(kind) != "transient":
            return self._stop(f"{kind} failure: not retryable "
                              f"({_err_str(error)})")
        if duration_s is not None and p.fast_failure_s is not None:
            if duration_s < p.fast_failure_s:
                self.consecutive_fast += 1
                if (p.max_fast_failures is not None
                        and self.consecutive_fast >= p.max_fast_failures):
                    return self._stop(
                        f"{self.consecutive_fast} consecutive fast "
                        f"failures (< {p.fast_failure_s:.0f}s each) — "
                        "deterministic in disguise")
            else:
                self.consecutive_fast = 0
        if p.max_attempts is not None and self.failures >= p.max_attempts:
            return self._stop(f"attempt budget exhausted "
                              f"({self.failures}/{p.max_attempts})")
        if p.budget_s is not None \
                and self.elapsed_s() + self.backoff_s() > p.budget_s:
            return self._stop(
                f"wall budget exhausted ({self.elapsed_s():.1f}s of "
                f"{p.budget_s:.1f}s spent after {self.failures} "
                "failure(s))")
        return "retry", ""

    def _stop(self, reason):
        self.stop_reason = reason
        if self._emit is not None:
            try:
                self._emit("retry_stop", label=self.label, reason=reason,
                           failures=self.failures)
            except Exception:
                pass
        return "stop", reason

    def wait(self):
        """Sleep the current jittered backoff; returns the seconds
        slept. Emits a ``retry_wait`` event when wired."""
        delay = self.backoff_s()
        if self._emit is not None:
            try:
                self._emit("retry_wait", label=self.label,
                           backoff_s=round(delay, 3),
                           failures=self.failures)
            except Exception:
                pass
        if delay > 0:
            self._sleep(delay)
        return delay


def retry_call(fn, args=(), kwargs=None, policy=None,
               classify=classify_exception, clock=time.monotonic,
               sleep=time.sleep, rng=None, emit=None, label="",
               on_failure=None):
    """Call ``fn(*args, **kwargs)``, retrying transient failures with
    backoff under the policy's budgets.

    Deterministic failures (per ``classify``) re-raise immediately —
    "deterministic failure => no retry" is the whole point. When the
    budget runs out the LAST exception re-raises unchanged, so callers
    see the real failure, not a wrapper. ``on_failure(exc, retrier)``
    (optional) observes each failed attempt before the decision.
    """
    r = Retrier(policy, clock=clock, sleep=sleep, rng=rng, emit=emit,
                label=label)
    while True:
        t0 = clock()
        try:
            return fn(*args, **(kwargs or {}))
        except BaseException as e:  # noqa: B036 — re-raised below
            if on_failure is not None:
                try:
                    on_failure(e, r)
                except Exception:
                    pass
            decision, _ = r.note_failure(kind=classify(e),
                                         duration_s=clock() - t0, error=e)
            if decision == "stop":
                raise
            r.wait()


def _err_str(error):
    if error is None:
        return "no detail"
    if isinstance(error, BaseException):
        return f"{type(error).__name__}: {error}"
    return str(error)
