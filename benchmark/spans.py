"""Host spans of the benchmark's own: name, unit, start and end on the
host clock, kept in memory. A span is also a ``TraceAnnotation``, so a
profiled unit carries the same names on the profiler's clock and an idle
gap of the device can be laid against what the host was doing.

In a traced run each span is closed by ``block_until_ready`` on what its
call returned (``Span.close_on``), so it holds its own device work and
not its predecessor's; an untraced run syncs only where the driver says.
"""

import contextlib
import time

PREFIX = "bench:"


class Span:
    def __init__(self, sync):
        self._sync = sync

    def close_on(self, value):
        """Wait for ``value`` in a traced run; return it either way."""
        if self._sync:
            import jax
            jax.block_until_ready(value)
        return value


class Spans:
    def __init__(self, sync):
        self.sync = bool(sync)
        self.rows = []        # (name, unit kind, unit index, t0, t1)
        self.unit = ("setup", -1)

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield Span(self.sync)
        self.rows.append((name, *self.unit, t0, time.perf_counter()))

    def total(self, names, kind, units=None):
        """Seconds spent in spans called ``names`` inside units of
        ``kind`` (all of them, or those whose index is in ``units``)."""
        return sum(t1 - t0 for n, k, i, t0, t1 in self.rows
                   if n in names and k == kind
                   and (units is None or i in units))

    def count(self, names, kind):
        return sum(1 for n, k, *_ in self.rows if n in names and k == kind)
