"""Upstream's shipped loop (``stage_loop``) with the program's own
host-span recorder left on for the whole run, as under ``--profile`` or
an operator's always-on telemetry. The loop body, the rows it writes and
the check are ``stage_loop.Driver``'s, untouched; what differs is that
the recorder's rows are handed to the harness once a block, so that a
metric file can read a program span by name, and that the program's
annotations carry the harness's prefix, so that ``trace_reduce`` keeps
them and an idle gap is named by the program's span over it."""

import json

from benchmark.drivers import stage_loop
from benchmark.spans import PREFIX


class Driver(stage_loop.Driver):
    def __init__(self, system, traffic, spans):
        super().__init__(system, traffic, spans)
        self.scope = system.ps.obs.scope
        try:
            self._recording = self.scope.recording(annotation_prefix=PREFIX)
        except TypeError:
            raise SystemExit(
                "benchmark: this pystella_tpu's obs.recording takes no "
                "annotation_prefix; nothing run") from None
        #: the recorder's rows; and the window's block rows (parents
        #: re-based) with the blocks they came from, for the table
        self._recorder = self._recording.__enter__()
        self._window, self._blocks = [], 0

    def adopt(self):
        """Drain the recorder into the harness's rows: one tuple per
        program row, named by its path, stamped with the unit in
        progress, in seconds on the harness's clock (``perf_counter``,
        which ``perf_counter_ns`` counts in nanoseconds). Inside the
        timed unit on purpose: part of what leaving the recorder on
        costs."""
        rows = self._recorder.drain()
        kind, index = self.spans.unit
        self.spans.rows.extend(
            (path, kind, index, t0 / 1e9, t1 / 1e9)
            for path, (_, _, t0, t1) in zip(self.scope.span_paths(rows),
                                            rows))
        if kind == "block":
            base = len(self._window)
            self._window.extend(
                [name, parent + base if parent >= 0 else -1, t0, t1]
                for name, parent, t0, t1 in rows)
            self._blocks += 1

    def first_steps(self):
        super().first_steps()
        self.adopt()

    def block(self):
        super().block()
        self.adopt()

    def finish(self):
        try:
            super().finish()
        finally:
            self._recording.__exit__(None, None, None)
        print("program spans: " + json.dumps(self.scope.span_table(
            self._window, steps=self._blocks * self.block_steps)),
            flush=True)
