"""Structured JSONL run-event log.

Every event is one JSON object per line, appended and flushed
immediately so a killed run keeps everything emitted before the kill.
Schema (version 2):

===========  ======================================================
key          meaning
===========  ======================================================
``v``        schema version (``2``)
``ts``       wall-clock POSIX seconds (cross-host correlation)
``mono``     ``time.monotonic()`` seconds (robust to clock steps;
             durations within one process difference correctly)
``host``     jax process index (``0`` outside a jax process)
``kind``     event kind, a short snake_case string (``"compile"``,
             ``"diverged"``, ``"checkpoint_save"``, ``"mg_cycle"``,
             ``"bench_metric"``, ``"fault_detected"``, ...). Payload
             keys must not shadow this schema's own field names —
             e.g. the resilience events carry ``fault_kind``, not
             ``kind``. Every kind the package emits is registered in
             :func:`registered_event_kinds` (the source lint's
             ``event-registry`` check enforces it, the way the scope
             registry gates trace-scope literals)
``step``     simulation step number, or ``null``
``data``     kind-specific payload (flat, JSON-safe)
===========  ======================================================

Readers take a record by these keys and ignore any other (a version-1
log, or an older version-2 log with ``trace``/``span``/``parent``
keys, still ingests).

This module is importable without jax (a supervisor that must stay
off the chip can log through it); the host id is resolved lazily from
an already-imported jax only.

Usage::

    from pystella_tpu import obs
    obs.configure("run_events.jsonl")       # or env PYSTELLA_EVENT_LOG
    obs.emit("checkpoint_save", step=1200, path="ckpts/1200")
    ...
    for ev in obs.read_events("run_events.jsonl"):
        ...

With no configured path (and no ``PYSTELLA_EVENT_LOG``) the default log
is a disabled sink and :func:`emit` costs one attribute check.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

__all__ = ["EventLog", "configure", "emit", "get_log", "read_events",
           "register_event_kind", "registered_event_kinds",
           "rotated_family", "SCHEMA_VERSION"]

SCHEMA_VERSION = 2


# ---------------------------------------------------------------------------
# event-kind registry: the emit vocabulary, centrally declared
# ---------------------------------------------------------------------------

#: kind -> one-line description; seeded below with the in-tree
#: vocabulary. The source lint's ``event-registry`` check audits every
#: ``emit("<literal>", ...)`` in the package against this registry
#: (same pattern as ``obs.scope.register_scope``), so the ledger's
#: kind vocabulary cannot silently drift from emit sites.
_KIND_REGISTRY = {}


def register_event_kind(name, help=""):
    """Register an event kind (idempotent; returns ``name``). Call this
    for any new ``emit("<kind>", ...)`` literal — the tier-1 lint
    (``event-registry``) fails on unregistered kinds, exactly as the
    scope registry gates trace-scope literals."""
    _KIND_REGISTRY.setdefault(str(name), str(help))
    return name


def registered_event_kinds():
    """The registered kind vocabulary as a ``{name: description}``
    dict (copy)."""
    return dict(_KIND_REGISTRY)


for _name, _help in (
    # -- core telemetry (obs) -----------------------------------------------
    ("step_time", "one step's wall time in ms (StepTimer emit_steps)"),
    ("step_timer", "StepTimer window report (ms_per_step, steps_per_s)"),
    ("compile", "one observed program compile (trace/compile split, "
                "fingerprint, cache and memory_analysis counters)"),
    ("compile_cache", "persistent XLA compilation cache wired"),
    ("device_memory", "live allocator stats (TPU backends)"),
    ("cold_start", "driver time-to-first-step phase breakdown"),
    ("warmstart_export", "AOT artifact serialized to the store"),
    ("warmstart_load", "AOT artifact loaded (fingerprint matched)"),
    ("warmstart_mismatch", "AOT artifact refused (stale fingerprint)"),
    ("warmstart_gc", "stale AOT artifacts collected"),
    ("trace_summary", "per-scope duration table from a Perfetto capture"),
    ("trace_missing", "a profiler capture produced no trace file"),
    ("health", "one decoded sentinel health vector"),
    ("diverged", "sentinel trip (non-finite fields / bound violation)"),
    ("forensic_bundle", "a sentinel trip wrote a forensic bundle"),
    ("forensic_failed", "a forensic bundle failed to write"),
    ("perf_report", "a PerfLedger wrote perf_report.json"),
    ("gate_verdict", "the perf gate ran (ok, exit_code, reasons)"),
    # -- numerics / solver hot paths ----------------------------------------
    ("mg_cycle", "one multigrid cycle (depth, smooths, errors, "
                 "dispatches: the programs the walk dispatched, "
                 "layout_copies: the stack and unstack programs among "
                 "them, 3 a cycle: unknowns in, sources in, unknowns "
                 "out)"),
    ("mg_level_plan", "a multigrid level's kernels were built: which "
                      "tier serves it ('streaming' with bx/by/grid, "
                      "'resident', or 'xla' with the reason), how many "
                      "sweeps a kernel pass of its smooth takes "
                      "(sweeps_per_pass 2 with pair_bx/pair_by, the "
                      "two-sweep kernel's blocking; 1 with, on a "
                      "streaming level, the pair_reason) and the "
                      "layout its programs take and give ('stacked': "
                      "one (nf, X, Y, Z) array)"),
    ("mg_transfer_plan", "a multigrid restriction program was traced: "
                         "the operator, the fine grid_shape, the form "
                         "each axis took ('split', 'contract' or, "
                         "where the mesh shards it, 'contract_halo'), "
                         "the contractions' precision and flop count"),
    ("spectral_plan", "a SpectralCollocator was built: the transform's "
                      "scheme, how a real field comes back ('xla' or "
                      "'matmul'), grid, dtype, how many fields "
                      "of a call go through one transform ('all'), "
                      "and what the mesh costs it: proc_shape, the "
                      "transposes between chips a forward and an "
                      "inverse transform make, and the bytes of one "
                      "field's k-space block a chip, which each "
                      "rearranges (0 on one device)"),
    ("spectra_plan", "a PowerSpectra or Projector (consumer) was built "
                     "on a mesh: the transform its outputs take (tier: "
                     "the class, scheme, real_inverse), grid, dtype, "
                     "proc_shape, and the transposes between chips a "
                     "forward and an inverse transform make with the "
                     "bytes of one component's k-space block a chip, "
                     "as spectral_plan counts them; none on one device"),
    ("laplacian_handed_in", "a generic stepper's per-stage dispatch "
                            "first passed its stage program a Laplacian "
                            "the right-hand side's collocator had just "
                            "returned for a leaf of the carry and nobody "
                            "held any more, in place of the transform "
                            "pair inside; the program consumes it "
                            "(stepper, stage, producer, leaf: its key "
                            "path, shape, dtype); one a stepper, the "
                            "counters stage_laplacians_handed_in of "
                            "stage_dispatches say how often"),
    ("overlap_plan", "a sharded stencil kernel was built: which launch "
                     "it takes on the mesh, path 'split' (the "
                     "interior/shell halo-overlap split: the two "
                     "kernels' lattice, bx/by/grid, halo (the "
                     "interior's x edges: 'inset', the ring over the "
                     "raw shard; the shells': 'padded'), reread, "
                     "stitch ('in_place': the shells' rows go into "
                     "the interior's outputs) and the stitch_bytes "
                     "of the copies still round them) or "
                     "'single' with the reason ('off', 'sums', "
                     "'y_sharded', 'thin', 'blocking')"),
    # -- fused kernel tiers --------------------------------------------------
    ("block_choice", "a fused kernel build chose its blocking "
                     "(bx/by/grid/win_halo, h: the stencil radius, "
                     "taps: shifted values a site and component's "
                     "derivatives take, 6h+1 a fused stage, "
                     "halo: each of (x, y) "
                     "'wrap', on a sharded axis 'slab' (then also "
                     "slab_bytes: what the ppermutes of its window "
                     "components' faces move a call and chip), or in the "
                     "overlap split's kernels 'inset' (x, the "
                     "interior) and 'padded' (the shells), in_place: "
                     "the extras it writes over, reread: modelled "
                     "bytes moved over ideal bytes at that by + source: "
                     "'explicit' "
                     "constructor pins, the choose_blocks "
                     "'heuristic', or 'split': a <kind>_interior / "
                     "<kind>_shell kernel of the overlap split)"),
    ("bincount_plan", "a binning program was built: what the one-hot "
                      "contraction took from the shapes (hi x lo "
                      "factorisation, tile, steps and partials, MXU "
                      "passes, weights' dtype)"),
    ("kernel_fallback", "a fused kernel tier degraded down the ladder "
                        "(chunk -> pair -> single), with the reason"),
    ("kernel_tier", "the kernel tier a fused stepper actually "
                    "dispatched (resident-chunk/streaming-chunk/pair/"
                    "single/xla) + modeled HBM bytes per step"),
    # -- checkpoints (utils.checkpoint) -------------------------------------
    ("checkpoint_save", "async checkpoint write SCHEDULED (not durable)"),
    ("checkpoint_durable", "durability barrier passed; last_good advanced"),
    ("checkpoint_restore", "a checkpoint was restored"),
    ("checkpoint_fallback", "restore walked back past a torn checkpoint"),
    # -- elastic runtime (resilience) ---------------------------------------
    ("fault_injected", "the fault harness fired a scripted fault"),
    ("fault_detected", "the supervisor detected a fault (triage result)"),
    ("recovery_attempt", "one recovery attempt (re-dial + restore)"),
    ("recovery_failed", "recovery gave up (budget / recurrence)"),
    ("run_resumed", "the run resumed (recovery MTTR or restart)"),
    ("run_degraded", "the run re-meshed to surviving devices"),
    ("run_preempted", "SIGTERM/preemption drain to a durable checkpoint"),
    ("supervisor_start", "a supervised run began"),
    ("supervisor_done", "supervised-run lifecycle totals"),
    ("remesh_plan", "one re-mesh decision record (RemeshPlanner)"),
    ("retry_wait", "one jittered backoff sleep (Retrier)"),
    ("retry_stop", "the retrier stopped (reason)"),
    # -- ensemble tier ------------------------------------------------------
    ("ensemble_run", "ensemble-driver queue grouping"),
    ("ensemble_chunk", "one batched dispatch window"),
    ("ensemble_done", "ensemble batch totals (member-steps/s, occupancy)"),
    ("ensemble_health", "per-chunk health-matrix summary"),
    ("member_started", "a batch slot was armed with a scenario job"),
    ("member_finished", "a member retired at its step budget"),
    ("member_evicted", "a member was evicted by the per-member sentinel"),
    ("member_preempted", "a driver drain captured a member as a requeue "
                         "record"),
    # -- driver-side kinds (examples, and what a driver may hand the
    # -- ledger; outside the package, so not lint-audited, but
    # -- registered so the vocabulary is one list)
    ("run_start", "example-driver run began"),
    ("run_complete", "example-driver run completed"),
    ("run_aborted", "example-driver run died (forensic tail)"),
    ("halo_traffic", "per-device ICI bytes per overlapped halo update"),
    ("spectra_time", "one spectra output's wall time"),
    ("fft_spectra", "a driver's sharded-spectra leg totals"),
    ("lint", "the static-analysis verdict of the run"),
):
    register_event_kind(_name, _help)
del _name, _help


def _rotated_name(path, index):
    """``run_events.jsonl`` -> ``run_events.<index>.jsonl``."""
    root, ext = os.path.splitext(path)
    return f"{root}.{index}{ext or '.jsonl'}"


def rotated_family(path):
    """Every file of a rotated event log, OLDEST FIRST and the live
    file last: ``[<stem>.0.jsonl, <stem>.1.jsonl, ..., <path>]``
    (missing members are skipped; an un-rotated log is just
    ``[path]``). This is the read-side contract of ``rotate_bytes=``:
    a consumer that wants the whole record reads the family in this
    order and sees one continuous stream."""
    family = []
    index = 0
    while True:
        rotated = _rotated_name(path, index)
        if not os.path.exists(rotated):
            break
        family.append(rotated)
        index += 1
    family.append(path)
    return family


def _host_id():
    """This process's index in the multi-controller cluster. Resolved
    from jax only when jax is already imported — a jax-free supervisor
    must be able to emit events without starting a backend."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    try:
        return int(jax.process_index())
    except Exception:
        return 0


def _jsonify(obj):
    """Best-effort JSON coercion for payload values (numpy/jax scalars,
    tuples, paths); unknown types fall back to ``str``."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): _jsonify(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [_jsonify(v) for v in obj]
    if hasattr(obj, "item") and getattr(obj, "ndim", None) in (None, 0):
        try:
            return _jsonify(obj.item())
        except Exception:
            pass
    if hasattr(obj, "tolist"):
        try:
            return _jsonify(obj.tolist())
        except Exception:
            pass
    return str(obj)


class EventLog:
    """Append-only JSONL event sink.

    :arg path: output file (parent directories are created), or ``None``
        for a disabled sink whose :meth:`emit` is a cheap no-op.
    :arg host: override the host id (default: lazy jax process index).
    :arg rotate_bytes: size-triggered rollover for long-lived processes
        (one unbounded JSONL is an operational hazard): when the live
        file reaches this size after a write, it is renamed to the next
        ``<stem>.<n>.jsonl`` member of the rotated family
        (:func:`rotated_family`) and a fresh file is opened at ``path``.
        Default: the registered ``PYSTELLA_EVENT_ROTATE_MB`` (unset
        disables). Rotation never splits a line — whole events only.

    Thread-safe; every line is flushed on write so concurrently-appending
    processes (a supervisor and its workers) interleave whole lines.
    """

    def __init__(self, path=None, host=None, rotate_bytes=None):
        self.path = None if path is None else os.path.abspath(str(path))
        self._host = host
        self._lock = threading.Lock()
        self._file = None
        self._warned = False
        self._subscribers = []
        self._subscriber_errored = False
        self._notify_tls = threading.local()
        if rotate_bytes is None:
            # direct read (not config.getenv): this module must stay
            # loadable BY FILE in a jax-free supervisor, where the
            # package import is unavailable
            mb = os.environ.get(
                "PYSTELLA_EVENT_ROTATE_MB")  # env-registry: PYSTELLA_EVENT_ROTATE_MB
            if mb:
                try:
                    rotate_bytes = float(mb) * 2**20
                except ValueError:
                    rotate_bytes = None
        self.rotate_bytes = (int(rotate_bytes)
                             if rotate_bytes else None)
        if self.path is not None:
            parent = os.path.dirname(self.path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._file = open(self.path, "a")

    def _maybe_rotate(self):
        """Roll the live file over once it reached ``rotate_bytes``
        (caller holds the lock; the just-written line stays whole in
        the rotated member). Rotation failures degrade to
        keep-appending — telemetry must never kill the run.

        Concurrent appenders (a supervisor and its workers) are
        tolerated via an inode check: when ANOTHER process already
        rotated the live file out from under this one, this writer
        re-points at the fresh live file instead of renaming it away —
        otherwise two writers would leapfrog-rotate each other's fresh
        files. Lines the laggard wrote into the rotated member before
        noticing remain there (whole, just earlier in the family), so
        the family read stays lossless; single-writer logs rotate
        exactly at the threshold."""
        try:
            st_fd = os.fstat(self._file.fileno())
            try:
                st_path = os.stat(self.path)
            except FileNotFoundError:
                st_path = None
            if st_path is None or (st_path.st_ino, st_path.st_dev) \
                    != (st_fd.st_ino, st_fd.st_dev):
                # someone else rotated (or removed) the live file:
                # follow them instead of rotating their fresh file
                self._file.close()
                self._file = open(self.path, "a")
                return
            if st_fd.st_size < self.rotate_bytes:
                return
            index = 0
            while os.path.exists(_rotated_name(self.path, index)):
                index += 1
            self._file.close()
            os.replace(self.path, _rotated_name(self.path, index))
            self._file = open(self.path, "a")
        except OSError as e:
            if not self._warned:
                self._warned = True
                print(f"pystella_tpu.obs: event log rotation failed "
                      f"({e}); continuing on the live file",
                      file=sys.stderr)
            if self._file is None or self._file.closed:
                try:
                    self._file = open(self.path, "a")
                except OSError:
                    self._file = None

    @property
    def enabled(self):
        return self._file is not None

    # -- subscribers: the in-process push channel ---------------------------

    def subscribe(self, fn):
        """Register ``fn(record)`` to receive every emitted record
        in-process, immediately after the write: how a harness reads a
        run's plan events (``block_choice``, ``kernel_tier``, ...)
        without tailing the log file. Subscribers survive
        size-triggered rotation (they hang off the log object, not the
        file handle) but NOT :func:`configure` (which builds a fresh
        log). A subscriber that raises never breaks the emit path: the
        failure is reported once on stderr and the subscriber stays
        registered; one that itself emits has that record written but
        not pushed again. Returns ``fn`` so a lambda can be kept for
        :meth:`unsubscribe`.
        """
        if fn not in self._subscribers:
            self._subscribers.append(fn)
        return fn

    def unsubscribe(self, fn):
        """Remove a subscriber (idempotent)."""
        try:
            self._subscribers.remove(fn)
        except ValueError:
            pass

    def _notify(self, rec):
        """Push ``rec`` to subscribers, outside the write lock and
        re-entrancy-guarded per thread: an emit made FROM a subscriber
        is written normally but not pushed again, so a subscriber that
        emits cannot recurse through its own hook."""
        if getattr(self._notify_tls, "active", False):
            return
        self._notify_tls.active = True
        try:
            for fn in list(self._subscribers):
                try:
                    fn(rec)
                except Exception as e:  # noqa: BLE001 — never break emit
                    if not self._subscriber_errored:
                        self._subscriber_errored = True
                        print("pystella_tpu.obs: event subscriber "
                              f"{fn!r} raised ({type(e).__name__}: {e});"
                              " telemetry continues without it",
                              file=sys.stderr)
        finally:
            self._notify_tls.active = False

    def emit(self, kind, step=None, **data):
        """Append one event; returns the record dict (``None`` when
        nothing consumed it: a disabled, subscriber-less sink, or a
        failed write — telemetry is best-effort by design and must
        never kill the instrumented run). Registered subscribers
        (:meth:`subscribe`) receive the record after the write — also
        on a file-less sink, so a tap works without a log."""
        if self._file is None and not self._subscribers:
            # cheap pre-check; file re-read under the lock
            return None
        rec = {"v": SCHEMA_VERSION, "ts": time.time(),
               "mono": time.monotonic(),
               "host": self._host if self._host is not None else _host_id(),
               "kind": str(kind),
               "step": None if step is None else int(step),
               "data": _jsonify(data)}
        written = False
        if self._file is not None:
            line = json.dumps(rec)
            with self._lock:
                f = self._file  # may have been closed/reconfigured since
                if f is not None:
                    try:
                        f.write(line + "\n")
                        f.flush()
                        written = True
                    except (OSError, ValueError) as e:  # ENOSPC, ...
                        if not self._warned:
                            self._warned = True
                            print("pystella_tpu.obs: event log write "
                                  f"failed ({e}); further events may "
                                  "be lost", file=sys.stderr)
                    if written and self.rotate_bytes:
                        self._maybe_rotate()
        self._notify(rec)
        return rec if (written or self._subscribers) else None

    def close(self):
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


#: module default: lazily built from ``PYSTELLA_EVENT_LOG`` on first use
_default = None


def get_log():
    """The process-default :class:`EventLog` (disabled sink unless
    :func:`configure` was called or ``PYSTELLA_EVENT_LOG`` is set). An
    unopenable ``PYSTELLA_EVENT_LOG`` path degrades to the disabled sink
    with a stderr warning — implicit env-driven telemetry must never
    kill the instrumented run (an explicit :func:`configure` call still
    raises, so startup misconfiguration surfaces)."""
    global _default
    if _default is None:
        # direct read, not pystella_tpu.config.getenv: this module must
        # stay loadable BY FILE in a jax-free supervisor, where no
        # package import is available
        path = os.environ.get(
            "PYSTELLA_EVENT_LOG") or None  # env-registry: PYSTELLA_EVENT_LOG
        try:
            _default = EventLog(path)
        except OSError as e:
            print(f"pystella_tpu.obs: cannot open event log {path!r} "
                  f"({e}); events disabled", file=sys.stderr)
            _default = EventLog(None)
    return _default


def configure(path=None, host=None, rotate_bytes=None):
    """(Re)point the process-default event log at ``path`` (``None``
    disables). Returns the new log; the previous one is closed."""
    global _default
    old, _default = _default, EventLog(path, host=host,
                                       rotate_bytes=rotate_bytes)
    if old is not None:
        old.close()
    return _default


def emit(kind, step=None, **data):
    """Emit on the process-default log (no-op when unconfigured)."""
    return get_log().emit(kind, step=step, **data)


def read_events(path, kind=None, include_rotated=False):
    """Load events from a JSONL file (newest last). Torn trailing lines
    from a killed writer are skipped.
    ``kind`` optionally filters. ``include_rotated=True`` reads the
    whole rotated family (:func:`rotated_family`) oldest-first, so a
    size-rotated long-lived log reads as one continuous record — the
    ledger ingests event logs this way."""
    out = []
    paths = rotated_family(path) if include_rotated else [path]
    for member in paths:
        try:
            with open(member) as f:
                for ln in f:
                    if not ln.strip():
                        continue
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        continue  # torn line
                    if kind is None or rec.get("kind") == kind:
                        out.append(rec)
        except OSError:
            continue
    return out
