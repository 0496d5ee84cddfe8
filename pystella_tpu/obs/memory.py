"""Compile-time and device-memory instrumentation.

Three kinds of evidence, all recorded into the event log:

- the **compile ledger** — every jit/AOT compile the package dispatches
  routes through here. :func:`compile_with_report` is the explicit
  ahead-of-time path (splitting *trace* seconds — Python tracing +
  StableHLO lowering — from *backend-compile* seconds, extracting XLA's
  ``memory_analysis()`` byte counts, and fingerprinting the program);
  :func:`instrument_jit` wraps the package's internal ``jax.jit``
  objects so a compile triggered by a first dispatch is attributed to a
  stable label (``step.LowStorageRK54``, ``fused.multi_step[10]``,
  ``mg.smooth``...) via jax's monitoring hooks instead of vanishing
  into startup time. Each observed compile emits a ``kind="compile"``
  event carrying the trace/compile split, a program fingerprint, and
  persistent-cache hit/miss attribution — the raw material of the perf
  ledger's ``cold_start`` section.
- :func:`ensure_compilation_cache` — wires jax's persistent
  compilation cache: the directory ``JAX_COMPILATION_CACHE_DIR`` names
  when the environment sets it, else ``bench_results/xla_cache`` in the
  checkout, so a restarted process pays XLA's backend compile once per
  program *ever*, not once per process. Hit/miss counts are read back
  through the same monitoring hooks.
- :func:`device_memory_report` — live allocator statistics
  (``Device.memory_stats()``: bytes in use, peak, limit). TPU backends
  populate these; CPU returns ``None`` and the report degrades to a
  no-op instead of raising, so instrumented drivers run everywhere.

The peak-HBM estimate in a :class:`CompileRecord` is exactly the number
that would have caught round 5's 183 MB overshoot *before* the
allocator rejected the 512^3 GW step: ``rec.peak_bytes`` vs the chip's
HBM. The trace/compile split is the number that would have caught
round 3's ~365 s multigrid cold start — and now does.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import platform
import re
import socket
import threading
import time

import jax

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import metrics as _metrics

__all__ = ["CompileRecord", "compile_with_report", "compile_watch",
           "instrument_jit", "InstrumentedJit", "program_name",
           "compile_totals",
           "ensure_compilation_cache",
           "program_fingerprint", "signature_fingerprint",
           "runtime_versions", "flags_fingerprint",
           "environment_fingerprint", "device_memory_stats",
           "device_memory_report"]


# ---------------------------------------------------------------------------
# jax monitoring bridge: trace/compile durations + persistent-cache events
# ---------------------------------------------------------------------------

#: monitoring events that measure Python-side program construction
#: (jaxpr tracing and StableHLO lowering — work a warm AOT start skips)
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
#: the XLA backend compile itself (work the persistent cache skips)
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
#: persistent compilation cache outcomes
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}

_totals_lock = threading.Lock()
_totals = {"trace_s": 0.0, "compile_s": 0.0,
           "cache_hits": 0, "cache_misses": 0}
_watchers = threading.local()
_listeners_installed = False
_install_lock = threading.Lock()


def _watcher_stack():
    stack = getattr(_watchers, "stack", None)
    if stack is None:
        stack = _watchers.stack = []
    return stack


def _on_duration(event, duration, **kwargs):
    if event in _TRACE_EVENTS:
        key = "trace_s"
    elif event == _BACKEND_EVENT:
        key = "compile_s"
    else:
        return
    with _totals_lock:
        _totals[key] += float(duration)
    for w in _watcher_stack():
        w._add(key, float(duration))


def _on_event(event, **kwargs):
    key = _CACHE_EVENTS.get(event)
    if key is None:
        return
    with _totals_lock:
        _totals[key] += 1
    for w in _watcher_stack():
        w._add(key, 1)


def _install_jax_listeners():
    """Register the monitoring listeners (idempotent; thread-safe).
    jax invokes them synchronously on the compiling thread, which is
    what lets a :class:`compile_watch` attribute activity to the
    program label whose dispatch triggered it."""
    global _listeners_installed
    if _listeners_installed:
        return
    with _install_lock:
        if _listeners_installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listeners_installed = True


def compile_totals():
    """Process-wide accumulated compile activity since the listeners
    were installed: ``{trace_s, compile_s, cache_hits, cache_misses}``.
    The denominators of a cold-start story — how much of startup went
    to building programs vs running them."""
    _install_jax_listeners()
    with _totals_lock:
        return dict(_totals)


class compile_watch:
    """Attribute jax compile activity inside a ``with`` block to a
    label. Cheap enough to wrap every dispatch (one list append/pop and
    four float adds per *compile*, nothing per cached call)::

        with compile_watch("mg.smooth") as w:
            out = fn(*args)
        if w.compiled:
            ...  # w.trace_seconds / w.compile_seconds / w.cache_hits

    Nested watches each observe the same activity (an outer driver-level
    watch sees the sum of everything its inner calls compiled).
    """

    def __init__(self, label=None):
        self.label = label
        self.trace_seconds = 0.0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _add(self, key, val):
        if key == "trace_s":
            self.trace_seconds += val
        elif key == "compile_s":
            self.compile_seconds += val
        elif key == "cache_hits":
            self.cache_hits += val
        elif key == "cache_misses":
            self.cache_misses += val

    @property
    def compiled(self):
        """Did any program construction happen inside the block?"""
        return (self.trace_seconds > 0.0 or self.compile_seconds > 0.0
                or self.cache_hits > 0 or self.cache_misses > 0)

    def __enter__(self):
        _install_jax_listeners()
        _watcher_stack().append(self)
        return self

    def __exit__(self, *exc):
        try:
            _watcher_stack().remove(self)
        except ValueError:
            pass


# ---------------------------------------------------------------------------
# program fingerprints
# ---------------------------------------------------------------------------

_versions_cache = None


def _version_of(dist):
    try:
        from importlib.metadata import version
        return version(dist)
    except Exception:
        return None


def runtime_versions():
    """The jax/jaxlib/libtpu version triple, the compiler stack that
    invalidates cached/AOT programs: a jax/jaxlib (or libtpu) bump must
    never silently load a stale executable, so these are baked into
    every program fingerprint and warm-start artifact. One definition,
    shared with the perf report's environment fingerprint
    (:func:`environment_fingerprint`) so the two can never diverge.
    Resolved from installed-distribution metadata and memoized
    (``importlib.metadata`` scans dist-info, and fingerprints are
    computed per observed compile)."""
    global _versions_cache
    if _versions_cache is None:
        _versions_cache = {
            "jax": _version_of("jax"),
            "jaxlib": _version_of("jaxlib"),
            # a libtpu bump changes the generated code: cached/AOT
            # programs keyed without it would silently serve stale
            # executables
            "libtpu": (_version_of("libtpu")
                       or _version_of("libtpu-nightly")),
        }
    return dict(_versions_cache)


def _leaf_signature(leaf):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    sig = [list(shape) if shape is not None else None,
           str(dtype) if dtype is not None else type(leaf).__name__]
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            sig.append(str(sharding.spec))
            mesh = getattr(sharding, "mesh", None)
            if mesh is not None:
                sig.append([list(mesh.shape.values()),
                            list(mesh.shape.keys()),
                            str(getattr(mesh.devices.flat[0],
                                        "device_kind", ""))])
        except Exception:
            pass
    return sig


#: env-var name substrings that make an XLA/libtpu flag relevant to a
#: fingerprint: async-collective and latency-hiding-scheduler toggles
#: change the compiled schedule, and so what a step-time comparison
#: means (the overlapped halo path depends on them to pay off)
_FLAG_MARKERS = ("async_collective", "async_all_gather",
                 "latency_hiding", "scheduler")


def flags_fingerprint(env=os.environ):
    """The scheduler-relevant flags in ``env`` (``XLA_FLAGS`` +
    ``LIBTPU_INIT_ARGS``) as ``{name: value}``, plus the
    ``PYSTELLA_HALO_OVERLAP`` policy setting when present, so a report
    says whether the overlapped code path was even eligible. Hashed
    into every program fingerprint and embedded in every report's
    environment fingerprint, so the gate can warn when two reports
    differ only in flags."""
    flags = {}
    for var in ("XLA_FLAGS", "LIBTPU_INIT_ARGS"):
        for tok in env.get(var, "").split():
            name, _, value = tok.lstrip("-").partition("=")
            if any(m in name for m in _FLAG_MARKERS):
                flags[name] = value if value else "true"
    setting = env.get("PYSTELLA_HALO_OVERLAP")
    if setting is not None:
        flags["PYSTELLA_HALO_OVERLAP"] = setting
    return flags


def environment_fingerprint():
    """Everything needed to decide whether two perf reports (or two
    forensic bundles) are comparable: python and compiler-stack
    versions, hostname, device kind and count, process count, and the
    scheduler-relevant flags."""
    env = {
        "python": platform.python_version(),
        **runtime_versions(),
        "hostname": socket.gethostname(),
        "platform": None,
        "device_kind": None,
        "num_devices": None,
        "num_processes": None,
        "xla_flags": flags_fingerprint(),
    }
    try:
        devs = jax.devices()
        env["platform"] = devs[0].platform
        env["device_kind"] = devs[0].device_kind
        env["num_devices"] = len(devs)
        env["num_processes"] = int(jax.process_count())
    except Exception:
        pass
    return env


def fingerprint_components(label="", args=None, kwargs=None):
    """The JSON-safe identity a program fingerprint hashes: label,
    per-leaf shape/dtype/sharding/mesh signature, compiler-stack
    versions (:func:`runtime_versions`), and the scheduler-relevant
    flag fingerprint (:func:`flags_fingerprint`, the same flags the
    perf-report environment records, because they change the compiled
    schedule)."""
    leaves = []
    if args is not None or kwargs is not None:
        leaves = [_leaf_signature(leaf) for leaf in
                  jax.tree_util.tree_leaves((args or (), kwargs or {}))]
    return {"label": str(label),
            "avals": leaves,
            "versions": runtime_versions(),
            "flags": flags_fingerprint()}


def _digest(components):
    blob = json.dumps(components, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def signature_fingerprint(label="", args=None, kwargs=None):
    """Cheap fingerprint from the call signature only (no re-lowering:
    safe to compute on a hot dispatch path). Returns
    ``(digest, components)``."""
    comp = fingerprint_components(label, args, kwargs)
    return _digest(comp), comp


def program_fingerprint(lowered=None, *, label="", args=None,
                        kwargs=None, text=None):
    """Full program fingerprint: the signature components plus a
    sha256 of the lowered StableHLO module (``lowered.as_text()`` or an
    explicit ``text``). Two programs share a fingerprint exactly when
    the compiler would rebuild the same executable for them — the key
    warm-start artifacts and the compile ledger are indexed by.
    Returns ``(digest, components)``."""
    comp = fingerprint_components(label, args, kwargs)
    if text is None and lowered is not None:
        text = lowered.as_text()
    if text is not None:
        comp["module_sha256"] = hashlib.sha256(
            text.encode() if isinstance(text, str) else text).hexdigest()
    return _digest(comp), comp


# ---------------------------------------------------------------------------
# compile records + the AOT path
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompileRecord:
    """One computation's compile cost and memory footprint (byte fields
    are ``None`` when the backend provides no memory analysis).

    ``trace_seconds`` is Python-side program construction (jaxpr trace
    + StableHLO lowering — the cost an AOT warm start skips);
    ``compile_seconds`` is the XLA backend-compile span (the cost the
    persistent compilation cache collapses — on a cache HIT the span
    still ticks for retrieval + executable deserialization, so judge
    "did it compile?" by ``cache_hit``, not by seconds alone). Older
    events carried the two lumped into ``compile_seconds``; consumers
    treat a missing ``trace_seconds`` as 0."""

    label: str
    compile_seconds: float
    trace_seconds: float = 0.0
    #: MLIR text serialization for the fingerprint —
    #: measurement overhead kept OUT of both spans above, but visible
    #: here so large-module hashing cost cannot hide
    serialize_seconds: float = 0.0
    fingerprint: str | None = None
    fingerprint_kind: str | None = None
    cache_hits: int = 0
    cache_misses: int = 0
    argument_bytes: int | None = None
    output_bytes: int | None = None
    temp_bytes: int | None = None
    alias_bytes: int | None = None
    generated_code_bytes: int | None = None

    @property
    def total_seconds(self):
        """Trace + backend compile: the whole cost of getting this
        program from Python to an executable."""
        return self.trace_seconds + self.compile_seconds

    @property
    def cache_hit(self):
        """Did the persistent cache serve this compile? (``None`` when
        the cache saw no request — cache disabled or nothing reached
        the backend.)"""
        if self.cache_hits == 0 and self.cache_misses == 0:
            return None
        return self.cache_misses == 0

    @property
    def peak_bytes(self):
        """Static peak-HBM estimate: arguments + outputs + temporaries
        (aliased/donated bytes discounted — they reuse input buffers)."""
        parts = [self.argument_bytes, self.output_bytes, self.temp_bytes]
        if all(p is None for p in parts):
            return None
        total = sum(p or 0 for p in parts)
        return total - (self.alias_bytes or 0)

    def asdict(self):
        d = dataclasses.asdict(self)
        d["peak_bytes"] = self.peak_bytes
        d["total_seconds"] = self.total_seconds
        d["cache_hit"] = self.cache_hit
        return d


def _memory_analysis(compiled):
    """``compiled.memory_analysis()`` as a plain field dict (empty when
    the backend returns nothing or the query itself raises)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    fields = {"argument_bytes": "argument_size_in_bytes",
              "output_bytes": "output_size_in_bytes",
              "temp_bytes": "temp_size_in_bytes",
              "alias_bytes": "alias_size_in_bytes",
              "generated_code_bytes": "generated_code_size_in_bytes"}
    return {k: int(getattr(ma, attr)) for k, attr in fields.items()
            if hasattr(ma, attr)}


def _record_compile_metrics(rec):
    _metrics.counter("compiles").inc()
    _metrics.timer("compile_s").observe(rec.compile_seconds)
    _metrics.timer("trace_s").observe(rec.trace_seconds)
    if rec.cache_hits:
        _metrics.counter("compile_cache_hits").inc(rec.cache_hits)
    if rec.cache_misses:
        _metrics.counter("compile_cache_misses").inc(rec.cache_misses)


def compile_with_report(fn, *args, label=None, log=None, step=None,
                        fingerprint=True, **kwargs):
    """AOT-compile ``fn(*args, **kwargs)`` and report the cost.

    :arg fn: a jitted callable (``jax.jit`` result — fused steppers'
        ``_jit_step`` qualifies) or a plain function (jitted here).
    :arg fingerprint: compute the full lowered-module fingerprint
        (default; pass ``False`` to skip hashing a very large module).
    :returns: ``(compiled, record)`` — the executable (call it directly
        to avoid a second compile) and the :class:`CompileRecord`.

    The record splits ``trace_seconds`` (the ``lower()`` wall time:
    jaxpr tracing + StableHLO lowering, pure Python-side cost) from
    ``compile_seconds`` (the ``compile()`` wall time: XLA's backend
    compile, which the persistent cache can satisfy — the record's
    ``cache_hits``/``cache_misses`` say whether it did).

    Side effects: a ``kind="compile"`` event on ``log`` (default: the
    process event log), a ``compiles`` counter increment, and
    ``compile_s``/``trace_s`` timer observations in the default metrics
    registry.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    label = label or getattr(fn, "__name__", None) or repr(fn)
    _install_jax_listeners()
    with compile_watch(label) as w:
        t0 = time.perf_counter()
        lowered = jitted.lower(*args, **kwargs)
        t1 = time.perf_counter()
        # MLIR serialization is Python-side measurement overhead —
        # keep it out of BOTH reported spans (a cache-hit
        # compile_seconds must show retrieval cost, not as_text()),
        # and skip it entirely unless the fingerprint needs the text
        text = lowered.as_text() if fingerprint else None
        tc = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    fp = kind = None
    if fingerprint:
        fp, _ = program_fingerprint(text=text, label=label, args=args,
                                    kwargs=kwargs)
        kind = "lowered"
    rec = CompileRecord(label=label, trace_seconds=t1 - t0,
                        compile_seconds=t2 - tc,
                        serialize_seconds=tc - t1,
                        fingerprint=fp, fingerprint_kind=kind,
                        cache_hits=w.cache_hits,
                        cache_misses=w.cache_misses,
                        **_memory_analysis(compiled))
    _record_compile_metrics(rec)
    (log if log is not None else _events.get_log()).emit(
        "compile", step=step, source="aot", **rec.asdict())
    return compiled, rec


# ---------------------------------------------------------------------------
# dispatch-path instrumentation
# ---------------------------------------------------------------------------

#: dispatch-path trace activity below this is not worth an event (tiny
#: helper jits re-traced inline inside an enclosing trace)
MIN_EVENT_TRACE_S = 0.005


class InstrumentedJit:
    """A thin proxy over a ``jax.jit`` object that attributes compiles
    triggered by dispatch to ``label`` and reports them as ``compile``
    events (``source="dispatch"``, signature fingerprint — no
    re-lowering is ever forced on the dispatch path). Steady-state
    calls pay one :class:`compile_watch` push/pop (~1 us); everything
    else (``lower``, attribute access) passes through, so the lint
    tier's ``.lower()`` audits and ``functools`` interop keep working.
    """

    __slots__ = ("_jitted", "_label")

    def __init__(self, jitted, label):
        self._jitted = jitted
        self._label = label

    def __call__(self, *args, **kwargs):
        with compile_watch(self._label) as w:
            out = self._jitted(*args, **kwargs)
        if (w.compile_seconds > 0.0 or w.cache_hits or w.cache_misses
                or w.trace_seconds >= MIN_EVENT_TRACE_S):
            try:
                rec = CompileRecord(
                    label=self._label, trace_seconds=w.trace_seconds,
                    compile_seconds=w.compile_seconds,
                    fingerprint_kind="signature",
                    cache_hits=w.cache_hits,
                    cache_misses=w.cache_misses)
                _record_compile_metrics(rec)
                # fingerprint hashing only pays off when the event is
                # actually recorded somewhere
                if _events.get_log().enabled:
                    rec.fingerprint, _ = signature_fingerprint(
                        self._label, args, kwargs)
                    _events.emit("compile", source="dispatch",
                                 **rec.asdict())
            except Exception:  # telemetry must never kill a dispatch
                pass
        return out

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def __repr__(self):
        return f"InstrumentedJit({self._label!r}, {self._jitted!r})"


def program_name(label):
    """The XLA module name a program labelled ``label`` gets: the label
    without its leading namespace (``fused.coupled_multi_step[4]`` ->
    ``coupled_multi_step_4``, ``spectra.spectra_bin_weights`` ->
    ``spectra_bin_weights``), every other run of non-identifier
    characters folded to one ``_``. jax prefixes ``jit_``; a trace keys
    each device op by this name."""
    head, dot, tail = str(label).partition(".")
    name = re.sub(r"\W+", "_", tail if dot and tail else head).strip("_")
    return name or "program"


def instrument_jit(fn, label, name=None, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` as a named, instrumented program:
    the one place a hot-path program gets its name. ``fn`` is jitted
    under ``name``, by default made from ``label``
    (:func:`program_name`), so the compiled module, and with it every
    row of a device trace, says which program it is instead of
    ``jit__unknown`` / ``jit_wrapped`` / ``jit__lambda``; its compiles
    land in the compile ledger under ``label``. The package's internal
    jit sites (steppers, fused chunks, operators, reductions, multigrid,
    spectra) all route through this,
    and a stencil kernel where it is dispatched eagerly (``name``
    given: the kernel's kind)."""
    def named(*args, **kwargs):
        return fn(*args, **kwargs)
    # jax resolves static/donated argument names through __wrapped__
    named.__wrapped__ = fn
    named.__name__ = named.__qualname__ = name or program_name(label)
    return InstrumentedJit(jax.jit(named, **jit_kwargs), str(label))


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

#: the in-checkout cache directory used when the environment names none
#: — a fixed path, because the path is part of the cache's key
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "bench_results", "xla_cache")


def ensure_compilation_cache(log=None):
    """Turn on jax's persistent compilation cache, so a restarted
    process pays each program's XLA backend compile once per *cache
    lifetime*, not once per process.

    Where the cache lives is decided OUTSIDE the program: when
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax already uses that
    directory and no directory is set in code; when it is not, the
    cache is :data:`DEFAULT_CACHE_DIR` (``bench_results/xla_cache`` in
    the checkout — a fixed path, never one built from a temp name, pid
    or time).

    The compile-time/entry-size floors are zeroed so even fast CPU
    (smoke) compiles populate and hit the cache — the smoke cold/warm
    e2e in CI depends on that, and production TPU compiles clear any
    floor anyway.

    Returns the cache dir jax uses. Emits one ``compile_cache`` event
    recording the wiring.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = jax.config.jax_compilation_cache_dir
        if not cache_dir:
            raise RuntimeError(
                "JAX_COMPILATION_CACHE_DIR is set but jax did not read "
                "it: set it before jax is imported")
    else:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax latches "is the cache used by this task" at the FIRST compile;
    # any compile before this call (package import, another test) would
    # freeze the cache off for the whole process — reset the latch so
    # the wiring takes effect now
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()
    _install_jax_listeners()
    (log if log is not None else _events.get_log()).emit(
        "compile_cache", dir=cache_dir, enabled=True,
        entries=len(os.listdir(cache_dir)))
    return cache_dir


# ---------------------------------------------------------------------------
# device memory
# ---------------------------------------------------------------------------

def device_memory_stats(device=None):
    """Live allocator stats for ``device`` (default: first local device)
    as a dict, or ``None`` where the backend keeps none (CPU)."""
    if device is None:
        device = jax.local_devices()[0]
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    return dict(stats) if stats else None


def device_memory_report(device=None, label="", step=None, log=None):
    """Record a ``kind="device_memory"`` event with the live HBM numbers
    (and mirror ``peak_bytes_in_use`` into a ``peak_hbm_bytes`` gauge);
    returns the stats dict, or ``None`` (and no event) on stat-less
    backends."""
    stats = device_memory_stats(device)
    if stats is None:
        return None
    keep = {k: stats[k] for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
             "largest_alloc_size") if k in stats}
    if "peak_bytes_in_use" in keep:
        _metrics.gauge("peak_hbm_bytes", reduce="max").set(
            keep["peak_bytes_in_use"])
    (log if log is not None else _events.get_log()).emit(
        "device_memory", step=step, label=label, **keep)
    return stats
