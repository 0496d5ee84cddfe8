"""Named trace scopes for hot paths, host spans where the program
dispatches and waits, and the central registry of both vocabularies.

Two context managers, one registry:

- :func:`trace_scope` names what the DEVICE runs: ``jax.named_scope``
  attaches the name to every op traced inside, so compiled-code
  profiles show ``fused_rk_stage_pair`` / ``halo_exchange`` /
  ``pallas_stencil_coupled_pair`` instead of raw XLA op names (on a TPU
  the HLO instruction of a Pallas call takes the innermost scope's
  name). Entered eagerly it also marks the host timeline
  (``jax.profiler.TraceAnnotation``); entered under ``jit`` tracing it
  does not: a host annotation there would fire once, at trace time, and
  time Python tracing under the scope's name.
- :func:`host_span` names what the HOST does at run time: one span at
  every dispatch and every fetch of the main path (the table in
  ``doc/observability.md`` "Host spans"). It is a ``TraceAnnotation``,
  so the span lies on the profiler's clock under the device's idle gaps,
  and, while a recorder is installed (:func:`recording`), one row
  ``[name, parent row, start_ns, end_ns]`` kept in memory until it is
  drained. A span never syncs.

Both cost about a microsecond when no profiler is attached and are
platform-agnostic (the CPU test suite runs them constantly).

The scope names survive into the lowered MLIR's debug locations, which
is how tests verify instrumentation without capturing a real trace:
:func:`lowered_scopes` / :func:`has_scope` parse them back out of a
``jax.jit(...).lower(...)`` result.

**Registry.** Every scope name the package emits must be registered here
(:func:`register_scope`): :data:`pystella_tpu.obs.trace.KNOWN_SCOPES` —
the vocabulary the Perfetto parser folds trace rows into, and therefore
everything the ledger's per-scope tables can ever show — is derived from
:func:`registered_scopes`. A tier-1 test
(``tests/test_scope_registry.py``) greps every ``trace_scope(...)`` /
``named_scope(...)`` literal in ``pystella_tpu/`` against the registry,
so a renamed hot-path scope can no longer silently vanish from
trace/ledger tables: the rename either updates the registry (and the
parser vocabulary with it) or fails CI.

jax is imported lazily inside the functions (not at module import), so
this module stays loadable by file in a jax-free supervisor, like
``obs/events.py``.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time

__all__ = ["trace_scope", "host_span", "recording", "span_table",
           "span_paths", "kernel_scope", "in_jax_trace", "lowered_scopes",
           "has_scope", "register_scope", "registered_scopes"]


#: the central scope-name registry (see module docstring); seeded below
#: with the in-tree instrumentation vocabulary
_SCOPE_REGISTRY = set()


def register_scope(name):
    """Register a scope name (idempotent; returns ``name``). Call this
    for any new ``trace_scope``/``named_scope`` literal so the Perfetto
    parser (:data:`pystella_tpu.obs.trace.KNOWN_SCOPES`) and the
    ledger's per-scope tables know about it — the tier-1 registry test
    fails on unregistered literals."""
    _SCOPE_REGISTRY.add(str(name))
    return name


def registered_scopes():
    """The registered scope names, as a frozenset."""
    return frozenset(_SCOPE_REGISTRY)


#: raw-op row spellings that fold into a registered scope: jax 0.9 names
#: an HLO instruction after the PRIMITIVE that produced it, so the
#: ``collective-permute`` ops of a halo exchange appear in a trace as
#: ``ppermute.N`` rows
RAW_OP_ALIASES = {"ppermute": "collective-permute"}


for _name in (
    # generic stepper stages (rk_stage0..N fold into this at parse time)
    "rk_stage",
    # fused Pallas steppers
    "fused_rk_stage", "fused_rk_stage_pair", "fused_rk_stage_energy",
    "fused_coupled_pair",
    # halo exchange: padded path and the overlapped interior/shell split
    "halo_exchange",
    "halo_overlap", "halo_overlap_interior", "halo_overlap_shells",
    # the slab ppermutes of the Pallas split (OverlapStreamingStencil),
    # issued ahead of its interior launch
    "halo_overlap_exchange",
    # the raw XLA ppermute op rows — device traces carry them with no
    # named-scope path; the ledger's communication-time denominator
    "collective-permute",
    # Pallas kernel dispatch: a streaming kernel of no stated kind
    # (bare StreamingStencil users) and the
    # whole-lattice-resident tier; the kinds follow below
    "pallas_stencil", "pallas_resident_stencil",
    # ...the fused steppers' kernels by kind (kernel_scope)...
    "pallas_stencil_stage", "pallas_stencil_pair",
    "pallas_stencil_coupled_pair", "pallas_stencil_energy",
    "pallas_stencil_chunk",
    # ...and FiniteDifferencer's operators
    "pallas_stencil_lap", "pallas_stencil_grad",
    "pallas_stencil_grad_lap", "pallas_stencil_pdx",
    "pallas_stencil_pdy", "pallas_stencil_pdz", "pallas_stencil_div",
    # ...and the multigrid levels' kernels (multigrid.relax): a sweep,
    # a residual pass, the FAS tau-corrected coarse source
    "pallas_stencil_mg_smooth", "pallas_stencil_mg_residual",
    "pallas_stencil_mg_tau",
    # the binning kernel behind every histogram and spectrum
    # (ops.histogram): a one-hot contraction on the MXU. Not a
    # pallas_stencil_* name: it streams no lattice windows, so no
    # stencil byte rule counts it
    "pallas_bincount",
    # the whole-RK-chunk (temporal blocking) kernel dispatch
    "chunk_stage",
    # the sanctioned carry_dtype quantization point (ops.fused): the one
    # scope under which an f32->bf16 narrowing is legal; the dataflow
    # lint tier treats any float downcast OUTSIDE this scope as a
    # POLICY_BF16_ACC32 violation
    "carry_quantize",
    # multigrid: the cycle, and a level's sweeps and residual pass
    # (mg_smooth is also the host span round a level's smooth with its
    # two error norms); the host spans of the cycle's walk beside them
    "mg_cycle", "mg_smooth", "mg_residual",
    "mg_transfer_down", "mg_transfer_up", "mg_errors_fetch",
    # driver-level span (the example's loop)
    "driver_step",
    # host spans (host_span): where the main path dispatches and where
    # it waits — doc/observability.md "Host spans" says which call
    # holds each
    "step_dispatch", "step_fetch", "lap_dispatch", "grad_dispatch",
    "reduce_dispatch", "reduce_fetch", "statistics", "expansion_step",
    "output_write", "sentinel_observe", "sentinel_poll",
    "histogram", "histogram_dispatch", "histogram_fetch",
    "spectra", "spectra_dispatch", "spectra_fetch", "gw_spectra",
    # the transverse-traceless projection of the -gws output
    # (fourier.projectors; its program is jit_tt_project)
    "tt_project",
    # spectral derivatives (fourier.derivs): the three parts of every
    # derivative, in the collocator's own programs (jit_spectral_lap,
    # ...) and in a stepper's stage program that inlines them; and the
    # host spans round the two calls a driver loop makes
    "spectral_forward", "spectral_symbol", "spectral_inverse",
    "spectral_lap_dispatch", "spectral_grad_dispatch",
    # the in-graph numerics health vector (obs.sentinel)
    "sentinel",
    # the ensemble tier (pystella_tpu.ensemble): the batched member
    # step and the in-graph evict/resample slot write
    "ensemble_step", "ensemble_evict",
    # the elastic runtime (pystella_tpu.resilience): each step taken
    # under Supervisor control — replayed spans after a recovery show
    # up as a second pass over the same step numbers in a trace
    "supervised_step",
    # the sharded pencil-FFT tier (fourier.pencil): per-axis local FFT
    # stages and the all_to_all transposes between them — the ledger's
    # `fft` section derives its exposed-vs-hidden transpose split from
    # these two rows, like the halo rows above
    "fft_stage", "fft_transpose",
    # the RAW XLA op rows of the same two phases — device traces (TPU
    # and the TFRT CPU backend) carry `all-to-all.N` / `fft.N` op rows
    # with no named-scope path; the ledger falls back to them when the
    # scope-path rows are absent (longest-match folding keeps a
    # TPU row like `jit(..)/fft_stage/fft.3` in `fft_stage`, not here)
    "all-to-all", "fft",
):
    register_scope(_name)
    if _name.startswith("pallas_stencil"):
        # a streaming kernel that OverlapStreamingStencil splits is two
        # kernels of its own on an x-sharded mesh: the interior launch
        # and the h-row shell launch, named after the kind they split
        # (``%pallas_stencil_pair_interior.N`` in a TPU trace), so a
        # device trace tells them from a single launch
        register_scope(_name + "_interior")
        register_scope(_name + "_shell")
del _name


def kernel_scope(kind=None):
    """The dispatch scope of a streaming stencil kernel of ``kind``:
    ``pallas_stencil_<kind>``, or the bare ``pallas_stencil`` for
    ``None``. The kinds are the fused steppers'
    (``FusedScalarStepper._build_stencil``'s ``kind``) and
    ``FiniteDifferencer``'s operators, registered above by their full
    spelling. A TPU trace shows the scope as the HLO instruction's name
    (``%pallas_stencil_pair.3``), so a breakdown by instruction is a
    breakdown by kind; the shared prefix is deliberate — whatever
    matches ``pallas_stencil`` keeps matching every kind. An unknown
    kind is an error here, at build time, rather than a row no table
    folds."""
    name = "pallas_stencil" if kind is None else f"pallas_stencil_{kind}"
    if name not in _SCOPE_REGISTRY:
        raise ValueError(f"stencil kernel kind {kind!r}: register_scope("
                         f"{name!r}) in obs/scope.py first")
    return name


#: jax's own "is no trace in progress on this thread" and its
#: ``TraceAnnotation``, resolved on first use (this module imports no jax)
_trace_state_clean = _TraceAnnotation = None


def _resolve_jax():
    global _trace_state_clean, _TraceAnnotation
    import jax
    _TraceAnnotation = jax.profiler.TraceAnnotation
    try:
        from jax._src.core import trace_state_clean
    except ImportError:  # a jax that moved it: annotate as before
        def trace_state_clean():
            return True
    _trace_state_clean = trace_state_clean


def in_jax_trace():
    """Is a jax trace (``jit``, ``shard_map``, ``vmap`` ...) in progress
    on this thread?"""
    if _trace_state_clean is None:
        _resolve_jax()
    return not _trace_state_clean()


@contextlib.contextmanager
def trace_scope(name):
    """Name everything inside for compiled-code traces
    (``jax.named_scope``) and, when entered eagerly, for the host
    timeline too (``jax.profiler.TraceAnnotation``). Under ``jit``
    tracing only the named scope is entered: the host would otherwise
    record, once, how long Python took to trace the block, and
    ``trace_summary`` would count that as the scope's time."""
    import jax
    with jax.named_scope(name):
        if in_jax_trace():
            yield
        else:
            with jax.profiler.TraceAnnotation(name):
                yield


# -- host spans ------------------------------------------------------------

#: the installed recorder, or ``None``: the rows, the stack of open
#: rows, the prefix its spans' annotations take, and the one thread whose
#: spans are recorded (the driver's; a span on another thread is an
#: annotation only, so no lock is needed)
_RECORDER = None


class _Recorder(list):
    """The rows themselves (what :func:`recording` yields), with the
    recorder's state beside them."""

    __slots__ = ("open", "thread", "prefix")

    def __init__(self, prefix):
        super().__init__()
        self.open, self.prefix = [], prefix
        self.thread = threading.get_ident()

    def drain(self):
        """The rows closed so far, taken out of the recorder, which is
        left empty: what a run that keeps the recorder on calls once a
        block, so that the rows do not fill memory. Legal only while no
        span is open (a parent still open would be cut from its
        children); parent indices of what is returned point into it."""
        if self.open:
            raise RuntimeError(
                f"drain() inside the open span {self[self.open[-1]][0]!r}")
        rows = self[:]
        del self[:]
        return rows


class host_span:
    """``with host_span("reduce_fetch"): ...`` — one host-side span at
    run time: a ``jax.profiler.TraceAnnotation`` (so it lies on the
    profiler's clock, under the device's idle gaps) and, while
    :func:`recording` is active, one row ``[name, parent, start_ns,
    end_ns]`` on ``time.perf_counter_ns``; ``parent`` is the index of
    the row that was open when this one started, or ``-1``. With no
    recorder the added cost over the annotation is one comparison with
    ``None``; with one, the annotation is named with the recorder's
    prefix in front (the row keeps the bare name). A span never waits
    for the device: put one round a call that already does
    (``np.asarray``) to time the wait, never a ``block_until_ready`` of
    its own. Entered under ``jit`` tracing (an operator called from
    inside someone's program) it does nothing: there is no run-time host
    work to name."""

    __slots__ = ("_name", "_ann", "_rec")

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        self._rec = self._ann = None
        if in_jax_trace():  # called inside someone's jit: no run-time span
            return self
        rec = _RECORDER
        if rec is None:
            self._ann = _TraceAnnotation(self._name)
            self._ann.__enter__()
            return self
        self._ann = _TraceAnnotation(rec.prefix + self._name)
        self._ann.__enter__()
        if rec.thread == threading.get_ident():
            rec.append([self._name, rec.open[-1] if rec.open else -1,
                        time.perf_counter_ns(), 0])
            rec.open.append(len(rec) - 1)
            self._rec = rec
        return self

    def __exit__(self, exc_type, exc, tb):
        rec = self._rec
        if rec is not None:
            rec[rec.open.pop()][3] = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


@contextlib.contextmanager
def recording(annotation_prefix=""):
    """Install the host-span recorder for the block and yield its rows
    (a list that fills as spans close; read it after the block, hand it
    to :func:`span_table`, or, in a run that keeps the recorder on,
    empty it once a block with its ``drain()``). While it is installed
    every ``host_span``'s annotation is named ``annotation_prefix +
    name``, so that whoever reads the profiler's trace can tell the
    program's annotations by the prefix; the rows keep the bare names.
    One recorder at a time: a nested ``recording()`` raises. Only the
    installing thread's spans are recorded."""
    global _RECORDER
    if _RECORDER is not None:
        raise RuntimeError("a host-span recorder is already installed")
    rec = _RECORDER = _Recorder(annotation_prefix)
    try:
        yield rec
    finally:
        _RECORDER = None


def span_paths(rows):
    """Each row's name qualified by its ancestors' (``reduce_fetch``
    inside ``statistics`` is ``statistics/reduce_fetch``; a top-level
    row keeps its bare name), in the rows' order: the energy's fetch and
    the statistics' are two names."""
    paths = []
    for name, parent, _, _ in rows:
        paths.append(name if parent < 0 else f"{paths[parent]}/{name}")
    return paths


def span_table(rows, steps=None):
    """Fold recorder rows into ``{"spans": {name: {"count", "total_ms",
    "self_ms"[, "ms_per_step"]}}, "fetches": n, "dispatches": m[,
    "host_syncs_per_step", "dispatches_per_step"]}``. A span's self time
    is its duration minus its children's; a fetch is a row whose name
    ends in ``_fetch`` (each is one host sync of the program's own), a
    dispatch one whose name ends in ``_dispatch`` (each enqueues one
    call's programs). Rows still open (``end_ns == 0``) are left out."""
    child_ns = [0] * len(rows)
    for name, parent, t0, t1 in rows:
        if t1 and parent >= 0:
            child_ns[parent] += t1 - t0
    spans, fetches, dispatches = {}, 0, 0
    for i, (name, parent, t0, t1) in enumerate(rows):
        if not t1:
            continue
        acc = spans.setdefault(name, [0, 0, 0])
        acc[0] += 1
        acc[1] += t1 - t0
        acc[2] += t1 - t0 - child_ns[i]
        fetches += name.endswith("_fetch")
        dispatches += name.endswith("_dispatch")
    table = {}
    for name, (count, total, own) in sorted(spans.items()):
        table[name] = {"count": count, "total_ms": total / 1e6,
                       "self_ms": own / 1e6}
        if steps:
            table[name]["ms_per_step"] = total / 1e6 / steps
    out = {"spans": table, "fetches": fetches, "dispatches": dispatches}
    if steps:
        out["steps"] = int(steps)
        out["host_syncs_per_step"] = fetches / steps
        out["dispatches_per_step"] = dispatches / steps
    return out


def lowered_scopes(lowered):
    """The set of debug-location name paths in a ``jax.stages.Lowered``
    — every ``jax.named_scope`` entered during tracing appears as a
    path component (e.g. ``jit(step)/fused_rk_stage_pair/concatenate``).
    Used by tests to assert instrumentation presence under CPU lowering,
    no TPU or live profiler required."""
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    return set(re.findall(r'loc\("([^"]*)"', asm))


def has_scope(lowered, name):
    """True when ``name`` appears in any of ``lowered``'s scope paths."""
    return any(name in path for path in lowered_scopes(lowered))
