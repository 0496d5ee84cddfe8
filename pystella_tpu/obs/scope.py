"""Named trace scopes for hot paths, plus the central scope registry.

One context manager, two sinks:

- ``jax.named_scope`` attaches the name to every op traced inside, so
  compiled-code profiles (Perfetto / TensorBoard traces captured with
  :class:`pystella_tpu.trace`) show ``fused_rk_stage_pair`` /
  ``halo_exchange`` / ``pallas_stencil`` regions instead of raw XLA op
  names;
- ``jax.profiler.TraceAnnotation`` marks the host-side timeline, so
  eager driver loops (per-stage protocol, multigrid cycle orchestration)
  show up as named spans in the same trace.

Both are no-ops costing ~a microsecond when no profiler is attached and
are platform-agnostic (the CPU test suite runs them constantly).

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
import functools
import re

__all__ = ["trace_scope", "traced", "lowered_scopes", "has_scope",
           "register_scope", "registered_scopes"]


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
    # the raw XLA ppermute op rows — device traces carry them with no
    # named-scope path; the ledger's communication-time denominator
    "collective-permute",
    # Pallas kernel dispatch
    "pallas_stencil", "pallas_resident_stencil",
    # the whole-RK-chunk (temporal blocking) kernel dispatch and the
    # persistent autotuner's timed candidate probes (ops.autotune)
    "chunk_stage", "autotune_probe",
    # the sanctioned carry_dtype quantization point (ops.fused): the one
    # scope under which an f32->bf16 narrowing is legal; the dataflow
    # lint tier treats any float downcast OUTSIDE this scope as a
    # POLICY_BF16_ACC32 violation
    "carry_quantize",
    # multigrid
    "mg_cycle", "mg_smooth", "mg_residual",
    # driver-level spans (bench smoke / example loops)
    "bench_step", "driver_step",
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
    # k-space stencil application through the transform
    # (ops.fft_stencil)
    "fft_stencil",
    # the scenario service's request-scoped span vocabulary
    # (obs.spans): the SpanAssembler exports assembled request
    # timelines as Perfetto complete-span rows under THESE names, so
    # hardware profiler captures and service traces fold through one
    # parser (obs.trace.scope_durations) — the critical-path phases...
    "service_queue_wait", "service_admission", "service_compile",
    "service_chunk_compute", "service_checkpoint_barrier",
    "service_recovery_replay", "service_preempt_drain",
    # ...plus the structural spans they hang off
    "service_request_span", "service_lease_span",
):
    register_scope(_name)
del _name


@contextlib.contextmanager
def trace_scope(name):
    """Name everything inside for both compiled-code traces
    (``jax.named_scope``) and the host timeline
    (``jax.profiler.TraceAnnotation``)."""
    import jax
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


def traced(name=None):
    """Decorator form of :func:`trace_scope` (defaults to the function's
    ``__name__``)."""
    def wrap(fn):
        scope_name = name if name is not None else fn.__name__

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with trace_scope(scope_name):
                return fn(*args, **kwargs)
        return inner
    return wrap


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
