"""IR-tier lint: jaxpr/HLO hazard audits over real step functions.

Each :class:`GraphTarget` traces + lowers an actual step computation
(the same ``jax.jit`` objects the drivers dispatch) and audits two
artifacts:

- the lowered **StableHLO module** (``lowered.compiler_ir()`` with
  debug info): buffer-donation attributes (``tf.aliasing_output`` /
  ``jax.buffer_donor`` on the ``@main`` parameters), every tensor
  element type, the named-scope debug locations, and host-interaction
  markers;
- the **compiled HLO** (``compiled.as_text()``): the collectives that
  actually survived SPMD partitioning (an all-gather born from a bad
  sharding constraint only exists here), each carrying its originating
  ``op_name`` metadata path.

The audits:

``donation``
    Inputs the target declares donatable (the state pytree a step
    fully replaces) must alias outputs in the lowered module. A miss
    is reported as wasted HBM bytes — the difference between fitting
    and not fitting a large system (doc/performance.md).
``dtype``
    Every tensor element type must be in the target's dtype policy
    (default :data:`POLICY_F32`: no silent f64 — the classic x64-mode
    upcast that doubles traffic and silently de-vectorizes TPUs).
``collectives``
    Every collective op in the compiled module must match the target's
    allowlist (halo ``collective-permute``\\ s, registered sentinel/
    energy ``all-reduce``\\ s). An unexpected all-gather/all-to-all is
    an error naming the originating op path.
``host``
    No infeed/outfeed/host callbacks on the step path — any of them
    serializes the dispatch queue against the host.
``fusion``
    Scope names that must appear inside the SAME lowered module (the
    PR-4 sentinel reductions piggybacking on the step rather than
    launching separately).
"""

from __future__ import annotations

import dataclasses
import re
import time

from pystella_tpu.lint.report import Violation

__all__ = ["POLICY_F32", "POLICY_F64", "POLICY_BF16_ACC32",
           "POLICY_SPECTRAL_F32",
           "ArtifactCache", "GraphTarget", "audit_artifacts",
           "audit_target", "audit_targets", "lower_and_compile",
           "parse_main_params", "tensor_nbytes"]

#: bytes per MLIR tensor element type
_ELT_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "i64": 8, "i32": 4, "i16": 2, "i8": 1, "i1": 1,
    "ui64": 8, "ui32": 4, "ui16": 2, "ui8": 1,
    "complex<f32>": 8, "complex<f64>": 16,
}

#: the production single-precision policy: no f64 anywhere in the step
#: module. Integer/bool/index types are unrestricted — x64 mode makes
#: shape arithmetic i64, which moves no lattice data.
POLICY_F32 = {
    "name": "f32-strict",
    "allow_floats": ("f32", "f16", "bf16", "f8e4m3fn", "f8e5m2"),
}

#: reference-parity double precision (the f64 test-suite configs)
POLICY_F64 = {
    "name": "f64",
    "allow_floats": ("f64", "f32", "f16", "bf16"),
}

#: the bf16-carry GW configuration: bf16 storage, f32 accumulation —
#: f64 AND f16 both violate (an f16 sneaking in means the carry cast
#: went through the wrong intermediate)
POLICY_BF16_ACC32 = {
    "name": "bf16-in/f32-acc",
    "allow_floats": ("bf16", "f32"),
}

#: the f32 spectral programs (pencil FFT + binning): complex64 is the
#: transform's working type and is allowed; complex128/f64 still
#: violate (the classic x64 upcast doubling transpose traffic)
POLICY_SPECTRAL_F32 = {
    "name": "f32-spectral",
    "allow_floats": ("f32", "f16", "bf16", "f8e4m3fn", "f8e5m2",
                     "complex<f32>"),
}

#: collective base op names recognized in compiled HLO
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter",
                   "collective-broadcast")

#: substrings in either IR that mean the computation talks to the host
_HOST_MARKERS = ("infeed", "outfeed", "xla_python_cpu_callback",
                 "xla_ffi_python_cpu_callback", "tpu_host_callback",
                 "SendToHost", "RecvFromHost", "host_callback")


@dataclasses.dataclass
class GraphTarget:
    """One step function to audit.

    :arg build: zero-arg callable returning ``(jitted_or_lowered,
        args, kwargs, donatable)`` — ``donatable`` is a pytree (or
        list of arrays) whose total byte size the donation audit
        expects to see aliased, or ``None`` to skip that audit.
    :arg dtype_policy: one of the ``POLICY_*`` dicts (default
        :data:`POLICY_F32`).
    :arg collectives: ``{base-op-name: reason}`` allowlist for the
        compiled module (empty: any collective is a violation).
    :arg fused_scopes: scope names that must all appear in the lowered
        module's debug locations (the static fusion check).
    """

    name: str
    build: callable = None
    dtype_policy: dict = None
    collectives: dict = dataclasses.field(default_factory=dict)
    fused_scopes: tuple = ()


def tensor_nbytes(dims, elt):
    """Byte size of ``tensor<dims x elt>`` (0 for dynamic dims)."""
    n = 1
    for d in dims:
        if not d.isdigit():
            return 0
        n *= int(d)
    return n * _ELT_BYTES.get(elt, 0)


def _main_signature(asm):
    """The text of ``@main``'s parameter list (parens balanced — attr
    dicts and loc() annotations nest, and attr strings contain
    brackets)."""
    start = asm.find("@main(")
    if start < 0:
        return ""
    i = start + len("@main(")
    depth, in_str = 1, False
    j = i
    while j < len(asm) and depth:
        ch = asm[j]
        if in_str:
            in_str = ch != '"'
        elif ch == '"':
            in_str = True
        elif ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        j += 1
    return asm[i:j - 1]


def _split_params(sig):
    """Split a parameter list at top-level commas (commas inside
    ``<>``/``{}``/``()`` nests and quoted strings — sharding attrs —
    do not separate parameters)."""
    parts, cur = [], []
    depth, in_str = 0, False
    for ch in sig:
        if in_str:
            cur.append(ch)
            in_str = ch != '"'
            continue
        if ch == '"':
            in_str = True
        elif ch in "<{(":
            depth += 1
        elif ch in ">})":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        cur.append(ch)
    if "".join(cur).strip():
        parts.append("".join(cur))
    return parts


def _split_type(inner):
    """``"2x16x16xf32"`` -> ``(["2","16","16"], "f32")``."""
    m = re.match(r"^((?:[\d?]+x)*)(.+)$", inner)
    dims = [d for d in (m.group(1) or "").split("x") if d]
    return dims, m.group(2)


_PARAM_HEAD_RE = re.compile(r"%arg(\d+):\s*tensor<([^<>]*(?:<[^<>]*>)?)>")


def parse_main_params(asm):
    """``[(index, dims, elt, attrs)]`` for every ``@main`` parameter —
    ``attrs`` is the raw text after the type (attribute dict + loc)."""
    out = []
    for part in _split_params(_main_signature(asm)):
        m = _PARAM_HEAD_RE.search(part)
        if m is None:
            continue
        dims, elt = _split_type(m.group(2))
        out.append((int(m.group(1)), dims, elt, part[m.end():]))
    return out


def _scope_paths(asm):
    return set(re.findall(r'loc\("([^"]*)"', asm))


def _nbytes_of(tree):
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(getattr(x, "nbytes",
                           getattr(x, "size", 0) * 4) for x in leaves))


# -- audits ----------------------------------------------------------------

def audit_donation(name, asm, donatable_bytes):
    """Donation misses as wasted HBM bytes."""
    params = parse_main_params(asm)
    aliased = sum(tensor_nbytes(dims, elt)
                  for _, dims, elt, attrs in params
                  if "tf.aliasing_output" in attrs
                  or "jax.buffer_donor" in attrs)
    total_in = sum(tensor_nbytes(dims, elt)
                   for _, dims, elt, attrs in params)
    stats = {"donatable_bytes": int(donatable_bytes),
             "aliased_bytes": int(aliased),
             "input_bytes": int(total_in),
             "coverage_pct": (100.0 * aliased / donatable_bytes
                              if donatable_bytes else 100.0)}
    violations = []
    if donatable_bytes and aliased < donatable_bytes:
        wasted = int(donatable_bytes - aliased)
        stats["wasted_bytes"] = wasted
        violations.append(Violation(
            checker="donation", where=name,
            message=f"donation miss: {wasted:,} of "
                    f"{int(donatable_bytes):,} donatable input bytes "
                    "are not aliased into outputs — the step holds two "
                    "copies of that state in HBM; pass donate=True / "
                    "donate_argnums for the state argument",
            detail=stats))
    else:
        stats["wasted_bytes"] = 0
    return violations, stats


def audit_dtypes(name, asm, policy=None):
    """Element types present vs the per-kernel dtype policy."""
    policy = policy or POLICY_F32
    allow = set(policy["allow_floats"])
    found = {}
    for m in re.finditer(r"tensor<([^<>]*(?:<[^<>]*>)?)>", asm):
        _, elt = _split_type(m.group(1))
        found[elt] = found.get(elt, 0) + 1
    bad = {e: n for e, n in found.items()
           if e.startswith(("f", "bf", "complex")) and e not in allow}
    violations = []
    for elt, count in sorted(bad.items()):
        # name the first offending op's scope path so the upcast is
        # findable (debug-info lowering keeps loc() per line)
        site = next((ln for ln in asm.splitlines()
                     if f"x{elt}>" in ln or f"<{elt}>" in ln), "")
        loc = re.search(r'loc\("([^"]*)"', site)
        violations.append(Violation(
            checker="dtype", where=name,
            message=f"dtype policy {policy['name']!r} violated: "
                    f"{count} tensor(s) of {elt} in the step module"
                    + (f" (first at scope {loc.group(1)!r})"
                       if loc else ""),
            detail={"element_type": elt, "count": count,
                    "policy": policy["name"]}))
    return violations, {"policy": policy["name"],
                        "element_types": found,
                        "violating": sorted(bad)}


#: one HLO shape token: ``f32[2,16,16,16]{...}``
_HLO_SHAPE_TOKEN_RE = re.compile(r"(\w+)\[([\d,]*)\]")

#: collectives at or below this result size are scalar assembly (the
#: sentinel packing its reduced invariants into one health vector, a
#: replicated norm) — orders of magnitude under any lattice buffer, and
#: not what the audit hunts (an accidental all-gather of field data)
SMALL_COLLECTIVE_BYTES = 4096


def _shape_bytes(shape_text):
    """Total bytes of an HLO result shape — a single shape token or a
    tuple of them (XLA's collective combiner merges per-field ops into
    variadic collectives with tuple results; every element counts)."""
    total = None
    for m in _HLO_SHAPE_TOKEN_RE.finditer(shape_text):
        elt = m.group(1)
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total = (total or 0) + n * _ELT_BYTES.get(elt, 4)
    return total


def audit_collectives(name, hlo_text, allowlist,
                      small_bytes=SMALL_COLLECTIVE_BYTES):
    """Collectives in the compiled module vs the target allowlist.
    Ops moving at most ``small_bytes`` pass as scalar assembly either
    way (recorded in the stats, never a violation)."""
    seen, small = {}, {}
    # the result shape before the op name is either one token or a
    # space-containing tuple ``(f32[...], f32[...])`` — match both
    for m in re.finditer(
            r"=\s+(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVE_OPS)
            + r")(?:-start|-done)?\(", hlo_text):
        base = m.group(2)
        line = hlo_text[hlo_text.rfind("\n", 0, m.start()) + 1:
                        hlo_text.find("\n", m.end())]
        op_name = re.search(r'op_name="([^"]*)"', line)
        site = op_name.group(1) if op_name else "(no op_name metadata)"
        nbytes = _shape_bytes(m.group(1))
        if nbytes is not None and nbytes <= small_bytes:
            small.setdefault(base, []).append(site)
        else:
            seen.setdefault(base, []).append((site, nbytes))
    violations = []
    for base, sites in sorted(seen.items()):
        if base in allowlist:
            continue
        first_site, first_bytes = sites[0]
        size = (f", {first_bytes:,} B" if first_bytes else "")
        violations.append(Violation(
            checker="collectives", where=name,
            message=f"unexpected {base} in the compiled step module "
                    f"({len(sites)} occurrence(s); first from "
                    f"{first_site!r}{size}) — an unallowlisted "
                    "collective usually means a sharding constraint "
                    "forced a resharding mid-step",
            detail={"op": base, "count": len(sites),
                    "sites": [s for s, _ in sites[:8]]}))
    return violations, {
        "seen": {b: len(s) for b, s in seen.items()},
        "small": {b: len(s) for b, s in small.items()},
        "allowlist": dict(allowlist)}


def audit_host(name, asm, hlo_text):
    """Host-interaction markers in either IR."""
    found = sorted({marker for marker in _HOST_MARKERS
                    if marker in asm or marker in hlo_text})
    violations = [Violation(
        checker="host", where=name,
        message=f"host interaction on the step path: {marker} — "
                "infeed/outfeed/callbacks serialize the dispatch "
                "queue against the host",
        detail={"marker": marker}) for marker in found]
    return violations, {"markers": found}


def audit_fusion(name, asm, fused_scopes):
    """Required scope names all present in ONE lowered module."""
    paths = _scope_paths(asm)
    present = {s: any(s in p for p in paths) for s in fused_scopes}
    violations = []
    missing = [s for s, ok in present.items() if not ok]
    if missing:
        violations.append(Violation(
            checker="fusion", where=name,
            message="scopes expected INSIDE the step computation are "
                    f"missing from its lowered module: {missing} — the "
                    "work runs as a separate launch (extra dispatch "
                    "and, for reductions, an extra HBM pass)",
            detail={"missing": missing,
                    "present": sorted(s for s, ok in present.items()
                                      if ok)}))
    return violations, {"scopes": present}


# -- driver ----------------------------------------------------------------

def lower_and_compile(fn, args=(), kwargs=None):
    """``(stablehlo_asm_with_debug_info, compiled_hlo_text)`` for a
    jitted callable (or an already-``Lowered``) — the two artifacts
    every audit reads."""
    import warnings
    lowered = fn if hasattr(fn, "compiler_ir") else fn.lower(
        *args, **(kwargs or {}))
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    with warnings.catch_warnings():
        # CPU backends warn that donation is unimplemented; the audit
        # reads the platform-independent lowering attrs
        warnings.simplefilter("ignore")
        hlo_text = lowered.compile().as_text()
    return asm, hlo_text


class ArtifactCache:
    """Per-lint-run cache of built/lowered/compiled target artifacts.

    Each target's ``build()`` + ``lower()`` + ``compile()`` — by far
    the dominant lint cost — runs ONCE per run; the IR-tier audits and
    the dataflow tier (:mod:`pystella_tpu.lint.dataflow`) then share
    one ``{asm, hlo_text, donatable_bytes, build_s}`` record through
    :meth:`get`. Build failures are remembered too (``failed``), so a
    broken target is reported once and never rebuilt within a run.
    ``stats()`` — ``{"builds", "hits"}`` — lands in the report summary
    so the sharing is auditable.
    """

    def __init__(self):
        self._arts = {}
        self.failed = {}
        self.builds = 0
        self.hits = 0

    def get(self, target):
        """The artifact record for ``target`` (building on first use).
        Re-raises the remembered error for a target that already
        failed to build this run."""
        name = target.name
        if name in self._arts:
            self.hits += 1
            return self._arts[name]
        if name in self.failed:
            self.hits += 1
            raise RuntimeError(self.failed[name])
        t0 = time.perf_counter()
        try:
            fn, args, kwargs, donatable = target.build()
            asm, hlo_text = lower_and_compile(fn, args, kwargs)
        except Exception as e:  # noqa: BLE001 — remembered for the caller
            self.failed[name] = f"{type(e).__name__}: {e}"
            self.builds += 1
            raise
        self.builds += 1
        art = {"asm": asm, "hlo_text": hlo_text,
               "donatable_bytes": (None if donatable is None
                                   else _nbytes_of(donatable)),
               "build_s": round(time.perf_counter() - t0, 4)}
        self._arts[name] = art
        return art

    def stats(self):
        return {"builds": self.builds, "hits": self.hits}


def audit_artifacts(name, asm, hlo_text, donatable_bytes=None,
                    dtype_policy=None, collectives=None,
                    fused_scopes=(), timings=None):
    """Run every IR-tier audit over already-lowered artifacts; returns
    ``(violations, stats)``. This is also the entry point for drivers
    that audit the executable they are about to dispatch. ``timings``,
    when given a dict, is filled with per-audit wall seconds keyed by
    checker name."""
    violations = []
    stats = {"built": True}

    def run(label, fn, *a, **k):
        t0 = time.perf_counter()
        out = fn(*a, **k)
        if timings is not None:
            timings[label] = round(time.perf_counter() - t0, 4)
        return out

    if donatable_bytes is not None:
        v, stats["donation"] = run("donation", audit_donation,
                                   name, asm, donatable_bytes)
        violations += v
    v, stats["dtype"] = run("dtype", audit_dtypes, name, asm,
                            dtype_policy)
    violations += v
    v, stats["collectives"] = run("collectives", audit_collectives,
                                  name, hlo_text, collectives or {})
    violations += v
    v, stats["host"] = run("host", audit_host, name, asm, hlo_text)
    violations += v
    if fused_scopes:
        v, stats["fusion"] = run("fusion", audit_fusion, name, asm,
                                 fused_scopes)
        violations += v
    return violations, stats


def audit_target(target, cache=None):
    """Build, lower, compile and audit one target (through ``cache``
    when given — see :class:`ArtifactCache`); returns ``(violations,
    stats)``. Build/compile failures surface as an ``error`` violation
    rather than killing the whole lint run. ``stats["timing"]`` records
    the build and per-audit wall seconds."""
    if cache is None:
        cache = ArtifactCache()
    t_start = time.perf_counter()
    try:
        art = cache.get(target)
    except Exception as e:  # noqa: BLE001 — any build failure is a finding
        return [Violation(
            checker="graph-build", where=target.name,
            message=f"target failed to build/lower/compile: "
                    f"{type(e).__name__}: {e}")], {"built": False}
    timings = {}
    violations, stats = audit_artifacts(
        target.name, art["asm"], art["hlo_text"],
        donatable_bytes=art["donatable_bytes"],
        dtype_policy=target.dtype_policy,
        collectives=target.collectives,
        fused_scopes=target.fused_scopes,
        timings=timings)
    stats["timing"] = {
        "build_s": art["build_s"],
        "audits": timings,
        "total_s": round(time.perf_counter() - t_start, 4)}
    return violations, stats


def audit_targets(targets, cache=None):
    """Audit a list of targets; returns ``(violations, graph_stats,
    donation_summary)`` where ``donation_summary`` aggregates coverage
    across every target that declared donatable state. Pass a shared
    :class:`ArtifactCache` so a following dataflow tier reuses the
    same lowered/compiled modules."""
    violations = []
    graph = {}
    donatable = aliased = 0
    if cache is None:
        cache = ArtifactCache()
    for t in targets:
        v, stats = audit_target(t, cache=cache)
        violations += v
        graph[t.name] = stats
        don = stats.get("donation")
        if don:
            donatable += don["donatable_bytes"]
            aliased += min(don["aliased_bytes"], don["donatable_bytes"])
    summary = None
    if donatable:
        summary = {"donatable_bytes": donatable,
                   "aliased_bytes": aliased,
                   "coverage_pct": 100.0 * aliased / donatable,
                   "wasted_bytes": donatable - aliased}
    return violations, graph, summary
