"""Dataflow lint tier: precision-flow enforcement + a static comm model.

The IR tier (:mod:`pystella_tpu.lint.graph`) checks *set membership*:
which element types and which collective ops appear anywhere in a step
module. That is too coarse for the two properties the ROADMAP's
mixed-precision production tier actually needs:

**Precision-flow** (``audit_precision``). ``POLICY_BF16_ACC32`` ("bf16
fields, f32 accumulation") is a statement about *where* bf16 is allowed
to flow, not about whether it appears. This audit parses the lowered
StableHLO module (with debug locations) into a def-use graph and
propagates value roles from annotated roots:

- ``state`` — module parameters and everything derived pointwise from
  them (the lattice fields and their updates);
- ``carry`` — the result of a float narrowing performed under a
  registered carry scope (:data:`CARRY_SCOPES` — ``ops/fused.py``
  wraps its ``carry_dtype`` quantization in ``carry_quantize``);
- ``acc`` — the result of a reduction and everything downstream of it
  (an accumulation chain);
- ``scalar`` — constants/iota and values derived only from them.

Enforced flow rules (each violation names the originating ``op_name``
scope path from the debug locations):

1. a float narrowing to a sub-f32 type (``bf16``/``f16``/``f8*``) whose
   scope path passes through neither a registered carry scope nor a
   registered kernel-dispatch scope (:data:`KERNEL_SCOPES`) is an
   unsanctioned mid-chain precision loss. Interpret-mode Pallas
   lowering erases per-op name stacks inside a kernel body (every
   in-kernel op carries only the dispatch site's path), so in-kernel
   narrowing is attributed to the kernel-build funnel —
   ``ops/fused.py`` routes every carry narrowing through its
   ``_carry_cast`` helper — and rule 2 independently guarantees no
   narrow value is ever *computed with*;
2. any arithmetic op (add/multiply/…/reduce/dot) whose RESULT element
   type is sub-f32 runs math in narrow precision — bf16 is a storage
   format here, every computation and accumulation must be f32;
3. any value of sub-f32 float type whose propagated role is ``acc``
   continues an accumulation chain in narrow precision.

For ``POLICY_BF16_ACC32`` targets this *replaces* the allow-set check
(whose float allow-set is vacuous for bf16) with a strictly stronger
flow property; for f32/f64 targets the rules are vacuously green (no
sub-f32 narrowing exists in those modules).

**Static comm model** (``model_comm``). Every collective surviving SPMD
partitioning in the *compiled* HLO is attributed to its scope, its
per-invocation bytes computed from the result shape, and classified:

- ``halo`` — ``collective-permute`` (boundary-slab exchange);
- ``transpose`` — ``all-to-all`` (pencil-FFT axis transposes);
- ``reduction`` / ``scalar`` — ``all-reduce``/``reduce-scatter`` above
  or below :data:`~pystella_tpu.lint.graph.SMALL_COLLECTIVE_BYTES`;
- ``gather`` / ``replication`` — ``all-gather``/``collective-broadcast``;
  an op materializing at least *half a field's bytes* per invocation is
  classified ``replication`` and reported as an **error even when the
  base op is allowlisted** (generalizing the PR-5 sentinel all-gather
  find: an allowlist names ops, not sizes).

The per-target ``static_comm`` block lands in ``lint_report.json``; a
driver may hand the same block to the event log (a ``lint`` event),
:class:`~pystella_tpu.obs.ledger.PerfLedger` joins it
against measured ``halo_bytes_exchanged`` traffic into the report's
``comm`` section, and :mod:`pystella_tpu.obs.gate` fails evidence whose
measured traffic exceeds the model (lost overlap or a replication
regression in a shipped program).

Known approximation: MLIR SSA ids are scoped per region, so values
inside ``while``/``reduce`` body regions can shadow top-level ids in
the flat def-use map. Rules 1-2 are line-local and unaffected; rule 3's
propagation may conservatively widen a role across a shadowed id, which
can only make the audit stricter, never let a violation escape.
"""

from __future__ import annotations

import re

from pystella_tpu.lint.graph import (
    _COLLECTIVE_OPS, _split_type, SMALL_COLLECTIVE_BYTES,
    parse_main_params, tensor_nbytes,
)
from pystella_tpu.lint.report import Violation

__all__ = ["CARRY_SCOPES", "NARROW_FLOATS", "DATAFLOW_CHECKS",
           "parse_ops", "audit_precision", "model_comm",
           "audit_dataflow_artifacts", "audit_dataflow_targets"]

#: checker names this tier contributes to the report's ``checks`` list
DATAFLOW_CHECKS = ("precision-flow", "static-comm")

#: named scopes under which a float narrowing is sanctioned — the
#: ``carry_dtype`` quantization point ``ops/fused.py`` wraps every
#: carry downcast in. Extend via ``audit_precision(carry_scopes=...)``
#: when registering a new quantization point (doc/static_analysis.md).
CARRY_SCOPES = ("carry_quantize",)

#: kernel-dispatch scopes: inside these, interpret-mode Pallas lowering
#: erases per-op name stacks (every op carries the dispatch site's
#: path), so a narrowing cannot be pinned to a carry scope from the IR.
#: Narrowing here is sanctioned because the stencil build funnel
#: (``ops/fused.py _build_stencil``) routes every carry cast through
#: ``_carry_cast``, and rule 2 still rejects any narrow-typed
#: arithmetic the kernel might try. The binning kernel
#: (``ops/histogram.py``, ``pallas_bincount``) narrows only the operands
#: of a product it accumulates in f32: one-hot entries, exact in bf16,
#: and the three bf16 pieces of an f32 weight, together exact to its 24
#: bits — an operand format of the MXU, not a precision loss.
KERNEL_SCOPES = ("pallas_stencil", "pallas_resident_stencil",
                 "pallas_bincount")

#: sub-f32 float element types: legal as state/carry storage, never as
#: an accumulator
NARROW_FLOATS = ("bf16", "f16", "f8e4m3fn", "f8e5m2")

#: float widths for narrowing detection (a convert is a *downcast* when
#: the destination is strictly narrower)
_FLOAT_WIDTH = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
                "f8e4m3fn": 1, "f8e5m2": 1}

#: ops whose result is an accumulation (reduction roots of rule 2/3)
_REDUCE_OPS = ("stablehlo.reduce", "stablehlo.reduce_window",
               "stablehlo.dot_general", "stablehlo.convolution",
               "mhlo.reduce", "mhlo.dot_general")

#: arithmetic mnemonics (dialect-stripped): a narrow-float RESULT from
#: any of these means math ran in narrow precision (rule 2). Data
#: movement (slice/concat/broadcast/select/convert/while-carries) is
#: how bf16 storage legitimately flows and is NOT listed.
_ARITH_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "negate", "power",
    "remainder", "atan2", "sqrt", "rsqrt", "cbrt", "exponential",
    "exponential_minus_one", "log", "log_plus_one", "logistic",
    "tanh", "sine", "cosine", "tan", "expm1", "fma",
    "reduce", "reduce_window", "dot_general", "dot", "convolution",
))

#: ops whose result carries no lattice data (role ``scalar`` roots)
_SCALAR_OPS = ("stablehlo.constant", "stablehlo.iota",
               "mhlo.constant", "mhlo.iota")

_ROLE_RANK = {"acc": 3, "carry": 2, "state": 1, "scalar": 0}


# -- StableHLO parsing -----------------------------------------------------

#: a named debug-location alias: ``#loc17 = loc("jit(f)/.../mul"(#loc3))``
#: (the quoted name is the full transform/named-scope path). File
#: locations (``loc("file.py":1:2)``) and callsites don't match — they
#: carry no scope path.
_LOC_ALIAS_RE = re.compile(
    r'^#loc(\d+)\s*=\s*loc\("([^"]*)"(?:\(#loc\d+\))?\)\s*$', re.M)

#: one SSA op line: ``%4 = stablehlo.convert %3 : (...) -> ... loc(#loc9)``
_OP_LINE_RE = re.compile(
    r'^\s*%(?P<res>[A-Za-z0-9_$.-]+)(?::\d+)?\s*=\s*'
    r'"?(?P<op>[A-Za-z_][\w.]*)"?')

_TENSOR_RE = re.compile(r"tensor<([^<>]*(?:<[^<>]*>)?)>")
_OPERAND_RE = re.compile(r"%([A-Za-z0-9_$.-]+)")
_LOC_REF_RE = re.compile(r'loc\((?:#loc(\d+)|"([^"]*)")')


def _elt_of(type_text):
    """Element type of the FIRST tensor type in ``type_text`` (the
    result element type of the ``-> tensor<...>`` tail), or ``None``."""
    m = _TENSOR_RE.search(type_text)
    if m is None:
        return None
    _, elt = _split_type(m.group(1))
    return elt


def parse_ops(asm):
    """Flat def-use parse of a debug-info StableHLO module: a list of
    ``{result, op, operands, in_elts, out_elt, scope}`` dicts in
    program order. ``scope`` is the resolved named-location path
    (``""`` when the op carries only file/callsite locations)."""
    locs = {m.group(1): m.group(2) for m in _LOC_ALIAS_RE.finditer(asm)}
    ops = []
    for line in asm.splitlines():
        m = _OP_LINE_RE.match(line)
        if m is None:
            continue
        # scope: trailing loc(#locN) alias or inline loc("...")
        scope = ""
        lm = None
        for lm in _LOC_REF_RE.finditer(line):
            pass  # keep the LAST loc() on the line (op location)
        if lm is not None:
            scope = (locs.get(lm.group(1), "") if lm.group(1)
                     else lm.group(2) or "")
            if "/" not in scope:
                # a bare file path / param name is not a scope path
                scope = "" if "." in scope or " " in scope else scope
        # types: the segment after the last top-level " : " holds the
        # op's type signature — either "(in...) -> out" or one type
        body = line[m.end():]
        tsig = ""
        ci = body.rfind(" : ")
        if ci >= 0:
            tsig = body[ci + 3:]
            body = body[:ci]
        out_elt = None
        in_elts = []
        arrow = tsig.rfind("->")
        if arrow >= 0:
            out_elt = _elt_of(tsig[arrow + 2:])
            in_elts = [e for e in
                       (_elt_of("tensor<%s>" % t.group(1))
                        for t in _TENSOR_RE.finditer(tsig[:arrow]))
                       if e]
        else:
            out_elt = _elt_of(tsig)
            if out_elt:
                in_elts = [out_elt]
        operands = [o for o in _OPERAND_RE.findall(body)]
        ops.append({"result": m.group("res"), "op": m.group("op"),
                    "operands": operands, "in_elts": in_elts,
                    "out_elt": out_elt, "scope": scope})
    return ops


def _in_scopes(scope, names):
    """True when any ``/``-separated component of the scope path is one
    of ``names`` (tolerating jax's de-duplication suffixes)."""
    return any(comp == n or comp.startswith(n)
               for comp in scope.split("/") for n in names)


# -- precision flow --------------------------------------------------------

def audit_precision(name, asm, policy=None, carry_scopes=CARRY_SCOPES):
    """The three flow rules over one lowered module; returns
    ``(violations, stats)``. Runs for every dtype policy — sub-f32
    narrowing is only ever legal at a carry point, whatever the
    allow-set says."""
    ops = parse_ops(asm)
    policy_name = (policy or {}).get("name", "f32-strict")
    roles = {}
    for idx, _dims, _elt, _attrs in parse_main_params(asm):
        roles[f"arg{idx}"] = "state"
    violations = []
    counts = {"ops": len(ops), "converts": 0, "carry_converts": 0,
              "kernel_converts": 0, "reduces": 0, "narrow_values": 0}
    roles_count = {"state": 0, "carry": 0, "acc": 0, "scalar": 0}
    for op in ops:
        mnemonic, out_elt, scope = op["op"], op["out_elt"], op["scope"]
        short = mnemonic.rsplit(".", 1)[-1]
        narrow_out = out_elt in NARROW_FLOATS
        if narrow_out:
            counts["narrow_values"] += 1
        # role of this op's result
        if mnemonic in _SCALAR_OPS:
            role = "scalar"
        elif mnemonic in _REDUCE_OPS:
            counts["reduces"] += 1
            role = "acc"
        else:
            role = None
            for o in op["operands"]:
                r = roles.get(o.split("#")[0])
                if r and (role is None
                          or _ROLE_RANK[r] > _ROLE_RANK[role]):
                    role = r
            role = role or "state"
        if mnemonic.endswith(".convert"):
            counts["converts"] += 1
            src = op["in_elts"][0] if op["in_elts"] else None
            src_w = _FLOAT_WIDTH.get(src)
            dst_w = _FLOAT_WIDTH.get(out_elt)
            if (narrow_out and src_w is not None and dst_w is not None
                    and dst_w < src_w):
                # rule 1: narrowing only at a registered carry point
                # (or inside a registered kernel dispatch, where
                # per-op scopes are erased — see KERNEL_SCOPES)
                if _in_scopes(scope, carry_scopes):
                    counts["carry_converts"] += 1
                    role = "carry"
                elif _in_scopes(scope, KERNEL_SCOPES):
                    counts["kernel_converts"] += 1
                    role = "carry"
                else:
                    violations.append(Violation(
                        checker="precision-flow", where=name,
                        message=f"{src}->{out_elt} downcast outside a "
                                "registered carry point at scope "
                                f"{scope or '(no scope path)'!r} — a "
                                "mid-chain precision loss; sanctioned "
                                "carry quantization must run under one "
                                f"of {list(carry_scopes)} "
                                "(ops/fused.py CARRY_SCOPE)",
                        detail={"op": mnemonic, "from": src,
                                "to": out_elt, "scope": scope,
                                "policy": policy_name}))
        if narrow_out and short in _ARITH_OPS:
            # rule 2: math in narrow precision (covers reductions —
            # the accumulator type IS the result type)
            what = ("accumulation" if mnemonic in _REDUCE_OPS
                    else "arithmetic")
            violations.append(Violation(
                checker="precision-flow", where=name,
                message=f"{what} in {out_elt} ({short}) at scope "
                        f"{scope or '(no scope path)'!r} — bf16 is a "
                        "storage format under POLICY_BF16_ACC32; "
                        "every computation and accumulation chain "
                        "must run in f32 (widen the operands before "
                        "computing)",
                detail={"op": mnemonic, "element_type": out_elt,
                        "scope": scope, "policy": policy_name}))
        elif narrow_out and role == "acc":
            # rule 3: a narrow value continuing an accumulation chain
            violations.append(Violation(
                checker="precision-flow", where=name,
                message=f"accumulation chain continues in {out_elt} "
                        f"({mnemonic}) at scope "
                        f"{scope or '(no scope path)'!r} — values "
                        "downstream of a reduction must stay f32 "
                        "until a registered carry point",
                detail={"op": mnemonic, "element_type": out_elt,
                        "scope": scope, "role": role,
                        "policy": policy_name}))
        roles[op["result"]] = role
        roles_count[role] += 1
    stats = dict(counts)
    stats["policy"] = policy_name
    stats["roles"] = roles_count
    stats["carry_scopes"] = list(carry_scopes)
    stats["ok"] = not violations
    return violations, stats


# -- static comm model -----------------------------------------------------

#: one compiled-HLO collective, counted ONCE per op (async collectives
#: appear as ``-start``/``-done`` pairs; only the start carries the work)
_HLO_COLLECTIVE_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVE_OPS)
    + r")(-start|-done)?\(")


def _classify(base, nbytes, small_bytes, repl_threshold):
    if base == "collective-permute":
        return "halo"
    if base == "all-to-all":
        return "transpose"
    small = nbytes is not None and nbytes <= small_bytes
    if base in ("all-reduce", "reduce-scatter"):
        return "scalar" if small else "reduction"
    # all-gather / collective-broadcast
    if small:
        return "scalar"
    if (repl_threshold and nbytes is not None
            and nbytes >= repl_threshold):
        return "replication"
    return "gather"


def model_comm(name, asm, hlo_text, small_bytes=SMALL_COLLECTIVE_BYTES):
    """The static communication model of one compiled module; returns
    ``(violations, static_comm_block)``. Bytes are per single
    invocation of the program, per participating device (HLO shapes
    are post-SPMD). Field size — the replication yardstick — is the
    largest ``@main`` parameter of the pre-partition StableHLO."""
    from pystella_tpu.lint.graph import _shape_bytes
    field_bytes = 0
    for _idx, dims, elt, _attrs in parse_main_params(asm):
        field_bytes = max(field_bytes, tensor_nbytes(dims, elt))
    repl_threshold = field_bytes // 2 if field_bytes else None
    entries = {}
    for m in _HLO_COLLECTIVE_RE.finditer(hlo_text):
        if m.group(3) == "-done":
            continue  # the paired -start already carried the bytes
        base = m.group(2)
        nbytes = _shape_bytes(m.group(1))
        line = hlo_text[hlo_text.rfind("\n", 0, m.start()) + 1:
                        hlo_text.find("\n", m.end())]
        op_name = re.search(r'op_name="([^"]*)"', line)
        scope = op_name.group(1) if op_name else "(no op_name metadata)"
        cls = _classify(base, nbytes, small_bytes, repl_threshold)
        e = entries.setdefault((base, cls), {
            "op": base, "class": cls, "count": 0, "bytes": 0,
            "scopes": []})
        e["count"] += 1
        e["bytes"] += int(nbytes or 0)
        if scope not in e["scopes"] and len(e["scopes"]) < 8:
            e["scopes"].append(scope)
    per_class = {}
    for e in entries.values():
        per_class[e["class"]] = per_class.get(e["class"], 0) + e["bytes"]
    violations = []
    for (base, cls), e in sorted(entries.items()):
        if cls != "replication":
            continue
        violations.append(Violation(
            checker="static-comm", where=name,
            message=f"field-sized {base} in the compiled module: "
                    f"{e['bytes']:,} B across {e['count']} "
                    f"occurrence(s), first from {e['scopes'][0]!r} — "
                    "a collective materializing >= half a field "
                    f"({repl_threshold:,} B) per invocation is "
                    "accidental replication, whatever the allowlist "
                    "says; fix the sharding constraint or shrink the "
                    "gathered operand",
            detail={"op": base, "bytes": e["bytes"],
                    "count": e["count"], "scopes": e["scopes"],
                    "replication_threshold": repl_threshold}))
    block = {
        "modeled": True,
        "field_bytes": int(field_bytes),
        "small_bytes": int(small_bytes),
        "replication_threshold": (int(repl_threshold)
                                  if repl_threshold else None),
        "per_invocation_bytes": per_class,
        "total_bytes": int(sum(per_class.values())),
        "collectives": sorted(entries.values(),
                              key=lambda e: (-e["bytes"], e["op"])),
    }
    return violations, block


# -- tier driver -----------------------------------------------------------

def audit_dataflow_artifacts(name, asm, hlo_text, dtype_policy=None,
                             carry_scopes=CARRY_SCOPES, timings=None):
    """Both dataflow audits over already-lowered artifacts; returns
    ``(violations, stats)`` with ``precision`` and ``static_comm``
    blocks. The entry point for drivers auditing the executable they
    are about to dispatch."""
    import time as _time
    violations, stats = [], {}
    t0 = _time.perf_counter()
    v, stats["precision"] = audit_precision(
        name, asm, policy=dtype_policy, carry_scopes=carry_scopes)
    violations += v
    t1 = _time.perf_counter()
    v, stats["static_comm"] = model_comm(name, asm, hlo_text)
    violations += v
    if timings is not None:
        timings["precision-flow"] = round(t1 - t0, 4)
        timings["static-comm"] = round(_time.perf_counter() - t1, 4)
    return violations, stats


def audit_dataflow_targets(targets, cache=None):
    """Run the dataflow tier over a target list through a shared
    :class:`~pystella_tpu.lint.graph.ArtifactCache`; returns
    ``(violations, per_target_stats)``. A target the IR tier already
    failed to build is skipped silently (the cache remembers the
    failure; the ``graph-build`` violation is not duplicated)."""
    from pystella_tpu.lint.graph import ArtifactCache
    if cache is None:
        cache = ArtifactCache()
    violations, per_target = [], {}
    for t in targets:
        fresh = t.name not in cache.failed
        try:
            art = cache.get(t)
        except Exception as e:  # noqa: BLE001 — any failure is a finding
            if fresh:
                violations.append(Violation(
                    checker="graph-build", where=t.name,
                    message=f"target failed to build/lower/compile: "
                            f"{type(e).__name__}: {e}"))
            per_target[t.name] = {"built": False}
            continue
        timings = {}
        v, stats = audit_dataflow_artifacts(
            t.name, art["asm"], art["hlo_text"],
            dtype_policy=t.dtype_policy, timings=timings)
        violations += v
        stats["timing_audits"] = timings
        per_target[t.name] = stats
    return violations, per_target
