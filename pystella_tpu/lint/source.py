"""Source-tier lint: AST audits over a package directory.

Three checkers, all purely static (``ast`` over the files — nothing is
imported from the linted package, so a seeded-violation fixture package
need not even be importable):

- ``host-sync`` — forbidden host-synchronizing calls. In *hot-path
  modules* (the files whose function bodies get traced into the step
  computations: :data:`HOT_MODULES`, plus any file carrying a
  ``# lint: hot-path`` marker) the true syncs ``.item()``,
  ``.block_until_ready()``, ``jax.block_until_ready(...)`` and
  ``jax.device_get(...)`` are banned outright. Additionally, in ANY
  module, the host-materializing calls ``float(...)``, ``int(...)``,
  ``np.asarray(...)`` and ``np.array(...)`` are banned *lexically
  inside a ``with trace_scope(...)`` / ``named_scope(...)`` block* —
  those blocks are exactly the registered traced hot regions, where a
  host conversion either breaks the trace or forces a device round
  trip.
- ``env-registry`` — every ``os.environ`` / ``os.getenv`` read of a
  project-prefixed (``PYSTELLA_*``) variable outside
  ``config.py`` must carry an ``# env-registry: NAME`` pragma naming a
  variable registered in :mod:`pystella_tpu.config` (the escape hatch
  for stdlib-only modules that stay loadable by file); reads through
  :func:`pystella_tpu.config.getenv` are the normal path and are not
  flagged. Non-literal variable names need the pragma too. The
  registry is recovered *statically* (AST over ``config.py``), so this
  checker works on any package layout.
- ``scope-registry`` — every literal scope name passed to
  ``trace_scope`` / ``named_scope`` / ``host_span`` must be registered in
  :func:`pystella_tpu.obs.scope.registered_scopes` (f-string literals
  normalize by dropping the interpolated parts, matching the trace
  parser's fold rule). This absorbs the grep that used to live in
  ``tests/test_scope_registry.py``.
- ``event-registry`` — every literal event kind passed to an
  ``emit(...)`` call must be registered in
  :func:`pystella_tpu.obs.events.registered_event_kinds` (same pattern
  as the scope registry): the ledger's kind vocabulary cannot silently
  drift from the emit sites.

Plus a doc-coverage check when linting the real package:

- ``env-doc`` — every variable registered in ``config.py`` must appear
  in the "Environment variables" table of ``doc/observability.md``.

A finding can be locally waived with a trailing ``# lint: allow(<checker>)``
comment on (or one line above) the offending statement.
"""

from __future__ import annotations

import ast
import os
import re

from pystella_tpu.lint.report import Violation

__all__ = ["HOT_MODULES", "check_package", "registered_env_vars"]

#: package-relative paths of the modules whose function bodies are
#: traced into the compiled step computations — the host-sync audit's
#: strict set. A module outside this list opts in with a
#: ``# lint: hot-path`` comment anywhere in the file.
HOT_MODULES = (
    "step.py",
    "ops/elementwise.py",
    "ops/derivs.py",
    "ops/fused.py",
    "ops/pallas_stencil.py",
    "multigrid/relax.py",
)

#: ``jax.<fn>`` host syncs banned anywhere in a hot module (alongside
#: the ``.item()`` / ``.block_until_ready()`` method forms)
_SYNC_JAX_FNS = ("block_until_ready", "device_get")
#: host materializers banned inside trace-scope blocks (any module)
_HOST_BUILTINS = ("float", "int")
_HOST_NP_FNS = ("asarray", "array")

#: the first two open a traced region (host materializers inside are
#: flagged); a ``host_span`` names run-time host work, fetches included,
#: so it only has to be registered
_SCOPE_FNS = ("trace_scope", "named_scope", "host_span")

_HOT_MARKER = re.compile(r"#\s*lint:\s*hot-path")
_ALLOW_PRAGMA = re.compile(r"#\s*lint:\s*allow\(([\w., -]+)\)")
_ENV_PRAGMA = re.compile(r"#\s*env-registry:\s*([\w, ]+)")

_PROJECT_PREFIXES = ("PYSTELLA_",)


def iter_py_files(pkg_dir):
    for dirpath, dirnames, files in os.walk(pkg_dir):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in sorted(files):
            if fname.endswith(".py"):
                yield os.path.join(dirpath, fname)


def _call_name(node):
    """``("jax", "device_get")`` for ``jax.device_get(...)``,
    ``(None, "float")`` for ``float(...)`` — (base, attr) of a Call's
    func, or ``(None, None)`` when it is something more exotic."""
    fn = node.func
    if isinstance(fn, ast.Name):
        return None, fn.id
    if isinstance(fn, ast.Attribute):
        base = fn.value.id if isinstance(fn.value, ast.Name) else None
        return base, fn.attr
    return None, None


def _pragmas(src):
    """Per-line pragma maps: ``(allow, env_names)`` where ``allow`` maps
    lineno -> set of waived checker names and ``env_names`` maps
    lineno -> set of declared registered env-var names."""
    allow, env_names = {}, {}
    for i, line in enumerate(src.splitlines(), start=1):
        m = _ALLOW_PRAGMA.search(line)
        if m:
            allow[i] = {tok.strip() for tok in m.group(1).split(",")}
        m = _ENV_PRAGMA.search(line)
        if m:
            env_names[i] = {tok.strip() for tok in m.group(1).split(",")
                            if tok.strip()}
    return allow, env_names


def _pragma_hits(per_line, node):
    """Union of pragma entries in the node's line window (one line above
    through its last line — multi-line calls carry the pragma on any of
    their lines)."""
    out = set()
    end = getattr(node, "end_lineno", node.lineno) or node.lineno
    for ln in range(node.lineno - 1, end + 1):
        out |= per_line.get(ln, set())
    return out


def _literal_str(node):
    """The string a Constant-or-f-string argument denotes, with
    f-string interpolations dropped (``f"rk_stage{s}"`` -> ``rk_stage``,
    the trace parser's fold rule); ``None`` for non-literals."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        return "".join(v.value for v in node.values
                       if isinstance(v, ast.Constant)
                       and isinstance(v.value, str))
    return None


def registered_env_vars(config_path):
    """The env-var names registered in ``config.py``, recovered
    statically (every literal first argument of a ``register(...)``
    call)."""
    with open(config_path) as f:
        tree = ast.parse(f.read(), filename=config_path)
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            _, attr = _call_name(node)
            if attr == "register" and node.args:
                lit = _literal_str(node.args[0])
                if lit:
                    names.add(lit)
    return names


class _FileChecker(ast.NodeVisitor):
    def __init__(self, path, rel, src, hot, env_registry):
        self.path, self.rel, self.hot = path, rel, hot
        self.env_registry = env_registry
        self.allow, self.env_names = _pragmas(src)
        self.scope_depth = 0        # inside a trace_scope/named_scope with
        self.violations = []
        self.scope_literals = {}    # name -> [lineno, ...]
        self.emit_literals = {}     # event kind -> [lineno, ...]
        self.is_config = os.path.basename(rel) == "config.py"

    # -- helpers -----------------------------------------------------------

    def _flag(self, checker, node, message, **detail):
        if checker in _pragma_hits(self.allow, node):
            return
        self.violations.append(Violation(
            checker=checker, message=message,
            where=f"{self.rel}:{node.lineno}",
            detail={"file": self.rel, "line": node.lineno, **detail}))

    # -- visitors ----------------------------------------------------------

    def visit_With(self, node):
        opens_scope = any(
            isinstance(item.context_expr, ast.Call)
            and _call_name(item.context_expr)[1] in _SCOPE_FNS[:2]
            for item in node.items)
        for item in node.items:
            self.visit(item.context_expr)
        if opens_scope:
            self.scope_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        if opens_scope:
            self.scope_depth -= 1

    visit_AsyncWith = visit_With

    def visit_Call(self, node):
        base, attr = _call_name(node)

        # scope-registry: literal names handed to trace_scope/named_scope/
        # host_span
        if attr in _SCOPE_FNS and node.args:
            lit = _literal_str(node.args[0])
            if lit is not None:
                self.scope_literals.setdefault(lit, []).append(node.lineno)

        # event-registry: literal kinds handed to any emit(...) call
        # (obs.events.emit, EventLog.emit, a `log`/`sink` variable —
        # the method NAME is the contract; non-literal first args are
        # simply not kinds). A kind= keyword literal counts the same,
        # and so does the private _emit(kind, ...) wrapper
        # (resilience.retry), which would otherwise drift past the
        # registry silently. The keyword check is scoped to emit calls
        # on purpose: kind= elsewhere means something else entirely
        # (config.register's value type).
        if attr in ("emit", "_emit"):
            if node.args:
                lit = _literal_str(node.args[0])
                if lit is not None:
                    self.emit_literals.setdefault(
                        lit, []).append(node.lineno)
            for kw in node.keywords:
                if kw.arg == "kind":
                    lit = _literal_str(kw.value)
                    if lit is not None:
                        self.emit_literals.setdefault(
                            lit, []).append(node.lineno)

        # host-sync, strict set: anywhere in a hot module
        if self.hot and isinstance(node.func, ast.Attribute):
            if attr == "item" and not node.args:
                self._flag("host-sync", node,
                           ".item() forces a device->host sync on the "
                           "traced hot path")
            elif attr == "block_until_ready" and base != "jax":
                self._flag("host-sync", node,
                           ".block_until_ready() blocks the dispatch "
                           "queue on the traced hot path")
            elif base == "jax" and attr in _SYNC_JAX_FNS:
                self._flag("host-sync", node,
                           f"jax.{attr}() syncs device->host on the "
                           "traced hot path")

        # host-sync, scope-block set: host materializers inside a traced
        # region (any module)
        if self.scope_depth > 0:
            if base is None and attr in _HOST_BUILTINS \
                    and isinstance(node.func, ast.Name):
                self._flag("host-sync", node,
                           f"{attr}() inside a trace_scope block "
                           "materializes a device value on host")
            elif base in ("np", "numpy") and attr in _HOST_NP_FNS:
                self._flag("host-sync", node,
                           f"{base}.{attr}() inside a trace_scope block "
                           "pulls the array to host")

        # env-registry: os.environ reads outside config.py
        if not self.is_config:
            env_read = None
            if base == "os" and attr == "getenv" and node.args:
                env_read = node.args[0]
            elif attr == "get" and isinstance(node.func, ast.Attribute) \
                    and self._is_os_environ(node.func.value) and node.args:
                env_read = node.args[0]
            if env_read is not None:
                self._check_env_read(node, env_read)

        self.generic_visit(node)

    def visit_Subscript(self, node):
        if not self.is_config and isinstance(node.ctx, ast.Load) \
                and self._is_os_environ(node.value):
            self._check_env_read(node, node.slice)
        self.generic_visit(node)

    @staticmethod
    def _is_os_environ(node):
        return (isinstance(node, ast.Attribute) and node.attr == "environ"
                and isinstance(node.value, ast.Name)
                and node.value.id == "os")

    def _check_env_read(self, node, name_node):
        name = _literal_str(name_node)
        if name is not None and not name.startswith(_PROJECT_PREFIXES):
            return  # external variables (XLA_FLAGS, ...) are not gated
        declared = _pragma_hits(self.env_names, node)
        if name is not None and name not in self.env_registry:
            self._flag("env-registry", node,
                       f"env var {name!r} is not registered in "
                       "pystella_tpu/config.py — declare it there "
                       "(default + description) first",
                       var=name)
        elif not declared:
            what = repr(name) if name is not None else "a non-literal name"
            self._flag("env-registry", node,
                       f"direct os.environ read of {what} outside "
                       "config.py: read it through "
                       "pystella_tpu.config.getenv, or mark a by-file-"
                       "loadable module's read with '# env-registry: "
                       "NAME'", var=name)
        else:
            undeclared = declared - self.env_registry
            if undeclared:
                self._flag("env-registry", node,
                           "pragma names unregistered env var(s) "
                           f"{sorted(undeclared)}", var=name)


def check_package(pkg_dir, config_path=None, doc_path=None,
                  registered_scopes=None, registered_event_kinds=None,
                  checks=None):
    """Run the source tier over ``pkg_dir``.

    :arg config_path: the registry module to recover env-var names from
        (default: ``<pkg_dir>/config.py``; env reads become violations
        when the file is absent and a project-prefixed read appears).
    :arg doc_path: when given and the file exists, run the ``env-doc``
        coverage check against its "Environment variables" table.
    :arg registered_scopes: the scope-name vocabulary for the
        ``scope-registry`` check; default imports
        :func:`pystella_tpu.obs.scope.registered_scopes`. Pass an empty
        set to skip literal checking on fixture packages.
    :arg registered_event_kinds: the event-kind vocabulary for the
        ``event-registry`` check; default imports
        :func:`pystella_tpu.obs.events.registered_event_kinds`. Same
        fixture escape hatch as ``registered_scopes``.
    :arg checks: iterable restricting which checkers run.
    :returns: ``(violations, stats)`` where ``stats`` carries
        ``files_scanned`` and the collected ``scope_literals`` /
        ``emit_literals`` maps.
    """
    pkg_dir = os.path.abspath(pkg_dir)
    if config_path is None:
        candidate = os.path.join(pkg_dir, "config.py")
        config_path = candidate if os.path.exists(candidate) else None
    env_registry = (registered_env_vars(config_path)
                    if config_path else set())
    enabled = set(checks) if checks is not None else {
        "host-sync", "env-registry", "scope-registry",
        "event-registry", "env-doc"}

    violations = []
    scope_literals = {}
    emit_literals = {}
    nfiles = 0
    for path in iter_py_files(pkg_dir):
        rel = os.path.relpath(path, pkg_dir)
        with open(path) as f:
            src = f.read()
        nfiles += 1
        hot = rel.replace(os.sep, "/") in HOT_MODULES \
            or bool(_HOT_MARKER.search(src))
        checker = _FileChecker(path, rel, src, hot, env_registry)
        checker.visit(ast.parse(src, filename=path))
        violations.extend(
            v for v in checker.violations if v.checker in enabled)
        for name, linenos in checker.scope_literals.items():
            scope_literals.setdefault(name, []).extend(
                f"{rel}:{ln}" for ln in linenos)
        for name, linenos in checker.emit_literals.items():
            emit_literals.setdefault(name, []).extend(
                f"{rel}:{ln}" for ln in linenos)

    if "event-registry" in enabled and emit_literals:
        if registered_event_kinds is None:
            from pystella_tpu.obs.events import (
                registered_event_kinds as _rk)
            registered_event_kinds = _rk()
        for name in sorted(emit_literals):
            if name not in registered_event_kinds:
                violations.append(Violation(
                    checker="event-registry",
                    message=f"event kind {name!r} is not registered: "
                            "add a register_event_kind() entry in "
                            "pystella_tpu/obs/events.py so the span "
                            "assembler and ledger keep a complete kind "
                            "vocabulary",
                    where=emit_literals[name][0],
                    detail={"kind": name,
                            "sites": emit_literals[name]}))

    if "scope-registry" in enabled and scope_literals:
        if registered_scopes is None:
            from pystella_tpu.obs.scope import registered_scopes as _rs
            registered_scopes = _rs()
        for name in sorted(scope_literals):
            if name not in registered_scopes:
                where = scope_literals[name][0]
                violations.append(Violation(
                    checker="scope-registry",
                    message=f"trace scope {name!r} is not registered: "
                            "add a register_scope() entry in "
                            "pystella_tpu/obs/scope.py so the Perfetto "
                            "parser and ledger tables keep seeing it",
                    where=where,
                    detail={"scope": name,
                            "sites": scope_literals[name]}))

    if "env-doc" in enabled and doc_path and os.path.exists(doc_path) \
            and env_registry:
        with open(doc_path) as f:
            doc = f.read()
        for name in sorted(env_registry):
            if not re.search(rf"`{re.escape(name)}`", doc):
                violations.append(Violation(
                    checker="env-doc",
                    message=f"registered env var {name} is missing from "
                            f"the environment-variable table in "
                            f"{os.path.basename(doc_path)}",
                    where=os.path.basename(doc_path),
                    detail={"var": name}))

    stats = {"package": pkg_dir, "files_scanned": nfiles,
             "scope_literals": scope_literals,
             "emit_literals": emit_literals,
             "env_registry": sorted(env_registry)}
    return violations, stats
