"""The package's imports point one way: down the layer diagram in
``README.md`` ("Layers"). One case a module file; every ``import`` /
``from`` statement in it, at any depth (a lazy import inside a function
is still an arrow), must name a module of its own layer or a lower one.
The diagram is the table: change the layers there, not here."""

import ast
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "pystella_tpu"


def _modules():
    """``{dotted name relative to the package: path}``; the package's own
    ``__init__.py`` is ``"__init__"``, a subpackage's is the
    subpackage's name."""
    mods = {}
    for d, _, files in os.walk(os.path.join(ROOT, PKG)):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(d, f)
            name = os.path.relpath(path, os.path.join(ROOT, PKG))[:-3]
            name = name.replace(os.sep, ".")
            if name.endswith(".__init__"):
                name = name[:-len(".__init__")]
            mods[name] = path
    return mods


def _layers():
    """``{module prefix: layer number}`` from the README's diagram."""
    text = open(os.path.join(ROOT, "README.md")).read()
    block = text.split("<!-- layers:", 1)[1].split("```")[1]
    table = {}
    for line in block.splitlines():
        m = re.match(r"(\d+) (\S+(?: \S+)*)  +(\S.*)$", line)
        if m:
            for prefix in m.group(3).split():
                table[prefix] = int(m.group(1))
    return table


MODULES = _modules()
LAYERS = _layers()


def _longest_prefix(name, table):
    """The longest dotted prefix of ``name`` that ``table`` holds."""
    parts = name.split(".")
    while parts and ".".join(parts) not in table:
        parts.pop()
    return ".".join(parts) or None


def _layer(name):
    prefix = _longest_prefix(name, LAYERS)
    assert prefix, (f"README.md's layer diagram places no prefix of "
                    f"{PKG}.{name}")
    return LAYERS[prefix]


def _resolve(dotted):
    """The module file a dotted name under the package lands in:
    ``pystella_tpu.obs.events.emit`` -> ``obs.events``."""
    return _longest_prefix(dotted.partition(".")[2], MODULES) or "__init__"


def _imports(name):
    """``[(line, target module)]`` for every in-package import of
    module ``name``."""
    path = MODULES[name]
    is_pkg = os.path.basename(path) == "__init__.py"
    here = [PKG] + ([] if name == "__init__" else name.split("."))
    if not is_pkg:
        here = here[:-1]
    out = []
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            out += [(node.lineno, _resolve(a.name)) for a in node.names
                    if a.name.split(".")[0] == PKG]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = here[:len(here) - (node.level - 1)]
                source = ".".join(base + ([node.module]
                                          if node.module else []))
            else:
                source = node.module or ""
            if source.split(".")[0] != PKG:
                continue
            # ``from pystella_tpu.obs import events`` names the module
            # obs.events, not the facade obs
            out += [(node.lineno, _resolve(f"{source}.{a.name}"))
                    for a in node.names]
    return [(line, target) for line, target in out if target != name]


def test_diagram_has_at_most_eight_boxes_and_places_every_module():
    assert 1 <= len(set(LAYERS.values())) <= 8
    for name in MODULES:
        _layer(name)
    for prefix in LAYERS:
        assert prefix in MODULES, f"the diagram names {prefix}: no such file"


@pytest.mark.parametrize("module", sorted(MODULES))
def test_imports_point_down(module):
    mine = _layer(module)
    rel = os.path.relpath(MODULES[module], ROOT)
    upward = [f"{rel}:{line} imports {PKG}.{target} "
              f"(layer {_layer(target)} > {mine})"
              for line, target in _imports(module)
              if _layer(target) > mine]
    assert not upward, "\n".join(upward)


def test_package_import_loads_no_service():
    """``import pystella_tpu`` is every cell's set-up: it loads the
    engine and its runtimes, and nothing named ``service``."""
    code = ("import sys, pystella_tpu; "
            "print([m for m in sys.modules "
            "if m.split('.')[:2] == ['pystella_tpu', 'service']])")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
    assert not hasattr(__import__(PKG), "service")
