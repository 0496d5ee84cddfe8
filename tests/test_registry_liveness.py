"""Every registered name is alive: a knob of ``config.py`` has a reader
and an event kind of ``obs/events.py`` has an emitter, in the program
(``pystella_tpu/``, ``examples/``, ``benchmark/``, ``chip_smoke.py``;
never ``tests/``). The source lint holds the other direction (a read or
an ``emit`` of an unregistered name fails); this holds that a name
whose last user went goes with it."""

import os
import re

import jax
import pytest

from pystella_tpu import config
from pystella_tpu.obs import events

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: kinds the ledger reads and nothing in the program emits: what a
#: driver may hand the ledger (debt 4b of ROADMAP.md's design queue:
#: gate + ledger; they go, or get their emitter, with it)
READER_ONLY_KINDS = ("halo_traffic", "fft_spectra", "lint")


def _sources():
    paths = [os.path.join(ROOT, "chip_smoke.py")]
    for top in ("pystella_tpu", "examples", "benchmark"):
        for d, _, files in os.walk(os.path.join(ROOT, top)):
            paths += [os.path.join(d, f) for f in files
                      if f.endswith(".py")]
    return {os.path.relpath(p, ROOT): open(p).read() for p in paths}


SOURCES = _sources()
REGISTRIES = ("pystella_tpu/config.py", "pystella_tpu/obs/events.py")


def _found(pattern, skip):
    rx = re.compile(pattern)
    return [path for path, text in SOURCES.items()
            if path != skip and rx.search(text)]


def _quoted(name):
    return rf"""["']{re.escape(name)}["']"""


def _emitters(kind):
    """Files with an ``emit("<kind>"`` (or the retrier's ``_emit``)."""
    return _found(rf"\b_?emit\(\s*{_quoted(kind)}", skip=REGISTRIES[1])


#: knobs jax itself reads as a config option of the same name: ours
#: only to document and to pin in the fingerprint
JAX_OWNED = ("JAX_ENABLE_X64",)

#: ``obs.memory.flags_fingerprint`` reads the variables of this loop
FINGERPRINT_LOOP = re.compile(
    r"for var in \(([^)]*)\):\s*for tok in env\.get\(var\b")


@pytest.mark.parametrize("name", sorted(config.registered()))
def test_knob_has_a_reader(name):
    if name in JAX_OWNED:
        assert hasattr(jax.config, name.lower()), name
        return
    quoted = _quoted(name)
    # config.getenv / get_int / get_float / get_bool, or a mapping's
    # .get (os.environ, a caller's env); obs/events.py reads its two
    # directly and says so in a pragma the lint checks
    readers = _found(rf"\bget(?:env|_int|_float|_bool)?\(\s*{quoted}",
                     skip=REGISTRIES[0])
    readers += _found(rf"# env-registry:[^\n]*\b{re.escape(name)}\b",
                      skip=REGISTRIES[0])
    looped = FINGERPRINT_LOOP.search(SOURCES["pystella_tpu/obs/memory.py"])
    if re.search(quoted, looped.group(1)):
        readers.append("pystella_tpu/obs/memory.py")
    assert readers, (f"{name} is registered in config.py and nothing "
                     "reads it: delete the registration")


@pytest.mark.parametrize("kind", sorted(events.registered_event_kinds()))
def test_event_kind_has_an_emitter(kind):
    if kind in READER_ONLY_KINDS:
        # the debt's case: the ledger's reader is there, and a kind
        # that gets its emitter leaves the tuple
        assert _found(rf"kind == {_quoted(kind)}", skip=REGISTRIES[1]), kind
        assert not _emitters(kind), kind
        return
    assert _emitters(kind), (f"{kind} is registered in obs/events.py and "
                             "nothing emits it: delete the registration")
