"""Tier-1 guard for the central scope-name registry (obs.scope): every
``trace_scope(...)`` / ``named_scope(...)`` literal in ``pystella_tpu/``
must be registered, so a renamed hot-path scope cannot silently vanish
from the Perfetto parser's vocabulary and the ledger's per-scope
tables — the rename either updates the registry or fails here.

The grep that used to live in this file is now the source-tier lint's
``scope-registry`` checker (:mod:`pystella_tpu.lint.source`), shared
with ``python -m pystella_tpu.lint`` and the smoke run's in-run lint —
this test drives that one checker and pins its vocabulary-side
contracts."""

import os

import pytest

import common  # noqa: F401  (side effect: enables x64)

from pystella_tpu.lint import source as lint_source
from pystella_tpu.obs import scope as obs_scope
from pystella_tpu.obs import trace as obs_trace

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "pystella_tpu")


def test_every_scope_literal_is_registered():
    violations, stats = lint_source.check_package(
        PKG, checks={"scope-registry"})
    found = stats["scope_literals"]
    # the checker really sees the hot paths (a broken AST walk must not
    # vacuously pass)
    # (the streaming kernels' scopes are no literals any more: each is
    # registered by its full spelling and built by kernel_scope(kind) —
    # tests/test_host_spans.py pins those; host spans are literals)
    for expected in ("fused_rk_stage_pair", "halo_exchange", "mg_cycle",
                     "pallas_resident_stencil", "sentinel", "rk_stage",
                     "step_dispatch", "reduce_fetch", "output_write",
                     "pallas_bincount"):
        assert expected in found, (expected, sorted(found))
    assert violations == [], (
        "unregistered trace scopes — add register_scope() entries in "
        "pystella_tpu/obs/scope.py so the Perfetto parser and ledger "
        "tables keep seeing them:\n"
        + "\n".join(str(v) for v in violations))


def test_checker_flags_unregistered_literals():
    """The lint checker itself must catch a rename (no vacuous pass):
    run it against a vocabulary missing a known scope."""
    registered = set(obs_scope.registered_scopes()) - {"rk_stage"}
    violations, _ = lint_source.check_package(
        PKG, checks={"scope-registry"},
        registered_scopes=frozenset(registered))
    assert any(v.detail.get("scope") == "rk_stage" for v in violations)


def test_fstring_literals_fold():
    """f-string scope names drop their interpolations (rk_stage{s} ->
    rk_stage), matching the trace parser's fold rule."""
    _, stats = lint_source.check_package(PKG, checks={"scope-registry"})
    assert "rk_stage" in stats["scope_literals"]
    assert not any(name.startswith("rk_stage{")
                   for name in stats["scope_literals"])


def test_parser_vocabulary_is_the_registry():
    """KNOWN_SCOPES derives from the registry — registering a scope is
    sufficient for traces and ledger tables to pick it up."""
    assert set(obs_trace.KNOWN_SCOPES) == set(obs_scope.registered_scopes())
    # and the trace-only names (raw XLA op rows) are registry members
    assert "collective-permute" in obs_trace.KNOWN_SCOPES


def test_binning_kernel_scope_is_no_stencil_kind():
    """The binning kernel's scope is registered under a name of its own:
    nothing that matches ``pallas_stencil`` (the benchmark's generic
    kernel file, ``kernel_scope``) may claim it and count it by the
    stencil byte rule."""
    assert "pallas_bincount" in obs_scope.registered_scopes()
    assert not "pallas_bincount".startswith("pallas_stencil")
    with pytest.raises(ValueError, match="register_scope"):
        obs_scope.kernel_scope("bincount")


def test_register_scope_idempotent_and_live():
    before = obs_scope.registered_scopes()
    assert obs_scope.register_scope("rk_stage") == "rk_stage"
    assert obs_scope.registered_scopes() == before
    # registry views are snapshots, not live aliases
    assert isinstance(before, frozenset)


def test_trace_scope_still_usable_with_any_name():
    """The registry gates CI, not runtime: ad-hoc scopes (user drivers)
    still work."""
    with obs_scope.trace_scope("adhoc_user_scope"):
        pass


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
