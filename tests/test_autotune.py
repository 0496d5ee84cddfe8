"""Persistent per-device autotuner (ops.autotune): table round trips,
stale-fingerprint refusal (the WarmstartStore rule), tuned-vs-heuristic
kernel parity, and the consult plumbing (stepper build + advisor)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pystella_tpu as ps
from pystella_tpu.obs import events
from pystella_tpu.ops import autotune
from pystella_tpu.ops.fused import FusedScalarStepper

_TPU_SESSION = jax.default_backend() == "tpu"
_XKW = {"interpret": True} if _TPU_SESSION else {}


def _potential(f):
    return 0.5 * 1.2e-2 * f[0] ** 2 + 0.125 * f[0] ** 2 * f[1] ** 2


def _devs(n):
    return (jax.devices("cpu") if _TPU_SESSION else jax.devices())[:n]


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    events.configure(path)
    yield path
    events.configure(None)


def _store(tmp_path):
    return autotune.AutotuneStore(root=str(tmp_path / "tables"),
                                  device_kind="cpu")


def _record(store, local_shape=(16, 16, 16), proc_shape=(1, 1, 1),
            dtype=np.float32, **winner):
    digest, comp = autotune.stepper_key(
        "fused_scalar", local_shape, 2, dtype, 2,
        proc_shape=proc_shape)
    # "assemble": tables written before the kernels became one call
    # hold the key; a reader ignores it
    winner = {"bx": 4, "by": 8, "chunk": 0, "assemble": "concat",
              "ms_per_step": 1.0, **winner}
    store.record(digest, comp, winner)
    return digest, comp


# -- the key ---------------------------------------------------------------

def test_stepper_key_structural_components():
    """The digest hashes the kernel's structural identity only — shape,
    dtype, halo, mesh, system — and NOT the compiler-stack versions
    (those are checked at lookup time so staleness refuses loudly
    instead of silently missing)."""
    d0, c0 = autotune.stepper_key("fused_scalar", (16, 16, 16), 2,
                                  np.float32, 2)
    d_same, _ = autotune.stepper_key("fused_scalar", (16, 16, 16), 2,
                                     np.float32, 2)
    assert d0 == d_same
    assert "versions" not in c0 and "flags" not in c0
    for other in (
            autotune.stepper_key("fused_scalar", (32, 16, 16), 2,
                                 np.float32, 2),          # shape
            autotune.stepper_key("fused_scalar", (16, 16, 16), 4,
                                 np.float32, 2),          # halo
            autotune.stepper_key("fused_scalar", (16, 16, 16), 2,
                                 np.float64, 2),          # dtype
            autotune.stepper_key("fused_scalar", (16, 16, 16), 2,
                                 np.float32, 2,
                                 proc_shape=(2, 2, 1)),   # mesh
            autotune.stepper_key("fused_preheat", (16, 16, 16), 2,
                                 np.float32, 2),          # system
    ):
        assert other[0] != d0, other[1]


# -- store round trips -----------------------------------------------------

def test_store_round_trip(tmp_path):
    """record -> fresh store instance (the cross-process spelling: only
    the JSON file is shared) -> lookup serves the entry; a different
    structural key misses."""
    store = _store(tmp_path)
    digest, comp = _record(store, bx=2, by=16, ms_per_step=0.5)
    assert os.path.basename(store.path) == "autotune_cpu.json"

    fresh = _store(tmp_path)
    entry = fresh.lookup(digest, comp)
    assert entry is not None
    assert (entry["bx"], entry["by"]) == (2, 16)
    assert entry["key"] == comp
    assert entry["device_kind"] == "cpu"
    # a different shape is a MISS (shape is part of the digest)
    other_digest, _ = autotune.stepper_key(
        "fused_scalar", (32, 32, 32), 2, np.float32, 2)
    assert fresh.lookup(other_digest) is None


def test_store_round_trip_sharded_mesh_key(tmp_path, event_log):
    """Round trip on the (2, 2, 1) CPU mesh: the entry keys on the
    LOCAL shape + proc_shape, a sharded stepper build consults it, the
    pair kernel realizes the tuned blocking, and the block_choice
    event records source='autotune'."""
    if len(_devs(4)) < 4:
        pytest.skip("needs 4 devices")
    decomp = ps.DomainDecomposition((2, 2, 1), devices=_devs(4))
    grid = (16, 16, 16)
    local = decomp.rank_shape(grid)
    store = _store(tmp_path)
    _record(store, local_shape=local, proc_shape=(2, 2, 1),
            bx=2, by=8)

    sector = ps.ScalarSector(2, potential=_potential)
    stepper = FusedScalarStepper(sector, decomp, grid, (0.3,) * 3, 2,
                                 dtype=jnp.float32, autotune=store,
                                 **_XKW)
    assert stepper._autotune_entry is not None
    assert (stepper._pair_st.bx, stepper._pair_st.by) == (2, 8)
    choices = events.read_events(event_log, kind="block_choice")
    pair_rows = [r for r in choices if r["data"]["kernel"] == "pair"]
    assert pair_rows and pair_rows[-1]["data"]["source"] == "autotune"


# -- staleness refusal (the WarmstartStore.load rule) ----------------------

def test_lookup_refuses_stale_versions(tmp_path, event_log):
    """A version-component mismatch against the live process REFUSES
    the entry (autotune_mismatch event + None) — a jax bump can never
    silently apply last quarter's blocking."""
    store = _store(tmp_path)
    digest, comp = _record(store)
    table = json.load(open(store.path))
    table["entries"][digest]["versions"]["jax"] = "0.0.1-stale"
    json.dump(table, open(store.path, "w"))

    assert store.lookup(digest, comp) is None
    recs = events.read_events(event_log, kind="autotune_mismatch")
    assert recs, "refusal must be auditable"
    assert any("jax" in p for p in recs[-1]["data"]["problems"])
    # the consult wrapper falls back to the heuristic the same way
    entry, _ = autotune.consult("fused_scalar", (16, 16, 16), 2,
                                np.float32, 2, store=store)
    assert entry is None


def test_lookup_refuses_stale_flags(tmp_path, event_log):
    store = _store(tmp_path)
    digest, comp = _record(store)
    table = json.load(open(store.path))
    table["entries"][digest]["flags"] = {"stale": "flagset"}
    json.dump(table, open(store.path, "w"))
    assert store.lookup(digest, comp) is None
    recs = events.read_events(event_log, kind="autotune_mismatch")
    assert any("flags" in p for p in recs[-1]["data"]["problems"])


def test_lookup_refuses_structural_mismatch(tmp_path, event_log):
    """Shape-component refusal: an entry whose stored key differs from
    the requested components (digest collision / hand-edited table) is
    refused rather than applying a blocking tuned for another kernel."""
    store = _store(tmp_path)
    digest, comp = _record(store)
    table = json.load(open(store.path))
    table["entries"][digest]["key"]["local_shape"] = [64, 64, 64]
    json.dump(table, open(store.path, "w"))
    assert store.lookup(digest, comp) is None
    assert events.read_events(event_log, kind="autotune_mismatch")


def test_gc_removes_only_stale(tmp_path):
    """gc removes exactly the entries lookup would refuse; matching
    entries are never touched (the warmstart gc contract)."""
    store = _store(tmp_path)
    d_fresh, _ = _record(store)
    d_stale, _ = _record(store, local_shape=(32, 32, 32))
    table = json.load(open(store.path))
    table["entries"][d_stale]["versions"]["jaxlib"] = "stale"
    json.dump(table, open(store.path, "w"))

    kept, removed = store.gc(dry_run=True)
    assert set(kept) == {d_fresh} and set(removed) == {d_stale}
    assert set(store.entries()) == {d_fresh, d_stale}  # dry run
    kept, removed = store.gc()
    assert set(store.entries()) == {d_fresh}


def test_consult_policy(tmp_path, monkeypatch):
    """store=False skips; PYSTELLA_AUTOTUNE=0 (the suite default)
    disables the default store; an explicit store beats the policy."""
    store = _store(tmp_path)
    digest, comp = _record(store)
    entry, d = autotune.consult("fused_scalar", (16, 16, 16), 2,
                                np.float32, 2, store=False)
    assert entry is None and d == digest
    monkeypatch.setenv("PYSTELLA_AUTOTUNE", "0")
    entry, _ = autotune.consult("fused_scalar", (16, 16, 16), 2,
                                np.float32, 2)
    assert entry is None
    entry, _ = autotune.consult("fused_scalar", (16, 16, 16), 2,
                                np.float32, 2, store=store)
    assert entry is not None


# -- tuned vs heuristic kernels --------------------------------------------

def test_tuned_vs_heuristic_bitexact(tmp_path, event_log):
    """Blocking never enters the math: a stepper built from a table
    winner must be BIT-EXACT against the heuristic build — and the
    block_choice record names who chose (autotune vs heuristic)."""
    grid = (16, 16, 16)
    sector = ps.ScalarSector(2, potential=_potential)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=_devs(1))
    kw = dict(dtype=jnp.float32, **_XKW)

    heur = FusedScalarStepper(sector, decomp, grid, (0.3,) * 3, 2,
                              autotune=False, **kw)
    store = _store(tmp_path)
    # a DIFFERENT feasible blocking than the heuristic's
    tuned_blocks = (4, 8)
    assert (heur._pair_st.bx, heur._pair_st.by) != tuned_blocks
    _record(store, bx=tuned_blocks[0], by=tuned_blocks[1])
    tuned = FusedScalarStepper(sector, decomp, grid, (0.3,) * 3, 2,
                               autotune=store, **kw)
    assert tuned._autotune_entry is not None
    assert (tuned._pair_st.bx, tuned._pair_st.by) == tuned_blocks

    rng = np.random.default_rng(31)
    host = {
        "f": rng.standard_normal((2,) + grid).astype(np.float32),
        "dfdt": 0.1 * rng.standard_normal((2,) + grid)
        .astype(np.float32),
    }
    args = {"a": np.float32(1.2), "hubble": np.float32(0.3)}
    ref = heur.multi_step({k: jnp.asarray(v) for k, v in host.items()},
                          2, 0.0, np.float32(0.01), args)
    got = tuned.multi_step({k: jnp.asarray(v) for k, v in host.items()},
                           2, 0.0, np.float32(0.01), args)
    for name in ("f", "dfdt"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(ref[name])), \
            f"{name}: tuned blocking changed the numbers"

    srcs = [(r["data"]["kernel"], r["data"]["source"])
            for r in events.read_events(event_log, kind="block_choice")]
    assert ("pair", "heuristic") in srcs
    assert ("pair", "autotune") in srcs


def test_force_blocks_override(tmp_path, monkeypatch, event_log):
    """PYSTELLA_FORCE_BLOCKS beats the table AND the heuristic, and the
    block_choice event says so."""
    store = _store(tmp_path)
    _record(store, bx=4, by=8)
    monkeypatch.setenv("PYSTELLA_FORCE_BLOCKS", "2,8")
    sector = ps.ScalarSector(2, potential=_potential)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=_devs(1))
    st = FusedScalarStepper(sector, decomp, (16, 16, 16), (0.3,) * 3,
                            2, dtype=jnp.float32, autotune=store,
                            **_XKW)
    assert (st._pair_st.bx, st._pair_st.by) == (2, 8)
    rows = [r["data"] for r in
            events.read_events(event_log, kind="block_choice")]
    assert all(r["source"] == "override" for r in rows
               if r["kernel"] == "pair")


def test_chunk_depth_from_table(tmp_path):
    """chunk_stages=None defers the depth decision to the table: a
    winner recording chunk=4 builds the chunk kernel (and its
    blocking); a chunk=0 winner keeps the pair tier."""
    store = _store(tmp_path)
    _record(store, bx=4, by=8, chunk=4)
    sector = ps.ScalarSector(2, potential=_potential)
    decomp = ps.DomainDecomposition((1, 1, 1), devices=_devs(1))
    st = FusedScalarStepper(sector, decomp, (16, 16, 16), (0.3,) * 3,
                            2, dtype=jnp.float32, autotune=store,
                            **_XKW)
    assert st._chunk_depth == 4 and st._chunk_call is not None
    assert (st._chunk_st.bx, st._chunk_st.by) == (4, 8)
    assert st.kernel_tier_report()["autotune"]["source"] == "autotune"


# -- advisor + CLI ---------------------------------------------------------

def test_advisor_consults_table(tmp_path):
    """utils.advisor renders the SAME lookup the kernel build performs,
    so its advice names the tuned blocking."""
    store = _store(tmp_path)
    _record(store, bx=2, by=16, chunk=4, ms_per_step=0.25)
    rep = ps.advise_shapes((16, 16, 16), 1, autotune_store=store)
    best = rep.best()
    assert any("autotuned: bx=2 by=16 chunk=4" in n
               for n in best.notes), best.notes
    assert best.tiers["fused stepper"].endswith("+chunk")
    # without the store the note is absent
    rep2 = ps.advise_shapes((16, 16, 16), 1, autotune_store=False)
    assert not any("autotuned" in n for n in rep2.best().notes)


def test_cli_show_and_gc(tmp_path, capsys):
    store = _store(tmp_path)
    _record(store)
    rc = autotune.main(["show", "--dir", store.root,
                        "--device-kind", "cpu", "--check"])
    out = capsys.readouterr().out
    assert rc == 0 and "fused_scalar" in out and "ok" in out
    rc = autotune.main(["gc", "--dir", store.root,
                        "--device-kind", "cpu", "--dry-run"])
    assert rc == 0
    assert "would remove 0" in capsys.readouterr().out


@pytest.mark.slow
def test_sweep_records_winner(tmp_path):
    """An in-process mini sweep: candidates from the choose_blocks
    model, the min-over-rounds paired estimator, the winner persisted
    and immediately servable to a tuned build."""
    store = _store(tmp_path)
    results = autotune.sweep((8, 8, 8), store=store, nsteps=1,
                             rounds=2, max_blocks=1, chunk_depths=(0,),
                             interpret=True if _TPU_SESSION else None,
                             log=lambda m: None)
    assert results and "ms_per_step" in results[0]
    digest, comp = autotune.stepper_key("fused_scalar", (8, 8, 8), 2,
                                        np.float32, 2)
    entry = store.lookup(digest, comp)
    assert entry is not None and entry["ms_per_step"] > 0
    assert entry["swept"]
