"""Cold-start observability tests: the compile ledger's trace/compile
split and fingerprints, the persistent-compilation-cache wiring, and
the AOT warm-start store — including
the tier-1 cold->warm round trip: a (2,2,1)-mesh step program exported
here, reloaded in a FRESH subprocess, pinned bit-exact against the jit
path with no backend compile for its fingerprint."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax

import pystella_tpu as ps
from pystella_tpu import obs
from pystella_tpu.obs import events
from pystella_tpu.obs import memory as obs_memory
from pystella_tpu.obs import warmstart

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure(path)
    yield path
    obs.configure(None)


def _mesh_step(make_decomp, donate=False):
    """A tiny generic LowStorageRK54 step on the (2,2,1) mesh — the
    sharded program the satellite round trip pins."""
    decomp = make_decomp((2, 2, 1))
    grid = (16, 16, 16)
    lattice = ps.Lattice(grid, (5.0, 5.0, 5.0), dtype=np.float32)
    dt = np.float32(0.1 * min(lattice.dx))
    derivs = ps.FiniteDifferencer(decomp, 2, lattice.dx, mode="halo")

    def rhs(state, t, a):
        return {"f": state["dfdt"],
                "dfdt": derivs.lap(state["f"]) - a * state["f"]}

    stepper = ps.LowStorageRK54(rhs, dt=dt, donate=donate)
    rng = np.random.default_rng(23)
    host = {
        "f": 1e-1 * rng.standard_normal((2,) + grid).astype(np.float32),
        "dfdt": 1e-2 * rng.standard_normal((2,) + grid).astype(np.float32),
    }
    state = {k: decomp.shard(v) for k, v in host.items()}
    return decomp, stepper, state, host, dt


# -- fingerprints ----------------------------------------------------------

def test_fingerprint_kinds_and_sensitivity():
    x = jax.device_put(np.ones((8,), np.float32))
    f = jax.jit(lambda a: a * 2)
    sig, comp = obs_memory.signature_fingerprint("lbl", (x,))
    assert "module_sha256" not in comp
    full, comp2 = obs_memory.program_fingerprint(
        f.lower(x), label="lbl", args=(x,))
    assert "module_sha256" in comp2
    assert sig != full
    # the versions component invalidates on a compiler-stack bump
    assert comp2["versions"]["jax"]
    tampered = dict(comp2)
    tampered["versions"] = dict(comp2["versions"], jax="9.9.9")
    assert obs_memory._digest(tampered) != full
    # a different arg shape is a different program
    y = jax.device_put(np.ones((9,), np.float32))
    sig2, _ = obs_memory.signature_fingerprint("lbl", (y,))
    assert sig2 != sig


def test_runtime_versions_in_env_fingerprint():
    """Satellite: jax/jaxlib (and libtpu when present) versions ride
    the report environment fingerprint AND the warm-start fingerprint
    components, so a version bump invalidates stale programs."""
    vers = obs_memory.runtime_versions()
    assert vers["jax"] and vers["jaxlib"]
    env = obs.environment_fingerprint()
    assert env["jax"] == vers["jax"]
    assert "libtpu" in env  # None on CPU containers — but recorded


# -- compile watch / instrumented dispatch ---------------------------------

def test_compile_watch_and_instrument_jit(event_log):
    with obs_memory.compile_watch("unit") as w:
        jax.jit(lambda a: a + 1)(np.float32(1.0))
    assert w.compiled and w.trace_seconds > 0

    inst = obs.instrument_jit(
        jax.jit(lambda a: a * 3), "unit.instrumented")
    x = jax.device_put(np.ones((64, 64), np.float32))
    out = inst(x)
    assert np.allclose(np.asarray(out), 3.0)
    inst(x)  # steady-state call: no second compile event
    evs = [e for e in events.read_events(event_log, kind="compile")
           if e["data"].get("label") == "unit.instrumented"]
    assert len(evs) == 1
    assert evs[0]["data"]["source"] == "dispatch"
    assert evs[0]["data"]["fingerprint_kind"] == "signature"
    # lower() passes through for the lint tier
    assert "stablehlo" in inst.lower(x).as_text()


# -- persistent cache + donation policy ------------------------------------

def test_ensure_compilation_cache_follows_the_environment(
        isolated_cache, event_log):
    """With JAX_COMPILATION_CACHE_DIR set the program uses that
    directory and sets no other."""
    cache = obs.ensure_compilation_cache()
    assert cache == isolated_cache and os.path.isdir(cache)
    assert jax.config.jax_compilation_cache_dir == isolated_cache
    evs = events.read_events(event_log, kind="compile_cache")
    assert evs and evs[-1]["data"]["dir"] == cache


def test_ensure_compilation_cache_default_is_in_checkout(monkeypatch):
    """Unset, the cache is bench_results/xla_cache in the checkout — a
    fixed path (the path is part of the cache's key)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        cache = obs.ensure_compilation_cache()
        assert cache == os.path.join(REPO, "bench_results", "xla_cache")
        assert cache == obs_memory.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == cache
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_donated_compile_uses_cache(isolated_cache, event_log,
                                    make_decomp):
    """A DONATED program's compile goes through the persistent cache
    like any other (the jaxlib-0.4.37 CPU hazard that once forced a
    bypass does not reproduce on jaxlib 0.9.0 — CHANGES.md, PR 21)."""
    obs.ensure_compilation_cache()
    _, stepper, state, _, dt = _mesh_step(make_decomp, donate=True)
    _, rec = obs.compile_with_report(
        stepper._jit_step, state, np.float32(0.0), dt,
        {"a": np.float32(1.0)}, label="donated_step")
    assert rec.cache_misses >= 1 and rec.cache_hit is False


# -- warm-start store ------------------------------------------------------

def test_warmstart_roundtrip_sharded_mesh(tmp_path, event_log,
                                          make_decomp):
    """Save/load round trip of the (2,2,1)-mesh step program in one
    process: loaded program is bit-exact with the jit path."""
    decomp, stepper, state, host, dt = _mesh_step(make_decomp)
    t, a = np.float32(0.0), np.float32(1.0)
    store = warmstart.WarmstartStore(str(tmp_path / "store"))
    meta = store.save("t1_step", stepper._jit_step,
                      (state, t, dt, {"a": a}))
    assert meta["fingerprint"] and meta["serialized_bytes"] > 0

    state2 = {k: decomp.shard(v) for k, v in host.items()}
    prog = store.load("t1_step", args=(state2, t, dt, {"a": a}))
    assert prog is not None
    got = prog(state2, t, dt, {"a": a})
    ref = stepper._jit_step(
        {k: decomp.shard(v) for k, v in host.items()}, t, dt, {"a": a})
    for k in ref:
        assert np.array_equal(np.asarray(got[k]), np.asarray(ref[k]))
    kinds = [e["kind"] for e in events.read_events(event_log)]
    assert "warmstart_export" in kinds and "warmstart_load" in kinds


def test_warmstart_version_mismatch_refused(tmp_path, event_log):
    """Satellite: a compiler-stack bump must invalidate artifacts
    instead of silently loading stale executables."""
    x = jax.device_put(np.arange(16, dtype=np.float32))
    store = warmstart.WarmstartStore(str(tmp_path / "store"))
    store.save("toy", jax.jit(lambda a: a * 2), (x,))
    # tamper the recorded jax version -> stale
    meta_path = [os.path.join(store.root, n)
                 for n in os.listdir(store.root)
                 if n.endswith(warmstart.META_SUFFIX)][0]
    meta = json.load(open(meta_path))
    meta["components"]["versions"]["jax"] = "0.0.1"
    json.dump(meta, open(meta_path, "w"))
    assert store.load("toy") is None
    mism = events.read_events(event_log, kind="warmstart_mismatch")
    assert mism and "versions" in mism[-1]["data"]["reason"]
    # unknown label also refuses (with an event, not an exception)
    assert store.load("absent") is None


def test_warmstart_stale_artifact_does_not_shadow_match(tmp_path,
                                                        event_log):
    """A NEWER stale artifact (exported under other flags/versions)
    must not shadow an older matching one in a shared store: load()
    returns the first entry that matches the live process, and only
    emits a mismatch when none does."""
    x = jax.device_put(np.arange(16, dtype=np.float32))
    store = warmstart.WarmstartStore(str(tmp_path / "store"))
    good = store.save("toy", jax.jit(lambda a: a * 2), (x,))
    # forge a newer sidecar for the same label with a stale version
    meta_path = [os.path.join(store.root, n)
                 for n in os.listdir(store.root)
                 if n.endswith(warmstart.META_SUFFIX)][0]
    stale = json.load(open(meta_path))
    stale["fingerprint"] = "deadbeef"
    stale["created_ts"] = stale["created_ts"] + 1000
    stale["components"]["versions"]["jax"] = "0.0.1"
    json.dump(stale, open(os.path.join(
        store.root, "toy-deadbeef" + warmstart.META_SUFFIX), "w"))
    assert store.entries("toy")[0]["fingerprint"] == "deadbeef"
    prog = store.load("toy")
    assert prog is not None
    assert prog.fingerprint == good["fingerprint"]
    assert not events.read_events(event_log, kind="warmstart_mismatch")


def test_warmstart_signature_mismatch_refused(tmp_path):
    x = jax.device_put(np.arange(16, dtype=np.float32))
    store = warmstart.WarmstartStore(str(tmp_path / "store"))
    store.save("toy", jax.jit(lambda a: a * 2), (x,))
    wrong = jax.device_put(np.arange(8, dtype=np.float32))
    assert store.load("toy", args=(wrong,)) is None


def test_warmstart_verify_persisted_and_failure_cleans_up(
        tmp_path, monkeypatch):
    """The sidecar records a successful verification on disk, and a
    save() whose verification fails leaves NO loadable pair behind — a
    later warm process must never serve a program that never
    successfully ran."""
    from jax import export as jexport
    x = jax.device_put(np.arange(16, dtype=np.float32))
    store = warmstart.WarmstartStore(str(tmp_path / "good"))
    meta = store.save("toy", jax.jit(lambda a: a * 2), (x,))
    assert meta["verified"] is True
    assert store.entries("toy")[0]["verified"] is True

    def boom(blob):
        raise RuntimeError("verify boom")
    monkeypatch.setattr(jexport, "deserialize", boom)
    bad = warmstart.WarmstartStore(str(tmp_path / "bad"))
    with pytest.raises(RuntimeError, match="verify boom"):
        bad.save("toy", jax.jit(lambda a: a * 3), (x,))
    assert bad.entries() == []
    assert os.listdir(bad.root) == []


def test_warmstart_store_dir_from_env(tmp_path, monkeypatch):
    """PYSTELLA_WARMSTART_DIR is the store's default location; unset
    and rootless is an explicit error, not a silent cwd write."""
    monkeypatch.delenv("PYSTELLA_WARMSTART_DIR", raising=False)
    with pytest.raises(ValueError, match="PYSTELLA_WARMSTART_DIR"):
        warmstart.WarmstartStore()
    monkeypatch.setenv("PYSTELLA_WARMSTART_DIR", str(tmp_path / "ws"))
    store = warmstart.WarmstartStore()
    assert store.root == str(tmp_path / "ws")


# -- the satellite: cold -> warm across processes --------------------------

_WARM_SCRIPT = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    store_dir, data_path, out_path = sys.argv[1:4]
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    import pystella_tpu as ps
    from pystella_tpu import obs
    from pystella_tpu.obs import warmstart

    events_path = os.path.join(os.path.dirname(out_path), "warm.jsonl")
    obs.configure(events_path)
    obs.ensure_compilation_cache()
    obs.emit("run_start", mode="warm-subprocess")

    data = np.load(data_path)
    decomp = ps.DomainDecomposition((2, 2, 1),
                                    devices=jax.devices()[:4])
    state = {k: decomp.shard(data[k]) for k in ("f", "dfdt")}
    t, dt, a = (np.float32(data["t"]), np.float32(data["dt"]),
                np.float32(data["a"]))

    store = warmstart.WarmstartStore(store_dir)
    with obs.compile_watch("warm-leg") as w:
        prog = store.load("t1_step", args=(state, t, dt, {"a": a}))
        assert prog is not None, "artifact refused in warm process"
        out = prog(state, t, dt, {"a": a})
        jax.block_until_ready(out)

    # jit-path reference IN THIS PROCESS (fresh trace+compile)
    lattice = ps.Lattice(tuple(data["f"].shape[1:]), (5.0, 5.0, 5.0),
                         dtype=np.float32)
    derivs = ps.FiniteDifferencer(decomp, 2, lattice.dx, mode="halo")
    def rhs(state, t, a):
        return {"f": state["dfdt"],
                "dfdt": derivs.lap(state["f"]) - a * state["f"]}
    stepper = ps.LowStorageRK54(rhs, dt=dt)
    ref = stepper._jit_step({k: decomp.shard(data[k])
                             for k in ("f", "dfdt")}, t, dt, {"a": a})
    jax.block_until_ready(ref)

    led = obs.PerfLedger.from_events(events_path, label="warm")
    cold = led.cold_start()
    rows = [c for c in cold["compiles"]
            if c.get("fingerprint") == prog.fingerprint]
    json.dump({
        "bitexact": all(bool(np.array_equal(np.asarray(out[k]),
                                            np.asarray(ref[k])))
                        for k in ref),
        "warm_backend_compile_s": w.compile_seconds,
        "warm_cache_hits": w.cache_hits,
        "fingerprint": prog.fingerprint,
        "report_rows": rows,
        "ref_sum": float(np.sum(np.asarray(ref["dfdt"]))),
    }, open(out_path, "w"))
""")


def test_cold_to_warm_subprocess_roundtrip(tmp_path, make_decomp,
                                           isolated_cache):
    """The PR acceptance pin: export the (2,2,1)-mesh step program,
    reload it in a FRESH process against the same compilation cache,
    and require (a) bit-exact outputs vs that process's own jit path,
    (b) NO backend compile for the warm program's fingerprint — its
    compile table row shows a cache hit with 0 compile seconds."""
    obs.ensure_compilation_cache()
    decomp, stepper, state, host, dt = _mesh_step(make_decomp)
    t, a = np.float32(0.0), np.float32(1.0)
    store = warmstart.WarmstartStore(str(tmp_path / "store"))
    # save(verify=True) runs the exported program once, landing its
    # backend compile in the shared persistent cache — that is what
    # the warm process's hit is
    store.save("t1_step", stepper._jit_step,
               (state, t, dt, {"a": a}))
    ref = stepper._jit_step(
        {k: decomp.shard(v) for k, v in host.items()},
        t, dt, {"a": a})
    np.savez(tmp_path / "data.npz", t=t, dt=dt, a=a, **host)

    script = tmp_path / "warm_leg.py"
    script.write_text(_WARM_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    out_path = tmp_path / "verdict.json"
    res = subprocess.run(
        [sys.executable, str(script), store.root,
         str(tmp_path / "data.npz"), str(out_path)],
        capture_output=True, text=True, timeout=240, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    verdict = json.load(open(out_path))
    assert verdict["bitexact"] is True
    # warm leg: the artifact skipped tracing, and the persistent
    # cache served the backend compile — the fingerprint's report
    # row attributes a HIT and no miss. (jax's backend-compile
    # timer still ticks on a hit — it includes cache retrieval and
    # executable deserialization — so the seconds are small but
    # nonzero; the hit/miss attribution is the no-compile proof.)
    assert verdict["warm_cache_hits"] >= 1
    assert verdict["warm_backend_compile_s"] < 1.0
    rows = verdict["report_rows"]
    assert rows, "warm program's fingerprint missing from report"
    assert all(r["cache_hit"] is True for r in rows)
    # and the warm process agrees with THIS process bit-for-bit
    assert verdict["ref_sum"] == pytest.approx(
        float(np.sum(np.asarray(ref["dfdt"]))), rel=0, abs=0)


def test_warmstart_list_and_gc(tmp_path, event_log):
    """The store-tending satellite: ``list`` enumerates artifacts with
    match-status, ``gc`` removes exactly the stale (version/flag-
    mismatched) pairs and never touches a matching one — the same
    staleness rule ``load()`` refuses on."""
    store = warmstart.WarmstartStore(str(tmp_path / "store"))
    x = jax.device_put(np.ones((8,), np.float32))
    fn = jax.jit(lambda a: a * 2 + 1)
    meta = store.save("tended", fn, (x,))
    # a stale sibling: same label, fake fingerprint, old versions
    stale = dict(meta, fingerprint="feedfacefeedface",
                 artifact="tended-feedfacefeedface.jaxexport",
                 components={**meta["components"],
                             "versions": {"jax": "0.0.1",
                                          "jaxlib": "0.0.1",
                                          "libtpu": None}})
    with open(os.path.join(store.root, stale["artifact"]), "wb") as f:
        f.write(b"stale-bytes")
    with open(os.path.join(
            store.root, "tended-feedfacefeedface.meta.json"), "w") as f:
        json.dump(stale, f)

    # dry run reports without removing
    kept, removed = warmstart.gc_store(store, dry_run=True)
    assert [m["fingerprint"] for m in removed] == ["feedfacefeedface"]
    assert len(kept) == 1
    assert os.path.exists(os.path.join(store.root, stale["artifact"]))

    # real gc removes the stale pair, keeps (and still loads) the match
    kept, removed = warmstart.gc_store(store)
    assert len(removed) == 1 and len(kept) == 1
    assert not os.path.exists(os.path.join(store.root,
                                           stale["artifact"]))
    assert not os.path.exists(os.path.join(
        store.root, "tended-feedfacefeedface.meta.json"))
    assert store.load("tended", args=(x,)) is not None
    gc_events = events.read_events(event_log, kind="warmstart_gc")
    assert gc_events[-1]["data"]["removed"] == 1

    # the CLI spellings, in-process (same argparse path as -m)
    assert warmstart.main(["list", "--dir", store.root]) == 0
    assert warmstart.main(["gc", "--dir", store.root]) == 0
    assert warmstart.main(["verify", "--dir", store.root]) == 0


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
