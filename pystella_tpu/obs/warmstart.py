"""AOT warm-start: serialize the dispatched step programs, reload them
at startup, skip trace+compile entirely.

Cold start has two compiler-side costs the compile ledger
(:mod:`pystella_tpu.obs.memory`) now itemizes: Python-side **tracing**
(jaxpr + StableHLO lowering — round 3's 512^3 multigrid spent minutes
here) and the XLA **backend compile**. The persistent compilation cache
(:func:`~pystella_tpu.obs.memory.ensure_compilation_cache`) kills the
second; this module kills the first: the very step programs the lint
tier already lowers (:mod:`pystella_tpu.lint.targets`) are exported
with ``jax.export``, serialized next to a metadata sidecar, and keyed
by their **program fingerprint** — lowered-module hash + arg
shape/dtype/sharding signature + jax/jaxlib/libtpu versions + the
scheduler-flag fingerprint. A warmed process deserializes and calls;
with the persistent cache also populated (``save(verify=True)`` runs
the exported program once, so its backend compile is cached too), the
warm path does **no tracing and no backend compile**.

Staleness is structural, not hoped-for: loading re-derives the
version/flag components from the live process and refuses a mismatched
artifact (``warmstart_mismatch`` event + ``None`` return — the caller
falls back to the jit path). A jax upgrade therefore invalidates every
artifact instead of silently calling a stale executable, and the perf
gate refuses a report that *claims* warm start over mismatched
fingerprints (``obs.gate``).

CLI::

    python -m pystella_tpu.obs.warmstart export --out DIR [--target N]
    python -m pystella_tpu.obs.warmstart verify --dir DIR
    python -m pystella_tpu.obs.warmstart list --dir DIR
    python -m pystella_tpu.obs.warmstart gc --dir DIR [--dry-run]

(all directories default to ``PYSTELLA_WARMSTART_DIR`` when set)

``export`` builds the lint target registry's step programs (the same
CPU-safe 8-device builds the IR audit lowers) and serializes each;
``verify`` checks every artifact in a directory against the live
process's versions/flags (exit 1 when any is stale); ``list``
enumerates artifacts with fingerprint/version/match-status (always
exit 0); ``gc`` removes version- or flag-STALE exports — the tending a
long-lived store needs, since otherwise it only ever grows
(a matching artifact is never touched; staleness is exactly the rule
:meth:`WarmstartStore.load` refuses on). Exit codes: 0 ok, 1
mismatch/failure, 2 bad usage.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import memory as _memory

__all__ = ["WarmProgram", "WarmstartStore", "export_target",
           "gc_store", "main"]

#: serialized jax.export payload / metadata sidecar suffixes
ARTIFACT_SUFFIX = ".jaxexport"
META_SUFFIX = ".meta.json"

#: fingerprint components that must match the live process for an
#: artifact to be loadable (aval components are checked only when the
#: caller supplies example args)
_STALENESS_KEYS = ("versions", "flags")


def _safe_label(label):
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", str(label)) or "program"


class WarmProgram:
    """A deserialized AOT program plus its export-time metadata.
    Calling it dispatches the exported computation (no tracing; the
    backend compile of the deserialized module hits the persistent
    cache when the artifact was saved with ``verify=True`` against the
    same cache directory)."""

    def __init__(self, exported, meta, path):
        self.exported = exported
        self.meta = meta
        self.path = path
        self.label = meta.get("label")
        self.fingerprint = meta.get("fingerprint")

    def __call__(self, *args, **kwargs):
        with _memory.compile_watch(f"warmstart.{self.label}") as w:
            out = self.exported.call(*args, **kwargs)
        if w.compiled:
            rec = _memory.CompileRecord(
                label=f"warmstart.{self.label}",
                trace_seconds=w.trace_seconds,
                compile_seconds=w.compile_seconds,
                fingerprint=self.fingerprint,
                fingerprint_kind="lowered",
                cache_hits=w.cache_hits,
                cache_misses=w.cache_misses)
            _memory._record_compile_metrics(rec)
            _events.emit("compile", source="warmstart", **rec.asdict())
        return out

    def __repr__(self):
        return (f"WarmProgram({self.label!r}, "
                f"fingerprint={self.fingerprint!r})")


class WarmstartStore:
    """A directory of AOT-exported programs, one
    ``<label>-<fingerprint>.jaxexport`` + ``.meta.json`` pair each.

    :meth:`save` exports a jitted program for concrete example
    arguments; :meth:`load` deserializes the newest matching artifact
    for a label, refusing (returning ``None``) when the live process's
    versions/flags — or, when example args are given, the call
    signature — differ from the export-time fingerprint components.
    """

    def __init__(self, root=None):
        if root is None:
            from pystella_tpu import config as _config
            root = _config.getenv("PYSTELLA_WARMSTART_DIR")
            if not root:
                raise ValueError(
                    "WarmstartStore needs a directory: pass root= or "
                    "set PYSTELLA_WARMSTART_DIR")
        self.root = os.path.abspath(str(root))
        os.makedirs(self.root, exist_ok=True)

    # -- save --------------------------------------------------------------

    def save(self, label, fn, args=(), kwargs=None, verify=True,
             log=None):
        """Export ``fn(*args, **kwargs)`` (a ``jax.jit`` object, an
        :class:`~pystella_tpu.obs.memory.InstrumentedJit`, or a plain
        function) under ``label``; returns the metadata dict.

        ``verify=True`` (default) additionally *calls* the exported
        program once on ``args`` — proving the artifact actually runs
        on this mesh AND populating the persistent compilation cache
        with its backend compile, so a later warm process skips that
        too."""
        import jax
        from jax import export as _export
        kwargs = kwargs or {}
        jitted = getattr(fn, "_jitted", fn)  # unwrap InstrumentedJit
        if not hasattr(jitted, "lower"):
            jitted = jax.jit(jitted)
        exported = _export.export(jitted)(*args, **kwargs)
        # the exported module is the ONE lowering this save pays for —
        # an explicit .lower() for the fingerprint would re-trace the
        # whole program (minutes for the 512^3 targets this store
        # exists for)
        text = exported.mlir_module()
        fingerprint, components = _memory.program_fingerprint(
            text=text, label=label, args=args, kwargs=kwargs)
        blob = exported.serialize()
        stem = f"{_safe_label(label)}-{fingerprint}"
        artifact = os.path.join(self.root, stem + ARTIFACT_SUFFIX)
        with open(artifact, "wb") as f:
            f.write(blob)
        meta = {
            "label": str(label),
            "fingerprint": fingerprint,
            "components": components,
            "artifact": os.path.basename(artifact),
            "serialized_bytes": len(blob),
            "created_ts": time.time(),
            "platforms": list(exported.platforms),
            "nr_devices": int(exported.nr_devices),
        }
        if verify:
            # verify via a DESERIALIZED copy: proves the artifact bytes
            # on disk actually run on this mesh, and populates the
            # persistent compilation cache with the exact calling
            # wrapper a warm process will build from those same bytes —
            # so the warm process's backend compile is a cache hit
            try:
                reloaded = _export.deserialize(blob)
                jax.block_until_ready(reloaded.call(*args, **kwargs))
            except Exception:
                # a failed verify must not leave a loadable pair behind
                # (load() keys on the sidecar, written below)
                try:
                    os.remove(artifact)
                except OSError:
                    pass
                raise
            meta["verified"] = True
        meta_path = os.path.join(self.root, stem + META_SUFFIX)
        with open(meta_path, "w") as f:
            json.dump(meta, f, indent=1, sort_keys=True)
            f.write("\n")
        (log if log is not None else _events.get_log()).emit(
            "warmstart_export", label=str(label),
            fingerprint=fingerprint, path=artifact,
            serialized_bytes=len(blob), verified=bool(verify))
        return meta

    # -- load --------------------------------------------------------------

    def entries(self, label=None):
        """Metadata dicts for every artifact in the store (newest
        first), optionally filtered by ``label``."""
        metas = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return []
        for name in names:
            if not name.endswith(META_SUFFIX):
                continue
            try:
                with open(os.path.join(self.root, name)) as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                continue
            if label is not None and meta.get("label") != str(label):
                continue
            metas.append(meta)
        metas.sort(key=lambda m: m.get("created_ts", 0), reverse=True)
        return metas

    def _mismatches(self, meta, args=None, kwargs=None):
        """Why the live process cannot trust ``meta``'s artifact:
        version/flag drift always checked; aval signature checked when
        example args are supplied."""
        live = _memory.fingerprint_components(
            meta.get("label", ""), args, kwargs)
        saved = meta.get("components") or {}
        problems = []
        for key in _STALENESS_KEYS:
            if saved.get(key) != live.get(key):
                problems.append(
                    f"{key}: exported {saved.get(key)!r} "
                    f"vs live {live.get(key)!r}")
        if args is not None or kwargs is not None:
            if saved.get("avals") != live.get("avals"):
                problems.append("avals: call signature differs from "
                                "the exported program's")
        return problems

    def load(self, label, args=None, kwargs=None,
             expect_fingerprint=None, log=None):
        """Deserialize the newest artifact for ``label`` that MATCHES
        the live process (a stale newer artifact — e.g. exported under
        different scheduler flags, or before a jax rollback — must not
        shadow an older matching one); ``None`` (plus a
        ``warmstart_mismatch`` event) when no artifact exists or none
        matches — the caller then takes the cold jit path.
        ``expect_fingerprint`` pins an exact program; ``args``/
        ``kwargs`` additionally validate the call signature."""
        sink = log if log is not None else _events.get_log()
        metas = self.entries(label)
        if expect_fingerprint is not None:
            metas = [m for m in metas
                     if m.get("fingerprint") == expect_fingerprint]
        if not metas:
            sink.emit("warmstart_mismatch", label=str(label),
                      reason="no artifact", dir=self.root,
                      expect_fingerprint=expect_fingerprint)
            return None
        meta = first_problems = None
        for candidate in metas:
            problems = self._mismatches(candidate, args, kwargs)
            if not problems:
                meta = candidate
                break
            if first_problems is None:
                first_problems = (candidate, problems)
        if meta is None:
            candidate, problems = first_problems
            sink.emit("warmstart_mismatch", label=str(label),
                      reason="; ".join(problems),
                      fingerprint=candidate.get("fingerprint"),
                      candidates=len(metas),
                      dir=self.root)
            return None
        path = os.path.join(self.root, meta["artifact"])
        from jax import export as _export
        try:
            with open(path, "rb") as f:
                exported = _export.deserialize(f.read())
        except Exception as e:
            sink.emit("warmstart_mismatch", label=str(label),
                      reason=f"deserialize failed: {e}", dir=self.root)
            return None
        sink.emit("warmstart_load", label=str(label),
                  fingerprint=meta.get("fingerprint"), path=path)
        return WarmProgram(exported, meta, path)


def _gc_candidates(store):
    """``(meta, problems)`` per stored artifact, newest first —
    ``problems`` empty when the artifact matches the live process."""
    return [(meta, store._mismatches(meta))
            for meta in store.entries()]


def gc_store(store, dry_run=False, log=None):
    """Garbage-collect STALE artifacts (version/flag mismatch against
    the live process): exports keyed on yesterday's compiler stack
    only cost disk and load-time refusals. Returns ``(kept, removed)``
    metadata lists; with ``dry_run`` nothing is deleted. Emits one
    ``warmstart_gc`` event.

    Artifacts that merely belong to OTHER labels stay: staleness is
    strictly the fingerprint components the loader itself refuses on
    (:meth:`WarmstartStore.load`), so gc never removes anything load
    would still serve."""
    kept, removed = [], []
    for meta, problems in _gc_candidates(store):
        if not problems:
            kept.append(meta)
            continue
        removed.append({**meta, "problems": problems})
        if dry_run:
            continue
        artifact = meta.get("artifact") or (
            f"{_safe_label(meta.get('label'))}-"
            f"{meta.get('fingerprint')}{ARTIFACT_SUFFIX}")
        stem = artifact[:-len(ARTIFACT_SUFFIX)] \
            if artifact.endswith(ARTIFACT_SUFFIX) else artifact
        for name in (artifact, stem + META_SUFFIX):
            try:
                os.remove(os.path.join(store.root, name))
            except OSError:
                pass
    (log if log is not None else _events.get_log()).emit(
        "warmstart_gc", dir=store.root, kept=len(kept),
        removed=len(removed), dry_run=bool(dry_run),
        removed_labels=[m.get("label") for m in removed][:32])
    return kept, removed


def export_target(store, target, log=None):
    """Build one :class:`~pystella_tpu.lint.graph.GraphTarget` (the
    registry entry the IR audit lowers) and export its program; returns
    the metadata dict."""
    fn, args, kwargs, _ = target.build()
    return store.save(target.name, fn, args, kwargs, log=log)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        prog="python -m pystella_tpu.obs.warmstart",
        description="AOT-export the dispatched step programs "
                    "(jax.export) and verify stored artifacts against "
                    "the live compiler stack")
    sub = p.add_subparsers(dest="cmd", required=True)
    pe = sub.add_parser("export", help="build + serialize the lint "
                                       "target registry's programs")
    pe.add_argument("--out", default=None,
                    help="artifact directory (default: "
                         "$PYSTELLA_WARMSTART_DIR)")
    pe.add_argument("--target", action="append", default=None,
                    help="target name (repeatable; default: all)")
    pv = sub.add_parser("verify", help="check every artifact against "
                                       "the live versions/flags")
    pv.add_argument("--dir", default=None,
                    help="artifact directory (default: "
                         "$PYSTELLA_WARMSTART_DIR)")
    pl = sub.add_parser(
        "list", help="enumerate stored artifacts with fingerprint, "
                     "version, and match-status against the live "
                     "process (informational: always exit 0)")
    pl.add_argument("--dir", default=None,
                    help="artifact directory (default: "
                         "$PYSTELLA_WARMSTART_DIR)")
    pg = sub.add_parser(
        "gc", help="garbage-collect STALE exports (version- or "
                   "flag-mismatched against the live process); "
                   "matching artifacts are never touched")
    pg.add_argument("--dir", default=None,
                    help="artifact directory (default: "
                         "$PYSTELLA_WARMSTART_DIR)")
    pg.add_argument("--dry-run", action="store_true",
                    help="report what would be removed, remove nothing")
    args = p.parse_args(argv)

    if args.cmd == "export":
        # the lint CLI's platform default: the targets want the
        # 8-device CPU mesh unless JAX_PLATFORMS says otherwise
        from pystella_tpu.lint.__main__ import _force_platform
        _force_platform()
        from pystella_tpu.lint.targets import targets_by_name
        # verification then populates the persistent compilation cache
        _memory.ensure_compilation_cache()
        try:
            store = WarmstartStore(args.out)
        except ValueError as e:
            print(f"warmstart: {e}", file=sys.stderr)
            return 2
        try:
            targets = targets_by_name(args.target or None).values()
        except KeyError as e:
            print(f"warmstart: {e}", file=sys.stderr)
            return 2
        failures = 0
        for tgt in targets:
            try:
                meta = export_target(store, tgt)
            except Exception as e:  # noqa: BLE001 — report and continue
                failures += 1
                print(f"warmstart: export {tgt.name} FAILED: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                continue
            print(f"warmstart: exported {tgt.name} "
                  f"[{meta['fingerprint']}] "
                  f"({meta['serialized_bytes']:,} B) -> {store.root}")
        return 1 if failures else 0

    try:
        store = WarmstartStore(args.dir)
    except ValueError as e:
        print(f"warmstart: {e}", file=sys.stderr)
        return 2

    if args.cmd == "gc":
        kept, removed = gc_store(store, dry_run=args.dry_run)
        verb = "would remove" if args.dry_run else "removed"
        for meta in removed:
            print(f"warmstart: {verb} {meta.get('label')} "
                  f"[{meta.get('fingerprint')}] "
                  f"({'; '.join(meta.get('problems') or [])})")
        print(f"warmstart: gc {store.root}: {len(kept)} kept, "
              f"{len(removed)} stale artifact(s) {verb}")
        return 0

    metas = store.entries()
    if not metas:
        print(f"warmstart: no artifacts under {store.root}",
              file=sys.stderr)
        return 0 if args.cmd == "list" else 1
    stale = 0
    for meta in metas:
        problems = store._mismatches(meta)
        tag = "OK" if not problems else "STALE"
        stale += bool(problems)
        extra = ""
        if args.cmd == "list":
            versions = (meta.get("components") or {}).get("versions")
            extra = (f" jax={_fmt_versions(versions)} "
                     f"{meta.get('serialized_bytes', 0):,} B "
                     f"devices={meta.get('nr_devices')}")
        print(f"warmstart: {meta.get('label')} "
              f"[{meta.get('fingerprint')}] {tag}{extra}"
              + (f" ({'; '.join(problems)})" if problems else ""))
    if args.cmd == "list":
        return 0
    return 1 if stale else 0


def _fmt_versions(versions):
    if not isinstance(versions, dict):
        return "?"
    return "/".join(str(versions.get(k)) for k in ("jax", "jaxlib"))


if __name__ == "__main__":
    sys.exit(main())
