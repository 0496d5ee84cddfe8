"""Persistent per-device-kind kernel autotuner for the fused Pallas tier.

The ``choose_blocks`` heuristic picks a *safe* blocking from a VMEM
model; the measured optimum per (device kind, lattice shape, system)
can differ, and the temporal-blocking chunk depth
(:class:`~pystella_tpu.ops.fused.FusedScalarStepper` ``chunk_stages``)
is a genuine tradeoff — redundant halo recompute vs eliminated HBM
round trips — that only a measurement settles. This module makes that
measurement once per device kind and PERSISTS it:

- :func:`sweep` enumerates ``(bx, by, chunk depth)`` candidates
  from the same VMEM model the heuristic uses
  (:func:`~pystella_tpu.ops.pallas_stencil.feasible_blocks` — the
  autotuner can never propose a config the builder would reject),
  measures each with the min-over-rounds **paired** estimator (the
  tests' sentinel-overhead idiom, adapted: candidates interleave
  inside each round so shared-host frequency/scheduler drift hits all
  of them equally, and each candidate's estimate is the minimum over
  rounds of that round's per-step time — noise only ever ADDS time),
  and records the winner;
- :class:`AutotuneStore` persists winners to
  ``bench_results/autotune_<device-kind>.json``, keyed on the PR-6
  program-fingerprint components (kernel shape / dtype / halo / mesh)
  with the compiler-stack versions and scheduler-flag fingerprint
  stored alongside; :meth:`AutotuneStore.lookup` re-derives those from
  the live process and REFUSES a stale entry (``autotune_mismatch``
  event + ``None`` return) exactly as ``WarmstartStore.load`` refuses a
  stale AOT artifact — a jax/libtpu bump can never silently apply last
  quarter's blocking;
- kernel builds consult the table before the heuristic
  (``FusedScalarStepper`` at construction; ``utils.advisor`` renders
  the same lookups so its advice matches what the kernel will really
  pick), emitting a ``block_choice`` event that records the blocking
  actually chosen and its source (``autotune`` | ``heuristic`` |
  ``override``).

Because the table is keyed on the same fingerprint components the
warm-start store uses, a TUNED kernel is AOT-servable through the
PR-12 scenario service's warm pool: sweep on a window, export the tuned
programs, and a later lease dispatches them with zero backend compiles.

CLI::

    python -m pystella_tpu.ops.autotune sweep --grid 256 [--dry-run]
    python -m pystella_tpu.ops.autotune show
    python -m pystella_tpu.ops.autotune gc [--dry-run]

``sweep --dry-run`` shrinks the grid and rounds so the whole path
rehearses on CPU (interpret-mode kernels; the numbers are then
meaningless but the table round trip is real).
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

from pystella_tpu import config as _config

__all__ = ["AutotuneStore", "stepper_key", "default_store", "consult",
           "sweep", "candidate_configs", "measure_candidates"]

SCHEMA_VERSION = 1

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _repo_anchored(path):
    """Relative table dirs anchor at the repository root, not the cwd
    (the ``ensure_compilation_cache`` rule — a tool run from anywhere
    must find the same table)."""
    if not os.path.isabs(path):
        return os.path.join(_REPO_ROOT, path)
    return path


def _device_kind():
    import jax
    kind = getattr(jax.devices()[0], "device_kind", "") or \
        jax.default_backend()
    return str(kind)


def _kind_slug(kind):
    return "".join(c if c.isalnum() else "_" for c in str(kind).lower())


def _live_components():
    """The process components a table entry must match to be served:
    compiler-stack versions and the scheduler-relevant flag fingerprint
    — the exact staleness rule ``WarmstartStore.load`` refuses on."""
    from pystella_tpu.obs.memory import runtime_versions
    from pystella_tpu.parallel.overlap import flags_fingerprint
    return {"versions": runtime_versions(), "flags": flags_fingerprint()}


def stepper_key(kind, local_shape, h, dtype, nscalars,
                gravitational_waves=False, proc_shape=(1, 1, 1),
                carry_dtype=None, tableau="LowStorageRK54"):
    """The structural identity a tuned-stepper entry is keyed on —
    everything that changes the kernels the builder would construct
    (local lattice shape, stencil radius, dtypes, system widths, mesh)
    and nothing that merely labels the run. Returns
    ``(digest, components)``; the version/flag components are checked
    at lookup time, not hashed into the key, so a stale entry is
    REFUSED loudly instead of silently missed."""
    comp = {
        "kind": str(kind),
        "local_shape": [int(s) for s in local_shape],
        "h": int(h),
        "dtype": str(np.dtype(dtype)),
        "carry_dtype": (None if carry_dtype is None
                        else str(np.dtype(carry_dtype))),
        "nscalars": int(nscalars),
        "gravitational_waves": bool(gravitational_waves),
        "proc_shape": [int(p) for p in proc_shape],
        "tableau": str(tableau),
    }
    blob = json.dumps(comp, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16], comp


def _emit(kind, **data):
    try:
        from pystella_tpu.obs import events as _events
        _events.emit(kind, **data)
    except Exception:  # noqa: BLE001 — telemetry must never break a build
        pass


class AutotuneStore:
    """The persistent winner table for ONE device kind.

    :arg root: table directory (default ``PYSTELLA_AUTOTUNE_DIR``,
        itself defaulting to ``bench_results/``; relative paths anchor
        at the repository root).
    :arg device_kind: defaults to the live process's first device's
        ``device_kind`` — which requires jax; pass it explicitly to
        stay jax-free (``show``/``gc`` on a machine without the
        hardware).
    """

    def __init__(self, root=None, device_kind=None):
        if root is None:
            # only the ENV-DEFAULT root anchors at the repo (the
            # ensure_compilation_cache rule); an explicit root resolves
            # like every other artifact path the caller controls
            root = _repo_anchored(
                str(_config.getenv("PYSTELLA_AUTOTUNE_DIR")))
        self.root = os.path.abspath(str(root))
        self.device_kind = (device_kind if device_kind is not None
                            else _device_kind())
        self.path = os.path.join(
            self.root, f"autotune_{_kind_slug(self.device_kind)}.json")

    # -- persistence -------------------------------------------------------

    def _load(self):
        try:
            with open(self.path) as f:
                table = json.load(f)
        except FileNotFoundError:
            return {"schema": SCHEMA_VERSION,
                    "device_kind": self.device_kind, "entries": {}}
        except (OSError, ValueError) as e:
            # a torn/corrupt table is a cache, not data: start fresh
            # but say so (the sweep that repopulates it is cheap next
            # to silently tuning from garbage)
            _emit("autotune_mismatch", path=self.path,
                  problems=[f"unreadable table: {type(e).__name__}: {e}"])
            return {"schema": SCHEMA_VERSION,
                    "device_kind": self.device_kind, "entries": {}}
        table.setdefault("entries", {})
        return table

    def _save(self, table):
        os.makedirs(self.root, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def entries(self):
        """``{digest: entry}`` as persisted (no staleness filtering —
        use :meth:`lookup` for serving decisions)."""
        return dict(self._load()["entries"])

    # -- serving -----------------------------------------------------------

    def _mismatches(self, entry, live=None):
        """The staleness problems that refuse an entry: any
        version/flag component differing from the live process (the
        ``WarmstartStore.load`` rule, verbatim in spirit)."""
        live = live or _live_components()
        problems = []
        for name, val in live["versions"].items():
            have = (entry.get("versions") or {}).get(name)
            if have != val:
                problems.append(f"version {name}: table has {have!r}, "
                                f"process has {val!r}")
        if entry.get("flags") != live["flags"]:
            problems.append(
                f"scheduler flags: table has {entry.get('flags')!r}, "
                f"process has {live['flags']!r}")
        return problems

    def lookup(self, digest, components=None):
        """The winning config for a structural key, or ``None`` — with
        a ``autotune_mismatch`` event when an entry EXISTS but is
        version/flag-stale against the live process (refused, exactly
        like a stale warm-start artifact; the caller falls back to the
        ``choose_blocks`` heuristic)."""
        entry = self._load()["entries"].get(digest)
        if entry is None:
            return None
        problems = self._mismatches(entry)
        if problems:
            _emit("autotune_mismatch", digest=digest, path=self.path,
                  problems=problems,
                  key_kind=(entry.get("key") or {}).get("kind"))
            return None
        if components is not None and entry.get("key") != components:
            # a digest collision with differing structural components
            # would apply a blocking tuned for another kernel — refuse
            _emit("autotune_mismatch", digest=digest, path=self.path,
                  problems=["structural components differ from the "
                            "stored key"])
            return None
        return dict(entry)

    def record(self, digest, components, winner, measurements=None):
        """Persist a sweep winner. ``winner`` carries the tuned config
        (``bx``/``by``/``chunk`` + the measured
        ``ms_per_step``); ``measurements`` optionally keeps the ranked
        candidate table for forensics."""
        table = self._load()
        entry = {
            "key": components,
            **_live_components(),
            "device_kind": self.device_kind,
            "ts": time.time(),
            **winner,
        }
        if measurements is not None:
            entry["swept"] = measurements
        table["entries"][digest] = entry
        self._save(table)
        _emit("autotune_record", digest=digest, path=self.path,
              key_kind=components.get("kind"), **{
                  k: winner.get(k)
                  for k in ("bx", "by", "chunk", "ms_per_step")})
        return entry

    def gc(self, dry_run=False):
        """Remove version/flag-STALE entries (exactly the rule
        :meth:`lookup` refuses on; matching entries are never touched).
        Returns ``(kept, removed)`` digest->entry dicts."""
        table = self._load()
        live = _live_components()
        kept, removed = {}, {}
        for digest, entry in table["entries"].items():
            if self._mismatches(entry, live):
                removed[digest] = entry
            else:
                kept[digest] = entry
        if removed and not dry_run:
            table["entries"] = kept
            self._save(table)
            _emit("autotune_gc", path=self.path, removed=len(removed),
                  kept=len(kept))
        return kept, removed


def default_store():
    """The policy-gated store kernel builds consult: ``None`` when
    ``PYSTELLA_AUTOTUNE=0`` (the tier-1 suite pins it off so ambient
    builds stay hermetic; sweeps and drivers opt in explicitly)."""
    if not _config.get_bool("PYSTELLA_AUTOTUNE"):
        return None
    return AutotuneStore()


def consult(kind, local_shape, h, dtype, nscalars,
            gravitational_waves=False, proc_shape=(1, 1, 1),
            carry_dtype=None, store=None, tableau="LowStorageRK54"):
    """Table lookup for a stepper build: ``(entry, digest)`` with
    ``entry=None`` on miss/stale/policy-off. ``store`` may be an
    explicit :class:`AutotuneStore` (hermetic drivers/tests), ``False``
    to skip, or ``None`` for the env-gated default."""
    digest, comp = stepper_key(
        kind, local_shape, h, dtype, nscalars,
        gravitational_waves=gravitational_waves, proc_shape=proc_shape,
        carry_dtype=carry_dtype, tableau=tableau)
    if store is False:
        return None, digest
    if store is None:
        store = default_store()
    if store is None:
        return None, digest
    return store.lookup(digest, comp), digest


# ---------------------------------------------------------------------------
# sweep: candidate generation + the min-over-rounds paired estimator
# ---------------------------------------------------------------------------

def candidate_configs(local_shape, h, dtype, nscalars,
                      gravitational_waves=False, chunk_depths=(0, 4),
                      max_blocks=4):
    """The sweep grid: for each chunk depth (0 = the pair tier), the
    top ``max_blocks`` feasible ``(bx, by)`` blockings of the WIDEST
    kernel that depth builds, straight from the ``choose_blocks`` VMEM
    model (``feasible_blocks``). Returns a list of
    ``{"bx", "by", "chunk"}`` dicts, heuristic-preferred order first
    per depth."""
    from pystella_tpu.ops.pallas_stencil import feasible_blocks
    F = int(nscalars) + (6 if gravitational_waves else 0)
    itemsize = np.dtype(dtype).itemsize
    out = []
    for chunk in chunk_depths:
        if chunk:
            # chunk kernel: all four arrays windowed, no extras — the
            # same (win_halo, stages) the builder passes in
            # FusedScalarStepper._maybe_build_chunk
            n_win, n_extra, stages = 4 * F, 0, int(chunk)
            win_halo = (int(chunk) // 2) * int(h)
        else:
            # pair kernel: f/dfdt/kf windowed, kdfdt a blockwise extra.
            # stages=1, NOT 2: the builder's pair build uses the
            # default VMEM model, and the candidate set must be exactly
            # the builder's feasible set (else the heuristic's own
            # default blocking could never be measured)
            n_win, n_extra, stages = 3 * F, F, 1
            win_halo = int(h)
        blocks = feasible_blocks(
            n_win, local_shape, int(h), itemsize, n_extra, 4 * F,
            win_halo=win_halo, stages=stages)
        for bx, by in blocks[:int(max_blocks)]:
            out.append({"bx": bx, "by": by, "chunk": int(chunk)})
    return out


def measure_candidates(build_and_step, configs, nsteps=4, rounds=3,
                       warmup=1):
    """Measure ``ms_per_step`` for each candidate with the
    min-over-rounds paired estimator. ``build_and_step(config)``
    returns a runner: a zero-arg callable that runs (and blocks on)
    ``nsteps`` steps of the already-built candidate and RETURNS the
    wall seconds of the stepping alone — build/compile AND any
    host-to-device staging stay outside the runner's own clock (a
    512^3 sweep would otherwise time ~GiB PCIe transfers into every
    candidate). Candidates INTERLEAVE inside each round (the pairing:
    shared-host drift hits every candidate of a round equally); per
    candidate the estimate is the MINIMUM over rounds of that round's
    per-step time — scheduler noise only ever adds time, so the
    minimum converges on the true cost while a single contaminated
    round cannot flip a ranking. Returns the configs with
    ``ms_per_step`` filled in, fastest first; failed candidates carry
    ``error`` instead and sort last."""
    from pystella_tpu.obs.scope import trace_scope
    runners, results = [], []
    for cfg in configs:
        rec = dict(cfg)
        try:
            runners.append(build_and_step(cfg))
        except Exception as e:  # noqa: BLE001 — an infeasible candidate
            # is data (the sweep table records WHY), not a sweep abort
            runners.append(None)
            rec["error"] = f"{type(e).__name__}: {e}"
        results.append(rec)
    for runner in runners:
        if runner is not None:
            for _ in range(max(0, int(warmup))):
                runner()  # compile + steady-state outside the estimate
    rounds_ms = [[] for _ in results]
    for _ in range(max(1, int(rounds))):
        for k, runner in enumerate(runners):
            if runner is None:
                continue
            with trace_scope("autotune_probe"):
                dt_s = runner()
            rounds_ms[k].append(dt_s * 1e3 / max(1, int(nsteps)))
    for rec, samples in zip(results, rounds_ms):
        if samples:
            rec["ms_per_step"] = float(min(samples))
            rec["rounds_ms_per_step"] = [float(s) for s in samples]
    results.sort(key=lambda r: r.get("ms_per_step", float("inf")))
    return results


def _sweep_state(grid_shape, dtype=np.float32, nscalars=2):
    """The deterministic host-side sweep state (one copy per sweep —
    at 512^3 each candidate closure holding its own would cost ~2 GiB
    of identical arrays apiece)."""
    rng = np.random.default_rng(7)
    return {
        "f": 1e-3 * rng.standard_normal(
            (nscalars,) + tuple(grid_shape)).astype(dtype),
        "dfdt": 1e-4 * rng.standard_normal(
            (nscalars,) + tuple(grid_shape)).astype(dtype),
    }


def _build_sweep_stepper(grid_shape, cfg, dtype=np.float32, h=2,
                         nscalars=2, interpret=None, autotune=False,
                         make_state=True):
    """One candidate FusedScalarStepper (the bench preheat system — the
    same potential the retired root-level ``bench_tune.py`` swept) with
    the candidate's blocking/chunk pinned and the autotune consult OFF
    by default (a sweep must measure its own candidates, not last
    quarter's winner). Drivers reuse it with an explicit store + empty
    ``cfg`` to build the TUNED stepper the table round-trip proofs
    dispatch."""
    import jax
    import pystella_tpu as ps
    decomp = ps.DomainDecomposition((1, 1, 1),
                                    devices=jax.devices()[:1])
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=dtype)
    mphi, gsq = 1.20e-6, 2.5e-7

    def potential(f):
        return (mphi**2 / 2 * f[0]**2
                + gsq / 2 * f[0]**2 * f[1]**2) / mphi**2

    sector = ps.ScalarSector(nscalars, potential=potential)
    kwargs = dict(dtype=dtype, interpret=interpret, autotune=autotune)
    if cfg.get("chunk"):
        kwargs.update(chunk_stages=int(cfg["chunk"]),
                      chunk_bx=cfg.get("bx"), chunk_by=cfg.get("by"))
    else:
        kwargs.update(pair_bx=cfg.get("bx"), pair_by=cfg.get("by"))
    stepper = ps.FusedScalarStepper(sector, decomp, grid_shape,
                                    lattice.dx, h, **kwargs)
    if cfg.get("chunk") and stepper._chunk_call is None:
        raise ValueError("chunk kernel infeasible at this config")
    if not make_state:
        return stepper, None
    state0 = {k: decomp.shard(v) for k, v in
              _sweep_state(grid_shape, dtype, nscalars).items()}
    return stepper, state0


def sweep(grid_shape, store=None, nsteps=4, rounds=3,
          chunk_depths=(0, 4), max_blocks=4,
          dtype=np.float32, h=2, nscalars=2, interpret=None, log=print):
    """Sweep the bench preheat system at ``grid_shape`` on the live
    backend, record the winner into ``store`` (default:
    :class:`AutotuneStore` for the live device kind), and return the
    ranked measurement list. The timed quantity is
    ``multi_step(nsteps)`` — the production hot loop, stage pairing or
    chunking across step boundaries included."""
    import jax

    store = store or AutotuneStore()
    configs = candidate_configs(grid_shape, h, dtype, nscalars,
                                chunk_depths=chunk_depths,
                                max_blocks=max_blocks)
    if not configs:
        raise ValueError(
            f"no feasible sweep candidates for lattice {grid_shape} "
            "(choose_blocks VMEM model admits nothing; see "
            "pystella_tpu.advise_shapes)")
    rhs_args = {"a": np.asarray(1.0, dtype), "hubble":
                np.asarray(0.5, dtype)}
    dt = float(0.1 * 5.0 / max(grid_shape))
    # ONE shared host state for every candidate (identical by seed):
    # multi_step donates its input, so each timed run replays from it
    host0 = _sweep_state(grid_shape, dtype, nscalars)

    def build_and_step(cfg):
        stepper, _ = _build_sweep_stepper(
            grid_shape, cfg, dtype=dtype, h=h, nscalars=nscalars,
            interpret=interpret, make_state=False)

        def run():
            # stage OUTSIDE the clock (donation consumes the buffers,
            # so each run needs fresh ones — but the transfer is not
            # what the table should record)
            fresh = {k: jax.device_put(v) for k, v in host0.items()}
            jax.block_until_ready(fresh)
            t0 = time.perf_counter()
            out = stepper.multi_step(fresh, nsteps, 0.0, dt, rhs_args)
            jax.block_until_ready(out)
            return time.perf_counter() - t0
        return run

    results = measure_candidates(build_and_step, configs,
                                 nsteps=nsteps, rounds=rounds)
    for rec in results:
        if "ms_per_step" in rec:
            log(f"  bx={rec['bx']:3d} by={rec['by']:4d} "
                f"chunk={rec['chunk']}: "
                f"{rec['ms_per_step']:8.3f} ms/step")
        else:
            log(f"  bx={rec['bx']:3d} by={rec['by']:4d} "
                f"chunk={rec['chunk']}: "
                f"FAILED {rec['error']}")
    best = next((r for r in results if "ms_per_step" in r), None)
    if best is None:
        raise RuntimeError("every sweep candidate failed to build/run")
    digest, comp = stepper_key(
        "fused_scalar", grid_shape, h, dtype, nscalars)
    sites = float(np.prod(grid_shape))
    winner = {k: best[k] for k in ("bx", "by", "chunk", "ms_per_step")}
    winner["site_updates_per_s"] = sites * 1e3 / best["ms_per_step"]
    store.record(digest, comp, winner, measurements=[
        {k: r.get(k) for k in ("bx", "by", "chunk", "ms_per_step",
                               "error")}
        for r in results])
    _emit("autotune_sweep", grid_shape=list(grid_shape),
          candidates=len(results), path=store.path, **winner)
    log(f"autotune: winner bx={best['bx']} by={best['by']} "
        f"chunk={best['chunk']} "
        f"({best['ms_per_step']:.3f} ms/step) -> {store.path}")
    return results


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cmd_sweep(args):
    if args.dry_run:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    n = 16 if args.dry_run and args.grid is None else (args.grid or 256)
    grid = (n, n, n)
    kwargs = {}
    if args.dry_run:
        kwargs.update(nsteps=2, rounds=2, max_blocks=2)
    store = AutotuneStore(root=args.dir) if args.dir else AutotuneStore()
    print(f"autotune sweep: {n}^3, device kind "
          f"{store.device_kind!r}, table {store.path}")
    sweep(grid, store=store,
          chunk_depths=tuple(int(c) for c in args.chunks.split(",")),
          **kwargs)
    return 0


def _cmd_show(args):
    store = AutotuneStore(root=args.dir or None,
                          device_kind=args.device_kind)
    entries = store.entries()
    if not entries:
        print(f"no entries in {store.path}")
        return 0
    live = _live_components() if args.check else None
    print(f"{store.path}: {len(entries)} entr(ies)")
    for digest, e in sorted(entries.items()):
        key = e.get("key") or {}
        line = (f"  {digest}  {key.get('kind', '?'):13s} "
                f"{'x'.join(map(str, key.get('local_shape', [])))}"
                f" h={key.get('h')} {key.get('dtype')}"
                f" -> bx={e.get('bx')} by={e.get('by')}"
                f" chunk={e.get('chunk')}"
                f" ({e.get('ms_per_step', float('nan')):.3f} ms/step)")
        if live is not None:
            problems = store._mismatches(e, live)
            line += "  STALE" if problems else "  ok"
        print(line)
    return 0


def _cmd_gc(args):
    store = AutotuneStore(root=args.dir or None,
                          device_kind=args.device_kind)
    kept, removed = store.gc(dry_run=args.dry_run)
    verb = "would remove" if args.dry_run else "removed"
    print(f"{store.path}: kept {len(kept)}, {verb} {len(removed)} "
          "stale entr(ies)")
    return 0


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        prog="python -m pystella_tpu.ops.autotune",
        description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    ps_ = sub.add_parser("sweep", help="measure candidates, record the "
                                       "winner for this device kind")
    ps_.add_argument("--grid", type=int, default=None,
                     help="cube edge (default 256; 16 under --dry-run)")
    ps_.add_argument("--chunks", default="0,4",
                     help="comma-separated chunk depths (0 = pair tier)")
    ps_.add_argument("--dir", default=None,
                     help="table directory (default "
                          "$PYSTELLA_AUTOTUNE_DIR -> bench_results/)")
    ps_.add_argument("--dry-run", action="store_true",
                     help="CPU rehearsal: tiny grid, 2 rounds")

    pshow = sub.add_parser("show", help="print the table")
    pshow.add_argument("--dir", default=None)
    pshow.add_argument("--device-kind", default=None,
                       help="table to read (default: live device)")
    pshow.add_argument("--check", action="store_true",
                       help="mark entries stale vs the live process")

    pgc = sub.add_parser("gc", help="remove version/flag-stale entries")
    pgc.add_argument("--dir", default=None)
    pgc.add_argument("--device-kind", default=None)
    pgc.add_argument("--dry-run", action="store_true")

    args = p.parse_args(argv)
    return {"sweep": _cmd_sweep, "show": _cmd_show,
            "gc": _cmd_gc}[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
