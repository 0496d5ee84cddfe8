"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once, in one process,
through the entry point a user calls — ``examples/scalar_preheating.py::
main(argv)``: two-field preheating at 512**3 float32, fused Pallas RK54
stages, WKB initial fluctuations, self-consistent expansion, energy,
statistics, spectra, histogram, HDF5 output, sentinel, checkpoints —

1. as coupled 4-step chunks (the deferred-drag stage-pair kernels) for
   16 steps with a checkpoint every 8, then
2. as the stage-by-stage host loop upstream ships, for 2 steps,

and checks what comes out by the repository's own means: the run events
(completion, no kernel or assembly fallback, every kernel on the
streaming tier with the blocking it took), the HDF5 series, the
checkpoint read back, the Friedmann constraint, the chip's own
``peak_bytes_in_use`` — two steps of the fused stepper against the
plain ``LowStorageRK54`` + XLA ``FiniteDifferencer`` reference from one
seeded state (:func:`fused_parity`), two stages of the stage loop's
in-place programs against undonated ones, bit for bit
(:func:`in_place_stages`), the transform pair's round
trip, and the histogram and one spectrum of a seeded field against
numpy's float64 binning of the same field. With four chips it repeats all of
it on a ``(2, 2, 1)`` mesh and checks the work is spread over them, and
again on the slab decomposition ``(4, 1, 1)`` at ``(2048, 512, 512)``
(upstream's ``-proc 4 1 1 -box 20 5 5``, frozen chunks: the
``preheat-mesh4x-f32`` deployment), the one mesh on which the fused
kernels take the interior/shell halo-overlap split: there it prints the
``overlap_plan`` events and the split's two ``block_choice`` events and
holds four steps of the split against the single launch bit for bit
(:func:`split_against_single`). ``--legs slab,mesh,chip`` picks legs.

It exits non-zero, before building anything, unless jax finds a TPU. Its
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. The readings
it prints on the way (set-up seconds, ms per chunk) are smoke readings,
not a benchmark. The compile cache follows
``pystella_tpu.obs.ensure_compilation_cache``: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``bench_results/xla_cache`` in the checkout.
"""

import hashlib
import json
import logging
import os
import sys
import tempfile
import time
import warnings

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

GRID = (512, 512, 512)
#: the slab leg: 512**3 a chip on (4, 1, 1), the box scaled with the grid
SLAB_GRID, SLAB_BOX = (2048, 512, 512), (20.0, 5.0, 5.0)
#: two steps of the fused stepper may differ from the generic reference
#: by float32 round-off; the compiled kernels read 2.7e-7 at 128**3
PARITY_BOUND = 1e-5
#: warning text that means a tier other than the designed one was built
#: (ops/fused.py and ops/derivs.py)
FALLBACK_WARNINGS = ("falling back", "fusion disabled",
                     "kernels unavailable", "the option is ignored")
#: kernels of the main path, all on the streaming tier at these sizes
MAIN_KERNELS = ("stage", "pair", "coupled_pair", "energy")


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


class watch_fallbacks:
    """Collect, over a ``with`` block, every warning and every
    ``pystella_tpu`` log record that names a tier fallback."""

    def __enter__(self):
        self.messages = []
        self._handler = logging.Handler(logging.WARNING)
        self._handler.emit = lambda rec: self.messages.append(
            rec.getMessage())
        logging.getLogger("pystella_tpu").addHandler(self._handler)
        self._caught = warnings.catch_warnings(record=True)
        self._warned = self._caught.__enter__()
        warnings.simplefilter("always")
        return self

    def __exit__(self, *exc):
        self._caught.__exit__(*exc)
        logging.getLogger("pystella_tpu").removeHandler(self._handler)
        self.messages += [str(w.message) for w in self._warned]
        self.fallbacks = [m for m in self.messages
                          if any(t in m for t in FALLBACK_WARNINGS)]


def run_example(argv, label):
    """``main(argv)`` of the flagship example, in-process; returns the
    final constraint and the fallback warnings it raised (none, on a
    passing run)."""
    sys.path.insert(0, os.path.join(HERE, "examples"))
    try:
        import scalar_preheating
    finally:
        sys.path.pop(0)
    say(f"{label}: scalar_preheating.main({' '.join(argv)})")
    t0 = time.perf_counter()
    with watch_fallbacks() as watch:
        constraint = scalar_preheating.main(argv)
    say(f"{label}: returned in {time.perf_counter() - t0:.1f}s, "
        f"constraint {constraint:.6e}")
    return float(constraint), watch.fallbacks


def check_run_events(events, label, kernels):
    """The run's own record: completed, never aborted, every fused
    kernel the designed tier. Returns the ``{kernel: (bx, by, source)}``
    table and the ``run_complete`` payload."""
    kinds = [e["kind"] for e in events]
    require("run_complete" in kinds, f"{label}: no run_complete event")
    require("run_aborted" not in kinds, f"{label}: run_aborted")
    for kind in ("kernel_fallback", "diverged"):
        hit = [e["data"] for e in events if e["kind"] == kind]
        require(not hit, f"{label}: {kind} event(s): {hit}")
    blocks = {}
    for e in events:
        if e["kind"] != "block_choice":
            continue
        d = e["data"]
        require(d["stencil"] == "StreamingStencil",
                f"{label}: kernel {d['kernel']} took {d['stencil']}")
        require(d["source"] in ("heuristic", "explicit", "split"),
                f"{label}: kernel {d['kernel']} blocked from "
                f"{d['source']} (an uncommitted table or override)")
        blocks[d["kernel"]] = (d["bx"], d["by"], d["source"])
    missing = [k for k in kernels if k not in blocks]
    require(not missing, f"{label}: no block_choice for {missing}")
    for k, (bx, by, source) in blocks.items():
        say(f"{label}: kernel {k}: (bx, by) = ({bx}, {by}), {source}")
    for e in events:
        if e["kind"] == "overlap_plan":
            say(f"{label}: overlap_plan {json.dumps(e['data'])}")
    done = [e for e in events if e["kind"] == "run_complete"][-1]
    done = {"step": done["step"], **done["data"]}
    require(np.isfinite(done["constraint"]),
            f"{label}: constraint {done['constraint']}")
    return blocks, done


def check_hdf5(path, label):
    import h5py
    with h5py.File(path, "r") as f:
        for series in ("energy/total", "energy/constraint",
                       "statistics/f/mean", "spectra/scalar",
                       "spectra/rho", "rho_histogram/linear"):
            require(series in f, f"{label}: {path} lacks {series}")
            data = np.asarray(f[series])
            require(data.shape[0] >= 1 and np.all(np.isfinite(data)),
                    f"{label}: {series} empty or non-finite")
        require(np.isfinite(f.attrs["final_constraint"]),
                f"{label}: final_constraint attribute")
        return int(f["energy/total"].shape[0])


def state_digest(ckpt_dir, decomp, step):
    """sha256 of the final state, read back from the checkpoint the run
    wrote at ``step`` straight onto ``decomp``'s mesh."""
    import pystella_tpu as ps
    with ps.Checkpointer(ckpt_dir) as ckpt:
        require(ckpt.latest_step == step,
                f"newest checkpoint is step {ckpt.latest_step}, the run "
                f"ended at {step}")
        _, state, meta = ckpt.restore(step, mesh=decomp)
    h = hashlib.sha256()
    for name in sorted(state):
        h.update(np.asarray(state[name]).tobytes())
    return h.hexdigest()[:16], state, meta


def generic_stepper(sector, decomp, dx, dt):
    """The plain reference: ``LowStorageRK54`` over the XLA halo
    ``FiniteDifferencer``'s Laplacian, no Pallas kernel in it."""
    import pystella_tpu as ps

    fd = ps.FiniteDifferencer(decomp, 2, dx, mode="halo")
    rhs = ps.compile_rhs_dict(sector.rhs_dict)

    def full_rhs(s, t, a, hubble):
        return rhs(s, t, lap_f=fd.lap(s["f"]), a=a, hubble=hubble)

    return ps.LowStorageRK54(full_rhs, dt=dt)


def build_preheat_step(grid_shape, decomp):
    """The two-field preheating system in float32 on ``decomp``'s mesh,
    stepped by the plain reference; returns ``(stepper, dt)``. The
    re-mesh drill (``tests/test_remesh.py``) rebuilds its step from this
    on each mesh it lands on."""
    import pystella_tpu as ps

    lattice = ps.Lattice(grid_shape, (5.0, 5.0, 5.0), dtype=np.float32)
    dt = np.float32(0.1 * min(lattice.dx))
    mphi, gsq = 1.20e-6, 2.5e-7

    def potential(f):
        phi, chi = f[0], f[1]
        return (mphi**2 / 2 * phi**2 + gsq / 2 * phi**2 * chi**2) / mphi**2

    sector = ps.ScalarSector(2, potential=potential)
    return generic_stepper(sector, decomp, lattice.dx, dt), dt


def seeded_system(grid_shape, seed):
    """A two-field float32 system and a seeded host state for the
    comparisons below: ``(sector, lattice, dt, host)``."""
    import pystella_tpu as ps

    grid_shape = tuple(grid_shape)
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=np.float32)
    dt = np.float32(0.1 * min(lattice.dx))
    sector = ps.ScalarSector(
        2, potential=lambda f: 0.5 * f[0]**2 + 0.125 * f[0]**2 * f[1]**2)
    rng = np.random.default_rng(seed)
    host = {k: 0.1 * rng.standard_normal((2,) + grid_shape).astype(
        np.float32) for k in ("f", "dfdt")}
    return sector, lattice, dt, host


def fused_parity(grid_shape, decomp, nsteps):
    """The compiled Pallas path against the plain reference: ``nsteps``
    of :class:`~pystella_tpu.FusedScalarStepper` ``step()`` vs
    :func:`generic_stepper` from one seeded float32 state, on
    ``decomp``'s mesh. Returns the largest difference relative to each
    field's scale. One path at a time, results staged on the host: at
    512**3 the two paths' device buffers together would crowd a chip."""
    import pystella_tpu as ps

    sector, lattice, dt, host = seeded_system(grid_shape, 21)
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}

    fused = ps.FusedScalarStepper(sector, decomp, grid_shape, lattice.dx,
                                  2, dtype=np.float32, dt=dt)
    generic = generic_stepper(sector, decomp, lattice.dx, dt)

    results = []
    for stepper in (fused, generic):
        state = {k: decomp.shard(v) for k, v in host.items()}
        for _ in range(nsteps):
            state = stepper.step(state, 0.0, dt, args)
        results.append({k: np.asarray(v) for k, v in state.items()})
        del state
    got, ref = results
    maxrel = 0.0
    for k in ref:
        if not (np.all(np.isfinite(got[k])) and np.all(np.isfinite(ref[k]))):
            raise FloatingPointError(f"non-finite {k} after {nsteps} step(s)")
        scale = np.max(np.abs(ref[k])) or 1.0
        maxrel = max(maxrel, float(np.max(np.abs(got[k] - ref[k])) / scale))
    return maxrel


def in_place_stages(grid_shape, decomp):
    """The per-stage programs of a ``donate=True`` stepper, whose stage
    kernel writes ``dfdt`` and the RK registers where it reads them,
    against a ``donate=False`` stepper's (fresh outputs, no alias):
    stages 0 and 1 through ``stepper(stage, ...)`` from one seeded
    float32 state. Returns how many values of the four arrays differ;
    none may. The order in which a block is read and its output written
    back is Mosaic's pipeline's, so this is checked where Mosaic runs.
    One stepper at a time, results staged on the host."""
    import pystella_tpu as ps

    sector, lattice, dt, host = seeded_system(grid_shape, 22)
    results = []
    for donate in (True, False):
        stepper = ps.FusedScalarStepper(
            sector, decomp, grid_shape, lattice.dx, 2, dtype=np.float32,
            dt=dt, donate=donate)
        extras = tuple(stepper._stage_st.extra_defs)
        require(stepper._stage_st.in_place == (extras if donate else ()),
                f"donate={donate}: stage kernel in place for "
                f"{stepper._stage_st.in_place}")
        carry = {k: decomp.shard(v) for k, v in host.items()}
        for stage in (0, 1):
            carry = stepper(stage, carry, 0.0, dt, a=np.float64(1.0),
                            hubble=np.float64(0.1))
        results.append([np.asarray(x) for tree in carry
                        for _, x in sorted(tree.items())])
        del carry
    return sum(int((a != b).sum()) + int(not np.all(np.isfinite(a)))
               for a, b in zip(*results))


def split_against_single(grid_shape, decomp, nsteps=4):
    """On an x-only mesh: ``multi_step`` of ``nsteps`` through the
    interior/shell halo-overlap split (the default there) against the
    slab-fed single launch (``overlap=False``), from one seeded float32
    state. Every output element sees the same taps and arithmetic on
    both paths, so none may differ; that Mosaic compiles the two
    kernels' bodies alike is checked where Mosaic runs. Returns
    ``(values that differ, the split's plan events)``. One stepper at a
    time, results staged on the host."""
    import pystella_tpu as ps
    from pystella_tpu import obs

    sector, lattice, dt, host = seeded_system(grid_shape, 23)
    args = {"a": np.float32(1.0), "hubble": np.float32(0.5)}
    results, plans = [], []
    tap = obs.get_log().subscribe(
        lambda rec: plans.append(rec["data"])
        if rec["kind"] == "overlap_plan" else None)
    try:
        for overlap in (None, False):
            stepper = ps.FusedScalarStepper(
                sector, decomp, grid_shape, lattice.dx, 2,
                dtype=np.float32, dt=dt, donate=True, overlap=overlap)
            state = {k: decomp.shard(v) for k, v in host.items()}
            state = stepper.multi_step(state, nsteps, 0.0, dt, args)
            results.append({k: np.asarray(v) for k, v in state.items()})
            del state, stepper
    finally:
        obs.get_log().unsubscribe(tap)
    differing = sum(
        int((results[0][k] != results[1][k]).sum())
        + int(not np.all(np.isfinite(results[0][k]))) for k in results[0])
    return differing, plans


def run_leg(grid_shape, proc_shape, workdir, box=(5.0, 5.0, 5.0),
            chunk_mode="coupled"):
    """The whole smoke on one mesh: both driver invocations, their
    checks, the checkpoint read-back and the parity comparison (at the
    run's own lattice: the generic path fits the chip at 512**3). Raises
    :class:`SmokeFailure` when a check does not hold; returns the leg's
    summary dict."""
    import jax
    import pystella_tpu as ps
    from pystella_tpu import obs
    from pystella_tpu.obs.events import read_events

    grid_shape = tuple(int(n) for n in grid_shape)
    proc_shape = tuple(int(p) for p in proc_shape)
    ndev = int(np.prod(proc_shape))
    devices = jax.devices()[:ndev]
    label = "x".join(str(p) for p in proc_shape)
    os.makedirs(workdir, exist_ok=True)
    dt = 0.1 * min(b / n for b, n in zip(box, grid_shape))
    slab = proc_shape[0] > 1 and proc_shape[1:] == (1, 1)
    common = ["--grid-shape", *map(str, grid_shape),
              "--proc-shape", *map(str, proc_shape),
              "--box-dim", *map(str, box),
              "--dtype", "float32", "--halo-shape", "2", "--fused",
              "--forensics-dir", os.path.join(workdir, "forensics")]

    # 1. coupled chunks: 16 steps as 4 chunks of 4, checkpoint every 8
    nsteps, chunk = 16, 4
    ev_path = os.path.join(workdir, "chunk_events.jsonl")
    ckpt_dir = os.path.join(workdir, "ckpt")
    _, bad = run_example(
        common + ["--chunk-steps", str(chunk), "--chunk-mode", chunk_mode,
                  "--end-time", repr((nsteps - 0.5) * dt),
                  "--checkpoint-dir", ckpt_dir,
                  "--checkpoint-interval", "8",
                  "--outfile", os.path.join(workdir, "chunk"),
                  "--event-log", ev_path], f"{label} chunked")
    require(not bad, f"{label} chunked: fallback warning(s): {bad}")
    events = read_events(ev_path)
    # frozen chunks are multi_step's pair kernels alone; on an x-only
    # mesh each kernel without sums is the split's two beside itself
    kernels = (MAIN_KERNELS if chunk_mode == "coupled"
               else ("stage", "pair"))
    if slab:
        kernels += tuple(f"{k}_{part}" for k in ("stage", "pair")
                         for part in ("interior", "shell"))
    blocks, done = check_run_events(events, f"{label} chunked", kernels)
    require(done["step"] == nsteps,
            f"{label} chunked: ended at step {done['step']}, "
            f"expected {nsteps}")
    rows = check_hdf5(os.path.join(workdir, "chunk.h5"), f"{label} chunked")
    saves = [e for e in events if e["kind"] == "checkpoint_durable"]
    require(saves, f"{label} chunked: no durable checkpoint")
    chunk_ms = [e["data"]["ms"] for e in events if e["kind"] == "step_time"]
    require(chunk_ms, f"{label} chunked: no step_time event")

    # the final state, read back from the checkpoint onto the same mesh
    decomp = ps.DomainDecomposition(proc_shape, devices=devices)
    digest, state, meta = state_digest(ckpt_dir, decomp, nsteps)
    shards = state["f"].addressable_shards
    local = (2,) + tuple(n // p for n, p in zip(grid_shape, proc_shape))
    require(len(state["f"].sharding.device_set) == ndev,
            f"{label}: restored state on "
            f"{len(state['f'].sharding.device_set)} device(s)")
    require(all(tuple(s.data.shape) == local for s in shards),
            f"{label}: restored shards {[s.data.shape for s in shards]}")
    for name in sorted(state):
        require(bool(np.all(np.isfinite(np.asarray(state[name])))),
                f"{label}: restored {name} non-finite")
    del state
    say(f"{label}: final state digest {digest} (checkpoint step "
        f"{nsteps}, t = {meta['t']:.6f}, a = {meta['a']:.9f})")

    # the run's own account of where its state lived
    require(done["devices"] == ndev
            and tuple(done["shard_shape"]) == local,
            f"{label}: run state on {done['devices']} device(s), shards "
            f"{done['shard_shape']}; expected {ndev} x {local}")
    if ndev > 1:
        require(done["halo_bytes"] > 0,
                f"{label}: no halo traffic traced on a sharded mesh")

    # 2. the stage-by-stage host loop, 2 steps
    ev2_path = os.path.join(workdir, "stage_events.jsonl")
    _, bad = run_example(
        common + ["--end-time", repr(1.5 * dt),
                  "--outfile", os.path.join(workdir, "stage"),
                  "--event-log", ev2_path], f"{label} stage loop")
    require(not bad, f"{label} stage loop: fallback warning(s): {bad}")
    _, done2 = check_run_events(read_events(ev2_path),
                                f"{label} stage loop", ("stage",))
    require(done2["step"] == 2, f"{label} stage loop: ended at step "
                                f"{done2['step']}, expected 2")
    check_hdf5(os.path.join(workdir, "stage.h5"), f"{label} stage loop")
    obs.configure(None)

    # 3. parity against the plain reference, on the same devices
    with watch_fallbacks() as watch:
        maxrel = fused_parity(grid_shape, decomp, nsteps=2)
    require(not watch.fallbacks,
            f"{label} parity: fallback warning(s): {watch.fallbacks}")
    say(f"{label}: parity at {grid_shape}: max relative difference "
        f"{maxrel:.3e} (bound {PARITY_BOUND:g})")
    require(maxrel <= PARITY_BOUND,
            f"{label}: parity {maxrel:.3e} over {PARITY_BOUND:g}")

    # 3b. the stage loop's in-place programs against undonated ones
    differing = in_place_stages(grid_shape, decomp)
    say(f"{label}: stages 0 and 1 at {grid_shape}, in place against "
        f"fresh outputs: {differing} value(s) differ")
    require(differing == 0,
            f"{label}: the in-place stage programs differ from the "
            f"undonated ones in {differing} value(s)")

    # 3c. an x-only mesh: the overlap split against the single launch
    split_differing = None
    if slab:
        split_differing, plans = split_against_single(grid_shape, decomp)
        for d in plans:
            say(f"{label}: overlap_plan {json.dumps(d)}")
        paths = [(d["kernel"], d["path"], d.get("reason")) for d in plans]
        require(("pair", "split", None) in paths
                and ("pair", "single", "off") in paths,
                f"{label}: the two steppers' plans read {paths}")
        say(f"{label}: 4 steps at {grid_shape}, the split against the "
            f"single launch: {split_differing} value(s) differ")
        require(split_differing == 0,
                f"{label}: the overlap split differs from the single "
                f"launch in {split_differing} value(s)")

    # 4. the transform pair the seeded state and the spectra go through:
    # back is what went in, twice the same (XLA's own inverse real
    # transform is neither on this chip: PERF.md section 6, PR 28)
    fft = ps.DFT(decomp, grid_shape=grid_shape, dtype=np.float32,
                 real_inverse="matmul")
    x = jax.jit(lambda k: jax.random.normal(k, grid_shape, np.float32),
                out_shardings=decomp.sharding(0))(jax.random.key(5))
    fk = fft.dft(x)
    back, again = fft.idft(fk), fft.idft(fk)
    trip = float(abs(back - x).max() / abs(x).max())
    say(f"{label}: idft(dft(x)) against x at {grid_shape}: {trip:.3e} "
        f"(bound {PARITY_BOUND:g})")
    require(trip <= PARITY_BOUND,
            f"{label}: transform round trip {trip:.3e} over "
            f"{PARITY_BOUND:g}")
    require(bool((back == again).all()),
            f"{label}: two inverse transforms of one input differ")
    del back, again

    # 5. the binning behind the histogram and every spectrum (the
    # one-hot contraction, ops.histogram) against numpy's float64
    # binning of the same field, fetched: counts equal, sums to 1e-5
    num_bins = 1000
    lo, hi = float(x.min()), float(x.max())
    pos = jax.jit(lambda f: (f - lo) / (hi - lo) * num_bins)(x)
    hists = ps.Histogrammer(
        decomp, {"counts": (ps.Field("f"), 1),
                 "sums": (ps.Field("f"), ps.Field("w"))}, num_bins)(
        f=pos, w=x * x)
    index = np.clip(np.floor(np.asarray(pos)), 0, num_bins - 1).astype(
        np.int64).ravel()
    xh = np.asarray(x, np.float64).ravel()
    counts = np.bincount(index, minlength=num_bins)
    sums = np.bincount(index, weights=xh * xh, minlength=num_bins)
    require(np.array_equal(hists["counts"], counts),
            f"{label}: histogram counts differ from numpy's in "
            f"{int((hists['counts'] != counts).sum())} of {num_bins} bins")
    hist_gap = float(np.max(np.abs(hists["sums"] - sums)
                            / np.where(sums == 0, 1.0, sums)))
    del pos, index, xh
    lattice = ps.Lattice(grid_shape, (5.0,) * 3, dtype=np.float32)
    spectra = ps.PowerSpectra(decomp, fft, lattice.dk, lattice.volume)
    power = spectra.bin_power(fk, k_power=3)
    weights = (np.asarray(spectra._counts, np.float64)
               * np.asarray(spectra._kmags, np.float64)**3
               * np.abs(np.asarray(fk).astype(np.complex128))**2)
    expected = np.bincount(
        np.asarray(spectra._bin_idx).ravel(), weights=weights.ravel(),
        minlength=spectra.num_bins) / spectra.bin_counts
    spec_gap = float(np.max(np.abs(power - expected)
                            / np.where(expected == 0, 1.0, expected)))
    say(f"{label}: binning at {grid_shape} against numpy's float64: "
        f"{num_bins}-bin counts equal, weighted sums {hist_gap:.3e}, "
        f"{spectra.num_bins}-bin spectrum {spec_gap:.3e} "
        f"(bound {PARITY_BOUND:g})")
    require(max(hist_gap, spec_gap) <= PARITY_BOUND,
            f"{label}: binned sums {hist_gap:.3e}, {spec_gap:.3e} over "
            f"{PARITY_BOUND:g}")
    del x, fk, weights

    # the chip's own memory account (None on a backend without one —
    # which would mean this did not run on the chip)
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        peak = stats.get("peak_bytes_in_use") if stats else None
        peaks.append(peak)
    say(f"{label}: peak_bytes_in_use per device: {peaks}")
    return {
        "proc_shape": list(proc_shape), "grid_shape": list(grid_shape),
        "steps": nsteps, "chunk_steps": chunk,
        "constraint": done["constraint"],
        "stage_loop_constraint": done2["constraint"],
        "digest": digest, "parity_maxrel": maxrel,
        "in_place_differing": differing,
        "split_differing": split_differing,
        "blocks": {k: list(v) for k, v in blocks.items()},
        "energy_rows": rows, "checkpoints": len(saves),
        "last_chunk_ms": chunk_ms[-1],
        "halo_bytes": done["halo_bytes"],
        "peak_bytes_in_use": peaks,
    }


def check_peaks(leg):
    """Every device reports a peak, and on a mesh the largest is within
    1.5x of the smallest — everything on device 0 is the failure to
    look for."""
    peaks = leg["peak_bytes_in_use"]
    require(all(isinstance(p, int) and p > 0 for p in peaks),
            f"peak_bytes_in_use {peaks}: not every device reports one")
    require(max(peaks) <= 1.5 * min(peaks),
            f"peak_bytes_in_use {peaks}: spread over 1.5x")


def main(argv=None):
    import argparse
    import jax
    import jaxlib
    ap = argparse.ArgumentParser()
    ap.add_argument("--legs", default="slab,mesh,chip",
                    help="comma-separated: slab (4,1,1), mesh (2,2,1), "
                    "chip (1,1,1); the two meshes need four chips")
    wanted = set(ap.parse_args(argv).legs.split(","))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: jax found {device}, not a TPU; nothing run",
              file=sys.stderr)
        return 1

    import pystella_tpu  # noqa: F401 — fail here, not mid-run, without it
    from importlib import metadata
    from pystella_tpu import obs
    from pystella_tpu.obs import ledger

    cache_dir = obs.ensure_compilation_cache()
    say(f"device {device}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {metadata.version('libtpu')}; "
        f"compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir))} entries)")
    say(f"device order: {[d.id for d in jax.devices()]}, coords "
        f"{[getattr(d, 'coords', None) for d in jax.devices()]}")
    require(any(key in dev.device_kind for key in ledger.HBM_PEAK_GBPS),
            f"device kind {dev.device_kind!r} matches no key of "
            "obs.ledger.HBM_PEAK_GBPS")

    legs = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if len(jax.devices()) >= 4:
            # first: an allocator's peak is for the life of the process,
            # and the one-chip leg leaves device 0's far above the rest
            if "mesh" in wanted:
                legs.append(run_leg(GRID, (2, 2, 1),
                                    os.path.join(tmp, "4chip")))
                check_peaks(legs[-1])
            if "slab" in wanted:
                legs.append(run_leg(SLAB_GRID, (4, 1, 1),
                                    os.path.join(tmp, "slab"),
                                    box=SLAB_BOX, chunk_mode="frozen"))
                check_peaks(legs[-1])
        else:
            say(f"four-chip legs not run: {len(jax.devices())} device(s)")
        if "chip" in wanted:
            legs.append(run_leg(GRID, (1, 1, 1),
                                os.path.join(tmp, "1chip")))
            check_peaks(legs[-1])

    totals = obs.compile_totals()
    say(f"set-up: trace {totals['trace_s']:.1f}s + compile "
        f"{totals['compile_s']:.1f}s; compile cache hits "
        f"{totals['cache_hits']}, misses {totals['cache_misses']}")
    for leg in legs:
        say(f"{leg['proc_shape']}: last {leg['chunk_steps']}-step chunk "
            f"{leg['last_chunk_ms']:.1f} ms (a smoke reading, output "
            "and checkpoint included — not a benchmark)")
    print(json.dumps({"summary": {
        "legs": legs,
        "setup_s": {"trace": totals["trace_s"],
                    "compile": totals["compile_s"]},
        "cache": {"dir": cache_dir, "hits": totals["cache_hits"],
                  "misses": totals["cache_misses"]},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu")}}}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
