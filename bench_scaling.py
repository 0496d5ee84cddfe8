"""Weak-scaling benchmark: constant per-device load over growing meshes.

Targets BASELINE.json's second metric — >=85% weak-scaling efficiency from
8 to 64 chips — by timing the headline preheating step (the same model
``bench.py`` builds) with a fixed per-device block while the x-sharded
mesh grows: ideal weak scaling keeps ms/step constant, so
``efficiency(N) = t(1) / t(N)``. The stencil's communication is two
(h, Y, Z) halo slabs per stage per neighbor over ICI, independent of mesh
size, so the model predicts near-flat scaling; this harness measures it.

It takes the devices jax finds and refuses anything but TPUs: virtual
CPU "devices" share the same physical cores, and a ratio of their times
is not a scaling number.

Prints one JSON line per mesh size (each naming the device) and a final
efficiency line.

Usage: ``python bench_scaling.py [--local 64] [--devices 1,2,4,8]
[--profile DIR]``. ``--profile`` wraps the LARGEST mesh's timed window in a
``jax.profiler`` capture; the parsed per-scope durations land in the
run-event log (``PYSTELLA_EVENT_LOG``) as a ``trace_summary`` event —
the at-scale halo-exchange/stencil breakdown the perf ledger cites.
"""

import json
import os
import sys
import time

import numpy as np
import jax

from bench import build_gw_step, build_preheat_step, device_block
from pystella_tpu.ops.pallas_stencil import LANE


def _factor2(n):
    """n = px * py with px >= py, as square as possible (the 2-D mesh
    shape the scaling model assumes at 64 chips: (8, 8, 1))."""
    best = (n, 1)
    for p in range(1, int(n**0.5) + 1):
        if n % p == 0:
            best = (n // p, p)
    return best


def _profiled_extra_window(profile_dir, tag, body):
    """Run ``body()`` once under a jax.profiler capture (a SEPARATE,
    untimed window: tracing overhead must never sit inside the measured
    loop — it would bias the efficiency ratio for whichever mesh gets
    profiled)."""
    if not profile_dir:
        return
    from pystella_tpu.obs import trace as obs_trace
    with obs_trace.capture(os.path.join(profile_dir, tag), label=tag):
        body()


def run_mesh(ndev, local_n, nsteps=10, nwarmup=2, dtype=np.float32,
             system="scalar", profile_dir=None):
    import pystella_tpu as ps

    if system == "gw":
        # the GW system rides the 2-D-mesh FusedPreheatStepper path —
        # the configuration that must carry a 512^3 GW production run
        # (single-chip is HBM-infeasible there; VERDICT r4 #6)
        px, py = _factor2(ndev)
        # sharded-y streaming windows need local Y % 8 == 0: round UP
        # so the claimed kernel tier is the one actually timed (the
        # caller gets the true grid back for sites accounting)
        local_y = -(-local_n // 8) * 8
        grid_shape = (local_n * px, local_y * py, local_n)
        decomp = ps.DomainDecomposition((px, py, 1),
                                        devices=jax.devices()[:ndev])
        stepper, state, dt = build_gw_step(grid_shape, dtype,
                                           decomp=decomp)
    else:
        grid_shape = (local_n * ndev, local_n, local_n)
        decomp = ps.DomainDecomposition((ndev, 1, 1),
                                        devices=jax.devices()[:ndev])
        # coupled_multi_step is a fused-stepper driver (and builds its
        # own ICs); otherwise the fused tier runs where its compiled
        # kernels can — a lane-aligned z axis — and the generic XLA
        # path elsewhere
        coupled = system == "coupled"
        stepper, state, dt = build_preheat_step(
            grid_shape, dtype, decomp=decomp,
            fused=coupled or local_n % LANE == 0,
            make_state=not coupled)
    t = dtype(0.0)

    if system == "coupled":
        # the energy-coupled science driver over the mesh: deferred-
        # drag pair kernels + one psum'ed energy feedback per stage
        # (the per-stage barrier the physics requires) — weak-scaling
        # evidence for the ACCURATE chunked path, not just the
        # frozen-background bench loop
        # near-homogeneous preheating ICs (random noise is violently
        # unstable under the g^2 phi^2 chi^2 coupling — same choice as
        # bench.py run_coupled)
        rng = np.random.default_rng(31)
        f0v, df0v = [0.193, 0.0], [-0.142231, 0.0]
        state = {
            "f": decomp.shard(np.stack(
                [np.full(grid_shape, f0v[i], dtype)
                 + 1e-4 * rng.standard_normal(grid_shape).astype(dtype)
                 for i in range(2)])),
            "dfdt": decomp.shard(np.stack(
                [np.full(grid_shape, df0v[i], dtype)
                 + 1e-4 * rng.standard_normal(grid_shape).astype(dtype)
                 for i in range(2)])),
        }

        def chunk(st):
            expand = ps.Expansion(0.0287, ps.LowStorageRK54)
            return stepper.coupled_multi_step(st, nsteps, expand, 0.0,
                                              dt)
        for _ in range(nwarmup):
            state = chunk(state)
        jax.block_until_ready(state)
        start = time.perf_counter()
        state = chunk(state)
        jax.block_until_ready(state)
        ms = (time.perf_counter() - start) / nsteps * 1e3

        def _profiled_chunk():
            with ps.obs.trace_scope("bench_step"):
                jax.block_until_ready(chunk(state))
        _profiled_extra_window(profile_dir, f"coupled-{ndev}dev",
                               _profiled_chunk)
        return ms, float(np.prod(grid_shape))

    args = {"a": dtype(1.0), "hubble": dtype(0.5)}
    # donate the state so peak HBM stays at one state (stepper.step's
    # own jit cannot donate: step() callers may reuse their input)
    step = jax.jit(lambda s: stepper.step(s, t, dt, args),
                   donate_argnums=0)

    for _ in range(nwarmup):
        state = step(state)
    jax.block_until_ready(state)
    start = time.perf_counter()
    for _ in range(nsteps):
        state = step(state)
    jax.block_until_ready(state)
    ms = (time.perf_counter() - start) / nsteps * 1e3

    def _profiled_steps():
        s = state
        for _ in range(nsteps):
            # host-side span per step, beside the device rows
            with ps.obs.trace_scope("bench_step"):
                s = step(s)
        jax.block_until_ready(s)
    _profiled_extra_window(profile_dir, f"{system}-{ndev}dev",
                           _profiled_steps)
    return ms, float(np.prod(grid_shape))


def main():
    local_n = 64
    dev_counts = None
    system = "scalar"
    argv = sys.argv[1:]
    if "--local" in argv:
        local_n = int(argv[argv.index("--local") + 1])
    if "--devices" in argv:
        dev_counts = [int(d) for d in
                      argv[argv.index("--devices") + 1].split(",")]
    if "--system" in argv:
        system = argv[argv.index("--system") + 1]
        assert system in ("scalar", "gw", "coupled"), system
    profile_dir = None
    if "--profile" in argv:
        profile_dir = argv[argv.index("--profile") + 1]
    dev = device_block()
    if dev["platform"] != "tpu":
        raise SystemExit(f"bench_scaling.py measures on TPUs; jax found "
                         f"{dev}")
    # persistent compilation cache: a weak-scaling sweep recompiles the
    # same per-device program shapes run after run
    from pystella_tpu.obs.memory import ensure_compilation_cache
    ensure_compilation_cache()
    navail = len(jax.devices())
    if dev_counts is None:
        dev_counts = [d for d in (1, 2, 4, 8, 16, 32, 64) if d <= navail]
    else:
        dropped = [d for d in dev_counts if d > navail]
        if dropped:
            print(f"# dropping {dropped}: only {navail} devices available",
                  file=sys.stderr, flush=True)
        dev_counts = [d for d in dev_counts if d <= navail]
    if not dev_counts:
        raise SystemExit("no runnable device counts")
    sysname = "" if system == "scalar" else f" {system}"
    times = {}
    for ndev in dev_counts:
        # profile only the largest mesh: that's the configuration whose
        # halo/stencil breakdown the scaling claim rests on
        ms, sites = run_mesh(
            ndev, local_n, system=system,
            profile_dir=profile_dir if ndev == max(dev_counts) else None)
        times[ndev] = ms
        print(json.dumps({
            "metric": f"weak-scaling{sysname} {ndev} dev "
                      f"({local_n}^3/dev)",
            "value": ms, "unit": "ms/step",
            "vs_baseline": None, "device": dev}), flush=True)
        print(f"# {ndev} devices: {ms:8.2f} ms/step "
              f"({sites * 1e3 / ms:.3e} site-updates/s total)",
              file=sys.stderr, flush=True)

    n0, n1 = min(times), max(times)
    eff = times[n0] / times[n1]
    print(json.dumps({
        "metric": f"weak-scaling{sysname} efficiency {n0}->{n1} dev",
        "value": eff, "unit": "fraction", "vs_baseline": eff / 0.85,
        "device": dev}), flush=True)


if __name__ == "__main__":
    main()
