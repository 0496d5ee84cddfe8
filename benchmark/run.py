"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Set-up (seeded state, the cell's own programs compiled or
read from the cache, the first steps, one warm-up block and output), then
a window of whole units of fixed work, then the check against the plain
reference. The last line of standard output is the result object; what
else a reader wants (units, steps, tier, cache, each number compared
beside its limit) goes on earlier lines. See ``benchmark/README.md``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: caches, the profile and the HDF5 file of a run: inside the checkout,
#: at a fixed path (the compile cache's key holds the path)
SCRATCH = os.path.join(ROOT, ".benchmark_cache")
GIB = float(2**30)


def say(msg):
    print(f"[bench +{time.perf_counter() - T0:7.2f}s] {msg}", flush=True)


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_named(kind, name):
    """``benchmark/<kind>/<name>.json`` — a configuration, a traffic mix,
    a kernel's byte count or a metric's reader, found by name."""
    return read_json("benchmark", kind, name + ".json")


def load_dir(kind):
    folder = os.path.join(HERE, kind)
    return {f[:-5]: load_named(kind, f[:-5])
            for f in sorted(os.listdir(folder)) if f.endswith(".json")}


class CompileCounter:
    """Compile activity as JAX's own monitoring reports it: seconds of
    tracing and lowering, seconds of backend compilation, and how many
    programs were asked of the compiler or the persistent cache."""

    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        from jax import monitoring
        self.trace_s = self.compile_s = 0.0
        self.programs = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in self.TRACE:
            self.trace_s += float(duration)
        elif event == self.BACKEND:
            self.compile_s += float(duration)
            self.programs += 1

    def _event(self, event, **kw):
        key = self.CACHE.get(event)
        if key:
            setattr(self, key, getattr(self, key) + 1)


class Watchdog(threading.Thread):
    """Sleeps 20 ms at a time and notes each wake-up that came over 50 ms
    late. A unit that ran long while this thread woke on time waited for
    the device; one in which this thread was late too sat in a process, or
    on a machine, that was not being run."""

    NAP, LATE = 0.02, 0.05

    def __init__(self):
        super().__init__(daemon=True)
        self.late, self.done = [], threading.Event()

    def run(self):
        while not self.done.is_set():
            t0 = time.perf_counter()
            time.sleep(self.NAP)
            late = time.perf_counter() - t0 - self.NAP
            if late > self.LATE:
                self.late.append((t0, late))

    def late_inside(self, t0, t1):
        return sum(late for t, late in self.late if t0 <= t <= t1)


def say_long_units(kind, seconds, watchdog):
    """Units that ran over 3 % longer than the median of their kind, each
    with how late the watchdog woke inside it."""
    if len(seconds) < 3:
        return
    median = statistics.median(s for _, _, s in seconds)
    for t0, t1, s in seconds:
        if s > 1.03 * median:
            say(f"long {kind}: {s:.4f}s against a median of {median:.4f}s; "
                f"the watchdog woke {watchdog.late_inside(t0, t1):.3f}s late "
                "inside it")


def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def peak_bytes(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def copy_probe(device, nbytes):
    """Sustained copy bandwidth of one chip, GB/s, bytes read plus bytes
    written: ``y = x + 1`` over ``nbytes`` (at least four times the
    128 MiB of VMEM), the best of five after a warm-up."""
    import jax
    import jax.numpy as jnp
    n = nbytes // 4
    x = jax.device_put(jnp.zeros((n,), jnp.float32), device)
    f = jax.jit(lambda v: v + 1.0)
    jax.block_until_ready(f(x))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(4):
            y = f(x)
        jax.block_until_ready(y)
        best = min(best, (time.perf_counter() - t0) / 4)
    del x, y
    return 2 * n * 4 / best / 1e9


def run_window(driver, spans, schedule, seconds, profile_dir):
    """The schedule's units, whole, until ``seconds`` have passed (and at
    least one cycle). With ``profile_dir`` the first cycle runs under the
    profiler. Returns ``(units, found, failed)``; a unit is ``(kind, index,
    start, end, ok)`` on the host clock."""
    import jax
    from pystella_tpu.obs.sentinel import SimulationDiverged
    units, found, failed = [], {}, 0
    tracing = profile_dir is not None
    if tracing:
        jax.profiler.start_trace(profile_dir)
    t_start = time.perf_counter()
    i = 0
    while time.perf_counter() - t_start < seconds or i < len(schedule):
        kind = schedule[i % len(schedule)]
        spans.unit = (kind, i)
        with jax.profiler.TraceAnnotation(f"bench:unit:{kind}"):
            t0 = time.perf_counter()
            try:
                if kind == "block":
                    driver.block()
                else:
                    for k, v in driver.output().items():
                        found[k] = found.get(k, 0) + v
                jax.block_until_ready(driver.state)
            except (FloatingPointError, SimulationDiverged) as e:
                say(f"unit {i} ({kind}) failed: {e}")
                failed += 1
                units.append((kind, i, t0, time.perf_counter(), False))
                break
            units.append((kind, i, t0, time.perf_counter(), True))
        i += 1
        if tracing and i == len(schedule):
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    try:
        driver.finish()
    except (FloatingPointError, SimulationDiverged) as e:
        say(f"final health check failed: {e}")
        failed += 1
    return units, found, failed


def reduce_trace(profile_dir, save_to, system, peak_gbps, steps_traced,
                 meta):
    """The traced cycle's numbers and breakdown (empty where the profiler
    wrote nothing); ``save_to`` keeps the compact record."""
    from benchmark import trace_reduce
    rec = trace_reduce.record(profile_dir)
    if rec is None:
        say("trace: the profiler wrote no trace")
        return {}, None
    rec["meta"] = dict(meta, local_shape=list(system.local_shape),
                       steps_traced=steps_traced)
    if save_to:
        os.makedirs(os.path.dirname(os.path.abspath(save_to)), exist_ok=True)
        trace_reduce.save(rec, save_to)
    traced, notes, breakdown = trace_reduce.reduce(
        rec, load_dir("kernels"), system.local_shape, peak_gbps,
        steps_traced)
    for note in notes:
        say("trace: " + note)
    return traced, breakdown or None


def main(argv=None, patch=None):
    """``patch(system, driver)`` is for the self-test that breaks the
    timed path underneath and must see ``correct`` come out false."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip (CPU, interpret mode): finds "
                    "wrong paths and control flow; prints no device metric")
    ap.add_argument("--config-override", default=None, metavar="JSON",
                    help="with --rehearse only: keys of the configuration "
                    "to replace, e.g. a tiny grid")
    ap.add_argument("--save-trace", default=None, metavar="PATH",
                    help="with --trace 1: keep the compact trace record "
                    "(.json.gz)")
    args = ap.parse_args(argv)

    bench = read_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = read_json(config["file"])
    traffic = load_named("traffic", cell["traffic"])
    seconds = float(bench["run_seconds"] if args.seconds is None
                    else args.seconds)
    if args.config_override:
        if not args.rehearse:
            raise SystemExit("--config-override is for --rehearse only")
        config.update(json.loads(args.config_override))

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(SCRATCH, "xla"))
    workdir = os.path.join(SCRATCH, "run")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    import jax
    import numpy as np

    devices = jax.devices()
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices)}
    chips = int(cell["chips"])
    if not args.rehearse and dev0.platform != "tpu":
        print(f"benchmark: jax found {device}, not a TPU; nothing run",
              file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"benchmark: cell needs {chips} chip(s), jax found {device}",
              file=sys.stderr)
        return 3
    peaks = read_json("benchmark", "peaks.json")["devices"]
    if not args.rehearse and dev0.device_kind not in peaks:
        print(f"benchmark: device kind {dev0.device_kind!r} is not in "
              "benchmark/peaks.json", file=sys.stderr)
        return 3

    counter = CompileCounter()
    import pystella_tpu as ps
    from benchmark import check, drivers, readers
    from benchmark.spans import Spans
    from benchmark.system import System

    cache_dir = ps.obs.ensure_compilation_cache()
    say(f"cell {cell['name']} seed {args.seed} seconds {seconds:g} trace "
        f"{args.trace}; device {device}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir))} entries)")
    events = []
    ps.obs.get_log().subscribe(
        lambda rec: events.append(rec) if rec["kind"] in check.WATCHED
        else None)

    # ---- set-up ----------------------------------------------------------
    system = System(config, devices[:chips],
                    outfile=os.path.join(workdir, "output"))
    spans = Spans(sync=bool(args.trace))
    driver = drivers.load(traffic["driver"])(system, traffic, spans)
    if patch is not None:
        patch(system, driver)
    state, expand, energy = system.initial_state(args.seed)
    driver.start(state, expand, energy)
    del state
    background = driver.background()
    say(f"built and initialised in {time.perf_counter() - T0:.2f}s; "
        f"peak_bytes_in_use so far {peak_bytes(system.devices)}; first "
        f"steps: {driver.first_nsteps}")
    # the first steps, from the seeded state, through the window's own
    # calls on the object the window will drive, their statistics row and
    # one output on the state they reached: kept for the check. That
    # output and one block are also the warm-up.
    spans.unit = ("warmup", -1)
    schedule = list(traffic["schedule"])
    first, t_snap = check.first_answers(driver, "output" in schedule)
    driver.block()
    jax.block_until_ready(driver.state)
    setup_s = time.perf_counter() - T0 - t_snap
    say(f"peak_bytes_in_use after the warm-up {peak_bytes(system.devices)}")
    say(f"set-up {setup_s:.2f}s (snapshot for the check {t_snap:.2f}s not "
        f"counted): trace {counter.trace_s:.2f}s, compile "
        f"{counter.compile_s:.2f}s, {counter.programs} program(s), cache "
        f"hits {counter.hits} misses {counter.misses}")
    setup_counts = (counter.trace_s, counter.compile_s)

    # ---- the window: whole units of fixed work ---------------------------
    programs_before = counter.programs
    profile_dir = os.path.join(workdir, "profile")
    steps0 = driver.step_count
    watchdog = Watchdog()
    watchdog.start()
    t_start = time.perf_counter()
    units, found, failed = run_window(driver, spans, schedule, seconds,
                                      profile_dir if args.trace else None)
    window_s = time.perf_counter() - t_start
    watchdog.done.set()
    watchdog.join()
    compiled_in_window = counter.programs - programs_before
    peak = peak_bytes(system.devices)

    timed = {kind: [(t0, t1, t1 - t0) for k, _, t0, t1, ok in units
                    if k == kind and ok] for kind in ("block", "output")}
    blocks = [s for _, _, s in timed["block"]]
    outputs = [s for _, _, s in timed["output"]]
    steps = len(blocks) * driver.block_steps
    sites_per_chip = system.grid_size / chips
    say(f"window {window_s:.2f}s: {len(blocks)} block(s) of "
        f"{driver.block_steps} steps ({steps} steps, step count "
        f"{steps0}->{driver.step_count}), {len(outputs)} output(s), "
        f"{compiled_in_window} program(s) compiled inside it")
    if blocks:
        say("block seconds: " + " ".join(f"{b:.4f}" for b in blocks))
        say(f"block ms/step: mean {1e3 * sum(blocks) / steps:.4f} median "
            f"{1e3 * statistics.median(blocks) / driver.block_steps:.4f}"
            + (" (spans closed by a sync: traced run)" if args.trace else ""))
    if outputs:
        say("output seconds: " + " ".join(f"{o:.4f}" for o in outputs)
            + f": mean {sum(outputs) / len(outputs):.4f} median "
            f"{statistics.median(outputs):.4f}")
    for kind in ("block", "output"):
        say_long_units(kind, timed[kind], watchdog)
    say(f"watchdog: {len(watchdog.late)} late wake-up(s) in the window, "
        f"{sum(late for _, late in watchdog.late):.3f}s in all")
    say(f"peak_bytes_in_use after the window {peak} "
        f"({(peak or 0) / GIB:.4f} GiB)")
    end = driver.end_numbers()

    # ---- metrics: all the work of the window over all its time -----------
    values = {}
    if blocks:
        values["site_updates_per_chip_s"] = (
            sites_per_chip * steps / sum(blocks))
    if outputs:
        values["output_s"] = sum(outputs) / len(outputs)
    values["setup_s"] = setup_s

    # ---- free the program's state, then the check ------------------------
    tier = [e["data"] for e in events if e["kind"] == "kernel_tier"]
    choices = {e["data"]["kernel"]: e["data"] for e in events
               if e["kind"] == "block_choice"}
    for kname, d in sorted(choices.items()):
        say(f"kernel {kname}: {d['stencil']} (bx, by) = ({d['bx']}, "
            f"{d['by']}) from {d['source']}")
    for d in tier:
        say(f"tier at {d['entrypoint']}: {d['tier']}, "
            f"{d['kernels_per_2_steps']} per 2 steps")
    driver.state = driver.energy = None
    t_check = time.perf_counter()
    numbers = check.compare(system, args.seed, first, background,
                            driver.first_nsteps, end, found, events,
                            compiled_in_window)
    limits = check.limits_for(cell["name"], args.rehearse)
    correct = failed == 0 and bool(blocks)
    for name, value in numbers.items():
        limit = limits.get(name)
        if limit is None:
            say(f"check {name}: {value!r} (no limit set for this cell: "
                "not compared)")
            continue
        ok = value is not None and np.isfinite(value) and value <= limit
        correct = correct and ok
        say(f"check {name}: {value!r} limit {limit!r} "
            f"{'ok' if ok else 'NOT OK'}")
    say(f"check took {time.perf_counter() - t_check:.2f}s; correct "
        f"{correct}")

    # ---- the result line -------------------------------------------------
    breakdown = None
    if args.trace:
        counters = {"trace_s": setup_counts[0], "compile_s": setup_counts[1]}
        if not args.rehearse:
            counters["copy_probe_gbps"] = copy_probe(
                system.devices[0], 4 * 128 * 2**20)
        peak_gbps = (peaks[dev0.device_kind]["hbm_gbps"]
                     if dev0.device_kind in peaks else float("nan"))
        nblocks = sum(1 for k in schedule if k == "block")
        traced, breakdown = reduce_trace(
            profile_dir, args.save_trace, system, peak_gbps,
            nblocks * driver.block_steps,
            {"cell": cell["name"], "device": dict(device)})
        ctx = {"spans": spans, "units": units, "traced": traced,
               "counters": counters, "block_steps": driver.block_steps,
               "rehearse": args.rehearse}
        metrics = {}
        for m in bench["per_layer"]:
            if reports(m, cell["name"]):
                value = readers.read(load_named("metrics", m["name"]), ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if "stencil_kernel_roofline" in traced \
                and "copy_probe_gbps" in counters:
            share = (traced["stencil_kernel_roofline"] * peak_gbps
                     / counters["copy_probe_gbps"])
            say(f"kernel share of the probe's bandwidth: {share:.2f} %")
        if not args.rehearse and traced:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if reports(m, cell["name"]) and m["name"] in values}
    if args.rehearse:
        # a CPU run's times are not device numbers: none is printed
        say("rehearsal: " + json.dumps(
            {"correct": correct, "units": len(units), "failed": failed,
             "metric_names": sorted(metrics)}))
        system.close()
        return 0 if correct else 1
    device["memory_peak_bytes"] = peak
    result = {"correct": bool(correct), "attempted": len(units),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    system.close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
