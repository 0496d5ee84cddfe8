"""The readings the limits of the ``correct`` decision are set from, taken
by hand on the chip at the cell's own size, many seeds to a process (the
benchmark's own runs never run this):

    python3 benchmark/control.py --workload <cell> --program --seeds 1 2 ...
    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

``--program``: sound runs. From each seed the program's first steps,
statistics row and one output, through the calls the window makes, against
the plain reference: the numbers ``check.compare`` gives.

Without it: the control. The plain reference computed one precision step
below the configuration's float32, put in the program's place and compared
with the float32 reference exactly as the program is: the stepping wholly
in bfloat16 and with only the RK carries in bfloat16 (the step that would
tempt a later PR: it halves the carries' traffic); the output wholly in
bfloat16 and with only the mode powers in bfloat16 (it would halve what
the binning reads); the statistics of the state rounded to bfloat16. One
reading of each kind has to lie above the limit of the number it is made
for.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# before jax is imported: the compile cache run.py uses
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".benchmark_cache", "xla"))


def build(cell_name, override, rehearse, stepper, outfile=None):
    from benchmark import run
    import jax
    import pystella_tpu as ps
    from benchmark.system import System
    ps.obs.ensure_compilation_cache()
    if outfile:
        os.makedirs(os.path.dirname(outfile), exist_ok=True)

    bench = run.read_json("BENCHMARK.json")
    cell = run.find_cell(bench, cell_name)
    config = run.read_json(next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    config.update(override or {})
    traffic = run.load_named("traffic", cell["traffic"])
    if not rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("control: not a TPU (pass --rehearse off the chip)")
    system = System(config, jax.devices()[:int(cell["chips"])],
                    stepper=stepper, outfile=outfile)
    return system, traffic


def new_driver(system, traffic, seed, keep_state):
    from benchmark import drivers
    from benchmark.spans import Spans
    state, expand, energy = system.initial_state(seed)
    driver = drivers.load(traffic["driver"])(system, traffic, Spans(False))
    driver.start(state if keep_state else None, expand, energy)
    return driver


def flat(prefix, tree):
    """``{"a": {"b": x}}`` as ``{"<prefix>.a.b": array}``, for ``np.savez``."""
    import numpy as np
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(f"{prefix}.{k}", v))
        else:
            out[f"{prefix}.{k}"] = np.asarray(v)
    return out


def program_readings(cell_name, seeds, override=None, rehearse=False,
                     outfile=None, dump=None):
    """``dump``: a directory that is given, per seed, what the program
    wrote and what the reference made of the same state (``.npz``), so
    that a gap can be defined anew without another run."""
    import numpy as np
    from benchmark import check
    from benchmark import run
    system, traffic = build(cell_name, override, rehearse, True,
                            outfile or os.path.join(run.SCRATCH, "run",
                                                    "control"))
    rows = []
    for seed in seeds:
        driver = new_driver(system, traffic, seed, True)
        background = driver.background()
        first, _ = check.first_answers(driver, "output" in traffic["schedule"])
        driver.state = driver.energy = None
        row, keep = {"seed": seed}, {}
        row.update(check.compare(system, seed, first, background,
                                 driver.first_nsteps, {}, {}, [], 0, keep))
        if dump:
            os.makedirs(dump, exist_ok=True)
            got = {k: first[k] for k in ("stats", "output") if first.get(k)}
            np.savez(os.path.join(dump, f"{cell_name}.{seed}.npz"),
                     **flat("got", got), **flat("ref", keep))
        del first
        rows.append(row)
        print(json.dumps(row), flush=True)
    system.close()
    return rows


def readings(cell_name, seeds, override=None, rehearse=False, dump=None):
    import jax.numpy as jnp
    import numpy as np
    from benchmark import check, reference

    system, traffic = build(cell_name, override, rehearse, False)
    with_output = "output" in traffic["schedule"]
    bins = (reference.SpectrumBins(system.grid_shape,
                                   system.config["box_dim"])
            if with_output else None)
    rows = []
    for seed in seeds:
        driver = new_driver(system, traffic, seed, False)
        background = driver.background()
        nsteps = driver.first_nsteps
        ref, a_ref, hubble = check.reference_state(system, seed, background,
                                                   nsteps)
        row = {"seed": seed}
        for name, kw in (("f32_again", {}),
                         ("bf16_carry", {"carry_dtype": jnp.bfloat16}),
                         ("bf16", {"dtype": jnp.bfloat16})):
            got, a, hub = check.reference_state(system, seed, background,
                                                nsteps, **kw)
            row[name] = reference.field_gap(got, ref)
            if background["mode"] == "coupled":
                row[name + "_a_gap"] = abs(a - a_ref) / abs(a_ref - 1.0)
                row[name + "_hubble_gap"] = abs(hub / hubble - 1.0)
            del got
        if driver.stats_every:
            row["bf16_stats_gap"] = reference.stats_gap(
                reference.statistics(ref["f"].astype(jnp.bfloat16)),
                reference.statistics(ref["f"]))
        if with_output:
            ref_out = check.reference_output(system, ref, a_ref, hubble, bins)
            kept = flat("ref", ref_out)
            for name, kw in (("bf16", {"dtype": jnp.bfloat16}),
                             ("bf16_power", {"power_dtype": jnp.bfloat16})):
                got = check.reference_output(system, ref, a_ref, hubble,
                                             bins, **kw)
                for key, gap in reference.spectra_gaps(got,
                                                       ref_out).items():
                    row[f"{name}_spectra_gap.{key}"] = gap
                row[name + "_hist_gap"], row[name + "_hist_edge_gap"] = \
                    reference.hist_gaps(got["hist"], ref_out["hist"])
                kept.update(flat(name, got))
            if dump:
                os.makedirs(dump, exist_ok=True)
                np.savez(os.path.join(
                    dump, f"{cell_name}.control.{seed}.npz"), **kept)
        del ref
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--config-override", default=None)
    ap.add_argument("--dump", default=None, metavar="DIR")
    a = ap.parse_args()
    override = json.loads(a.config_override) if a.config_override else None
    if a.program:
        program_readings(a.workload, a.seeds, override, a.rehearse,
                         dump=a.dump)
    else:
        readings(a.workload, a.seeds, override, a.rehearse, dump=a.dump)
