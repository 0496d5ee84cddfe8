"""``python -m pystella_tpu.lint``: run both tiers, write
``lint_report.json``, exit nonzero on violations.

Exit codes: 0 clean, 1 violations found, 2 bad usage.

The IR tier lowers the real step functions, which needs a jax backend:
static analysis needs no hardware, so unless ``JAX_PLATFORMS`` is set
the CLI defaults to the CPU platform with an 8-device virtual mesh.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys


def _force_platform():
    """Default to the CPU backend with 8 virtual devices (so the
    sharded targets exercise their collectives) unless ``JAX_PLATFORMS``
    chooses otherwise. The package import already pulled in jax, but no
    backend exists until first use, so the config update still lands."""
    if os.environ.get("JAX_PLATFORMS"):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


def _load_targets(spec):
    """``module:attr`` -> the target list (attr may be a list or a
    zero-arg callable returning one); a spec WITHOUT ``:`` is a
    comma-separated list of default-target names (``step_generic,
    mg_smooth``) resolved by ``targets.targets_by_name``."""
    if ":" not in spec:
        from pystella_tpu.lint.targets import targets_by_name
        names = [n.strip() for n in spec.split(",") if n.strip()]
        return list(targets_by_name(names).values())
    modname, _, attr = spec.partition(":")
    mod = importlib.import_module(modname)
    obj = getattr(mod, attr or "TARGETS")
    return obj() if callable(obj) else list(obj)


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m pystella_tpu.lint",
        description="graph & source static analysis: jaxpr/HLO hazard "
                    "audits over the real step functions + package AST "
                    "lint; writes lint_report.json, exits 1 on "
                    "violations")
    p.add_argument("--out", default=None, metavar="DIR",
                   help="directory for lint_report.json (default: "
                        "bench_results/ next to the package for an "
                        "in-repo checkout, else the cwd)")
    p.add_argument("--package", default=None, metavar="DIR",
                   help="package directory for the source tier "
                        "(default: the installed pystella_tpu)")
    p.add_argument("--targets", default=None, metavar="NAMES|MOD:ATTR",
                   help="comma-separated default-target names "
                        "(step_generic,mg_smooth) or a MOD:ATTR import "
                        "spec for a custom target list (default: "
                        "pystella_tpu.lint.targets:default_targets)")
    p.add_argument("--no-graph", action="store_true",
                   help="skip the IR + dataflow tiers (no jax needed "
                        "then)")
    p.add_argument("--no-source", action="store_true",
                   help="skip the source tier")
    p.add_argument("--no-dataflow", action="store_true",
                   help="skip the dataflow tier (precision-flow + "
                        "static comm model); the IR-tier allow-set "
                        "audits still run")
    p.add_argument("--json", action="store_true",
                   help="print the full report JSON to stdout instead "
                        "of the text summary")
    args = p.parse_args(argv)

    if args.no_graph and args.no_source:
        print("lint: nothing to do (--no-graph and --no-source)",
              file=sys.stderr)
        return 2

    if not args.no_graph:
        _force_platform()

    from pystella_tpu import lint

    targets = None
    if args.targets:
        try:
            targets = _load_targets(args.targets)
        except KeyError as e:
            print(f"lint: {e.args[0] if e.args else e}",
                  file=sys.stderr)
            return 2

    rep = lint.run_lint(
        pkg_dir=args.package, targets=targets,
        run_source=not args.no_source, run_graph=not args.no_graph,
        run_dataflow=not (args.no_graph or args.no_dataflow))

    out_dir = args.out
    if out_dir is None:
        repo = os.path.dirname(lint.package_dir())
        bench = os.path.join(repo, "bench_results")
        out_dir = bench if os.path.isdir(bench) else os.getcwd()
    os.makedirs(out_dir, exist_ok=True)
    path = rep.write(os.path.join(out_dir, "lint_report.json"))

    if args.json:
        print(json.dumps(rep.to_dict(), indent=1, sort_keys=True))
    else:
        print(rep.render_text())
    print(f"lint: report -> {path}", file=sys.stderr)
    return 0 if rep.ok else 1


if __name__ == "__main__":
    sys.exit(main())
