"""The static-analysis layer (pystella_tpu.lint): source-tier AST
checks, IR-tier jaxpr/HLO audits, the seeded-violation fixtures, the
report schema round-trip, and the donation satellite's bit-exactness
pin. The full CLI (both tiers over the real repo) runs in
``test_cli_clean_repo``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import lint
from pystella_tpu.lint import graph as lint_graph
from pystella_tpu.lint import source as lint_source
from pystella_tpu.lint.report import LintReport, Violation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "pystella_tpu")
BAD_PKG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "lint_bad_pkg")


def _sub_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.dirname(os.path.abspath(__file__))])
    return env


# -- source tier -----------------------------------------------------------

def test_source_tier_clean_on_repo():
    """The package itself carries no source-tier violations — this IS
    the CI gate for host syncs, env reads, scope literals, and env-var
    doc coverage."""
    violations, stats = lint_source.check_package(
        PKG, doc_path=os.path.join(REPO, "doc", "observability.md"))
    assert stats["files_scanned"] > 40
    assert violations == [], "\n".join(str(v) for v in violations)


def test_source_tier_names_seeded_violations():
    violations, _ = lint_source.check_package(
        BAD_PKG, registered_scopes=frozenset({"registered"}))
    by_checker = {}
    for v in violations:
        by_checker.setdefault(v.checker, []).append(v)
    # .item() in a # lint: hot-path module
    assert any(".item()" in v.message and "hotmod.py" in v.where
               for v in by_checker["host-sync"])
    # float()/np.asarray inside a trace_scope block
    assert any("float()" in v.message for v in by_checker["host-sync"])
    assert any("np.asarray" in v.message
               for v in by_checker["host-sync"])
    # unregistered env reads (no config.py in the fixture package)
    assert any("PYSTELLA_BOGUS_KNOB" in v.message
               for v in by_checker["env-registry"])
    # unregistered trace-scope literal
    assert any("not_a_registered_scope" in v.message
               for v in by_checker["scope-registry"])
    # unregistered event kind handed to emit()
    assert any("not_a_registered_event_kind" in v.message
               for v in by_checker["event-registry"])
    # ...also via the kind= keyword and an _emit wrapper (PR 17)
    assert any("not_a_registered_kw_kind" in v.message
               for v in by_checker["event-registry"])
    assert any("not_a_registered_wrapped_kind" in v.message
               for v in by_checker["event-registry"])


def test_source_tier_pragma_waives():
    """`# lint: allow(...)` / `# env-registry: NAME` waive a finding at
    that site — pinned on the package's own by-file-loadable modules,
    which carry the env pragmas."""
    violations, _ = lint_source.check_package(
        PKG, checks={"env-registry"})
    assert violations == [], "\n".join(str(v) for v in violations)


def test_env_registry_statically_recovered():
    names = lint_source.registered_env_vars(
        os.path.join(PKG, "config.py"))
    assert {"PYSTELLA_EVENT_LOG", "PYSTELLA_HALO_OVERLAP",
            "JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS"} <= names
    # and it matches the live registry exactly
    assert names == set(ps.config.registered())


def test_config_accessors():
    assert ps.config.getenv("PYSTELLA_HALO_OVERLAP") is not None
    assert ps.config.get_float("PYSTELLA_FFT_REPLICATE_LIMIT") > 0
    with pytest.raises(KeyError):
        ps.config.getenv("PYSTELLA_NOT_A_KNOB")
    snap = ps.config.snapshot()
    assert all(k in ps.config.registered() for k in snap)


# -- report schema ---------------------------------------------------------

def test_report_schema_round_trip(tmp_path):
    rep = LintReport()
    rep.extend([
        Violation(checker="donation", message="miss", where="t1",
                  detail={"wasted_bytes": 64}),
        Violation(checker="env-doc", message="undocumented",
                  severity="warning"),
    ])
    rep.add_check("donation")
    rep.graph = {"t1": {"built": True}}
    rep.donation = {"donatable_bytes": 128, "aliased_bytes": 64,
                    "coverage_pct": 50.0, "wasted_bytes": 64}
    rep.timing = {"targets": {"t1": 1.9}, "total_s": 1.9,
                  "cache": {"builds": 1, "hits": 1}}
    assert not rep.ok
    path = rep.write(str(tmp_path / "lint_report.json"))
    loaded = LintReport.load(path)
    assert loaded.to_dict()["summary"] == rep.to_dict()["summary"]
    assert [v.to_dict() for v in loaded.violations] \
        == [v.to_dict() for v in rep.violations]
    assert loaded.graph == rep.graph
    assert loaded.timing == rep.timing
    assert not loaded.ok
    # unknown schema versions are refused, not misread
    bad = rep.to_dict()
    bad["schema"] = 99
    with pytest.raises(ValueError):
        LintReport.from_dict(bad)


# -- IR tier ---------------------------------------------------------------

def test_param_parser_handles_sharding_attrs():
    asm = ('func.func public @main(%arg0: tensor<2x8xf32> '
           '{jax.buffer_donor = true, mhlo.sharding = '
           '"{devices=[1,2,2,1]<=[4]}"}, %arg1: tensor<8xf32>, '
           '%arg2: tensor<f32> {tf.aliasing_output = 0 : i32}) '
           '-> (tensor<2x8xf32>) {')
    params = lint_graph.parse_main_params(asm)
    assert [p[0] for p in params] == [0, 1, 2]
    assert "jax.buffer_donor" in params[0][3]
    assert params[1][3].strip() == ""
    assert "tf.aliasing_output" in params[2][3]
    assert lint_graph.tensor_nbytes(params[0][1], params[0][2]) == 64


def test_audit_donation_reports_waste():
    asm = ('func.func public @main(%arg0: tensor<4x4xf32>, '
           '%arg1: tensor<f32>) -> (tensor<4x4xf32>) {')
    violations, stats = lint_graph.audit_donation("t", asm, 64)
    assert stats["aliased_bytes"] == 0 and stats["wasted_bytes"] == 64
    assert violations and "donation miss" in violations[0].message
    asm_donated = asm.replace(
        "tensor<4x4xf32>,", "tensor<4x4xf32> {jax.buffer_donor = true},")
    violations, stats = lint_graph.audit_donation("t", asm_donated, 64)
    assert violations == [] and stats["coverage_pct"] == 100.0


def test_audit_step_sentinel_target():
    """One real IR-tier target end to end in-process: the sharded
    sentinel-piggybacked step must be clean — donation covered, no f64,
    only allowlisted collectives, sentinel fused into the step module."""
    from pystella_tpu.lint.targets import default_targets
    target = [t for t in default_targets()
              if t.name == "step_sentinel"][0]
    violations, stats = lint_graph.audit_target(target)
    assert stats["built"], stats
    assert violations == [], "\n".join(str(v) for v in violations)
    assert stats["donation"]["coverage_pct"] == 100.0
    assert stats["fusion"]["scopes"] == {"rk_stage": True,
                                         "sentinel": True}
    if len(jax.devices()) >= 4:
        # the sharded mesh's halo ppermutes are present and small at
        # this toy size; nothing outside the allowlist survived
        col = stats["collectives"]
        assert col["small"].get("collective-permute")
        assert not set(col["seen"]) - {"collective-permute",
                                       "all-reduce"}


def test_audit_catches_seeded_graph_hazards():
    import lint_fixture_targets as fx
    by_name = {}
    for t in fx.TARGETS:
        v, _ = lint_graph.audit_target(t)
        by_name[t.name] = v
    assert any(v.checker == "donation" and "donation miss" in v.message
               for v in by_name["undonated_step"])
    assert any(v.checker == "dtype" and "f64" in v.message
               for v in by_name["f64_step"])
    assert any(v.checker == "host" for v in by_name["callback_step"])


# -- dataflow tier ---------------------------------------------------------

# a hand-written debug-info StableHLO module exercising every
# precision-flow rule: %2 narrows under a plain scope (rule 1 fires),
# %3 under the registered carry scope and %4 under a kernel-dispatch
# scope (both sanctioned), %5 adds in bf16 (rule 2), %6 reduces with a
# bf16 accumulator (rule 2, accumulation), %7 moves the acc-role bf16
# value onward (rule 3)
_DF_ASM = """\
#loc3 = loc("jit(f)/jit(main)/rk_carry_math/convert_element_type")
#loc4 = loc("jit(f)/jit(main)/carry_quantize/convert_element_type")
#loc5 = loc("jit(f)/jit(main)/pallas_stencil/while/body/convert_element_type")
#loc6 = loc("jit(f)/jit(main)/rk_stage/add")
#loc7 = loc("jit(f)/jit(main)/energy/reduce")
#loc8 = loc("jit(f)/jit(main)/energy/broadcast_in_dim")
module @jit_f {
  func.func public @main(%arg0: tensor<64x64xf32>) -> (tensor<8x8xbf16>) {
    %0 = stablehlo.constant dense<2.000000e+00> : tensor<8x8xf32>
    %1 = stablehlo.multiply %arg0, %0 : tensor<8x8xf32>
    %2 = stablehlo.convert %1 : (tensor<8x8xf32>) -> tensor<8x8xbf16> loc(#loc3)
    %3 = stablehlo.convert %1 : (tensor<8x8xf32>) -> tensor<8x8xbf16> loc(#loc4)
    %4 = stablehlo.convert %1 : (tensor<8x8xf32>) -> tensor<8x8xbf16> loc(#loc5)
    %5 = stablehlo.add %3, %4 : tensor<8x8xbf16> loc(#loc6)
    %6 = stablehlo.reduce(%1 init: %0) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x8xbf16>, tensor<bf16>) -> tensor<bf16> loc(#loc7)
    %7 = stablehlo.broadcast_in_dim %6, dims = [] : (tensor<bf16>) -> tensor<8x8xbf16> loc(#loc8)
    return %5 : tensor<8x8xbf16>
  }
}
"""

# a hand-written compiled-HLO body: one halo permute, one scalar
# all-reduce, one transpose, one async all-reduce pair (the -done leg
# must not double-count), and one field-sized all-gather (the @main
# param above is 64x64xf32 = 16,384 B, so the replication threshold is
# 8,192 B and the 16,384 B gather classifies as replication)
_DF_HLO = """\
HloModule jit_f
ENTRY main {
  %cp = f32[16,64]{1,0} collective-permute(f32[16,64]{1,0} %x), channel_id=1, metadata={op_name="jit(f)/jit(main)/halo_exchange/ppermute"}
  %ar = f32[] all-reduce(f32[] %z), to_apply=%sum, metadata={op_name="jit(f)/jit(main)/energy/sum"}
  %ars = f32[32,4]{1,0} all-reduce-start(f32[32,4]{1,0} %q), to_apply=%sum, metadata={op_name="jit(f)/jit(main)/energy/psum"}
  %ard = f32[32,4]{1,0} all-reduce-done(f32[32,4]{1,0} %ars)
  %a2a = f32[32,64]{1,0} all-to-all(f32[32,64]{1,0} %w), dimensions={0}, metadata={op_name="jit(f)/jit(main)/fft_transpose/all_to_all"}
  %ag = f32[64,64]{1,0} all-gather(f32[16,64]{1,0} %y), dimensions={0}, metadata={op_name="jit(f)/jit(main)/replicate_field/all_gather"}
}
"""


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["counts", "weights"])
def test_binning_kernel_passes_precision_flow(weighted):
    """The one-hot contraction narrows f32 to bf16 (the one-hots; the
    three pieces of a weight) under its registered kernel scope and
    accumulates in f32: no rule fires on the real lowered program."""
    import jax
    import jax.numpy as jnp
    import pystella_tpu as ps
    from pystella_tpu.lint import dataflow
    from pystella_tpu.ops.histogram import bincount_core
    with jax.enable_x64(False):
        decomp = ps.DomainDecomposition((1, 1, 1),
                                        devices=jax.devices()[:1])
        core = bincount_core(decomp, (2,), 444, weighted)
        args = [jax.ShapeDtypeStruct((2, 16, 16, 9), jnp.int32)]
        if weighted:
            args.append(jax.ShapeDtypeStruct((2, 16, 16, 9), jnp.float32))
        asm = jax.jit(core).lower(*args).compiler_ir().operation.get_asm(
            enable_debug_info=True)
    violations, stats = dataflow.audit_precision(
        "binning", asm, policy=lint_graph.POLICY_SPECTRAL_F32)
    assert violations == [], [v.message for v in violations]
    assert stats["kernel_converts"] >= (4 if weighted else 2)
    assert stats["carry_converts"] == 0


def test_dataflow_parse_ops():
    from pystella_tpu.lint import dataflow
    ops = {o["result"]: o for o in dataflow.parse_ops(_DF_ASM)}
    assert ops["1"]["op"] == "stablehlo.multiply"
    assert ops["1"]["out_elt"] == "f32" and ops["1"]["scope"] == ""
    cv = ops["2"]
    assert cv["op"] == "stablehlo.convert"
    assert cv["in_elts"] == ["f32"] and cv["out_elt"] == "bf16"
    assert cv["operands"] == ["1"]
    assert cv["scope"].endswith("rk_carry_math/convert_element_type")
    assert "carry_quantize" in ops["3"]["scope"]
    assert ops["6"]["op"] == "stablehlo.reduce"


def test_precision_flow_rules():
    from pystella_tpu.lint import dataflow
    violations, stats = dataflow.audit_precision(
        "syn", _DF_ASM, policy=lint_graph.POLICY_BF16_ACC32)
    msgs = [v.message for v in violations]
    # rule 1: the rk_carry_math narrowing is named; the carry_quantize
    # and pallas_stencil narrowings are sanctioned
    r1 = [m for m in msgs if "downcast outside a registered carry" in m]
    assert len(r1) == 1 and "rk_carry_math" in r1[0]
    assert stats["carry_converts"] == 1
    assert stats["kernel_converts"] == 1
    # rule 2: bf16 add and the bf16-accumulator reduce
    assert any("arithmetic in bf16 (add)" in m for m in msgs)
    assert any("accumulation in bf16 (reduce)" in m for m in msgs)
    # rule 3: the broadcast of the acc-role bf16 value
    assert any("accumulation chain continues in bf16" in m
               for m in msgs)
    assert stats["ok"] is False and stats["reduces"] == 1
    assert stats["policy"] == "bf16-in/f32-acc"


def test_precision_flow_clean_without_narrowing():
    from pystella_tpu.lint import dataflow
    clean = "\n".join(l for l in _DF_ASM.splitlines()
                      if "bf16" not in l)
    violations, stats = dataflow.audit_precision("syn", clean)
    assert violations == [] and stats["ok"] is True


def test_static_comm_model():
    from pystella_tpu.lint import dataflow
    violations, block = dataflow.model_comm("syn", _DF_ASM, _DF_HLO)
    assert block["modeled"] is True
    assert block["field_bytes"] == 16384
    assert block["replication_threshold"] == 8192
    per = block["per_invocation_bytes"]
    assert per["halo"] == 16 * 64 * 4
    assert per["transpose"] == 32 * 64 * 4
    # the plain all-reduce plus the async pair counted ONCE
    assert per["scalar"] == 4 + 32 * 4 * 4
    assert per["replication"] == 64 * 64 * 4
    # the field-sized gather is an error naming its op_name scope
    assert len(violations) == 1
    assert violations[0].checker == "static-comm"
    assert "replicate_field" in violations[0].message
    rows = {(e["op"], e["class"]): e for e in block["collectives"]}
    assert rows[("all-reduce", "scalar")]["count"] == 2
    assert rows[("collective-permute", "halo")]["scopes"] \
        == ["jit(f)/jit(main)/halo_exchange/ppermute"]


def test_dataflow_catches_seeded_fixtures():
    """The two new seeded fixtures through the real build path: the
    mid-chain downcast violates precision-flow naming its scope, and
    the field-sized all-gather violates static-comm DESPITE its base
    op being allowlisted in the target."""
    import lint_fixture_targets as fx
    targets = [t for t in fx.TARGETS
               if t.name in ("bf16_downcast_step", "replicating_gather")]
    violations, per_target = lint.audit_dataflow_targets(targets)
    pf = [v for v in violations if v.checker == "precision-flow"]
    assert pf and any("rk_carry_math" in v.message for v in pf)
    sc = [v for v in violations if v.checker == "static-comm"]
    assert sc and any("replicate_field" in v.message for v in sc)
    blk = per_target["replicating_gather"]["static_comm"]
    assert blk["per_invocation_bytes"].get("replication")
    assert per_target["bf16_downcast_step"]["precision"]["ok"] is False


@pytest.mark.slow  # interpret-mode pallas build; the CLI acceptance
# run (test_cli_clean_repo) covers the same verdict
def test_bf16_chunk_target_flow_clean():
    """The positive pin of the tentpole: the streaming-chunk program
    built with carry_dtype=bf16 PASSES POLICY_BF16_ACC32 as a flow
    property — every narrowing is attributed to the carry funnel, no
    arithmetic runs narrow."""
    from pystella_tpu.lint.targets import targets_by_name
    t = targets_by_name(["bf16_chunk_multi_step"])["bf16_chunk_multi_step"]
    violations, per_target = lint.audit_dataflow_targets([t])
    assert violations == [], "\n".join(str(v) for v in violations)
    st = per_target["bf16_chunk_multi_step"]["precision"]
    assert st["ok"] and st["narrow_values"] > 0
    assert st["kernel_converts"] + st["carry_converts"] > 0


def test_targets_by_name_selection():
    from pystella_tpu.lint.__main__ import _load_targets
    ts = _load_targets("step_generic,mg_smooth")
    assert [t.name for t in ts] == ["step_generic", "mg_smooth"]
    with pytest.raises(KeyError):
        _load_targets("bogus_target")


def test_run_lint_no_dataflow_and_artifact_cache():
    """--no-dataflow semantics and the shared-artifact satellite: with
    the dataflow tier off only the IR checks run; with it on, the
    build is shared (one build, one reuse) and the per-target timing
    lands in the report."""
    import lint_fixture_targets as fx
    targets = [t for t in fx.TARGETS if t.name == "undonated_step"]
    rep = lint.run_lint(targets=targets, run_source=False,
                        run_dataflow=False)
    assert "donation" in rep.checks
    assert "precision-flow" not in rep.checks
    assert rep.timing["cache"] == {"builds": 1, "hits": 0}
    # run_dataflow=None follows run_graph: both tiers share one build
    rep2 = lint.run_lint(targets=targets, run_source=False)
    assert "precision-flow" in rep2.checks
    assert "static-comm" in rep2.checks
    assert rep2.timing["cache"] == {"builds": 1, "hits": 1}
    tgt = rep2.graph["undonated_step"]
    assert "precision" in tgt and "static_comm" in tgt
    assert tgt["timing"]["audits"].get("precision-flow") is not None
    assert rep2.timing["targets"]["undonated_step"] > 0


# -- CLI -------------------------------------------------------------------

def test_cli_source_fixture_exits_1():
    """`python -m pystella_tpu.lint` on the seeded package exits 1 and
    NAMES the violations."""
    res = subprocess.run(
        [sys.executable, "-m", "pystella_tpu.lint", "--no-graph",
         "--package", BAD_PKG, "--out", "/tmp/lint_fixture_out"],
        capture_output=True, text=True, timeout=180, env=_sub_env())
    assert res.returncode == 1, (res.stdout, res.stderr[-1500:])
    assert ".item()" in res.stdout
    assert "PYSTELLA_BOGUS_KNOB" in res.stdout
    rep = json.load(open("/tmp/lint_fixture_out/lint_report.json"))
    assert rep["ok"] is False and rep["summary"]["errors"] >= 4


@pytest.mark.slow
def test_cli_graph_fixture_exits_1():
    """The CLI leg of the seeded IR-tier fixtures (their audit logic is
    tier-1 via test_audit_catches_seeded_graph_hazards; the CLI exit
    path is tier-1 via test_cli_source_fixture_exits_1 — this
    subprocess only re-verifies the --targets loader against a fresh
    interpreter)."""
    res = subprocess.run(
        [sys.executable, "-m", "pystella_tpu.lint", "--no-source",
         "--targets", "lint_fixture_targets:TARGETS",
         "--out", "/tmp/lint_fixture_graph"],
        capture_output=True, text=True, timeout=300, env=_sub_env())
    assert res.returncode == 1, (res.stdout, res.stderr[-1500:])
    assert "donation miss" in res.stdout
    assert "f64" in res.stdout
    assert "host interaction" in res.stdout
    # the dataflow-tier seeds: the mid-chain downcast names its scope,
    # the allowlisted-but-field-sized gather is caught by bytes
    assert "rk_carry_math" in res.stdout
    assert "replicate_field" in res.stdout
    assert "accidental replication" in res.stdout


@pytest.mark.slow
def test_cli_clean_repo():
    """The acceptance run: both tiers over the real repo exit 0 (the
    tier-1 coverage of the same verdict is test_source_tier_clean_on_repo
    + test_audit_step_sentinel_target + the smoke e2e's in-run lint;
    this subprocess additionally compiles every default target)."""
    res = subprocess.run(
        [sys.executable, "-m", "pystella_tpu.lint",
         "--out", "/tmp/lint_clean_repo"],
        capture_output=True, text=True, timeout=540, env=_sub_env())
    assert res.returncode == 0, (res.stdout, res.stderr[-2000:])
    rep = json.load(open("/tmp/lint_clean_repo/lint_report.json"))
    assert rep["ok"] is True
    assert set(rep["graph"]) == {"step_generic", "step_sentinel",
                                 "fused_multi_step",
                                 "coupled_multi_step", "mg_smooth",
                                 "chunk_multi_step", "bf16_chunk_multi_step",
                                 "ensemble_step", "sharded_spectra"}
    assert rep["summary"]["donation"]["coverage_pct"] == 100.0
    # the dataflow tier ran on every target: the bf16-carry program
    # passes POLICY_BF16_ACC32 as a flow property, the artifact cache
    # built each target exactly once, per-target timing is recorded
    assert "precision-flow" in rep["summary"]["checks"]
    assert "static-comm" in rep["summary"]["checks"]
    bf16 = rep["graph"]["bf16_chunk_multi_step"]
    assert bf16["precision"]["ok"] is True
    assert bf16["precision"]["policy"] == "bf16-in/f32-acc"
    assert bf16["precision"]["kernel_converts"] \
        + bf16["precision"]["carry_converts"] > 0
    timing = rep["summary"]["timing"]
    assert timing["cache"]["builds"] == 9
    assert timing["cache"]["hits"] == 9
    assert set(timing["targets"]) == set(rep["graph"])
    # the sharded targets carry a sensible static comm model
    sc = rep["graph"]["step_sentinel"]["static_comm"]
    assert sc["modeled"] and "halo" in sc["per_invocation_bytes"]
    assert rep["graph"]["sharded_spectra"]["static_comm"][
        "per_invocation_bytes"].get("transpose")


# -- donation satellite ----------------------------------------------------

@pytest.mark.slow  # ~19 s interpret-mode; tier-1 keeps donation
# correctness via test_donation_roundoff_exact_generic (XLA tier) and
# the smoke e2e's donated step + lint donation audit
def test_donation_bit_exact_fused():
    """donate=True must not change a single bit of the FUSED stepper's
    output: the Pallas kernels materialize their outputs, so donation
    only aliases the jit boundary — the flagship hot loop
    (``multi_step``, which always donates) and the per-step path must
    agree exactly."""
    import warnings
    grid = (16, 16, 16)
    decomp = ps.DomainDecomposition((1, 1, 1),
                                    devices=jax.devices()[:1])
    sector = ps.ScalarSector(
        2, potential=lambda f: (0.5 * 1.2e-2 * f[0] ** 2
                                + 0.125 * f[0] ** 2 * f[1] ** 2))
    rng = np.random.default_rng(3)
    init = {
        "f": jnp.asarray(1e-3 * rng.standard_normal((2,) + grid),
                         jnp.float32),
        "dfdt": jnp.asarray(1e-4 * rng.standard_normal((2,) + grid),
                            jnp.float32),
    }
    args = {"a": np.float32(1.3), "hubble": np.float32(0.21)}
    dt = np.float32(0.01)

    def run(donate):
        state = {k: v.copy() for k, v in init.items()}
        # pair_stages=False: donation aliases the jit boundary, not the
        # kernel bodies, so the single-stage kernel pins the contract at
        # half the interpret-mode compile cost (pairing parity is
        # test_fused's job)
        stepper = ps.FusedScalarStepper(
            sector, decomp, grid, (0.3, 0.25, 0.2), 2,
            dtype=jnp.float32, bx=4, by=8, donate=donate,
            pair_stages=False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # cpu drops donation
            for i in range(3):
                state = stepper.step(state, np.float32(i) * dt, dt,
                                     args)
        return state

    plain, donated = run(False), run(True)
    for k in plain:
        np.testing.assert_array_equal(np.asarray(plain[k]),
                                      np.asarray(donated[k]))


def test_donation_roundoff_exact_generic():
    """The generic XLA-tier step under donate=True: XLA legitimately
    re-fuses around the aliased buffers (the PR-3 finding — composed
    jits re-contract FMAs at ~1 ulp), so the pin here is agreement to
    a few f32 ulps over chained steps plus the lowering actually
    carrying the donation attrs the IR audit reads."""
    import warnings
    grid_shape = (8, 8, 8)
    decomp = ps.DomainDecomposition((1, 1, 1),
                                    devices=jax.devices()[:1])
    derivs = ps.FiniteDifferencer(decomp, 2, 0.3)
    sector = ps.ScalarSector(
        1, potential=lambda f: 0.5 * 1e-2 * f[0] ** 2)
    rhs = ps.compile_rhs_dict(sector.rhs_dict)

    def full_rhs(state, t, a, hubble):
        return rhs(state, t, lap_f=derivs.lap(state["f"]),
                   a=a, hubble=hubble)

    rng = np.random.default_rng(3)
    init = {
        "f": jnp.asarray(
            1e-3 * rng.standard_normal((1,) + grid_shape),
            jnp.float32),
        "dfdt": jnp.asarray(
            1e-4 * rng.standard_normal((1,) + grid_shape),
            jnp.float32),
    }
    args = {"a": np.float32(1.0), "hubble": np.float32(0.1)}
    dt = np.float32(0.01)

    def run(donate):
        stepper = ps.LowStorageRK54(full_rhs, dt=dt, donate=donate)
        state = {k: v.copy() for k, v in init.items()}
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # cpu drops donation
            for i in range(5):
                state = stepper.step(state, np.float32(i) * dt, dt, args)
        return state

    plain, donated = run(False), run(True)
    for k in plain:
        p, d = np.asarray(plain[k]), np.asarray(donated[k])
        # a handful of ulps of FMA re-contraction, nothing more
        np.testing.assert_allclose(p, d, rtol=1e-5, atol=1e-10)
    # and the donated stepper's lowering really carries the attrs
    stepper = ps.LowStorageRK54(full_rhs, dt=dt, donate=True)
    asm, _ = lint.lower_and_compile(
        stepper._jit_step, (init, np.float32(0.0), dt, args))
    _, stats = lint_graph.audit_donation(
        "donated", asm, sum(v.nbytes for v in init.values()))
    assert stats["coverage_pct"] == 100.0


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
