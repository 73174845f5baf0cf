"""repro.analysis: the static hot-path auditor.

Seeded-violation tests prove each check actually fires (an auditor that
never fails is decoration); green-path tests prove the real serving
programs audit clean against the committed baseline; plus the satellite
surfaces this PR hardened — dispatch recorder reentrancy, the eager-only
calibration contract, hlo_cost's unknown-op accounting.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.analysis as analysis
from repro.analysis import checks, lifecycle, report, targets
from repro.dist import hlo_cost
from repro.kernels import dispatch
from repro.quant.ptq import calibrate_activation_ranges

KEY = jax.random.PRNGKey(0)


def _target(fn, args, *, n_params, int8_idx=frozenset(), quant="float",
            policy="jnp", program="decode", lower=False):
  """Hand-built TraceTarget over an arbitrary function (seeded programs)."""
  with dispatch.record_dispatch() as log:
    closed = jax.make_jaxpr(fn)(*args)
  low = jax.jit(fn).lower(*args).as_text() if lower else None
  return targets.TraceTarget(
      config="seeded", family="test", policy=policy, quant=quant,
      program=program, jaxpr=closed, dispatch_log=list(log),
      n_params=n_params, int8_param_idx=int8_idx, n_donated=0,
      lowered_text=low, compiled_text=None)


# ---------------------------------------------------------------------------
# Seeded violations: every check must fire on a program built to violate it.
# ---------------------------------------------------------------------------


def test_unrouted_param_gemm_is_flagged():
  w = jnp.zeros((16, 32))
  x = jnp.zeros((4, 16))
  t = _target(lambda w, x: x @ w, (w, x), n_params=1)
  findings, _ = checks.run_target_checks(t)
  assert [f.check for f in findings] == ["dispatch_coverage"]
  assert findings[0].key.startswith("unrouted:")
  # activation x activation contractions are intrinsic math, not GEMMs
  t2 = _target(lambda w, x: x @ x.T, (w, x), n_params=1)
  assert checks.run_target_checks(t2)[0] == []


def test_routed_gemm_via_dispatch_is_clean():
  from repro.core.factored import dense
  from repro.layers.common import gemm
  leaf = dense(KEY, 128, 256, name="fc")
  x = jnp.zeros((4, 128))
  # jnp regime: the dot_general itself sits under the dispatch scope
  t = _target(lambda lf, x: gemm(lf, x, dispatch.JNP_ONLY), (leaf, x),
              n_params=2)
  findings, info = checks.run_target_checks(t)
  assert findings == []
  assert info["n_dots_scoped"] == 1
  assert info["regimes"] == ["jnp"]
  # pallas regime: the GEMM becomes a pallas_call (no dot at jaxpr
  # level) — still clean, still recorded
  t2 = _target(lambda lf, x: gemm(lf, x, dispatch.decode_policy(8)),
               (leaf, x), n_params=2)
  findings2, info2 = checks.run_target_checks(t2)
  assert findings2 == []
  assert info2["n_dispatch_records"] >= 1


def test_dequantize_of_int8_weight_is_flagged():
  w8 = jnp.zeros((16, 32), jnp.int8)
  x = jnp.zeros((4, 16))
  t = _target(lambda w, x: x @ w.astype(jnp.float32), (w8, x),
              n_params=1, int8_idx=frozenset({0}), quant="int8")
  findings, _ = checks.run_target_checks(t)
  assert any(f.check == "quant_integrity" and
             f.key.startswith("dequantize:") for f in findings)
  # int8 -> int32 accumulation is the legitimate widening, not a dequant
  t2 = _target(lambda w, x: w.astype(jnp.int32).sum(), (w8, x),
               n_params=1, int8_idx=frozenset({0}), quant="int8")
  assert not any(f.check == "quant_integrity"
                 for f in checks.run_target_checks(t2)[0])


def test_host_callback_is_flagged():
  def fn(x):
    y = jax.pure_callback(lambda a: np.asarray(a),
                          jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    return y + 1.0
  t = _target(fn, (jnp.zeros((4,)),), n_params=0)
  findings, _ = checks.run_target_checks(t)
  assert any(f.check == "transfer_lint" and "pure_callback" in f.key
             for f in findings)


def test_dropped_donation_is_flagged():
  t = _target(lambda s: s + 1.0, (jnp.zeros((4,)),), n_params=0,
              lower=True)
  t.n_donated = 3          # claim 3 donated leaves; none alias
  findings, _ = checks.run_target_checks(t)
  assert any(f.key.startswith("donation-dropped:") for f in findings)


def test_retrace_instability_is_observable():
  """A shape that escapes bucketing shows up in compile_stats — the
  exact signal the lifecycle check gates on."""
  cfg = analysis.configs.get_smoke("qwen3-4b").with_(vocab_size=64)
  from repro.models.api import get_model
  from repro.serving.engine import LMEngine
  params = get_model(cfg).init(KEY, cfg)
  eng = LMEngine(cfg, params, batch_size=2, max_len=16)
  eng.generate(np.array([[1, 2], [3, 4]]), steps=2)
  stats = eng.compile_stats()
  assert stats["step"] == 1
  # seed the violation: feed the donated step a rogue batch-3 signature
  rogue = eng._init_state(3)
  eng._step(params, rogue, jnp.zeros((3, 1), jnp.int32),
            jnp.zeros((3,), jnp.int32))
  assert eng.compile_stats()["step"] == 2


# ---------------------------------------------------------------------------
# Green path: the real serving programs audit clean against the baseline.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["jnp", "pallas"])
@pytest.mark.parametrize("config", ["qwen3-4b", "zamba2-7b"])
def test_audit_green_against_baseline(config, policy):
  rep = analysis.run_audit([config], [policy],
                           run_lifecycle=False, run_sharding=False)
  rep.apply_baseline(analysis.load_baseline())
  assert rep.ok, "\n" + rep.summary()
  # the grid actually covered scoped GEMMs, not a vacuous pass
  decode = [t for t in rep.targets if t["program"] == "decode"]
  assert decode and all(t["n_dots_scoped"] > 0 for t in decode)
  assert any(t["quant"] == "int8" for t in decode)


def test_lifecycle_check_green():
  findings, infos = lifecycle.check_retrace_stability(["qwen3-4b"],
                                                      ["jnp"])
  assert findings == [], findings
  (info,) = infos
  stats = info["compile_stats"]
  assert stats["step"] == 1
  # the serve cycle really hit two prompt buckets + the refill path
  assert len(stats["prefill_buckets"]) >= 2
  assert stats["insert"] == 1


def test_prefix_splice_check_green():
  findings, infos = lifecycle.check_prefix_splice_stability(["qwen3-4b"],
                                                            ["jnp"])
  assert findings == [], findings
  (info,) = infos
  # the scenario really exercised the splice path, not a vacuous pass
  assert info["cache_stats"]["hits"] >= 1
  stats = info["compile_stats"]
  # warm set == cold set == the two designed buckets
  assert sorted(stats["prefill_buckets"]) == [(1, 4), (1, 8)]


def test_spec_window_check_green():
  findings, infos = lifecycle.check_spec_window_stability(["qwen3-4b"],
                                                          ["jnp"])
  assert findings == [], findings
  (info,) = infos
  stats = info["compile_stats"]
  # one verify program across greedy + sampled cycles AND a rank walk
  assert stats["window"] == 1
  assert info["rank_walks"] >= 1


def test_sharding_coverage_flags_known_debt():
  rep = report.AuditReport()
  analysis._sharding_findings(["qwen3-4b"], rep)
  idents = {f.ident for f in rep.findings}
  base = {e["ident"] for e in analysis.load_baseline()["allow"]}
  assert idents <= base, idents - base
  # the quantized tree's path-matched leaves are the documented gap
  assert any(f.quant == "int8" for f in rep.findings)


# ---------------------------------------------------------------------------
# Report / baseline mechanics.
# ---------------------------------------------------------------------------


def test_stable_key_masks_call_ids():
  assert report.stable_key("dispatch:jnp:c42/dot") == "dispatch:jnp:c*/dot"
  f1 = report.Finding(check="dispatch_coverage", config="c",
                      key=report.stable_key("site:c7"))
  f2 = report.Finding(check="dispatch_coverage", config="c",
                      key=report.stable_key("site:c9001"))
  assert f1.ident == f2.ident


def test_finding_rejects_unknown_check():
  with pytest.raises(ValueError, match="unknown check"):
    report.Finding(check="vibes", config="c", key="k")


def test_baseline_partition_and_stale(tmp_path):
  f = report.Finding(check="transfer_lint", config="c", key="k")
  rep = report.AuditReport(findings=[f])
  rep.apply_baseline({"allow": []})
  assert not rep.ok and rep.new == [f]
  rep.apply_baseline({"allow": [{"ident": f.ident},
                                {"ident": "gone|-|-|-|transfer_lint|x"}]})
  assert rep.ok and rep.allowed == [f]
  assert rep.stale == ["gone|-|-|-|transfer_lint|x"]
  # round-trip through write/load
  path = str(tmp_path / "base.json")
  report.write_baseline(rep, path)
  loaded = report.load_baseline(path)
  assert {e["ident"] for e in loaded["allow"]} == {f.ident}
  assert report.load_baseline(str(tmp_path / "missing.json")) == \
      {"allow": []}


def test_cli_exit_codes(tmp_path, capsys):
  from repro.analysis.__main__ import main
  common = ["audit", "--configs", "qwen3_4b", "--policies", "jnp",
            "--quants", "float", "--programs", "decode",
            "--no-lifecycle", "--no-sharding"]
  rep_path = str(tmp_path / "report.json")
  assert main(common + ["--report", rep_path]) == 0
  saved = json.loads(open(rep_path).read())
  assert saved["ok"] and saved["targets"]
  # whisper's tied-head readout einsum is a known unrouted debt: against
  # an EMPTY baseline it must turn the exit code red
  empty = str(tmp_path / "empty.json")
  code = main(["audit", "--configs", "whisper_small", "--policies", "jnp",
               "--quants", "float", "--programs", "decode",
               "--no-lifecycle", "--no-sharding", "--baseline", empty])
  assert code == 1
  assert "NEW" in capsys.readouterr().out
  # --write-baseline accepts those debts; the same audit then passes
  assert main(["audit", "--configs", "whisper_small", "--policies", "jnp",
               "--quants", "float", "--programs", "decode",
               "--no-lifecycle", "--no-sharding", "--baseline", empty,
               "--write-baseline"]) == 0
  assert main(["audit", "--configs", "whisper_small", "--policies", "jnp",
               "--quants", "float", "--programs", "decode",
               "--no-lifecycle", "--no-sharding",
               "--baseline", empty]) == 0


# ---------------------------------------------------------------------------
# Satellite surfaces: recorder reentrancy, calibration contract, hlo_cost.
# ---------------------------------------------------------------------------


def test_record_dispatch_reentrant_and_exception_safe():
  with dispatch.record_dispatch() as outer:
    dispatch._record("a", "jnp")
    with dispatch.record_dispatch() as inner:
      dispatch._record("b", "int8_gemm")
    with pytest.raises(RuntimeError):
      with dispatch.record_dispatch():
        raise RuntimeError("boom")
    dispatch._record("c", "jnp")
  assert [(r.name, r.regime) for r in outer] == \
      [("a", "jnp"), ("b", "int8_gemm"), ("c", "jnp")]
  assert [(r.name, r.regime) for r in inner] == [("b", "int8_gemm")]
  assert not dispatch._RECORDERS


def test_observe_gemm_inputs_reentrant():
  x = jnp.ones((2, 4))
  with dispatch.observe_gemm_inputs() as outer:
    with dispatch.observe_gemm_inputs() as inner:
      dispatch._observe("fc", x)
    dispatch._observe("fc2", 2 * x)
  assert inner == {"fc": 1.0}
  assert outer == {"fc": 1.0, "fc2": 2.0}
  assert not dispatch._OBSERVERS


def test_dispatch_record_is_tuple_compatible():
  rec = dispatch.DispatchRecord("fc", "int8_gemm", 7)
  assert rec == ("fc", "int8_gemm")
  name, regime = rec
  assert (name, regime) == (rec.name, rec.regime)
  assert rec.call_id == 7


def test_calibration_rejects_jitted_apply_fn():
  from repro.core.factored import dense
  from repro.layers.common import gemm
  leaf = dense(KEY, 32, 16, name="fc")

  @jax.jit
  def jitted(x):
    return gemm(leaf, x, dispatch.JNP_ONLY)

  with pytest.raises(RuntimeError, match="EAGERLY"):
    calibrate_activation_ranges(jitted, [jnp.ones((2, 32))])
  # the eager version of the same apply_fn calibrates fine
  got = calibrate_activation_ranges(
      lambda x: gemm(leaf, x, dispatch.JNP_ONLY), [jnp.ones((2, 32))])
  assert got == {"fc": 1.0}
  # zero batches is vacuous, not an error
  assert calibrate_activation_ranges(jitted, []) == {}


def test_hlo_cost_counts_unknown_ops():
  hlo = """
HloModule m, entry_computation_layout={()->f32[4]{0}}

ENTRY %main () -> f32[4] {
  %c = f32[4]{0} constant({1, 2, 3, 4})
  %w = weird9[4]{0} bitcast(f32[4]{0} %c)
  %bad = f32[4]{0} mystery-op with no operand parens
  ROOT %r = f32[4]{0} add(f32[4]{0} %c, f32[4]{0} %c)
}
"""
  rep = hlo_cost.analyze_module(hlo)
  assert rep.unknown_ops.get("dtype:weird9") == 1
  assert rep.unknown_ops.get("<unparsed>") == 1
  assert rep.hbm_bytes >= 16       # the unparsed f32[4] counted as traffic
  # a clean module reports nothing unknown
  clean = hlo.replace("weird9", "f32").replace(
      "\n  %bad = f32[4]{0} mystery-op with no operand parens", "")
  assert hlo_cost.analyze_module(clean).unknown_ops == {}
