"""analysis/ layer tests: analytic cost models (flops.py), roofline
estimates (roofline.py), and the repro-lint static-analysis pass
(analysis/lint/) — every rule R1–R5 gets one firing and one clean
fixture under tests/fixtures/lint/, plus the repo-wide clean pin."""
import dataclasses
import json
from pathlib import Path

import pytest

from repro.analysis import flops as F
from repro.analysis import roofline as R
from repro.analysis.lint import (BaselineEntry, HostSyncRule, LintConfig,
                                 NondeterminismRule, OwnershipRule,
                                 PallasKernelRule, ProtocolContractRule,
                                 RngLaneRule, ShardingConsistencyRule,
                                 SharedStateRule, VmemBudgetRule, core_rules,
                                 lint_paths, load_baseline, prune_baseline)
from repro.analysis.lint.__main__ import main as lint_main
from repro.configs import get_smoke_config
from repro.models.config import InputShape

REPO_ROOT = Path(__file__).resolve().parents[1]
FIXTURES = REPO_ROOT / "tests" / "fixtures" / "lint"


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("llama3.2-1b")


# ---------------------------------------------------------------------------
# flops.py
# ---------------------------------------------------------------------------


def test_forward_flops_linear_in_batch(cfg):
    one = F.forward_flops(cfg, 1, 128)
    assert one > 0
    assert F.forward_flops(cfg, 4, 128) == pytest.approx(4 * one)


def test_attention_flops_superlinear_in_seq(cfg):
    # causal attention is quadratic: doubling S more than doubles FLOPs
    short = F.forward_flops(cfg, 1, 256)
    assert F.forward_flops(cfg, 1, 512) > 2 * short


def test_sliding_window_caps_cache(cfg):
    windowed = dataclasses.replace(cfg, sliding_window=64)
    full = F.cache_bytes(cfg, 1, 1024)
    capped = F.cache_bytes(windowed, 1, 1024)
    assert capped < full
    # beyond the window the cache stops growing
    assert capped == F.cache_bytes(windowed, 1, 4096)


def test_int8_cache_is_smaller(cfg):
    int8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    assert F.cache_bytes(int8, 2, 512) < F.cache_bytes(cfg, 2, 512)
    # exactly (1 B data + 4/hd B per-slot-head f32 scale) per element
    hd = cfg.resolved_head_dim
    dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
    assert F.cache_bytes(int8, 2, 512) == pytest.approx(
        F.cache_bytes(cfg, 2, 512) * (1 + 4 / hd) / dtype_bytes)


def test_remat_multiplier(cfg):
    shape = InputShape("t", 128, 2, "train")
    base = F.train_cost(cfg, shape).flops
    remat = F.train_cost(dataclasses.replace(cfg, remat=True), shape).flops
    assert remat == pytest.approx(base * 4.0 / 3.0)


def test_grouped_decode_reads_cache_once(cfg):
    assert cfg.q_per_kv > 1  # GQA config, else the knob is moot
    shape = InputShape("d", 512, 4, "decode")
    naive = F.decode_cost(cfg, shape)
    grouped = F.decode_cost(
        dataclasses.replace(cfg, grouped_decode=True), shape)
    assert grouped.hbm_bytes < naive.hbm_bytes
    assert grouped.flops == pytest.approx(naive.flops)


def test_estimate_dispatches_on_mode(cfg):
    for name, mode, fn in (("t", "train", F.train_cost),
                           ("p", "prefill", F.prefill_cost),
                           ("d", "decode", F.decode_cost)):
        shape = InputShape(name, 128, 2, mode)
        assert F.estimate(cfg, shape) == fn(cfg, shape)


def test_per_chip_divides(cfg):
    est = F.prefill_cost(cfg, InputShape("p", 128, 4, "prefill"))
    half = est.per_chip(2)
    assert half.flops == pytest.approx(est.flops / 2)
    assert half.hbm_bytes == pytest.approx(est.hbm_bytes / 2)


# ---------------------------------------------------------------------------
# roofline.py
# ---------------------------------------------------------------------------


_HLO = """\
HloModule test

%body (p: f32[128]) -> f32[128] {
  %ar = f32[128]{0} all-reduce(f32[128]{0} %p), replica_groups={}
  ROOT %t = f32[128]{0} copy(%ar)
}

%cond (p: f32[128]) -> pred[] {
  %trip = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %trip), direction=LT
}

ENTRY %main (p: f32[128]) -> f32[128] {
  %ag = f32[256]{0} all-gather(f32[128]{0} %p), dimensions={0}
  %w = f32[128]{0} while(f32[128]{0} %ag), condition=%cond, body=%body
  ROOT %r = f32[128]{0} copy(%w)
}
"""


def test_collective_bytes_loop_aware():
    coll = R.collective_bytes(_HLO)
    assert coll["all-gather"] == 256 * 4          # once, in ENTRY
    assert coll["all-reduce"] == 4 * 128 * 4      # x4 while trips
    assert coll["all-to-all"] == 0


def test_collective_bytes_flat_fallback():
    # no ENTRY header: every collective counted once
    flat = "\n".join(line for line in _HLO.splitlines()
                     if not line.startswith(("ENTRY", "%", "HloModule", "}")))
    coll = R.collective_bytes(flat)
    assert coll["all-reduce"] == 128 * 4


class _FakeCompiled:
    def __init__(self, cost, text=_HLO):
        self._cost, self._text = cost, text

    def cost_analysis(self):
        return self._cost

    def as_text(self):
        return self._text


def test_analyze_bottleneck_and_terms():
    rf = R.analyze(_FakeCompiled({"flops": 1e12, "bytes accessed": 1e9}))
    assert rf.hlo_flops == 1e12
    assert rf.compute_s == pytest.approx(1e12 / R.PEAK_FLOPS)
    assert rf.memory_s == pytest.approx(1e9 / R.HBM_BW)
    assert rf.coll_bytes == 256 * 4 + 4 * 128 * 4
    assert rf.bottleneck == "compute"
    d = rf.as_dict()
    assert d["bottleneck"] == "compute"
    assert d["collective_by_kind"]["all-gather"] == 1024


def test_analyze_accepts_list_cost_analysis():
    # older jax returns [dict]
    rf = R.analyze(_FakeCompiled([{"flops": 5.0, "bytes accessed": 7.0}]))
    assert (rf.hlo_flops, rf.hlo_bytes) == (5.0, 7.0)
    rf = R.analyze(_FakeCompiled([]))
    assert rf.hlo_flops == 0.0


def test_analyze_analytic_override_per_chip():
    analytic = F.CostEstimate(2e12, 2e9)
    rf = R.analyze(_FakeCompiled({"flops": 1.0, "bytes accessed": 1.0}),
                   analytic=analytic, chips=2)
    assert rf.flops == pytest.approx(1e12)
    assert rf.bytes_accessed == pytest.approx(1e9)
    assert rf.hlo_flops == 1.0  # raw HLO numbers still recorded


def test_model_flops_train_vs_forward(cfg):
    fwd = R.model_flops(cfg, 1000, train=False)
    assert fwd == 2 * cfg.active_param_count() * 1000
    assert R.model_flops(cfg, 1000, train=True) == 3 * fwd


# ---------------------------------------------------------------------------
# repro-lint: rule fixtures
# ---------------------------------------------------------------------------


def _run(rule, *paths):
    return lint_paths([Path(p) for p in paths], rules=[rule], root=FIXTURES)


RULE_FIXTURES = [
    (NondeterminismRule, FIXTURES / "r1_fires.py", FIXTURES / "r1_clean.py"),
    (HostSyncRule, FIXTURES / "r2_fires.py", FIXTURES / "r2_clean.py"),
    (RngLaneRule, FIXTURES / "serving" / "r3_fires.py",
     FIXTURES / "serving" / "r3_clean.py"),
    (PallasKernelRule, FIXTURES / "r4_fires.py", FIXTURES / "r4_clean.py"),
    (SharedStateRule, FIXTURES / "r5_fires.py", FIXTURES / "r5_clean.py"),
    (VmemBudgetRule, FIXTURES / "r6_fires.py", FIXTURES / "r6_clean.py"),
    (ShardingConsistencyRule, FIXTURES / "r7_fires.py",
     FIXTURES / "r7_clean.py"),
    (OwnershipRule, FIXTURES / "r8_fires.py", FIXTURES / "r8_clean.py"),
    (ProtocolContractRule, FIXTURES / "r9_fires.py",
     FIXTURES / "r9_clean.py"),
]


@pytest.mark.parametrize("rule_cls,fires,clean", RULE_FIXTURES,
                         ids=[c.id for c, *_ in RULE_FIXTURES])
def test_rule_fires_and_clean(rule_cls, fires, clean):
    rule = rule_cls()
    fired = _run(rule, fires).findings
    assert fired, f"{rule.id} found nothing in {fires.name}"
    assert all(f.rule == rule.id for f in fired)
    assert all(f.line > 0 and f.hint for f in fired)
    assert _run(rule_cls(), clean).findings == []


def test_r1_finds_all_three_sources():
    msgs = [f.message for f in
            _run(NondeterminismRule(), FIXTURES / "r1_fires.py").findings]
    assert any("wall-clock" in m for m in msgs)
    assert any("RNG" in m for m in msgs)
    assert any("iteration over a set" in m for m in msgs)


def test_r2_traces_through_jit_and_lambda():
    msgs = [f.message for f in
            _run(HostSyncRule(), FIXTURES / "r2_fires.py").findings]
    assert any("int() coercion" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)
    assert any(".item()" in m for m in msgs)   # via jit(lambda) -> _inner


def test_r4_reports_each_inconsistency():
    msgs = [f.message for f in
            _run(PallasKernelRule(), FIXTURES / "r4_fires.py").findings]
    assert any("takes 1 args but grid+prefetch needs 2" in m for m in msgs)
    assert any("returns 3 coordinates" in m for m in msgs)
    assert any("specs provide 5" in m for m in msgs)
    assert any("does not divide" in m for m in msgs)
    assert any("scratch_shapes[1]" in m for m in msgs)
    assert len(msgs) == 5


def test_r5_names_class_and_field():
    found = _run(SharedStateRule(), FIXTURES / "r5_fires.py").findings
    assert {f.message for f in found} == {
        "write to Replica field 'name' from outside its methods",
        "write to Replica field 'tok_per_s' from outside its methods",
    }
    assert {f.scope for f in found} == {"EnginePool.__init__",
                                        "EnginePool.stream"}


def test_r6_computes_real_kernel_footprints():
    """R6's abstract evaluator resolves every shipped kernel's blocks
    AND scratch without a TPU, and publishes the footprints as notes."""
    report = lint_paths([REPO_ROOT / "src" / "repro" / "kernels"],
                        rules=[VmemBudgetRule()], root=REPO_ROOT)
    assert report.findings == []
    notes = [n for n in report.notes if "VMEM footprint" in n]
    # chunked prefill, paged prefill (one-page and multi-page calls),
    # dense and paged decode
    assert len(notes) == 5
    assert all("scratch" in n for n in notes)
    assert not any("+ 0 KiB scratch" in n for n in notes)


def test_r6_shrunk_budget_fails_real_kernels():
    """Break-an-invariant: a budget below the decode kernel's ~4 MiB
    footprint must turn the kernels into findings."""
    tiny = LintConfig(vmem_budget_bytes=600 * 1024)
    report = lint_paths([REPO_ROOT / "src" / "repro" / "kernels"],
                        rules=[VmemBudgetRule(tiny)], root=REPO_ROOT)
    assert any(f.rule == "R6" and "exceeds" in f.message
               for f in report.findings)
    # and the kernels' own 64 MiB scoped-VMEM limit accepts them
    roomy = LintConfig(vmem_budget_bytes=64 * 1024 * 1024)
    assert lint_paths([REPO_ROOT / "src" / "repro" / "kernels"],
                      rules=[VmemBudgetRule(roomy)],
                      root=REPO_ROOT).findings == []


def test_r7_reports_each_drift():
    msgs = [f.message for f in
            _run(ShardingConsistencyRule(), FIXTURES / "r7_fires.py")
            .findings]
    assert any("unknown mesh axis 'modle'" in m for m in msgs)
    assert any("appears twice" in m for m in msgs)
    assert any("ranks disagree" in m for m in msgs)
    assert any("data_axes" in m for m in msgs)
    assert any("sharded over 'model'" in m for m in msgs)


def test_r8_reports_each_escape():
    msgs = [f.message for f in
            _run(OwnershipRule(), FIXTURES / "r8_fires.py").findings]
    assert any(".append() mutates" in m and "'inflight'" in m for m in msgs)
    assert any("escapes EnginePool by reference" in m for m in msgs)
    assert any("through local alias 'jobs'" in m for m in msgs)
    assert any("not @dataclass(frozen=True)" in m for m in msgs)
    assert any("object.__setattr__ outside" in m for m in msgs)


def test_r8_cross_class_replica_write_fails(tmp_path):
    """Break-an-invariant: unlock a Replica write from gateway code and
    R8 must fail the run."""
    broken = tmp_path / "gateway.py"
    broken.write_text(
        "class Replica:\n"
        "    def __init__(self):\n"
        "        self.inflight = []\n\n\n"
        "class GatewayQueue:\n"
        "    def push(self, rep, job):\n"
        "        rep.inflight.append(job)\n")
    report = lint_paths([broken], rules=[OwnershipRule()], root=tmp_path)
    assert any(f.rule == "R8" and "'inflight'" in f.message
               for f in report.findings)


def test_r9_reports_each_contract_break():
    msgs = [f.message for f in
            _run(ProtocolContractRule(), FIXTURES / "r9_fires.py").findings]
    assert any("yield of a non-action value" in m for m in msgs)
    assert any("resume is discarded" in m for m in msgs)
    assert any("never checked against RemoteFailure" in m for m in msgs)
    assert any("approx_tokens" in m for m in msgs)


def test_r9_real_protocols_conform():
    """Every registered protocol in core/ satisfies the action contract."""
    report = lint_paths([REPO_ROOT / "src" / "repro" / "core"],
                        rules=[ProtocolContractRule()], root=REPO_ROOT)
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings)


# ---------------------------------------------------------------------------
# repro-lint: engine mechanics
# ---------------------------------------------------------------------------


def test_inline_disable_comment(tmp_path):
    f = tmp_path / "snippet.py"
    f.write_text(
        "import time\n"
        "a = time.time()  # repro-lint: disable=R1\n"
        "# repro-lint: disable=all\n"
        "b = time.time()\n"
        "c = time.time()  # repro-lint: disable=R3\n")
    report = lint_paths([f], rules=[NondeterminismRule()], root=tmp_path)
    # a and b suppressed; c's directive names the wrong rule
    assert report.inline_disabled == 2
    assert [fi.line for fi in report.findings] == [5]


def test_baseline_suppresses_and_reports_stale(tmp_path):
    rule = NondeterminismRule()
    raw = _run(rule, FIXTURES / "r1_fires.py").findings
    first = raw[0]
    baseline = [
        BaselineEntry(first.rule, first.file, first.scope, first.message,
                      "fixture: accepted on purpose"),
        BaselineEntry("R1", first.file, "gone_scope",
                      "wall-clock call time.time()",
                      "stale: the scope it matched was fixed"),
    ]
    report = lint_paths([FIXTURES / "r1_fires.py"], rules=[rule],
                        root=FIXTURES, baseline=baseline)
    assert len(report.findings) == len(raw) - 1
    assert [b.key for b in report.stale_baseline] == [baseline[1].key]
    assert all(f.key != first.key for f in report.findings)


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"findings": [
        {"rule": "R1", "file": "x.py", "scope": "", "message": "m"}]}))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(bl)


def test_cli_exit_codes(capsys):
    rc = lint_main([str(FIXTURES / "r1_fires.py"), "--no-baseline",
                    "--root", str(FIXTURES), "--fix-hints"])
    out = capsys.readouterr()
    assert rc == 1
    assert "R1" in out.out and "hint:" in out.out
    rc = lint_main([str(FIXTURES / "r1_clean.py"), "--no-baseline",
                    "--root", str(FIXTURES)])
    assert rc == 0
    assert lint_main(["--list-rules"]) == 0


def test_cli_list_rules_covers_r1_to_r9(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8", "R9"):
        assert rid in out


def test_cli_rules_filter(capsys):
    # R4 finds nothing in the R1 fixture -> clean exit
    rc = lint_main([str(FIXTURES / "r1_fires.py"), "--no-baseline",
                    "--root", str(FIXTURES), "--rules", "R4"])
    assert rc == 0
    # unknown rule ids are a usage error, not silently ignored
    rc = lint_main([str(FIXTURES / "r1_fires.py"), "--no-baseline",
                    "--rules", "R42"])
    capsys.readouterr()
    assert rc == 2


def test_cli_json_format(capsys):
    rc = lint_main([str(FIXTURES / "r1_fires.py"), "--no-baseline",
                    "--root", str(FIXTURES), "--format", "json"])
    out = capsys.readouterr().out
    assert rc == 1
    payload = json.loads(out)
    assert payload["version"] == 1
    assert payload["files"] == 1
    assert payload["findings"]
    for f in payload["findings"]:
        assert set(f) == {"rule", "file", "line", "col", "scope",
                          "message", "fix_hint"}
        assert f["rule"] == "R1" and f["line"] > 0 and f["fix_hint"]
    assert {"baselined", "inline_disabled", "stale_baseline",
            "notes"} <= set(payload)


def test_cli_github_format(capsys):
    rc = lint_main([str(FIXTURES / "r1_fires.py"), "--no-baseline",
                    "--root", str(FIXTURES), "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=r1_fires.py,line=" in out
    assert "title=repro-lint R1" in out
    # notes ride along as ::notice annotations
    rc = lint_main([str(REPO_ROOT / "src" / "repro" / "kernels"),
                    "--no-baseline", "--root", str(REPO_ROOT),
                    "--rules", "R6", "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "::notice title=repro-lint::" in out
    assert "VMEM footprint" in out


def test_prune_baseline_idempotent(tmp_path):
    """--prune-baseline drops exactly the stale entries, preserves the
    _comment and every kept justification, and is idempotent."""
    rule = NondeterminismRule()
    live = _run(rule, FIXTURES / "r1_fires.py").findings[0]
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({
        "_comment": ["hands off"],
        "findings": [
            {"rule": live.rule, "file": live.file, "scope": live.scope,
             "message": live.message, "justification": "still real"},
            {"rule": "R1", "file": live.file, "scope": "gone_scope",
             "message": "wall-clock call time.time()",
             "justification": "was fixed long ago"},
        ]}, indent=2) + "\n")
    baseline = load_baseline(bl)
    report = lint_paths([FIXTURES / "r1_fires.py"], rules=[rule],
                        root=FIXTURES, baseline=baseline)
    assert [e.scope for e in report.stale_baseline] == ["gone_scope"]
    assert prune_baseline(bl, report.stale_baseline) == 1
    data = json.loads(bl.read_text())
    assert data["_comment"] == ["hands off"]
    assert len(data["findings"]) == 1
    assert data["findings"][0]["justification"] == "still real"
    # idempotent: a second prune with a re-run report removes nothing
    report2 = lint_paths([FIXTURES / "r1_fires.py"], rules=[rule],
                         root=FIXTURES, baseline=load_baseline(bl))
    assert report2.stale_baseline == []
    assert prune_baseline(bl, report2.stale_baseline) == 0
    assert json.loads(bl.read_text())["findings"][0]["justification"] \
        == "still real"


def test_stale_scoped_to_linted_files_and_active_rules():
    """Split invocations (the R1/R3 pass over benchmarks/) must not
    report entries for files or rules outside the run as stale."""
    entry = BaselineEntry("R1", "elsewhere/mod.py", "main",
                          "wall-clock call time.time()", "other pass")
    report = lint_paths([FIXTURES / "r1_fires.py"],
                        rules=[NondeterminismRule()], root=FIXTURES,
                        baseline=[entry])
    assert report.stale_baseline == []      # file not linted here
    entry2 = BaselineEntry("R4", "r1_fires.py", "main", "anything",
                           "inactive rule")
    report = lint_paths([FIXTURES / "r1_fires.py"],
                        rules=[NondeterminismRule()], root=FIXTURES,
                        baseline=[entry2])
    assert report.stale_baseline == []      # rule not active here


# ---------------------------------------------------------------------------
# repro-lint: the repo itself
# ---------------------------------------------------------------------------


def test_repo_is_lint_clean_under_baseline():
    """The acceptance pin: src/repro has zero unbaselined findings and
    every baseline entry still matches a real finding (none stale)."""
    baseline = load_baseline(REPO_ROOT / "lint_baseline.json")
    report = lint_paths([REPO_ROOT / "src" / "repro"], rules=core_rules(),
                        root=REPO_ROOT, baseline=baseline)
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings)
    assert report.stale_baseline == [], [e.key for e in
                                         report.stale_baseline]
    assert report.baselined, "baseline should still be exercised"


def test_breaking_an_invariant_fails_lint(tmp_path):
    """The ISSUE's litmus test: wall-clock routing trips R1."""
    broken = tmp_path / "serving" / "routing.py"
    broken.parent.mkdir()
    broken.write_text(
        "import time\n\n\n"
        "def route_job(job, snapshots):\n"
        "    return min(snapshots, key=lambda s: s.depth + time.time())\n")
    report = lint_paths([broken], rules=core_rules(), root=tmp_path)
    assert any(f.rule == "R1" and "wall-clock" in f.message
               for f in report.findings)
