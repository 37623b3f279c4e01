"""repro.analysis — the lint suite linted by its own fixtures.

Every rule has at least one fixture file that must trip it (with exact
codes and line numbers) and one that must stay clean; the CC001 gate is
exercised against synthetic bench artifacts, including a deliberate
contract violation.  A final dogfood test pins the repo itself to
``--strict`` clean, so CI cannot drift from the lint contract.
"""
import ast
import json
import shutil
import subprocess
import types
from pathlib import Path

import pytest

from repro.analysis import (check_compile_gate, load_config, run_analysis)
from repro.analysis.config import AnalysisConfig
from repro.analysis.engine import changed_files
from repro.analysis.findings import scan_waivers
from repro.analysis.__main__ import main as cli_main

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"
REPO = Path(__file__).parent.parent


def lint(*files, strict=False, select=None):
    cfg = AnalysisConfig(root=FIXTURES, paths=tuple(files))
    return run_analysis(cfg, select=select, strict=strict)


def lines(findings, code=None):
    return sorted(f.line for f in findings
                  if code is None or f.code == code)


# ---------------------------------------------------------------- JX001

def test_jx001_bad_exact_sites():
    rep = lint("jx001_bad.py", select=["JX001"])
    assert lines(rep.active, "JX001") == [8, 13, 18, 24]


def test_jx001_good_is_clean():
    rep = lint("jx001_good.py", select=["JX001"])
    assert rep.active == []


# ---------------------------------------------------------------- JX002

def test_jx002_bad_flags_and_waives():
    rep = lint("jx002_bad.py", select=["JX002"])
    assert lines(rep.active, "JX002") == [8]
    waived = [f for f in rep.findings if f.waived]
    assert lines(waived, "JX002") == [13]
    assert waived[0].waiver_reason.startswith("fixture:")


def test_jx002_good_side_of_boundary():
    # jx001_good has host numpy on constants + host driver code: clean
    rep = lint("jx001_good.py", select=["JX002"])
    assert rep.active == []


# ---------------------------------------------------------------- JX003

def test_jx003_bad_exact_sites():
    rep = lint("jx003_bad.py", select=["JX003"])
    assert lines(rep.active, "JX003") == [12, 18, 24, 29, 36]


def test_jx003_good_host_effects_and_waiver():
    rep = lint("jx003_good.py", select=["JX003"])
    assert rep.active == []
    assert lines([f for f in rep.findings if f.waived], "JX003") == [18]


def test_jx003_host_span_in_jitted_body():
    """A profiler span inside a jitted body records once per compile; the
    same span around the jitted call on the host stays clean."""
    rep = lint("jx003_span.py", select=["JX003"])
    assert lines(rep.active, "JX003") == [11, 17, 23]
    assert all("span" in f.message for f in rep.active)


# ---------------------------------------------------------------- PT001

def test_pt001_bad_exact_sites():
    rep = lint("pt001_bad.py", select=["PT001"])
    got = lines(rep.active, "PT001")
    assert got == [13, 23, 33]
    msgs = {f.line: f.message for f in rep.active}
    assert "frozen" in msgs[13]
    assert "missing" in msgs[23]
    assert "meta" in msgs[33]


def test_pt001_good_including_loop_registration():
    rep = lint("pt001_good.py", select=["PT001"])
    assert rep.active == []


# ---------------------------------------------------------------- UN001

def test_un001_bad_fields_and_payload_keys():
    rep = lint("un001_bad.py", select=["UN001"])
    assert lines(rep.active, "UN001") == [9, 11, 15]


def test_un001_good_suffixes_and_allowlist():
    rep = lint("un001_good.py", select=["UN001"])
    assert rep.active == []


# ---------------------------------------------------------------- SC001

def test_sc001_bad_exact_sites():
    rep = lint("sc001_bad.py", select=["SC001"])
    assert lines(rep.active, "SC001") == [7, 16, 25, 34, 43, 54, 65]
    msgs = {f.line: f.message for f in rep.active}
    assert "(carry, ys) pair" in msgs[7]
    assert "arity diverges" in msgs[16]
    assert "reordered" in msgs[25]
    assert "true division" in msgs[34]
    assert "jax.numpy.mean" in msgs[43]
    assert "astype" in msgs[54]
    assert "return paths" in msgs[65]


def test_sc001_good_is_clean():
    # dict carries, floor division, float init, symmetric astype,
    # partial-bound bodies, opaque carry returns: all stable
    rep = lint("sc001_good.py", select=["SC001"])
    assert rep.active == []


# ---------------------------------------------------------------- DN001

def test_dn001_bad_exact_sites():
    rep = lint("dn001_bad.py", select=["DN001"])
    assert lines(rep.active, "DN001") == [14, 20, 32, 44, 52]
    for f in rep.active:
        assert "donated" in f.message and "read again" in f.message


def test_dn001_good_is_clean():
    # fresh buffers per call, rebinds, reads before the call, non-donated
    # keywords, and args of a multi-line donating call never flag
    rep = lint("dn001_good.py", select=["DN001"])
    assert rep.active == []


# ---------------------------------------------------------------- SH001

def test_sh001_bad_exact_sites():
    rep = lint("sh001_bad.py", select=["SH001"])
    assert lines(rep.active, "SH001") == [6, 10, 15, 21]
    msgs = {f.line: f.message for f in rep.active}
    assert "leading axis" in msgs[6]
    assert "device_put" in msgs[15] or "device placement" in msgs[15]
    assert "mesh" in msgs[21]


def test_sh001_good_is_clean():
    rep = lint("sh001_good.py", select=["SH001"])
    assert rep.active == []


# ---------------------------------------------------------------- severity

def test_severity_defaults_warn_gates_only_strict():
    rep = lint("sh001_bad.py", select=["SH001"])
    assert rep.active and all(f.severity == "warn" for f in rep.active)
    assert rep.ok                          # warns pass a default run
    rep = lint("sh001_bad.py", select=["SH001"], strict=True)
    assert not rep.ok                      # --strict promotes warns


def test_severity_overrides_change_gating():
    cfg = AnalysisConfig(root=FIXTURES, paths=("sh001_bad.py",),
                         severity=(("SH001", "error"),))
    assert not run_analysis(cfg, select=["SH001"]).ok
    cfg = AnalysisConfig(root=FIXTURES, paths=("sc001_bad.py",),
                         severity=(("SC001", "info"),))
    rep = run_analysis(cfg, select=["SC001"], strict=True)
    assert rep.active and rep.ok           # info prints, never gates


def test_severity_config_parsing():
    from repro.analysis.config import _parse_severity
    assert _parse_severity(["SH001=error"]) == (("SH001", "error"),)
    assert _parse_severity({"SC001": "info"}) == (("SC001", "info"),)
    with pytest.raises(ValueError):
        _parse_severity(["ZZ999=warn"])
    with pytest.raises(ValueError):
        _parse_severity(["SH001=loud"])


def test_finding_render_uses_severity_word():
    rep = lint("sh001_bad.py", select=["SH001"])
    assert "SH001 warning:" in rep.active[0].render()
    rep = lint("jx001_bad.py", select=["JX001"])
    assert "JX001 error:" in rep.active[0].render()


def test_report_payload_counts_per_severity():
    from repro.analysis.findings import report_payload
    rep = lint("sh001_bad.py", select=["SH001"])
    payload = report_payload(rep.findings)
    assert payload["summary"]["per_severity"] == {"warn": 4}


# ---------------------------------------------------------------- waivers

def test_waiver_scanning_forms():
    src = ("x = 1  # lint: waive JX001 -- same line\n"
           "# lint: waive UN001,PT001 -- next line\n"
           "y = 2\n")
    w = scan_waivers(src)
    assert w[1].codes == {"JX001"}
    assert w[2].codes == w[3].codes == {"UN001", "PT001"}
    assert w[3].reason == "next line"


def test_waiver_multi_code_trailing():
    src = "y = f()  # lint: waive JX003,SC001 -- counts compiles, stable\n"
    w = scan_waivers(src)
    assert w[1].codes == {"JX003", "SC001"}


def test_waiver_standalone_above_decorated_def():
    src = ("import dataclasses\n"
           "# lint: waive PT001 -- fixture: covers the class line too\n"
           "@dataclasses.dataclass\n"
           "class C:\n"
           "    x: int = 0\n")
    w = scan_waivers(src, ast.parse(src))
    assert 2 in w and 3 in w               # comment + first decorator line
    assert 4 in w and w[4].codes == {"PT001"}   # the class line itself
    # without the tree only the next-line form resolves
    w_plain = scan_waivers(src)
    assert 3 in w_plain and 4 not in w_plain


def test_waiver_on_continuation_line():
    src = ("x = (1 +\n"
           "     2)  # lint: waive UN001 -- fixture: continuation\n")
    w = scan_waivers(src, ast.parse(src))
    assert 2 in w
    assert 1 in w and w[1].codes == {"UN001"}   # the statement's lineno
    w_plain = scan_waivers(src)
    assert 1 not in w_plain


def test_wv001_only_in_strict():
    rep = lint("wv001_bad.py", select=["JX002"])
    assert rep.active == []                      # waiver applies
    rep = lint("wv001_bad.py", select=["JX002"], strict=True)
    assert [f.code for f in rep.active] == ["WV001"]


# ---------------------------------------------------------------- CC001

def _bench_payload(bench, counters):
    return {"schema": "repro.obs/bench/v1",
            "manifest": {"bench": bench,
                         "metrics": {"counters": counters}},
            "rows": []}


def _write(tmp_path, name, payload):
    p = tmp_path / name
    p.write_text(json.dumps(payload))
    return p


def test_cc001_within_contract(tmp_path):
    contracts = REPO / "src" / "repro" / "analysis" / "contracts.json"
    art = _write(tmp_path, "BENCH_speedup.json", _bench_payload(
        "speedup", {"kernel.jax.simulate.compile_count": 1,
                    "scenario.sweep.compile_count": 1}))
    assert check_compile_gate(contracts, [art]) == []


def test_cc001_deliberate_violation_fails_gate(tmp_path):
    # the checked-in contract allows 1 sweep compile for bench speedup; a
    # regressed jit cache key would recompile per call — the gate must trip
    contracts = REPO / "src" / "repro" / "analysis" / "contracts.json"
    art = _write(tmp_path, "BENCH_speedup.json", _bench_payload(
        "speedup", {"scenario.sweep.compile_count": 64}))
    findings = check_compile_gate(contracts, [art])
    assert len(findings) == 1
    assert findings[0].code == "CC001"
    assert "scenario.sweep.compile_count" in findings[0].message


def test_cc001_patched_contract_tightens(tmp_path):
    patched = _write(tmp_path, "contracts.json", {
        "schema": "repro.analysis/contracts/v1",
        "contracts": {"speedup": {"scenario.sweep.compile_count": 0}}})
    art = _write(tmp_path, "BENCH_speedup.json", _bench_payload(
        "speedup", {"scenario.sweep.compile_count": 1}))
    findings = check_compile_gate(patched, [art])
    assert [f.code for f in findings] == ["CC001"]


def test_cc001_unknown_bench_and_stray_counter(tmp_path):
    patched = _write(tmp_path, "contracts.json", {
        "schema": "repro.analysis/contracts/v1",
        "contracts": {"speedup": {}}})
    unknown = _write(tmp_path, "BENCH_new.json",
                     _bench_payload("brand_new", {}))
    stray = _write(tmp_path, "BENCH_speedup.json", _bench_payload(
        "speedup", {"scenario.sweep.compile_count": 2}))
    msgs = [f.message for f in check_compile_gate(patched, [unknown, stray])]
    assert any("no compile-count contract" in m for m in msgs)
    assert any("not in the contract" in m for m in msgs)


def test_cc001_pytest_plugin_flips_exit_status(tmp_path, monkeypatch):
    from repro.analysis import pytest_plugin
    patched = _write(tmp_path, "contracts.json", {
        "schema": "repro.analysis/contracts/v1",
        "contracts": {"speedup": {"scenario.sweep.compile_count": 0}}})
    _write(tmp_path, "BENCH_speedup.json", _bench_payload(
        "speedup", {"scenario.sweep.compile_count": 3}))
    monkeypatch.chdir(tmp_path)

    class _Config:
        def __init__(self):
            self.pluginmanager = types.SimpleNamespace(
                get_plugin=lambda name: None)

        def getoption(self, name):
            return {"--compile-contracts": str(patched),
                    "--compile-bench": "BENCH_*.json"}[name]

    session = types.SimpleNamespace(config=_Config(), exitstatus=0)
    pytest_plugin.pytest_sessionfinish(session, 0)
    assert session.exitstatus == 1


# ---------------------------------------------------------------- CLI

def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in ("JX001", "JX002", "JX003", "PT001", "UN001",
                 "SC001", "DN001", "SH001", "CC001", "WV001"):
        assert code in out
    assert "[warn" in out                  # SH001's default severity shows


def test_cli_exit_codes_and_report(tmp_path, capsys):
    report = tmp_path / "findings.json"
    rc = cli_main(["--root", str(FIXTURES), "--select", "JX001",
                   "--report", str(report), "jx001_bad.py"])
    assert rc == 1
    payload = json.loads(report.read_text())
    assert payload["schema"] == "repro.analysis/report/v1"
    assert payload["summary"]["per_code"]["JX001"] == 4
    rc = cli_main(["--root", str(FIXTURES), "--select", "JX001",
                   "jx001_good.py"])
    assert rc == 0


def test_cli_unknown_rule_is_usage_error(capsys):
    assert cli_main(["--select", "ZZ999"]) == 2


def test_cli_compile_gate(tmp_path, capsys):
    art = _write(tmp_path, "BENCH_speedup.json", _bench_payload(
        "speedup", {"scenario.sweep.compile_count": 99}))
    rc = cli_main(["--compile-gate", str(art)])
    assert rc == 1
    rc = cli_main(["--compile-gate", str(_write(
        tmp_path, "ok.json", _bench_payload(
            "speedup", {"scenario.sweep.compile_count": 1})))])
    assert rc == 0


def test_changed_files_runs(tmp_path):
    # no git in tmp_path: must degrade to an empty list, not raise
    assert changed_files(tmp_path) == []
    assert isinstance(changed_files(REPO), list)


# ---------------------------------------------------------------- --fix

def _seed_fix_tree(tmp_path):
    shutil.copy(FIXTURES / "fix_un001.py", tmp_path / "fix_un001.py")
    (tmp_path / "pyproject.toml").write_text(
        '[tool.repro.analysis]\npaths = ["fix_un001.py"]\n')
    return tmp_path


def _exec_summarize(path):
    ns = {}
    exec(compile(path.read_text(), str(path), "exec"), ns)
    return ns["summarize"](2.0)


def test_fix_applies_un001_renames(tmp_path, capsys):
    root = _seed_fix_tree(tmp_path)
    assert cli_main(["--root", str(root), "--select", "UN001"]) == 1
    assert cli_main(["--root", str(root), "--fix",
                     "--select", "UN001"]) == 0
    src = (root / "fix_un001.py").read_text()
    assert "energy_j: float" in src
    assert "power_w: float" in src
    assert "latency_us: float" in src
    assert '"latency_us"' in src               # dict keys follow the field
    assert "EnergyReport(energy_j=1.0" in src  # constructor call site
    assert "rep.energy_j" in src               # inferred attribute read
    assert "num_jobs: int" in src              # allow-listed name untouched


def test_fix_is_idempotent_and_behavior_preserving(tmp_path, capsys):
    root = _seed_fix_tree(tmp_path)
    before = _exec_summarize(root / "fix_un001.py")
    assert cli_main(["--root", str(root), "--fix",
                     "--select", "UN001"]) == 0
    first = (root / "fix_un001.py").read_text()
    capsys.readouterr()
    assert cli_main(["--root", str(root), "--fix",
                     "--select", "UN001"]) == 0
    assert (root / "fix_un001.py").read_text() == first
    assert "applied 0 edit(s)" in capsys.readouterr().out
    assert _exec_summarize(root / "fix_un001.py") == before


def test_fix_skips_waived_sites(tmp_path):
    root = _seed_fix_tree(tmp_path)
    src = (root / "fix_un001.py").read_text().replace(
        "    energy: float",
        "    # lint: waive UN001 -- fixture: stays dimensionless\n"
        "    energy: float")
    (root / "fix_un001.py").write_text(src)
    from repro.analysis.fix import apply_fixes, plan_fixes
    from repro.analysis.project import ProjectIndex
    cfg = load_config(root)
    result = apply_fixes(root, plan_fixes(
        ProjectIndex.build(root, cfg.paths), cfg))
    fixed = (root / "fix_un001.py").read_text()
    assert "    energy: float" in fixed        # waived field kept
    assert "power_w: float" in fixed           # the others still fixed
    assert any("waived" in note for note in result.skipped)


# ---------------------------------------------------------------- SARIF

def test_sarif_shape_and_suppressions(tmp_path):
    sarif = tmp_path / "findings.sarif"
    rc = cli_main(["--root", str(FIXTURES), "--select", "JX002",
                   "--sarif", str(sarif), "jx002_bad.py"])
    assert rc == 1
    log = json.loads(sarif.read_text())
    assert log["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in log["$schema"]
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "repro.analysis"
    rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert {"JX001", "UN001", "SC001", "DN001", "SH001"} <= set(rule_ids)
    results = run["results"]
    assert len(results) == 2                   # one active + one waived
    active = [r for r in results if "suppressions" not in r]
    waived = [r for r in results if "suppressions" in r]
    assert len(active) == 1 and len(waived) == 1
    assert active[0]["ruleId"] == "JX002"
    assert active[0]["level"] == "error"
    loc = active[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "jx002_bad.py"
    assert loc["region"]["startLine"] == 8
    assert loc["region"]["startColumn"] >= 1
    sup = waived[0]["suppressions"][0]
    assert sup["kind"] == "inSource"
    assert sup["justification"].startswith("fixture:")


def test_sarif_levels_follow_severity():
    from repro.analysis.sarif import sarif_payload
    rep = lint("sh001_bad.py", select=["SH001"])
    log = sarif_payload(rep.findings)
    levels = {r["level"] for r in log["runs"][0]["results"]}
    assert levels == {"warning"}               # SH001 defaults to warn


def test_cli_format_sarif_stdout(capsys):
    rc = cli_main(["--root", str(FIXTURES), "--select", "JX001",
                   "--format", "sarif", "jx001_bad.py"])
    assert rc == 1
    log = json.loads(capsys.readouterr().out)
    assert log["version"] == "2.1.0"
    assert len(log["runs"][0]["results"]) == 4


# ---------------------------------------------------------- changed-files

def _git(tmp, *args):
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    *args], cwd=tmp, check=True, capture_output=True)


def test_changed_files_resolves_renames(tmp_path):
    _git(tmp_path, "init", "-q")
    (tmp_path / "mod_a.py").write_text("VALUE = 1\n" * 20)
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")
    base = subprocess.run(["git", "rev-parse", "HEAD"], cwd=tmp_path,
                          capture_output=True, text=True).stdout.strip()
    _git(tmp_path, "mv", "mod_a.py", "mod_b.py")
    _git(tmp_path, "commit", "-qm", "rename")
    # a pure rename (R100) is content-identical to the base: nothing to lint
    assert changed_files(tmp_path, base) == []
    (tmp_path / "mod_b.py").write_text("VALUE = 1\n" * 20 + "EXTRA = 2\n")
    _git(tmp_path, "commit", "-aqm", "edit")
    # rename + edit lints the new path only — never the vanished old one
    assert changed_files(tmp_path, base) == ["mod_b.py"]


def test_cc001_message_names_bench_counter_and_delta(tmp_path):
    patched = _write(tmp_path, "contracts.json", {
        "schema": "repro.analysis/contracts/v1",
        "contracts": {"speedup": {"scenario.sweep.compile_count": 1}}})
    art = _write(tmp_path, "BENCH_speedup.json", _bench_payload(
        "speedup", {"scenario.sweep.compile_count": 64}))
    msg = check_compile_gate(patched, [art])[0].message
    assert "benchmark `speedup`" in msg
    assert "`scenario.sweep.compile_count`" in msg
    assert "+63 over budget" in msg


# ---------------------------------------------------------------- dogfood

def test_repo_is_strict_clean():
    cfg = load_config(REPO)
    rep = run_analysis(cfg, ignore=["CC001"], strict=True)
    assert rep.active == [], "\n".join(f.render() for f in rep.active)
    # the deliberate compile-counter waivers stay visible, not silenced
    waived = [f for f in rep.findings if f.waived and f.code == "JX003"]
    assert len(waived) >= 6
    assert all(f.waiver_reason for f in waived)


def test_repo_reachability_covers_kernels():
    from repro.analysis.project import ProjectIndex
    from repro.analysis.reachability import compute_reachable
    cfg = load_config(REPO)
    idx = ProjectIndex.build(cfg.root, cfg.paths)
    reach = compute_reachable(idx)
    names = {u.name for u in reach}
    # jit roots and their transitive callees, across modules
    for expected in ("_simulate", "_simulate_dtpm", "_sweep_grid",
                     "_epoch_scan", "exact_step_jax"):
        assert expected in names, sorted(names)
    assert {"policy", "num_jobs"} <= set(reach.static_param_names)
