"""Repo-root configuration for ``repro.analysis`` (``pyproject.toml``).

The ``[tool.repro.analysis]`` block selects rules, lint paths, per-rule
severity and the UN001 unit vocabulary::

    [tool.repro.analysis]
    paths = ["src/repro", "benchmarks", "examples"]
    disable = []                      # rule codes switched off repo-wide
    severity = ["SH001=warn"]         # per-rule level: error | warn | info
    unit-suffixes = ["_j", "_w", ...] # accepted unit suffixes (UN001)
    unit-structs = ["EnergyReport"]   # dataclasses UN001 audits
    unit-allow = ["util*", "*_idx"]   # dimensionless names (fnmatch)
    contracts = "src/repro/analysis/contracts.json"

Severity semantics: ``error`` findings gate (CLI exit 1), ``warn`` findings
print but only gate under ``--strict`` (the CI mode), ``info`` findings
never gate.  ``severity`` accepts either the ``["CODE=level", …]`` list
form above or a ``[tool.repro.analysis.severity]`` sub-table.
"""
from __future__ import annotations

import dataclasses
import tomllib
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ALL_RULES: Tuple[str, ...] = ("JX001", "JX002", "JX003", "PT001", "UN001",
                              "SC001", "DN001", "SH001", "CC001")

SEVERITY_LEVELS: Tuple[str, ...] = ("error", "warn", "info")

#: default per-rule severity; SH001's sharding-contract checks are
#: heuristics over placement conventions, so they warn by default (and
#: gate only under --strict, the CI mode)
DEFAULT_SEVERITY: Dict[str, str] = {
    "JX001": "error", "JX002": "error", "JX003": "error",
    "PT001": "error", "UN001": "error",
    "SC001": "error", "DN001": "error", "SH001": "warn",
    "CC001": "error", "WV001": "error",
}

#: one-line rule summaries (CLI --list-rules, SARIF rule metadata)
RULE_DOCS: Dict[str, str] = {
    "JX001": "tracer-leak: .item()/bool()/int()/float()/if/while on "
             "traced values in jit-reachable code",
    "JX002": "host-numpy-in-jit: np.* calls on traced data (use jnp)",
    "JX003": "impure-jit: print/wall-clock/host-RNG/global or self "
             "mutation inside jitted code",
    "PT001": "pytree-contract: register_dataclass targets frozen, "
             "data/meta split exact, meta fields hashable",
    "UN001": "unit-suffix: numeric fields and payload keys on result "
             "structs carry _us/_j/_w/_c/_hz/... suffixes",
    "SC001": "scan-carry: lax.scan/while_loop/fori_loop bodies must keep "
             "carry arity, element order and dtype stable",
    "DN001": "use-after-donate: arguments donated to a jit "
             "(donate_argnums/argnames) must not be read after the call",
    "SH001": "lane-sharding: leading-axis 'lanes' PartitionSpec, no "
             "device_put/mesh construction inside a traced body",
    "CC001": "compile-count gate: BENCH_*.json counters within "
             "contracts.json budgets",
    "WV001": "(strict only) waiver comment missing its -- justification",
}

DEFAULT_PATHS = ("src/repro", "benchmarks", "examples")
DEFAULT_SUFFIXES = ("_j", "_w", "_s", "_us", "_ms", "_c", "_hz", "_ghz")
DEFAULT_UNIT_STRUCTS = ("EnergyReport", "EvalResult", "Telemetry",
                        "GovernorPolicy", "Result", "SweepResult",
                        "TraceSpec", "ThermalSpec")
DEFAULT_UNIT_ALLOW = ("util*", "utilization", "*_idx", "*_count", "num_*",
                      "*_frac", "*_ratio", "up_threshold", "mix", "seed",
                      "bins", "repeats", "points", "schema", "kind",
                      "value", "axes", "telemetry")


@dataclasses.dataclass(frozen=True)
class AnalysisConfig:
    root: Path
    paths: Tuple[str, ...] = DEFAULT_PATHS
    disable: Tuple[str, ...] = ()
    severity: Tuple[Tuple[str, str], ...] = ()   # per-rule overrides
    unit_suffixes: Tuple[str, ...] = DEFAULT_SUFFIXES
    unit_structs: Tuple[str, ...] = DEFAULT_UNIT_STRUCTS
    unit_allow: Tuple[str, ...] = DEFAULT_UNIT_ALLOW
    contracts: str = "src/repro/analysis/contracts.json"

    def enabled_rules(self, select: Optional[List[str]] = None,
                      ignore: Optional[List[str]] = None) -> Tuple[str, ...]:
        rules = list(select) if select else [r for r in ALL_RULES
                                             if r not in self.disable]
        if ignore:
            rules = [r for r in rules if r not in ignore]
        unknown = [r for r in rules if r not in ALL_RULES]
        if unknown:
            raise ValueError(f"unknown rule code(s) {unknown}; "
                             f"known: {list(ALL_RULES)}")
        return tuple(rules)

    def severity_for(self, code: str) -> str:
        for rule, level in self.severity:
            if rule == code:
                return level
        return DEFAULT_SEVERITY.get(code, "error")


def find_root(start: Optional[Path] = None) -> Path:
    """Nearest ancestor holding a ``pyproject.toml`` (else ``start``)."""
    p = (start or Path.cwd()).resolve()
    for cand in (p, *p.parents):
        if (cand / "pyproject.toml").is_file():
            return cand
    return p


def load_config(root: Optional[Path] = None) -> AnalysisConfig:
    # an explicit root is authoritative (fixture trees have no pyproject);
    # otherwise walk up from cwd to the nearest pyproject.toml
    root = Path(root).resolve() if root is not None else find_root()
    pyproject = root / "pyproject.toml"
    block: Dict = {}
    if pyproject.is_file():
        data = tomllib.loads(pyproject.read_text())
        block = data.get("tool", {}).get("repro", {}).get("analysis", {})

    def tup(key: str, default: Tuple[str, ...]) -> Tuple[str, ...]:
        val = block.get(key, block.get(key.replace("-", "_")))
        return tuple(val) if val is not None else default

    return AnalysisConfig(
        root=root,
        paths=tup("paths", DEFAULT_PATHS),
        disable=tup("disable", ()),
        severity=_parse_severity(block.get("severity")),
        unit_suffixes=tup("unit-suffixes", DEFAULT_SUFFIXES),
        unit_structs=tup("unit-structs", DEFAULT_UNIT_STRUCTS),
        unit_allow=tup("unit-allow", DEFAULT_UNIT_ALLOW),
        contracts=str(block.get("contracts",
                                "src/repro/analysis/contracts.json")),
    )


def _parse_severity(val) -> Tuple[Tuple[str, str], ...]:
    """Per-rule severity overrides: a ``{"SH001": "warn"}`` sub-table or
    the ``["SH001=warn"]`` list form."""
    if val is None:
        return ()
    if isinstance(val, dict):
        pairs = [(str(k), str(v)) for k, v in val.items()]
    else:
        pairs = []
        for item in val:
            code, _, level = str(item).partition("=")
            pairs.append((code.strip(), level.strip()))
    for code, level in pairs:
        if code not in ALL_RULES and code != "WV001":
            raise ValueError(f"severity override for unknown rule {code!r}")
        if level not in SEVERITY_LEVELS:
            raise ValueError(f"severity for {code} must be one of "
                             f"{list(SEVERITY_LEVELS)}, got {level!r}")
    return tuple(pairs)
