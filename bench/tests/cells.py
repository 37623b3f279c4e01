"""Cells shrunk to a size the CPU tests hold: the same configuration,
entry and traffic kind as ``BENCHMARK.json`` names, with fewer lanes,
jobs and rates."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench.harness.spec import load_cell  # noqa: E402

TINY = {
    "fig3_grid": {"rates_jobs_per_ms": [10, 80], "traces_per_rate": 1,
                  "num_jobs": 12, "check_lanes": 6},
    "dtpm_run": {"num_jobs": 16, "check_lanes": 4},
    "dse_lhs": {"num_designs": 6, "traces_per_rate": 2, "num_jobs": 8,
                "check_lanes": 12},
}


def tiny_cell(name: str, root: Path = ROOT):
    cell = load_cell(root, name)
    cell.traffic.update(TINY[name])
    cell.config["trace_jobs"] = [TINY[name]["num_jobs"]]
    return cell
