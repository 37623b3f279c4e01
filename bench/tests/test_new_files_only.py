"""A later cell, entry or metric needs new files only: a traffic mix, an
entry, a metric reader and a limits file, found by the names in
``BENCHMARK.json`` and the traffic file.  Rehearsed here on a copy of the
benchmark (not added to the real one)."""
import json
import shutil
import time

import pytest

from bench.harness import runner
from bench.harness.spec import load_cell
from bench.tests.cells import ROOT

METRIC = '''"""Calls completed per second of the window."""
UNIT = "calls/s"
SOURCE = "host_clock"
BETTER = "higher"


def read(w):
    return len(w.call_s) / w.window_s
'''

# an entry of its own: run() with the per-window telemetry on, which must
# leave every simulated statistic as it is
ENTRY = '''from bench.entries.run import RunEntry


class TelemetryRun(RunEntry):
    def call(self, c):
        from repro.scenario import run
        with self.span("bench.run"):
            return run(c.args["scn"], backend="jax",
                       trace_override=c.args["trace"], telemetry=True)


ENTRY = TelemetryRun
'''

# a DTPM policy grid as data alone: throttle under four parameter maps
POLICY_GRID = {
    "entry": "sweep", "about": "rehearsal", "governor": "throttle",
    "axes": {"governor_params": [
        {"up_threshold": u, "thermal_cap_c": cap, "thermal_dt_s": 0.05}
        for u in (0.6, 0.9) for cap in (25.0, 29.0)]},
    "rates_jobs_per_ms": [20], "traces_per_rate": 2, "num_jobs": 10,
    "shard": False, "check_lanes": 8}

RUN = {"entry": "telemetry_run", "about": "rehearsal", "scheduler": "etf",
       "governor_cycle": ["ondemand"], "rates_jobs_per_ms": [20],
       "traces_per_rate": 1, "num_jobs": 10, "check_lanes": 2}


def _copy(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _add_cell(tmp_path, bench, name, traffic):
    (tmp_path / "bench" / "traffic" / f"{name}.json").write_text(
        json.dumps(traffic))
    shutil.copy(ROOT / "bench" / "limits" / "dtpm_run.json",
                tmp_path / "bench" / "limits" / f"{name}.json")
    bench["workloads"].append({
        "name": name, "config": "ds3_table2_wifi_tx", "traffic": name,
        "chips": 1, "why": "rehearsal"})


@pytest.mark.parametrize("name,traffic", [("policy_grid", POLICY_GRID),
                                          ("telemetry_run", RUN)])
def test_new_cell_and_metric_from_new_files(tmp_path, name, traffic):
    bench = _copy(tmp_path)
    _add_cell(tmp_path, bench, name, traffic)
    if traffic["entry"] == "telemetry_run":
        (tmp_path / "bench" / "entries" / "telemetry_run.py").write_text(
            ENTRY)
    (tmp_path / "bench" / "metrics" / "calls_per_s.py").write_text(METRIC)
    bench["end_to_end"].append({
        "name": "calls_per_s", "unit": "calls/s", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell(tmp_path, name)
    cell.config["trace_jobs"] = [traffic["num_jobs"]]
    res = runner.run_cell(tmp_path, cell, 5, 0.5, False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"sim_tasks_per_s", "setup_s",
                                   "calls_per_s"}
    assert res["metrics"]["calls_per_s"]["value"] > 0
