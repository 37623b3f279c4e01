"""The ``pd_cpi_grid`` cell rehearsed on the CPU at a tiny width (4 pulses,
2 Doppler bins, 3 CPIs per trace): its entry, reference and limits come
from files of their own, the sound path is ``correct``, and the bfloat16
control and a 1 % altered answer each fail the cell's limits.  Also the
``dse_grid_4chip`` cell resolves, and ``scan_device_us_per_task`` reads a
hand-built trace."""
import dataclasses
import time

import ml_dtypes
import numpy as np
import pytest

from bench.harness import check, runner, traffic
from bench.harness.runner import Window
from bench.harness.spec import load_cell
from bench.reference import pulse_doppler
from bench.tests.cells import ROOT
from bench.tests.test_trace_reduce import _reader, _view

SEED = 2**31 + 1234
TINY = {"rates_jobs_per_ms": [2.0, 5.0], "traces_per_rate": 1,
        "num_jobs": 3, "check_lanes": 4}


def _tiny():
    cell = load_cell(ROOT, "pd_cpi_grid")
    cell.config["dag"] = {"pulses": 4, "doppler_bins": 2}
    cell.config["trace_jobs"] = [TINY["num_jobs"]]
    cell.traffic.update(TINY)
    return cell


def test_cell_loads_its_own_files():
    cell = load_cell(ROOT, "pd_cpi_grid")
    assert cell.entry.__module__ == "bench_entry_cpi_grid"
    assert (ROOT / "bench" / "limits" / "pd_cpi_grid.json").exists()
    assert cell.config["dag"] == {"pulses": 128, "doppler_bins": 64}
    app = pulse_doppler.app(**cell.config["dag"])
    assert app.num_tasks == 449
    assert sum(len(t.predecessors) for t in app.tasks) == 8576
    # 12 lanes of 16 CPIs per call: 86,208 tasks
    entry = cell.entry(cell.config, cell.traffic, SEED, None)
    c = entry.inputs(traffic.WINDOW, 0)
    assert len(c.lanes) == 12 and c.tasks == 86208


def test_periodic_traces_jitter_within_bounds():
    cell = load_cell(ROOT, "pd_cpi_grid")
    entry = cell.entry(cell.config, cell.traffic, SEED, None)
    traces = entry.traces(traffic.WINDOW, 3)
    assert len(traces) == 6
    for t, rate in zip(traces, np.repeat(cell.traffic["rates_jobs_per_ms"],
                                         2)):
        gaps = np.diff(np.concatenate([[0.0], t.arrival_us]))
        period = 1000.0 / rate
        assert (gaps >= 0.95 * period - 1e-3).all()
        assert (gaps <= 1.05 * period + 1e-3).all()
    again = entry.traces(traffic.WINDOW, 3)
    assert all((a.arrival_us == b.arrival_us).all()
               for a, b in zip(traces, again))


def test_sound_path_is_correct():
    res = runner.run_cell(ROOT, _tiny(), SEED, 0.5, False,
                          time.perf_counter())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"sim_tasks_per_s", "setup_s"}


def _altered(call):
    def f(c):
        out = call(c)
        return dataclasses.replace(out,
                                   avg_latency_us=out.avg_latency_us * 1.01)
    return f


def test_altered_answer_is_not_correct():
    res = runner.run_cell(ROOT, _tiny(), SEED, 0.5, False,
                          time.perf_counter(), wrap=_altered)
    assert not res["correct"], res["checks"]
    assert res["checks"]["latency_rel_err"]["value"] > \
        res["checks"]["latency_rel_err"]["limit"]


def test_control_is_not_correct():
    import jax
    cell = _tiny()
    entry = cell.entry(cell.config, cell.traffic, SEED,
                       jax.profiler.TraceAnnotation)
    calls = [entry.inputs(traffic.WINDOW, i) for i in range(2)]
    stats = [entry.stats(entry.call(c), c) for c in calls]
    limits = check.load_limits(ROOT, "pd_cpi_grid")
    sound = runner.compare(cell, calls, stats, SEED)
    control = runner.compare(cell, calls, stats, SEED,
                             control_dtype=ml_dtypes.bfloat16)
    assert check.judge(sound, limits)[0], sound
    assert not check.judge(control, limits)[0], control


def test_dse_grid_4chip_cell_resolves():
    cell = load_cell(ROOT, "dse_grid_4chip")
    assert cell.chips == 4 and cell.traffic["designs"] == "grid"
    assert len(traffic.grid_designs(cell.config["design_space"])) == 360
    assert check.load_limits(ROOT, "dse_grid_4chip")
    assert "scan_device_us_per_task" in {m.name for m in cell.per_layer}


def test_scan_device_us_per_task_reads_every_device():
    # grid programs: 30 ns on dev0 + 10 ns on dev1 = 40 ns over 20 tasks
    w = Window(setup_s=1.0, window_s=1e-7, call_s=[4.5e-8, 3.2e-8],
               tasks=[10, 10], view=_view())
    assert _reader("scan_device_us_per_task").read(w) == \
        pytest.approx(40e-3 / 20)
    empty = dataclasses.replace(_view(), programs={"/device:TPU:0": []})
    assert _reader("scan_device_us_per_task").read(
        dataclasses.replace(w, view=empty)) is None
