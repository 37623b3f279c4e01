"""Pulse-Doppler at CPI width and the list-form dependency tables.

* ``pulse_doppler_cpi``'s shape: 1 + 3P + B tasks, a corner turn of
  in-degree P, the published 449 tasks at P = 128, B = 64;
* the padded predecessor / successor lists of ``SimTables`` hold exactly
  the DAG the dense ``pred_matrix`` / ``edge_bytes_matrix`` describe, for
  every reference application;
* ref ↔ jax on the wide DAG at small width (P = 8, B = 4): bit for bit on
  comm-free traces, within DESIGN.md §1's tolerances with interconnect
  cost, under MET and ETF, in the static, DTPM (ondemand, throttle) and
  fail-stop programs — rollback included;
* the ``sim.tables.*`` and ``sim.scan.steps`` counters in the run manifest;
* ``scheduler="table"`` refuses a DAG beyond the exact solver's reach.
"""
import numpy as np
import pytest

from repro.core.applications import (REFERENCE_APPS, get_application,
                                     pulse_doppler_cpi)
from repro.core.dvfs import OndemandGovernor
from repro.core.jobgen import deterministic_trace, poisson_trace
from repro.core.resources import CommModel, make_soc_table2
from repro.core.schedulers import get_scheduler, solve_optimal_table
from repro.core.simkernel_jax import (build_tables, build_tables_host,
                                      simulate_jax, simulate_jax_dtpm)
from repro.core.simkernel_ref import simulate
from repro.scenario import FaultSpec, Scenario, TraceSpec, run, sweep
from repro.scenario.config import TABLE_MAX_TASKS
from repro.scenario.faults import fault_plan, normalize_failures, \
    ref_failures

SMALL = pulse_doppler_cpi(8, 4)          # 29 tasks, corner turn of 8


def _comm_free_db():
    db = make_soc_table2()
    db.comm = CommModel(startup_us=0.0, bw_bytes_per_us=1e30)
    return db


# ------------------------------------------------------------- the DAG

@pytest.mark.parametrize("pulses,bins", [(1, 1), (8, 4), (128, 64)])
def test_pulse_doppler_cpi_shape(pulses, bins):
    app = pulse_doppler_cpi(pulses, bins)
    assert app.num_tasks == 1 + 3 * pulses + bins
    names = app.task_names
    assert names.count("pd_stack") == 1
    for n in ("fft", "conj_multiply", "inverse_fft"):
        assert names.count(n) == pulses
    assert names.count("doppler_fft") == bins
    iffts = {t.task_id for t in app.tasks if t.name == "inverse_fft"}
    for t in app.tasks:
        if t.name == "doppler_fft":
            assert set(t.predecessors) == iffts
    assert app.max_in_degree == pulses
    assert app.max_out_degree == max(pulses, bins)
    assert app.num_edges == 3 * pulses + pulses * bins
    assert app.depth == 4


def test_pulse_doppler_cpi_published_width():
    app = get_application("pulse_doppler_cpi")
    assert app.name == "pulse_doppler_cpi" and app.num_tasks == 449
    assert app.num_edges - 3 * 128 == 8192          # corner-turn edges
    assert SMALL.name == "pulse_doppler_cpi_8x4"
    # the toy keeps its 4-FFT bank
    assert get_application("pulse_doppler").num_tasks == 8


@pytest.mark.parametrize("name", sorted(REFERENCE_APPS))
def test_list_tables_reproduce_dense_dag(name):
    """Rebuilding the dense adjacency and edge bytes from the padded lists
    gives back ``pred_matrix`` / ``edge_bytes_matrix``; the successor lists
    are its transpose."""
    app = get_application(name)
    tb = build_tables_host(make_soc_table2(with_viterbi=True), [app])
    T = app.num_tasks
    pidx, pbytes = tb.pred_idx[0], tb.pred_bytes[0]
    pred = np.zeros((T, T), bool)
    ebytes = np.zeros((T, T), np.float32)
    for t in range(T):
        for k in np.flatnonzero(pidx[t] >= 0):
            pred[t, pidx[t, k]] = True
            ebytes[t, pidx[t, k]] = pbytes[t, k]
        assert (pidx[t] >= 0).sum() == len(app.tasks[t].predecessors)
    np.testing.assert_array_equal(pred, app.pred_matrix())
    np.testing.assert_array_equal(ebytes, app.edge_bytes_matrix())
    succ = np.zeros((T, T), bool)
    for t in range(T):
        succ[t, tb.succ_idx[0, t][tb.succ_idx[0, t] >= 0]] = True
    np.testing.assert_array_equal(succ, app.pred_matrix().T)
    assert tb.pred_idx.shape[-1] == max(1, app.max_in_degree)
    assert tb.succ_idx.shape[-1] == max(1, app.max_out_degree)
    assert tb.depth == app.depth


def test_mixed_apps_pad_lists_to_widest():
    apps = [get_application("wifi_tx"), SMALL]
    tb = build_tables_host(make_soc_table2(), apps)
    assert tb.pred_idx.shape == (2, SMALL.num_tasks, 8)
    assert tb.succ_idx.shape == (2, SMALL.num_tasks, 8)
    assert (tb.pred_idx[0, 6:] == -1).all() and not tb.valid[0, 6:].any()
    assert tb.depth == 5


# ------------------------------------------------------ ref <-> jax

def _assert_bitforbit(ref, jx, dtpm_tables=None, db=None):
    fin = np.asarray(jx["finish"])
    start = np.asarray(jx["start"])
    onpe = np.asarray(jx["onpe"])
    assert ref.records
    for r in ref.records:
        assert fin[r.job_id, r.task_id] == np.float32(r.finish_us)
        assert start[r.job_id, r.task_id] == np.float32(r.start_us)
        assert onpe[r.job_id, r.task_id] == r.pe_id
        if dtpm_tables is not None and db.pes[r.pe_id].is_cpu:
            f = np.asarray(dtpm_tables.opp_freq)[
                np.asarray(dtpm_tables.pe_domain)[r.pe_id],
                np.asarray(jx["onopp"])[r.job_id, r.task_id]]
            assert f == np.float32(r.freq_ghz)
    assert int(np.asarray(jx["scheduled"]).sum()) == len(ref.records)
    assert float(jx["makespan_us"]) == np.float32(ref.makespan_us)
    np.testing.assert_allclose(float(jx["avg_job_latency_us"]),
                               ref.avg_job_latency_us, rtol=1e-6)
    np.testing.assert_allclose(float(jx["energy_j"]),
                               ref.energy.total_energy_j, rtol=1e-5)


def _trace(gap_us=120.0, jobs=5):
    return deterministic_trace(1000.0 / gap_us, jobs, [SMALL.name])


@pytest.mark.parametrize("policy", ["met", "etf"])
def test_static_bitforbit_comm_free(policy):
    db = _comm_free_db()
    trace = _trace()
    ref = simulate(db, [SMALL], trace, get_scheduler(policy))
    jx = simulate_jax(build_tables(db, [SMALL]), policy, trace.arrival_us,
                      trace.app_index)
    _assert_bitforbit(ref, jx)


@pytest.mark.parametrize("policy", ["met", "etf"])
@pytest.mark.parametrize("seed", [0, 5])
def test_static_agrees_with_comm(policy, seed):
    db = make_soc_table2()
    trace = poisson_trace(8.0, 6, [SMALL.name], seed=seed)
    ref = simulate(db, [SMALL], trace, get_scheduler(policy))
    jx = simulate_jax(build_tables(db, [SMALL]), policy, trace.arrival_us,
                      trace.app_index)
    np.testing.assert_allclose(float(jx["avg_job_latency_us"]),
                               ref.avg_job_latency_us, rtol=1e-4)
    np.testing.assert_allclose(float(jx["makespan_us"]), ref.makespan_us,
                               rtol=1e-4)
    np.testing.assert_allclose(float(jx["energy_j"]),
                               ref.energy.total_energy_j, rtol=1e-3)


@pytest.mark.parametrize("policy", ["met", "etf"])
@pytest.mark.parametrize("cap_c", [None, 25.5])
def test_dtpm_bitforbit_comm_free(policy, cap_c):
    """Ondemand, and ondemand under a thermal cap (the throttle): the same
    schedule and latched OPPs in both kernels."""
    db = _comm_free_db()
    trace = _trace(gap_us=60.0, jobs=6)
    kw = {} if cap_c is None else dict(thermal_cap_c=cap_c,
                                       thermal_dt_s=0.05)
    gov = OndemandGovernor(sample_window_us=50.0, **kw)
    ref = simulate(db, [SMALL], trace, get_scheduler(policy), gov)
    tables = build_tables(db, [SMALL], governor=gov)
    jx = simulate_jax_dtpm(tables, policy, trace.arrival_us,
                           trace.app_index, gov.policy())
    _assert_bitforbit(ref, jx, tables, db)


@pytest.mark.parametrize("policy", ["met", "etf"])
@pytest.mark.parametrize("failures", [
    [FaultSpec(10, 150.0)],                       # one FFT accelerator
    [FaultSpec(10, 90.0), FaultSpec(0, 260.0)],   # an accelerator, a big core
], ids=["one_pe", "two_pes"])
def test_failstop_bitforbit_comm_free(policy, failures):
    """Fail-stop on the wide DAG: the rollback walks the successor lists
    through the corner turn and both kernels re-commit the same tasks."""
    db = _comm_free_db()
    trace = _trace(gap_us=100.0, jobs=5)
    ref = simulate(db, [SMALL], trace, get_scheduler(policy),
                   failures=ref_failures(normalize_failures(failures)))
    plan = fault_plan(normalize_failures(failures), db.num_pes)
    jx = simulate_jax(build_tables(db, [SMALL]), policy, trace.arrival_us,
                      trace.app_index, faults=plan)
    _assert_bitforbit(ref, jx)


def test_failstop_rolls_back_corner_turn():
    """A fault that lands while the corner turn runs takes committed
    descendants with it: both kernels roll back and re-commit alike, every
    task still finishes, and the CPI ends later than without the fault."""
    db = _comm_free_db()
    trace = _trace(gap_us=400.0, jobs=2)
    clean = simulate(db, [SMALL], trace, get_scheduler("met"))
    ffts = [r for r in clean.records
            if SMALL.tasks[r.task_id].name == "inverse_fft"
            and r.job_id == 0]
    t_fail = float(max(r.finish_us for r in ffts)) - 1.0
    failures = [FaultSpec(ffts[-1].pe_id, t_fail)]
    ref = simulate(db, [SMALL], trace, get_scheduler("met"),
                   failures=ref_failures(normalize_failures(failures)))
    jx = simulate_jax(build_tables(db, [SMALL]), "met", trace.arrival_us,
                      trace.app_index,
                      faults=fault_plan(normalize_failures(failures),
                                        db.num_pes))
    _assert_bitforbit(ref, jx)
    assert bool(np.asarray(jx["scheduled"]).all())
    assert ref.makespan_us > clean.makespan_us


def test_sweep_lanes_equal_run_on_wide_dag():
    """The sweep's vmapped lanes equal per-point run() on the wide DAG."""
    scn = Scenario(apps=(SMALL,), scheduler="etf",
                   trace=TraceSpec(rate_jobs_per_ms=6.0, num_jobs=4, seed=2))
    sr = sweep(scn, axes={"scheduler": ["met", "etf"],
                          "trace.seed": [2, 3]})
    for i, pol in enumerate(["met", "etf"]):
        for k, seed in enumerate([2, 3]):
            r = run(scn.replace(scheduler=pol, **{"trace.seed": seed}),
                    backend="jax")
            assert sr.avg_latency_us[i, k] == r.avg_latency_us
            assert sr.energy_j[i, k] == r.energy_j


# ---------------------------------------------------------- counters

def test_manifest_counts_edges_degree_and_scan_steps():
    from repro.obs import metrics
    from repro.scenario.run import _cached_tables, _cached_tables_host
    _cached_tables.cache_clear()
    _cached_tables_host.cache_clear()
    scn = Scenario(apps=(SMALL,), scheduler="etf",
                   trace=TraceSpec(rate_jobs_per_ms=6.0, num_jobs=3, seed=1))
    metrics.counter("sim.scan.steps").reset()
    res = run(scn, backend="jax")
    c = res.manifest["metrics"]["counters"]
    assert c["sim.tables.edges"] == 3 * 8 + 8 * 4
    assert c["sim.tables.max_in_degree"] == 8
    assert c["sim.scan.steps"] == 3 * SMALL.num_tasks
    sweep(scn, axes={"scheduler": ["met", "etf"], "trace.seed": [1, 2, 3]})
    assert metrics.counter("sim.scan.steps").value == \
        3 * SMALL.num_tasks * (1 + 2 * 3)


# -------------------------------------------------------- table limit

def test_table_scheduler_refuses_wide_dag():
    scn = Scenario(apps=(SMALL,), scheduler="table")
    with pytest.raises(ValueError, match=f"TABLE_MAX_TASKS={TABLE_MAX_TASKS}"):
        scn.schedule_table()
    with pytest.raises(ValueError, match="TABLE_MAX_TASKS"):
        run(scn, backend="jax")


def test_table_solver_budget_raises():
    """Out of states before an optimum is proved: an error naming the
    budget, never a table that is not optimal."""
    with pytest.raises(ValueError, match="max_states=50"):
        solve_optimal_table(make_soc_table2(), pulse_doppler_cpi(3, 2),
                            max_states=50)
