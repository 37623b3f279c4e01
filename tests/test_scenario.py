"""repro.scenario — unified facade tests.

Covers the backend-equivalence contract (run() is a bit-for-bit delegate to
the legacy entry points), the sweep contract (cross-product axes == per-point
run(), ONE compiled program per scheduler), the deprecation shims, and the
removal of the expired *_mj → *_j energy aliases.  Closed-loop DTPM (dynamic
governors on the jax backend) is covered in tests/test_dtpm.py.
"""
import dataclasses

import jax
import numpy as np
import pytest

import repro.core as core
import repro.dse as dse
from repro.core import simkernel_jax as skj
from repro.core import simkernel_ref as skr
from repro.core.dvfs import OndemandGovernor, UserspaceGovernor
from repro.core.resources import CPU_BIG, CPU_LITTLE, make_soc_table2
from repro.core.schedulers import get_scheduler
from repro.dse import DesignPoint
from repro.scenario import (FaultSpec, Result, Scenario, ThermalSpec,
                            TraceSpec, run, sweep)
from repro.scenario.sweep import compile_count

SCN = Scenario(apps=("wifi_tx",),
               trace=TraceSpec(rate_jobs_per_ms=25.0, num_jobs=24, seed=3))
MIX = Scenario(apps=("wifi_tx", "wifi_rx"),
               trace=TraceSpec(rate_jobs_per_ms=20.0, num_jobs=16, seed=1))


# --------------------------------------------------------- scenario config

def test_scenario_is_static_hashable_pytree():
    leaves, _ = jax.tree_util.tree_flatten(SCN)
    assert leaves == []                     # all fields are static metadata
    assert hash(SCN) == hash(SCN.replace())
    assert SCN.replace(**{"trace.seed": 9}).trace.seed == 9
    assert SCN.at_rate(60.0).trace.rate_jobs_per_ms == 60.0
    assert {SCN: "cache-key"}[SCN] == "cache-key"


def test_default_design_is_the_table2_soc():
    db, ref = SCN.soc(), make_soc_table2()
    assert [(p.pe_type, p.cluster, p.name) for p in db.pes] \
        == [(p.pe_type, p.cluster, p.name) for p in ref.pes]


def test_governor_materialisation():
    assert SCN.make_governor().name == "performance"
    gov = SCN.replace(governor="design").make_governor()
    assert isinstance(gov, UserspaceGovernor)
    assert gov.initial_freq(CPU_BIG) == SCN.design.big_freq_ghz
    gov = SCN.replace(governor="userspace",
                      governor_params=(("freq_ghz", 1.0),)).make_governor()
    assert gov.initial_freq(CPU_LITTLE) == 1.0


# ------------------------------------------------- backend equivalence: run

def test_run_ref_matches_legacy_simulate():
    res = run(SCN, backend="ref")
    legacy = skr.simulate(SCN.soc(), SCN.applications(), SCN.job_trace(),
                          get_scheduler(SCN.scheduler))
    assert res.avg_latency_us == float(legacy.avg_job_latency_us)
    assert res.makespan_us == float(legacy.makespan_us)
    assert res.energy_j == float(legacy.energy.total_energy_j)
    assert res.throughput_jobs_per_ms == float(legacy.throughput_jobs_per_ms)
    np.testing.assert_array_equal(res.utilization,
                                  legacy.pe_utilization(SCN.soc()))


@pytest.mark.parametrize("policy", ["met", "etf", "table"])
def test_run_jax_bitexact_vs_legacy_entry_point(policy):
    scn = SCN.replace(scheduler=policy)
    res = run(scn, backend="jax")
    tables = skj.build_tables(scn.soc(), scn.applications(),
                              governor=scn.make_governor(),
                              table=scn.schedule_table())
    trace = scn.job_trace()
    legacy = skj.simulate_jax(tables, policy, trace.arrival_us,
                              trace.app_index)
    assert set(res.raw) == set(legacy)
    for key in legacy:
        np.testing.assert_array_equal(np.asarray(res.raw[key]),
                                      np.asarray(legacy[key]))


def test_run_backends_agree_on_metrics():
    for scn in (SCN, MIX, SCN.replace(scheduler="met")):
        ref = run(scn, backend="ref")
        jx = run(scn, backend="jax")
        np.testing.assert_allclose(jx.avg_latency_us, ref.avg_latency_us,
                                   rtol=1e-4)
        np.testing.assert_allclose(jx.energy_j, ref.energy_j, rtol=1e-3)


def test_result_metrics_surface():
    for backend in ("ref", "jax"):
        res = run(SCN, backend=backend)
        assert isinstance(res, Result)
        assert res.utilization.shape == (SCN.design.num_pes,)
        assert res.throughput_jobs_per_ms > 0
        assert res.peak_temp_c >= 25.0 - 1e-6       # >= ambient
        assert res.energy_j > 0 and res.avg_power_w > 0


def test_run_jax_rejects_ref_only_features():
    with pytest.raises(ValueError, match="backend"):
        run(SCN, backend="gem5")
    # fail-stop injection is no longer ref-only (DESIGN.md §14) — it only
    # defers to ref for the pinned offline-table scheduler
    res = run(SCN.replace(failures=(FaultSpec(0, 100.0),)), backend="jax")
    assert res.makespan_us > 0
    with pytest.raises(ValueError, match="table"):
        run(SCN.replace(scheduler="table",
                        failures=(FaultSpec(0, 100.0),)), backend="jax")
    # ondemand is no longer ref-only: the DTPM kernel runs it (DESIGN.md §7)
    res = run(SCN.replace(governor="ondemand"), backend="jax")
    assert res.makespan_us > 0 and res.peak_temp_c >= 25.0 - 1e-6


def test_run_ref_supports_failures_and_ondemand():
    res = run(SCN.replace(failures=(FaultSpec(0, 50.0),),
                          governor="ondemand"),
              backend="ref")
    assert res.makespan_us > 0
    assert not any(r.pe_id == 0 and r.finish_us > 50.0
                   for r in res.raw.records)


# ------------------------------------------------------------------- sweep

def test_sweep_two_axes_matches_run_in_one_compiled_program():
    points = [DesignPoint(4, 4, 2, 4, 0), DesignPoint(1, 2, 0, 1, 0),
              DesignPoint(0, 4, 1, 2, 1, big_freq_ghz=1.4)]
    rates = [5.0, 40.0]
    n0 = compile_count.value
    sr = sweep(MIX, axes={"rate": rates, "design": points})
    assert compile_count.value - n0 <= 1       # ONE program (0 if cache-warm)
    assert sr.shape == (2, 3) and sr.avg_latency_us.shape == (2, 3)
    for i, rate in enumerate(rates):
        for d, p in enumerate(points):
            ref = run(MIX.at_rate(rate).replace(design=p), backend="jax")
            assert sr.avg_latency_us[i, d] == ref.avg_latency_us
            assert sr.makespan_us[i, d] == ref.makespan_us
            assert sr.energy_j[i, d] == ref.energy_j
            assert np.all(sr.busy_per_pe_us[i, d, :p.num_pes]
                          == np.asarray(ref.raw["busy_per_pe_us"]))
            assert np.all(sr.busy_per_pe_us[i, d, p.num_pes:] == 0)


def test_sweep_repeat_call_hits_jit_cache():
    axes = {"rate": [5.0, 40.0], "seed": [0, 1]}
    sweep(MIX, axes=axes)
    n0 = compile_count.value
    sweep(MIX, axes=axes)
    assert compile_count.value == n0


def test_sweep_scheduler_axis_is_static():
    n0 = compile_count.value
    sr = sweep(SCN, axes={"scheduler": ["met", "etf"], "rate": [5.0, 40.0]})
    assert sr.shape == (2, 2)
    assert compile_count.value - n0 <= 2       # one program per policy
    for j, rate in enumerate([5.0, 40.0]):
        ref = run(SCN.replace(scheduler="met").at_rate(rate), backend="jax")
        assert sr.avg_latency_us[0, j] == ref.avg_latency_us


def test_sweep_design_times_governor_axes():
    """The ROADMAP DTPM direction: governor axes over design batches."""
    points = [DesignPoint(4, 4, 2, 4, 0), DesignPoint(1, 2, 0, 1, 0)]
    sr = sweep(MIX, axes={"design": points,
                          "governor": ["performance", "powersave"]})
    assert sr.shape == (2, 2)
    for d, p in enumerate(points):
        for g, gov in enumerate(["performance", "powersave"]):
            ref = run(MIX.replace(design=p, governor=gov), backend="jax")
            assert sr.avg_latency_us[d, g] == ref.avg_latency_us


def test_sweep_design_batch_validation():
    from repro.dse.batch import build_design_batch
    points = [DesignPoint(2, 2, 1, 1, 0)]
    batch = build_design_batch(points, MIX.applications())
    with pytest.raises(ValueError, match="governor='design'"):
        sweep(MIX, axes={"design": points, "seed": [0]}, design_batch=batch)
    with pytest.raises(ValueError, match="application list"):
        sweep(SCN.replace(governor="design"),
              axes={"design": points, "seed": [0]}, design_batch=batch)


def test_sweep_frequency_cap_axis():
    sr = sweep(MIX.replace(governor="design"),
               axes={"design.big_freq_ghz": [1.4, 2.0], "seed": [0, 1]})
    assert sr.shape == (2, 2)
    # lower frequency cap -> no faster than nominal
    assert np.all(sr.avg_latency_us[0] >= sr.avg_latency_us[1] - 1e-6)


def test_sweep_ref_backend_matches_run():
    sr = sweep(SCN, axes={"rate": [5.0, 40.0], "seed": [0, 1]},
               backend="ref")
    ref = run(SCN.at_rate(40.0).with_seed(1), backend="ref")
    assert sr.avg_latency_us[1, 1] == ref.avg_latency_us
    assert sr.peak_temp_c[1, 1] == ref.peak_temp_c


def test_sweep_explicit_trace_axis_matches_spec_axis():
    specs = [dataclasses.replace(SCN.trace, seed=s) for s in (0, 1)]
    traces = [s.materialize(SCN.app_names()) for s in specs]
    a = sweep(SCN, axes={"trace": specs})
    b = sweep(SCN, axes={"trace": traces})
    np.testing.assert_array_equal(a.avg_latency_us, b.avg_latency_us)


def test_sweep_validates_axes():
    with pytest.raises(ValueError, match="unknown sweep axis"):
        sweep(SCN, axes={"voltage": [1.0]})
    with pytest.raises(ValueError, match="at least one"):
        sweep(SCN, axes={})
    with pytest.raises(ValueError, match="equal job counts"):
        sweep(SCN, axes={"jobs": [8, 16]})
    # but a ref-backend jobs sweep works (the error message points there)
    sr = sweep(SCN, axes={"jobs": [8, 16]}, backend="ref")
    assert sr.shape == (2,)
    with pytest.raises(ValueError, match="duplicate sweep axes"):
        sweep(SCN, axes={"seed": [0, 1], "trace.seed": [2, 3]})
    with pytest.raises(ValueError, match="conflicts"):
        sweep(SCN, axes={"seed": [0, 1], "trace": [SCN.trace]})
    with pytest.raises(ValueError, match="conflicts"):
        sweep(SCN, axes={"design": [SCN.design],
                         "design.big_freq_ghz": [1.4, 2.0]})


# ------------------------------------------------------- deprecation shims

def test_core_simulate_shim_warns_and_matches():
    with pytest.warns(DeprecationWarning, match="repro.scenario"):
        legacy = core.simulate(SCN.soc(), SCN.applications(),
                               SCN.job_trace(), get_scheduler("etf"))
    assert run(SCN, backend="ref").avg_latency_us \
        == float(legacy.avg_job_latency_us)


def test_core_simulate_jax_shim_warns_matches_and_aliases():
    tables = skj.build_tables(SCN.soc(), SCN.applications())
    trace = SCN.job_trace()
    with pytest.warns(DeprecationWarning, match="repro.scenario"):
        out = core.simulate_jax(tables, "etf", trace.arrival_us,
                                trace.app_index)
    assert "energy_mj" not in out          # one-release alias key removed
    res = run(SCN, backend="jax")
    np.testing.assert_array_equal(np.asarray(out["avg_job_latency_us"]),
                                  res.avg_latency_us)


def test_energy_mj_aliases_removed():
    """The one-release *_mj deprecation window is over: aliases are gone."""
    report = run(SCN, backend="ref").energy_report
    assert not hasattr(report, "total_energy_mj")
    assert not hasattr(report, "energy_per_pe_mj")
    assert report.total_energy_j > 0
    ev = dse.evaluate([DesignPoint(2, 2, 1, 1, 0)], MIX.applications(),
                      [MIX.job_trace()])
    assert not hasattr(ev, "energy_mj")
    assert np.all(ev.energy_j > 0)


# ----------------------------------------------------- facade delegation

def test_dse_evaluate_equals_sweep():
    points = [DesignPoint(4, 4, 2, 4, 0), DesignPoint(1, 2, 0, 1, 0)]
    traces = [MIX.with_seed(s).job_trace() for s in (0, 1, 2)]
    ev = dse.evaluate(points, MIX.applications(), traces, policy="etf")
    sr = sweep(MIX.replace(governor="design"),
               axes={"design": points, "seed": [0, 1, 2]})
    np.testing.assert_array_equal(ev.latency_per_trace_us,
                                  sr.avg_latency_us)
    np.testing.assert_array_equal(ev.energy_per_trace_j, sr.energy_j)
    np.testing.assert_array_equal(ev.temp_per_trace_c, sr.peak_temp_c)


def test_sweep_iter_records():
    sr = sweep(SCN, axes={"rate": [5.0, 40.0], "seed": [0]})
    recs = list(sr.iter_records())
    assert len(recs) == 2
    coords, metrics = recs[1]
    assert coords == {"rate": 40.0, "seed": 0}
    assert metrics["avg_latency_us"] == sr.avg_latency_us[1, 0]
