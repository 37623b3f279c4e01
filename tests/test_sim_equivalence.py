"""Cross-validation: the vectorised JAX kernel vs the event-heap reference.

This carries the paper's hardware-validation duty (we have no Zynq board):
the two independent implementations must agree — exactly on comm-free
integer-latency workloads, and to float tolerance in general.
"""
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

from repro.core import (TableScheduler, build_tables, deterministic_trace,
                        get_application, get_scheduler, make_soc_table2,
                        poisson_trace, solve_optimal_table, wifi_tx)
# kernels imported directly: the repro.core re-exports are deprecation shims
from repro.core.simkernel_jax import simulate_jax
from repro.core.simkernel_ref import simulate
from repro.core.applications import Application, Task
from repro.core.resources import ALL_PROFILES, CommModel, ResourceDB, make_soc
from repro.dse.batch import stack_tables
from repro.scenario.shardexec import _sweep_grid


APPS5 = ["wifi_tx", "wifi_rx", "single_carrier", "range_detection",
         "pulse_doppler"]


def _run_both(db, apps, trace, policy, table=None):
    sched = (TableScheduler(table) if policy == "table"
             else get_scheduler(policy))
    ref = simulate(db, apps, trace, sched)
    tables = build_tables(db, apps, table=table)
    jx = simulate_jax(tables, policy, trace.arrival_us, trace.app_index)
    return ref, jx


@pytest.mark.parametrize("policy", ["met", "etf", "table"])
@pytest.mark.parametrize("rate", [2.0, 20.0, 60.0])
def test_kernels_agree_wifi_tx(policy, rate):
    db = make_soc_table2()
    app = wifi_tx()
    table = solve_optimal_table(db, app) if policy == "table" else None
    trace = poisson_trace(rate, 80, ["wifi_tx"], seed=int(rate))
    ref, jx = _run_both(db, [app], trace, policy, table)
    np.testing.assert_allclose(float(jx["avg_job_latency_us"]),
                               ref.avg_job_latency_us, rtol=1e-4)
    np.testing.assert_allclose(float(jx["makespan_us"]), ref.makespan_us,
                               rtol=1e-4)
    np.testing.assert_allclose(float(jx["energy_j"]),
                               ref.energy.total_energy_j, rtol=1e-3)


@pytest.mark.parametrize("policy", ["met", "etf"])
def test_kernels_agree_five_app_mix(policy):
    db = make_soc_table2(with_viterbi=True)
    apps = [get_application(n) for n in APPS5]
    trace = poisson_trace(15.0, 60, APPS5, seed=7)
    ref, jx = _run_both(db, apps, trace, policy)
    np.testing.assert_allclose(float(jx["avg_job_latency_us"]),
                               ref.avg_job_latency_us, rtol=1e-4)


def test_exact_schedule_equality_comm_free():
    """Integer latencies + zero comm => bit-exact schedules in float32."""
    db = make_soc_table2()
    db.comm = CommModel(startup_us=0.0, bw_bytes_per_us=1e30)
    app = wifi_tx()
    trace = deterministic_trace(25.0, 64, ["wifi_tx"])
    ref, jx = _run_both(db, [app], trace, "etf")
    fin = np.asarray(jx["finish"])
    onpe = np.asarray(jx["onpe"])
    for r in ref.records:
        assert fin[r.job_id, r.task_id] == np.float32(r.finish_us)
        assert onpe[r.job_id, r.task_id] == r.pe_id


def test_batched_vmap_matches_loop():
    db = make_soc_table2()
    app = wifi_tx()
    tables = build_tables(db, [app])
    traces = [poisson_trace(r, 40, ["wifi_tx"], seed=s)
              for r in (5.0, 30.0) for s in (0, 1)]
    arr = np.stack([t.arrival_us for t in traces]).astype(np.float32)
    idx = np.stack([t.app_index for t in traces]).astype(np.int32)
    one = stack_tables([tables])                   # a single design lane
    batch, _ = _sweep_grid(one, one.node_of_pe, None, None, arr, idx,
                           policy="etf", num_jobs=40, bins=32, repeats=3,
                           scan_steps=None)
    for k, t in enumerate(traces):
        single = simulate_jax(tables, "etf", t.arrival_us, t.app_index)
        np.testing.assert_allclose(float(batch["avg_job_latency_us"][0, k]),
                                   float(single["avg_job_latency_us"]),
                                   rtol=1e-6)


# ------------------------------------------------------------- property-based

_TASK_NAMES = sorted(ALL_PROFILES.keys())


@st.composite
def random_dag_app(draw):
    n = draw(st.integers(min_value=1, max_value=8))
    tasks = []
    for i in range(n):
        name = draw(st.sampled_from(_TASK_NAMES))
        if i == 0:
            preds = ()
        else:
            k = draw(st.integers(min_value=0, max_value=min(i, 3)))
            preds = tuple(sorted(draw(
                st.sets(st.integers(0, i - 1), min_size=k, max_size=k))))
        nbytes = float(draw(st.sampled_from([256, 1024, 4096])))
        tasks.append(Task(name, i, preds, nbytes))
    return Application("rand", tuple(tasks))


@given(app=random_dag_app(),
       rate=st.sampled_from([2.0, 20.0, 80.0]),
       seed=st.integers(0, 10),
       policy=st.sampled_from(["met", "etf"]))
@settings(max_examples=25, deadline=None)
def test_property_kernels_agree_on_random_dags(app, rate, seed, policy):
    db = make_soc_table2(with_viterbi=True)
    trace = poisson_trace(rate, 20, ["rand"], seed=seed)
    ref, jx = _run_both(db, [app], trace, policy)
    np.testing.assert_allclose(float(jx["avg_job_latency_us"]),
                               ref.avg_job_latency_us, rtol=2e-4)
    # invariant: makespan at least the (exec-only) critical path of one job
    cp = np.zeros(app.num_tasks)
    for t in app.tasks:
        best = min(v for v in ALL_PROFILES[t.name].values())
        cp[t.task_id] = best + max([cp[p] for p in t.predecessors], default=0.0)
    assert float(jx["makespan_us"]) >= cp.max() - 1e-3
