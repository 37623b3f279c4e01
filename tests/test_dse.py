"""repro.dse — design-space exploration subsystem tests.

Covers: Pareto machinery on hand-checkable sets, design-space enumeration
determinism, padded-batch vs per-design kernel equivalence (the batching
correctness contract), and the JAX RC thermal model against the analytical
steady state / the numpy reference integrator.
"""
import numpy as np
import pytest

from repro.core import build_tables, poisson_trace, thermal, \
    wifi_tx, get_application
# kernels imported directly: the re-exports are deprecation shims
from repro.core.simkernel_jax import fixed_order_sum, simulate_jax
from repro.dse.batch import simulate_design_batch
from repro.dse import (DesignPoint, DesignSpace, binned_power_trace,
                       build_design_batch, crowding_distance, evaluate,
                       non_dominated_sort, pareto_mask, pareto_search,
                       peak_temperature_grid,
                       stack_traces, successive_halving, transient_trace)
from repro.dse import thermal_jax

APPS = ["wifi_tx", "wifi_rx"]


def _apps():
    return [get_application(n) for n in APPS]


def _traces(n=2, jobs=12, rate=25.0, seed=0):
    return [poisson_trace(rate, jobs, APPS, seed=seed + i) for i in range(n)]


# ------------------------------------------------------------------ pareto

def test_pareto_mask_hand_checkable():
    # minimise both axes; (1,5) (2,2) (5,1) are the front, rest dominated
    costs = np.array([[1.0, 5.0], [2.0, 2.0], [5.0, 1.0],
                      [2.0, 5.0], [3.0, 3.0], [6.0, 6.0]])
    assert pareto_mask(costs).tolist() == [True, True, True,
                                           False, False, False]


def test_pareto_duplicates_both_survive():
    costs = np.array([[1.0, 1.0], [1.0, 1.0], [2.0, 2.0]])
    assert pareto_mask(costs).tolist() == [True, True, False]


def test_non_dominated_sort_ranks():
    costs = np.array([[1.0, 4.0], [4.0, 1.0],      # front 0
                      [2.0, 5.0], [5.0, 2.0],      # front 1
                      [6.0, 6.0]])                 # front 2
    assert non_dominated_sort(costs).tolist() == [0, 0, 1, 1, 2]


def test_crowding_distance_boundaries_inf():
    costs = np.array([[0.0, 4.0], [1.0, 2.0], [2.0, 1.0], [4.0, 0.0]])
    d = crowding_distance(costs)
    assert np.isinf(d[0]) and np.isinf(d[3])
    assert np.all(np.isfinite(d[1:3])) and np.all(d[1:3] > 0)


# ------------------------------------------------------------- design space

def test_grid_deterministic_and_valid():
    space = DesignSpace(num_big=(0, 1), num_little=(0, 2), num_scr=(0, 1),
                        num_fft=(0, 1), num_vit=(0,),
                        big_freq_ghz=(2.0,), little_freq_ghz=(1.4,))
    g1, g2 = space.grid(), space.grid()
    assert g1 == g2
    assert all(p.is_valid() for p in g1)
    # 2*2*2*2 = 16 combos minus the 4 CPU-less (big=0, little=0) ones
    assert len(g1) == 12


def test_grid_budget_filter():
    space = DesignSpace()
    budget = 10.0
    pts = space.grid(budget_mm2=budget)
    assert pts and all(p.area_mm2 <= budget for p in pts)
    assert len(pts) < len(space.grid())


def test_sampling_deterministic_per_seed():
    space = DesignSpace()
    a = space.sample_lhs(24, seed=7)
    b = space.sample_lhs(24, seed=7)
    c = space.sample_lhs(24, seed=8)
    assert a == b and a != c
    assert len(a) == len(set(a)) == 24
    r1 = space.sample_random(16, seed=3)
    assert r1 == space.sample_random(16, seed=3)
    assert len(set(r1)) == 16 and all(space.contains(p) for p in r1)


def test_neighbors_stay_in_space():
    space = DesignSpace()
    p = space.sample_lhs(1, seed=0)[0]
    nbrs = space.neighbors(p)
    assert nbrs and all(space.contains(q) and q.is_valid() for q in nbrs)
    assert all(q != p for q in nbrs)


# ------------------------------------------------- batched kernel equivalence

def test_fixed_order_sum_ignores_padding_and_lane_width():
    """The result-path sum: trailing zero terms (inert padding) and vmapped
    lanes of any width leave it bit-identical; it is a float32 sum."""
    import jax
    x = np.random.default_rng(0).random((13, 5)).astype(np.float32)
    s = np.asarray(fixed_order_sum(x))
    padded = np.concatenate([x, np.zeros((7, 5), np.float32)])
    np.testing.assert_array_equal(np.asarray(fixed_order_sum(padded)), s)
    for lanes in (1, 3, 64):
        batched = jax.vmap(fixed_order_sum)(np.stack([x] * lanes))
        np.testing.assert_array_equal(np.asarray(batched)[lanes - 1], s)
    np.testing.assert_allclose(s, x.astype(np.float64).sum(axis=0),
                               rtol=1e-6)


@pytest.mark.parametrize("policy", ["met", "etf"])
def test_padded_batch_matches_per_design(policy):
    """The batching contract: stacking + vmap must reproduce per-design
    simulate_jax bit-for-bit (padding is inert, vmap lane == single call)."""
    points = [DesignPoint(4, 4, 2, 4, 0), DesignPoint(1, 2, 0, 1, 0),
              DesignPoint(0, 4, 1, 2, 1, big_freq_ghz=1.4),
              DesignPoint(2, 0, 2, 0, 0, cross_cluster_penalty=4.0)]
    apps = _apps()
    traces = _traces(3)
    batch = build_design_batch(points, apps)
    arrival, app_idx = stack_traces(traces)
    out = simulate_design_batch(batch, policy, arrival, app_idx)
    for d, p in enumerate(points):
        tables = build_tables(p.to_db(), apps, governor=p.governor())
        for s, tr in enumerate(traces):
            ref = simulate_jax(tables, policy, tr.arrival_us, tr.app_index)
            np.testing.assert_array_equal(
                np.asarray(out["avg_job_latency_us"])[d, s],
                np.asarray(ref["avg_job_latency_us"]))
            np.testing.assert_array_equal(
                np.asarray(out["makespan_us"])[d, s],
                np.asarray(ref["makespan_us"]))
            np.testing.assert_array_equal(
                np.asarray(out["energy_j"])[d, s],
                np.asarray(ref["energy_j"]))
            np.testing.assert_array_equal(
                np.asarray(out["busy_per_pe_us"])[d, s, :p.num_pes],
                np.asarray(ref["busy_per_pe_us"]))
            # padded PE slots never execute anything
            assert np.all(np.asarray(out["busy_per_pe_us"])[d, s,
                                                            p.num_pes:] == 0)


def test_build_tables_pad_validation():
    db = DesignPoint(2, 2, 1, 1, 0).to_db()
    with pytest.raises(ValueError):
        build_tables(db, [wifi_tx()], pad_pes=db.num_pes - 1)
    with pytest.raises(ValueError):
        build_tables(db, [wifi_tx()], pad_tasks=2)


# ------------------------------------------------------------------ thermal

def test_transient_matches_numpy_reference():
    rng = np.random.default_rng(0)
    trace = rng.uniform(0.0, 3.0, size=(50, 3))
    ref = thermal.simulate_trace(trace, dt_s=0.02)
    jx = np.asarray(transient_trace(trace, 0.02))
    np.testing.assert_allclose(jx, ref, rtol=1e-5, atol=1e-4)


def test_thermal_scan_converges_to_steady_state():
    power = np.array([3.0, 1.0, 0.5])
    expect = thermal.steady_state(power)
    trace = np.tile(power, (30000, 1))                # 30000 * 0.05s = 1500 s
    temps = np.asarray(transient_trace(trace, 0.05))
    np.testing.assert_allclose(temps[-1], expect, rtol=1e-3)
    # analytical jnp steady state agrees with the numpy oracle exactly-ish
    np.testing.assert_allclose(np.asarray(thermal_jax.steady_state(power)),
                               expect, rtol=1e-5)


def test_binned_power_conserves_energy():
    """∫ binned node power dt == the kernel's active+idle energy integral."""
    p = DesignPoint(2, 2, 1, 2, 0)
    apps = _apps()
    traces = _traces(2)
    batch = build_design_batch([p], apps)
    arrival, app_idx = stack_traces(traces)
    out = simulate_design_batch(batch, "etf", arrival, app_idx)
    for s in range(len(traces)):
        trace_kw, dt_us = binned_power_trace(
            out["start"][0, s], out["finish"][0, s], out["onpe"][0, s],
            out["scheduled"][0, s], batch.node_of_pe[0],
            batch.tables.power_active[0], batch.tables.power_idle[0],
            out["makespan_us"][0, s], bins=64)
        # node power (W) * bin width (us) * 1e-6 -> J, == kernel energy field
        e_binned = float(np.sum(np.asarray(trace_kw)) * np.asarray(dt_us)
                         * 1e6 * 1e-6)
        e_kernel = float(np.asarray(out["energy_j"])[0, s])
        assert e_binned == pytest.approx(e_kernel, rel=1e-3)


def test_peak_temperature_stable_for_long_bins():
    """Bin widths above the forward-Euler stability bound (~0.4 s for the
    LITTLE node) must not diverge: the exact linear-RC update is used."""
    rng = np.random.default_rng(3)
    trace = rng.uniform(0.0, 4.0, size=(32, 3))
    for dt in (1e-6, 0.1, 1.0, 60.0):
        peak = float(np.asarray(thermal_jax.peak_temperature(trace, dt)))
        assert np.isfinite(peak)
        assert thermal.T_AMBIENT_C - 1e-3 <= peak < 200.0
    # constant power at any dt stays pinned to the analytical steady state
    const = np.tile([3.0, 1.0, 0.5], (16, 1))
    expect = float(thermal.steady_state(const[0])[:3].max())
    got = float(np.asarray(thermal_jax.peak_temperature(const, 50.0)))
    assert got == pytest.approx(expect, rel=1e-4)


def test_peak_temperature_grid_monotone_in_power():
    """More loaded design (fewer, hotter big cores at fmax) runs hotter than
    an idle-ish LITTLE-only design; all temps are >= ambient."""
    points = [DesignPoint(4, 0, 0, 0, 0, big_freq_ghz=2.0),
              DesignPoint(0, 4, 0, 0, 0, little_freq_ghz=1.0)]
    apps = [wifi_tx()]
    traces = [poisson_trace(40.0, 16, ["wifi_tx"], seed=0)]
    batch = build_design_batch(points, apps)
    arrival, app_idx = stack_traces(traces)
    out = simulate_design_batch(batch, "etf", arrival, app_idx)
    temps = np.asarray(peak_temperature_grid(
        out, batch.node_of_pe, batch.tables.power_active,
        batch.tables.power_idle))
    assert temps.shape == (2, 1)
    assert np.all(temps >= thermal.T_AMBIENT_C - 1e-6)
    assert temps[0, 0] > temps[1, 0]


# ------------------------------------------------------------------- search

def test_evaluate_shapes_and_front():
    space = DesignSpace()
    pts = space.sample_lhs(8, seed=1)
    res = evaluate(pts, _apps(), _traces(2))
    assert res.objectives().shape == (8, 3)
    assert res.latency_per_trace_us.shape == (8, 2)
    mask = res.front_mask()
    assert mask.any() and mask.shape == (8,)


def test_successive_halving_prunes():
    space = DesignSpace()
    pts = space.sample_lhs(12, seed=2)
    res = successive_halving(pts, _apps(), _traces(3), eta=2,
                             min_survivors=4)
    assert res.num_designs == 6                       # 12 // eta
    assert set(res.points) <= set(pts)


def test_pareto_search_deterministic_and_grows():
    space = DesignSpace()
    kw = dict(rounds=2, batch_size=8, seed=5)
    a = pareto_search(space, [wifi_tx()],
                      [poisson_trace(20.0, 8, ["wifi_tx"], seed=0)], **kw)
    b = pareto_search(space, [wifi_tx()],
                      [poisson_trace(20.0, 8, ["wifi_tx"], seed=0)], **kw)
    assert a.archive.points == b.archive.points
    np.testing.assert_array_equal(a.archive.objectives(),
                                  b.archive.objectives())
    assert a.archive.num_designs > 8                  # refinement added points
    assert a.front.sum() >= 1
    assert len(a.rounds) == 2
