"""repro.dse — design-space exploration subsystem tests.

Covers: Pareto machinery on hand-checkable sets, design-space enumeration
determinism, padded-batch vs per-design kernel equivalence (the batching
correctness contract), and the JAX RC thermal model against the analytical
steady state / the numpy reference integrator.
"""
import jax
import numpy as np
import pytest

from repro.core import build_tables, poisson_trace, solve_optimal_table, \
    thermal, wifi_tx, get_application
from repro.core.dvfs import get_governor
# kernels imported directly: the re-exports are deprecation shims
from repro.core.simkernel_jax import build_tables_host, fixed_order_sum, \
    simulate_jax
from repro.dse import (DesignBatch, DesignPoint, DesignSpace,
                       binned_power_trace, build_design_batch,
                       crowding_distance, evaluate, non_dominated_sort,
                       pad_node_map, pareto_mask, pareto_search,
                       peak_temperature_grid, stack_tables, stack_traces,
                       successive_halving, transient_trace)
from repro.dse import thermal_jax
from repro.obs import metrics as _metrics
from repro.scenario.shardexec import _sweep_grid

APPS = ["wifi_tx", "wifi_rx"]


def _design_grid(batch, policy, arrival, app_idx):
    """(D designs, S traces) lanes of the sweep's one grid program."""
    out, _ = _sweep_grid(batch.tables, batch.node_of_pe, None, None, arrival,
                         app_idx, policy=policy,
                         num_jobs=int(arrival.shape[1]), bins=32, repeats=3,
                         scan_steps=None)
    return out


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
    out = _design_grid(batch, policy, arrival, app_idx)
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


# ------------------------------------------------------- host table build

_BATCH_POINTS = [DesignPoint(4, 4, 2, 4, 0), DesignPoint(1, 2, 0, 1, 0),
                 DesignPoint(0, 4, 1, 2, 1, big_freq_ghz=1.4),
                 DesignPoint(2, 0, 2, 0, 0, cross_cluster_penalty=4.0)]


def _device_path_batch(points, apps, pad_pes=None, governor=None):
    """The batch as built when every design's tables went to the device
    first: per-design device ``build_tables``, device ``stack_tables``."""
    dbs = [p.to_db() for p in points]
    P = pad_pes or max(db.num_pes for db in dbs)
    per_design = [
        build_tables(db, apps, governor=governor or p.governor(), pad_pes=P,
                     freq_caps=p.freq_caps() if governor else None)
        for p, db in zip(points, dbs)]
    return DesignBatch(points=tuple(points), tables=stack_tables(per_design),
                       node_of_pe=pad_node_map(dbs, P))


def _assert_same_leaves(got, want):
    """Same tree, and every leaf the same dtype, shape and bits."""
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("governor", [None, "throttle"])
def test_design_batch_matches_device_path(governor):
    """Host build + host stack + one placement gives the tables and node
    map the per-design device path gave, static caps and OPP ladders."""
    gov = get_governor(governor) if governor else None
    got = build_design_batch(_BATCH_POINTS, _apps(), pad_pes=16,
                             governor=gov)
    want = _device_path_batch(_BATCH_POINTS, _apps(), pad_pes=16,
                              governor=gov)
    assert got.dynamic == (governor is not None)
    _assert_same_leaves((got.tables, got.node_of_pe),
                        (want.tables, want.node_of_pe))


def test_design_batch_leaves_are_device_arrays():
    """Device arrays, uncommitted as jnp.stack leaves them: a committed
    argument would be a new jit cache entry for the batched programs."""
    batch = build_design_batch(_BATCH_POINTS, _apps(),
                               governor=get_governor("throttle"))
    leaves = jax.tree_util.tree_leaves((batch.tables, batch.node_of_pe))
    assert len(leaves) == 21
    assert all(isinstance(x, jax.Array) and not x.committed for x in leaves)


def test_design_batch_placements_do_not_grow_with_designs():
    """One host->device transfer per leaf, whatever the number of designs."""
    placements = _metrics.counter("dse.tables.placements")
    points = DesignSpace().sample_lhs(16, seed=4)
    counts = []
    for d in (2, 16):
        before = placements.value
        batch = build_design_batch(points[:d], _apps(), pad_pes=20)
        counts.append(placements.value - before)
    assert counts[0] == counts[1] == len(jax.tree_util.tree_leaves(
        (batch.tables, batch.node_of_pe)))


def test_evaluate_on_host_built_batch_matches_device_path():
    """evaluate(batch=...) on the host-built batch gives the device-built
    batch's outputs bit for bit, from the same compiled program."""
    from repro.scenario.sweep import compile_count
    pts = DesignSpace().sample_lhs(6, seed=9)
    apps, traces = _apps(), _traces(2)
    want = evaluate(pts, apps, traces, pad_pes=20,
                    batch=_device_path_batch(pts, apps, pad_pes=20))
    compiles = compile_count.value
    got = evaluate(pts, apps, traces, pad_pes=20,
                   batch=build_design_batch(pts, apps, pad_pes=20))
    assert compile_count.value == compiles
    for field in ("latency_per_trace_us", "energy_per_trace_j",
                  "temp_per_trace_c"):
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(want, field), err_msg=field)


@pytest.mark.parametrize("case", ["pad_pes", "table"])
def test_host_builder_matches_build_tables(case):
    """build_tables is the host builder's tables placed: same leaves."""
    db = DesignPoint(2, 2, 1, 1, 0).to_db()
    app = wifi_tx()
    kw = (dict(pad_pes=db.num_pes + 3) if case == "pad_pes"
          else dict(table=solve_optimal_table(db, app)))
    host = build_tables_host(db, [app], **kw)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(host))
    _assert_same_leaves(host, build_tables(db, [app], **kw))


# The per-design fill as it was before the batched one: every design's
# tables one scalar at a time, stacked.  The batched fill must equal it.

def _loop_fill_host(db, apps, governor, pad_pes, freq_caps=None):
    from repro.core.dvfs import MAX_OPP_LEVELS, padded_ladder
    from repro.core.power import active_power, idle_power
    from repro.core.resources import NOMINAL_FREQ
    from repro.core.simkernel_jax import MIN_DOMAINS, SimTables
    dynamic = governor.policy().dynamic
    if freq_caps is None:
        freq_caps = getattr(governor, "freq_caps", None)
    A, P = len(apps), pad_pes or db.num_pes
    T = max(a.num_tasks for a in apps)
    freq = {}
    for pe in db.pes:
        if pe.is_cpu and pe.cluster not in freq:
            freq[pe.cluster] = governor.initial_freq(pe.pe_type)
    k_in = max(1, max(a.max_in_degree for a in apps))
    k_out = max(1, max(a.max_out_degree for a in apps))
    exec_us = np.full((A, T, P), 1e30, dtype=np.float32)
    pred_idx = np.full((A, T, k_in), -1, dtype=np.int32)
    pred_bytes = np.zeros((A, T, k_in), dtype=np.float32)
    succ_idx = np.full((A, T, k_out), -1, dtype=np.int32)
    valid = np.zeros((A, T), dtype=bool)
    for ai, app in enumerate(apps):
        lat = db.latency_matrix(app.task_names)
        for t in range(app.num_tasks):
            valid[ai, t] = True
            for j, pe in enumerate(db.pes):
                base = lat[t, j]
                if np.isfinite(base):
                    scale = (NOMINAL_FREQ[pe.pe_type] / freq[pe.cluster]
                             if pe.is_cpu else 1.0)
                    exec_us[ai, t, j] = np.float32(
                        np.float32(base) * np.float32(scale))
        n = app.num_tasks
        pred_idx[ai, :n], pred_bytes[ai, :n] = app.pred_lists(k_in)
        succ_idx[ai, :n] = app.succ_lists(k_out)
    comm_mult = np.zeros((P, P), dtype=np.float32)
    for s in range(db.num_pes):
        for d in range(db.num_pes):
            if s != d:
                comm_mult[s, d] = (
                    db.comm.cross_cluster_penalty
                    if db.pes[s].cluster != db.pes[d].cluster else 1.0)
    p_act = np.zeros(P, dtype=np.float32)
    p_idle = np.zeros(P, dtype=np.float32)
    for j, pe in enumerate(db.pes):
        p_act[j] = active_power(pe, freq.get(pe.cluster, 0.0)
                                if pe.is_cpu else 0.0)
        p_idle[j] = idle_power(pe)
    C = max(MIN_DOMAINS, max(pe.cluster for pe in db.pes) + 1)
    nodes = thermal.cluster_nodes(db)
    node_of_pe = np.full(P, thermal.NODE_ACCEL, dtype=np.int32)
    node_of_pe[:db.num_pes] = nodes
    pe_domain = np.full(P, C - 1, dtype=np.int32)
    pe_is_cpu = np.zeros(P, dtype=np.float32)
    for j, pe in enumerate(db.pes):
        pe_domain[j] = pe.cluster
        pe_is_cpu[j] = 1.0 if pe.is_cpu else 0.0
    opp = {}
    if dynamic:
        K = MAX_OPP_LEVELS
        exec_opp = np.full((A, T, P, K), 1e30, dtype=np.float32)
        p_act_opp = np.zeros((P, K), dtype=np.float32)
        opp_freq = np.zeros((C, K), dtype=np.float32)
        num_opp = np.ones(C, dtype=np.int32)
        domain_node = np.full(C, thermal.NODE_ACCEL, dtype=np.int32)
        domain_cpu = np.zeros(C, dtype=np.float32)
        ladders = {pe.pe_type: padded_ladder(pe.pe_type, freq_caps)
                   for pe in db.pes if pe.is_cpu}
        for j, pe in enumerate(db.pes):
            if pe.is_cpu:
                _, row, n = ladders[pe.pe_type]
                num_opp[pe.cluster] = n
                domain_node[pe.cluster] = nodes[j]
                domain_cpu[pe.cluster] += 1.0
                for k in range(K):
                    opp_freq[pe.cluster, k] = row[k]
                    p_act_opp[j, k] = active_power(pe, row[k])
            else:
                p_act_opp[j, :] = active_power(pe, 0.0)
        for ai, app in enumerate(apps):
            lat = db.latency_matrix(app.task_names)
            for t in range(app.num_tasks):
                for j, pe in enumerate(db.pes):
                    base = lat[t, j]
                    if not np.isfinite(base):
                        continue
                    if pe.is_cpu:
                        row = ladders[pe.pe_type][1]
                        for k in range(K):
                            scale = np.float32(
                                NOMINAL_FREQ[pe.pe_type] / row[k])
                            exec_opp[ai, t, j, k] = np.float32(
                                np.float32(base) * scale)
                    else:
                        exec_opp[ai, t, j, :] = np.float32(base)
        opp = dict(exec_opp=exec_opp, power_active_opp=p_act_opp,
                   opp_freq=opp_freq, num_opp=num_opp,
                   domain_node=domain_node, domain_cpu=domain_cpu)
    return SimTables(
        exec_us=exec_us, pred_idx=pred_idx, pred_bytes=pred_bytes,
        succ_idx=succ_idx, valid=valid, comm_mult=comm_mult,
        comm_startup=np.asarray(db.comm.startup_us, np.float32),
        comm_inv_bw=np.asarray(1.0 / db.comm.bw_bytes_per_us, np.float32),
        power_active=p_act, power_idle=p_idle,
        table_pe=np.full((A, T), -1, dtype=np.int32),
        node_of_pe=node_of_pe, pe_domain=pe_domain, pe_is_cpu=pe_is_cpu,
        t_max=T, num_pes=P, depth=max(a.depth for a in apps), **opp)


def _loop_fill_batch(points, apps, pad_pes, governor=None):
    dbs = [p.to_db() for p in points]
    return stack_tables([
        _loop_fill_host(db, apps, governor or p.governor(), pad_pes,
                        p.freq_caps() if governor else None)
        for p, db in zip(points, dbs)], host=True)


# every sub-SoC of the Table-2 SoC with a CPU: 360 designs
_TABLE2_SPACE = DesignSpace(num_big=(0, 1, 2, 3, 4),
                            num_little=(0, 1, 2, 3, 4), num_scr=(0, 1, 2),
                            num_fft=(0, 1, 2, 3, 4), num_vit=(0,),
                            big_freq_ghz=(2.0,), little_freq_ghz=(1.4,),
                            cross_cluster_penalty=(2.0,))


@pytest.mark.parametrize("case", ["table2_grid", "lhs_ondemand",
                                  "lhs_throttle", "mixed_freq"])
def test_batched_fill_matches_per_design_fill(case):
    """The batched host fill is the per-design fill stacked, leaf for
    leaf and bit for bit, metas included."""
    gov, apps, pad = None, [wifi_tx()], 14
    if case == "table2_grid":
        points = _TABLE2_SPACE.grid()
        assert len(points) == 360
    elif case == "mixed_freq":
        # two big frequencies and two LITTLE ones: rows shared per pair
        points = DesignSpace().sample_lhs(24, seed=5)
        assert len({p.big_freq_ghz for p in points}) == 2
        apps, pad = _apps(), 20
    else:
        points = DesignSpace().sample_lhs(16, seed=3)
        gov, apps, pad = get_governor(case.split("_")[1]), _apps(), 20
    got = build_design_batch(points, apps, pad_pes=pad, governor=gov)
    want = _loop_fill_batch(points, apps, pad, governor=gov)
    assert (got.tables.t_max, got.tables.num_pes, got.tables.depth) == \
        (want.t_max, want.num_pes, want.depth)
    _assert_same_leaves(got.tables, want)
    np.testing.assert_array_equal(np.asarray(got.node_of_pe),
                                  want.node_of_pe)


@pytest.mark.parametrize("pad_pes", [None, 17])
@pytest.mark.parametrize("governor", ["performance", "throttle"])
def test_one_design_fill_matches_per_design_fill(governor, pad_pes):
    """build_tables_host is the batched fill's one-design case: its
    numpy leaves (0-d scalars included) equal the per-design fill's."""
    db = DesignPoint(2, 3, 1, 2, 1, little_freq_ghz=1.0).to_db()
    gov = get_governor(governor)
    got = build_tables_host(db, _apps(), governor=gov, pad_pes=pad_pes)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(got))
    _assert_same_leaves(got, _loop_fill_host(db, _apps(), gov, pad_pes))
    assert got.num_pes == (pad_pes or db.num_pes)


def test_rows_filled_counts_distinct_pe_rows():
    """One row per distinct (PE type, frequency) among the real slots,
    the same for 2 designs as for 64 drawn from the same space."""
    rows = _metrics.counter("dse.tables.rows_filled")
    sample = _TABLE2_SPACE.sample_lhs(64, seed=11)
    pairs = [DesignPoint(4, 4, 2, 4, 0), DesignPoint(1, 0, 1, 1, 0)]
    counts = []
    for points in (pairs, sample):
        build_design_batch(points, [wifi_tx()], pad_pes=14)
        keys = {(pe.pe_type, p.freq_caps().get(pe.pe_type))
                for p in points for pe in p.to_db().pes}
        assert rows.value == len(keys)
        counts.append(rows.value)
    assert counts == [4, 4]


def test_cached_host_tables_match_device_tables():
    """The chunked sweep's host tables are the device tables read back."""
    from repro.scenario import Scenario
    from repro.scenario.run import tables_for
    scn = Scenario(scheduler="table")
    host = tables_for(scn, pad_pes=16, host=True)
    device = tables_for(scn, pad_pes=16)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(host))
    _assert_same_leaves(host, jax.tree_util.tree_map(np.asarray, device))


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
    out = _design_grid(batch, "etf", arrival, app_idx)
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
    out = _design_grid(batch, "etf", arrival, app_idx)
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
