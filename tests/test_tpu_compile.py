"""The simulator's main-path programs, compiled for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, so what the chip's compiler would refuse fails
here.  The topology is described inside a module-scoped fixture (never at
import time), and every test that needs it lives in this one file: only one
process may hold the TPU library, and a test module that decided at import
whether its tests exist would split the test workers' collections.  The
persistent compilation cache is off around these compiles — an entry
written for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core.dvfs import stack_policies
from repro.core.simkernel_jax import _simulate, _simulate_dtpm
from repro.dse import DesignSpace, build_design_batch
from repro.dse.batch import stack_traces
from repro.jax_cache import enable_compile_cache
from repro.scenario import FaultSpec, Scenario, TraceSpec, tables_for
from repro.scenario import shardexec
from repro.scenario.faults import fault_scan_steps, stack_fault_plans
from repro.sharding import LANE_AXIS

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                 # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding):
    """Shapes (no arrays) of a pytree's leaves, placed by ``sharding``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


def _trace_specs(scn, sharding):
    tr = scn.job_trace()
    return (_specs(tr.arrival_us.astype(np.float32), sharding),
            _specs(tr.app_index.astype(np.int32), sharding))


SIM_SCN = Scenario(apps=("wifi_tx", "wifi_rx"),
                   trace=TraceSpec(rate_jobs_per_ms=20.0, num_jobs=150))


def test_static_kernel_compiles_for_v5e(one_chip):
    arrival, app_idx = _trace_specs(SIM_SCN, one_chip)
    compiled = _simulate.lower(
        _specs(tables_for(SIM_SCN), one_chip), policy="etf", num_jobs=150,
        arrival=arrival, app_idx=app_idx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_dtpm_kernel_compiles_for_v5e(one_chip):
    scn = SIM_SCN.replace(governor="ondemand")
    arrival, app_idx = _trace_specs(scn, one_chip)
    compiled = _simulate_dtpm.lower(
        _specs(tables_for(scn), one_chip), policy="etf", num_jobs=150,
        arrival=arrival, app_idx=app_idx,
        gov=_specs(scn.make_policy(), one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_faulted_static_grid_compiles_for_v5e(one_chip):
    """(3 fault lanes) x (1 design) x (4 traces) through the fail-stop
    sweep program at bench_faults' 64 jobs."""
    scn = Scenario(apps=("wifi_tx",), trace=TraceSpec(num_jobs=64))
    lanes = [(), (FaultSpec(0, 400.0),),
             (FaultSpec(0, 400.0), FaultSpec(10, 800.0))]
    tables = jax.tree_util.tree_map(lambda x: np.asarray(x)[None],
                                    tables_for(scn))
    plans, max_f = stack_fault_plans(lanes, scn.design.num_pes,
                                     width=tables.num_pes)
    arrival, app_idx = stack_traces([scn.with_seed(s).job_trace()
                                     for s in range(4)])
    nodes = np.zeros((1, tables.num_pes), np.int32)
    shardexec._sweep_grid_faults.lower(
        _specs(tables, one_chip), _specs(nodes, one_chip),
        _specs(plans, one_chip), _specs(arrival, one_chip),
        _specs(app_idx, one_chip), policy="etf", num_jobs=64, bins=32,
        repeats=3,
        scan_steps=fault_scan_steps(64, tables.t_max, max_f)).compile()


def test_dse_grid_shards_over_four_chips_without_collectives(topo):
    """The 64-design x 4-trace DSE grid with its lane axis split over the
    four described chips: lanes are independent, so the partitioned
    program must need no collective at all (DESIGN.md §13)."""
    mesh = Mesh(np.asarray(topo.devices), (LANE_AXIS,))
    lanes, replicated = (NamedSharding(mesh, PartitionSpec(LANE_AXIS)),
                         NamedSharding(mesh, PartitionSpec()))
    base = Scenario(apps=("wifi_tx", "wifi_rx"), governor="design",
                    trace=TraceSpec(rate_jobs_per_ms=20.0, num_jobs=32))
    batch = build_design_batch(DesignSpace().sample_lhs(64, seed=0),
                               base.applications())
    arrival, app_idx = stack_traces([base.with_seed(s).job_trace()
                                     for s in range(4)])
    compiled = shardexec._sweep_grid.lower(
        _specs(batch.tables, lanes), _specs(batch.node_of_pe, lanes),
        _specs(arrival, replicated), _specs(app_idx, replicated),
        policy="etf", num_jobs=32, bins=32, repeats=3).compile()
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]


@pytest.mark.parametrize("governor", ["performance", "ondemand"])
def test_lane_grid_programs_hold_no_contraction(governor):
    """The grid programs sum with masked reduces, never a contraction: at
    DEFAULT precision the TPU runs f32 contractions as bfloat16 passes, and
    it accumulates them in an order that changes with the lane width
    (DESIGN.md §1)."""
    scn = SIM_SCN.replace(governor=governor)
    tb = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None],
                                tables_for(scn))
    arrival, app_idx = stack_traces([scn.job_trace()])
    if governor == "performance":
        lowered = shardexec._sweep_grid.lower(
            tb, jnp.zeros((1, tb.num_pes), jnp.int32), arrival, app_idx,
            policy="etf", num_jobs=150, bins=32, repeats=3)
    else:
        lowered = shardexec._sweep_grid_dtpm.lower(
            tb, stack_policies([scn.make_policy()]), arrival, app_idx,
            policy="etf", num_jobs=150)
    assert "dot_general" not in lowered.as_text()


def test_compile_cache_location(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set in
    code; without it the cache goes to the caller's fixed directory."""
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        env_dir, repo_dir = str(tmp_path / "env"), str(tmp_path / "repo")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert enable_compile_cache(repo_dir) == env_dir
        assert jax.config.jax_compilation_cache_dir == old_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache(repo_dir) == repo_dir
        assert jax.config.jax_compilation_cache_dir == repo_dir
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
