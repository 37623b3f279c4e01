"""repro.dse — batched design-space exploration over the JAX sim kernel.

The paper positions system-level simulation as the enabler for "design space
exploration and dynamic resource management"; this package is that mode:

    space:       DesignSpace / DesignPoint — declarative SoC configurations
                 with grid / random / latin-hypercube enumeration
    batch:       pad + stack per-design SimTables into (D, …) tensors,
                 the design lanes of the sweep's one vmapped grid program
    thermal_jax: lax.scan RC thermal co-simulation -> peak temp per design
    pareto:      non-dominated sorting + crowding distance
    search:      evaluate / successive_halving / pareto_search refinement
    reports:     ASCII/CSV front reports + `python -m repro.dse.reports`

Design sweeps are one axis of the unified ``repro.scenario`` facade:
``sweep(scenario, axes={"design": points, …})`` simulates them, and
``sweep(..., design_batch=build_design_batch(...))`` reuses a batch built
once.
"""
from .batch import (DesignBatch, build_design_batch, pad_node_map,
                    stack_tables, stack_traces)
from .pareto import (crowding_distance, non_dominated_sort, pareto_mask,
                     pareto_order)
from .reports import format_front, front_csv
from .search import (OBJECTIVES, EvalResult, SearchResult, evaluate,
                     pareto_search, successive_halving)
from .space import AREA_MM2, AXES, DesignPoint, DesignSpace
from .thermal_jax import (binned_power_trace, peak_temperature,
                          peak_temperature_grid, steady_state,
                          transient_trace)


__all__ = [n for n in dir() if not n.startswith("_")]
