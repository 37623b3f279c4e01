"""DS3X core — the paper's DSSoC simulation framework, faithful half.

Public API:
    resources:    PE, ResourceDB, CommModel, make_soc_table2, make_soc
    applications: Application, Task, get_application, REFERENCE_APPS
    jobgen:       JobTrace, poisson_trace, deterministic_trace, rate_sweep
    schedulers:   get_scheduler, register_scheduler, solve_optimal_table
    simkernel:    simulate (reference) / build_tables + simulate_jax (vectorised)
    power/thermal/dvfs: analytical models + governors

Scenario-driven entry point: prefer ``repro.scenario`` — one declarative
``Scenario`` plus ``run()``/``sweep()`` replaces wiring the pieces above by
hand.  ``simulate`` / ``simulate_jax`` exported here are deprecation shims
that delegate to the unchanged kernels.
"""
from ._deprecation import deprecated_entry_point as _deprecated_entry_point
from .applications import (Application, REFERENCE_APPS, Task, get_application,
                           pulse_doppler, range_detection, single_carrier,
                           wifi_rx, wifi_tx)
from .dvfs import (GOVERNORS, Governor, GovernorPolicy, OndemandGovernor,
                   PerformanceGovernor, PowersaveGovernor, ThrottleGovernor,
                   UserspaceGovernor, get_governor, ondemand_index,
                   stack_policies, throttle_index)
from .jobgen import JobTrace, deterministic_trace, poisson_trace, rate_sweep
from .power import EnergyReport, active_power, energy_from_schedule, idle_power
from .resources import (ACC_FFT, ACC_SCRAMBLER, ACC_VITERBI, CPU_BIG,
                        CPU_LITTLE, CommModel, PE, ResourceDB, make_soc,
                        make_soc_table2)
from .schedulers import (ETFScheduler, METScheduler, SchedContext, Scheduler,
                         TableScheduler, available_schedulers, get_scheduler,
                         register_scheduler, solve_optimal_table)
from .simkernel_jax import SimTables, build_tables
from .simkernel_jax import simulate_jax as _simulate_jax_impl
from .simkernel_ref import SimResult, TaskRecord
from .simkernel_ref import simulate as _simulate_impl
from . import reports, thermal


simulate = _deprecated_entry_point(
    _simulate_impl,
    "repro.scenario.run(Scenario(...), backend='ref')")
simulate_jax = _deprecated_entry_point(
    _simulate_jax_impl,
    "repro.scenario.run(Scenario(...), backend='jax')")


__all__ = [n for n in dir() if not n.startswith("_")]
