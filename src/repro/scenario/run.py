"""``run(scenario, backend=...)`` — one scenario, either kernel.

The facade is a thin, bit-for-bit delegate: ``backend="ref"`` materialises
the scenario and calls the event-heap oracle exactly as
``repro.core.simulate`` always has; ``backend="jax"`` builds the same
``SimTables`` the legacy ``build_tables`` + ``simulate_jax`` pair would and
runs the unchanged kernel (the equivalence contract is tested in
``tests/test_scenario.py``).  Tables are cached on the (frozen, hashable)
scenario minus its trace, so repeated runs over different workloads reuse
the compiled program and device constants.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import numpy as np

from ..core import simkernel_jax as _jaxk
from ..core import simkernel_ref as _refk
from ..core.simkernel_jax import SimTables
from ..core.thermal import cluster_nodes
from ..dse import thermal_jax as _thermal_jax
from ..obs import metrics as _metrics
from ..obs import telemetry as _obs_tel
from . import faults as _faults
from .config import Scenario, ThermalSpec, TraceSpec
from .errors import BackendCapabilityError, ScenarioError
from .result import Result

BACKENDS = ("ref", "jax")


def _tables_key(scn: Scenario) -> Scenario:
    """Strip table-irrelevant fields so different workloads share tables.

    The scheduler only shapes tables through the offline ILP table, so all
    non-"table" policies collapse to one cache entry per design/governor.
    Dynamic (ondemand-family) governors collapse further: their OPP ladders
    depend on the design and applications alone, so every policy
    parameterisation shares one table set.
    """
    scheduler = scn.scheduler if scn.scheduler == "table" else "etf"
    key = dataclasses.replace(scn, trace=TraceSpec(), failures=(),
                              thermal=ThermalSpec(), scheduler=scheduler,
                              telemetry=False)
    if key.make_policy().dynamic:
        key = dataclasses.replace(key, governor="ondemand",
                                  governor_params=())
    return key


@functools.lru_cache(maxsize=256)
def _cached_tables(key: Scenario, pad_pes: Optional[int]) -> SimTables:
    db = key.soc()
    return _jaxk.build_tables(db, key.applications(),
                              governor=key.make_governor(),
                              table=key.schedule_table(), pad_pes=pad_pes)


@functools.lru_cache(maxsize=256)
def _cached_tables_host(key: Scenario, pad_pes: Optional[int]) -> SimTables:
    """Host-resident (numpy-leaf) twin of :func:`_cached_tables`: built
    fresh (not via the device cache) so only one design's device arrays are
    ever live during construction — the chunked sweep's streaming source."""
    db = key.soc()
    tb = _jaxk.build_tables(db, key.applications(),
                            governor=key.make_governor(),
                            table=key.schedule_table(), pad_pes=pad_pes)
    return jax.tree_util.tree_map(np.asarray, tb)


def tables_for(scn: Scenario, pad_pes: Optional[int] = None,
               host: bool = False) -> SimTables:
    """The scenario's ``SimTables`` (identical to the legacy ``build_tables``
    call), cached across traces/thermal settings.  ``host=True`` returns the
    numpy-leaf form the chunked/sharded sweep executor streams from
    (DESIGN.md §13)."""
    if host:
        return _cached_tables_host(_tables_key(scn), pad_pes)
    return _cached_tables(_tables_key(scn), pad_pes)


@functools.lru_cache(maxsize=256)
def _cached_nodes(design) -> np.ndarray:
    """Thermal node per PE for a design (depends on the design alone)."""
    return np.asarray(cluster_nodes(design.to_db()), np.int32)


@functools.partial(jax.jit, static_argnames=("bins", "repeats"))
def _peak_temp_single(start, finish, onpe, scheduled, nodes, p_act, p_idle,
                      makespan, bins, repeats):
    """One schedule's RC peak temperature (jitted; compiles per shape)."""
    power_trace, dt_s = _thermal_jax.binned_power_trace(
        start, finish, onpe, scheduled, nodes, p_act, p_idle, makespan,
        bins=bins)
    return _thermal_jax.peak_temperature(power_trace, dt_s, repeats=repeats)


def run(scenario: Scenario, backend: str = "ref", *,
        trace_override=None, telemetry: Optional[bool] = None) -> Result:
    """Simulate one scenario.

    ``backend="ref"``: the event-heap reference kernel — all governors and
    fail-stop injection supported.  ``backend="jax"``: the vectorised kernel
    — every governor, static or dynamic: static governors bake one OPP into
    the tables and report the binned RC co-simulation's peak temperature;
    the ondemand family runs the closed DTPM loop inside the epoch scan and
    reports the peak temperature of its inline RC feedback (DESIGN.md §7).
    Both kernels honour fail-stop ``scenario.failures`` bit-for-bit on
    comm-free traces (DESIGN.md §14); the jax backend needs a runtime
    scheduler (met/etf) for graceful degradation and defers to ``ref`` for
    in-loop telemetry under dynamic-governor faults.  Both return the same
    :class:`Result` surface, carrying an ``obs.metrics`` run manifest.

    ``trace_override``: a pre-materialised ``JobTrace`` replacing the
    scenario's trace spec (plumbing for ``sweep`` axes that carry explicit
    traces).

    ``telemetry`` (default: ``scenario.telemetry``): also record per-window
    (W, C) frequency/utilisation/power/temperature timelines on
    ``Result.telemetry`` (DESIGN.md §11).  Observation-only: with a dynamic
    governor the ref kernel records its sampling windows in-loop and the jax
    backend replays the kernel's window carry as a separate jitted scan —
    the simulation program and its outputs are identical either way
    (asserted in tests/test_obs.py).

    The whole call is the host span ``repro.run`` (DESIGN.md §11).
    """
    with _metrics.span("repro.run"):
        return _run(scenario, backend, trace_override, telemetry)


def _run(scenario: Scenario, backend: str, trace_override,
         telemetry: Optional[bool]) -> Result:
    want_tel = scenario.telemetry if telemetry is None else bool(telemetry)

    if backend == "ref":
        db = scenario.soc()
        pol = scenario.make_policy()
        governor = scenario.make_governor()
        rec = None
        if want_tel and pol.dynamic:
            rec = _obs_tel.TelemetryRecorder(pol.sample_window_us)
        res = _refk.simulate(db, scenario.applications(),
                             trace_override or scenario.job_trace(),
                             scenario.make_scheduler(), governor,
                             failures=_faults.ref_failures(scenario.failures),
                             telemetry=rec)
        tel = None
        if want_tel:
            tel = (rec.build(_obs_tel.domain_count(db)) if rec is not None
                   else _obs_tel.ref_static_telemetry(db, res, governor))
        result = Result.from_ref(scenario, db, res, telemetry=tel)

    elif backend == "jax":
        with _metrics.span("repro.stage"):
            # no-op fault specs (empty / all-inf) normalise to plan=None
            # here, so they take the exact fault-free program — same trace,
            # same cache key (the §14 no-op contract, asserted via
            # sweep.compile_count in tests).
            plan = _faults.fault_plan(scenario.failures,
                                      scenario.design.num_pes)
            if plan is not None and scenario.scheduler == "table":
                raise BackendCapabilityError(
                    "fail-stop injection with the 'table' scheduler", "jax",
                    "backend='ref'",
                    detail="the offline ILP table pins tasks to PEs, so "
                           "dead-PE fallback needs the runtime schedulers "
                           "(met/etf)")
            tables = tables_for(scenario)
            trace = trace_override or scenario.job_trace()
            pol = scenario.make_policy()
        if pol.dynamic:
            if plan is not None and want_tel:
                raise BackendCapabilityError(
                    "telemetry with faults under a dynamic governor", "jax",
                    "backend='ref' (it records sampling windows in-loop)",
                    detail="fail-stop rollback breaks the window-closure "
                           "invariant the post-hoc replay assumes")
            out = _jaxk.simulate_jax_dtpm(tables, scenario.scheduler,
                                          trace.arrival_us, trace.app_index,
                                          pol, faults=plan)
            with _metrics.span("repro.wait"):
                jax.block_until_ready(out)
            tel = (_obs_tel.jax_dtpm_telemetry(tables, pol, out,
                                               trace.app_index)
                   if want_tel else None)
            with _metrics.span("repro.assemble"):
                result = Result.from_jax(scenario, out,
                                         scenario.design.num_pes,
                                         float(out["peak_temp_c"]),
                                         telemetry=tel)
        else:
            out = _jaxk.simulate_jax(tables, scenario.scheduler,
                                     trace.arrival_us, trace.app_index,
                                     faults=plan)
            with _metrics.span("repro.launch"):
                peak = _peak_temp_single(
                    out["start"], out["finish"], out["onpe"],
                    out["scheduled"], _cached_nodes(scenario.design),
                    tables.power_active, tables.power_idle,
                    out["makespan_us"], bins=scenario.thermal.bins,
                    repeats=scenario.thermal.repeats)
            with _metrics.span("repro.wait"):
                jax.block_until_ready((out, peak))
            tel = (_obs_tel.jax_static_telemetry(
                       scenario.soc(), scenario.make_governor(), tables, out,
                       trace.app_index)
                   if want_tel else None)
            with _metrics.span("repro.assemble"):
                result = Result.from_jax(scenario, out,
                                         scenario.design.num_pes,
                                         float(peak), telemetry=tel)
    else:
        raise ScenarioError(f"unknown backend {backend!r}; have {BACKENDS}")

    with _metrics.span("repro.assemble"):
        result.manifest = _metrics.run_manifest(scenario=scenario,
                                                backend=backend)
    return result
