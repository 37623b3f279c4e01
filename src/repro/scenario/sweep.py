"""``sweep(scenario, axes={...})`` — cross-product scenario batches.

Any combination of scenario axes — arrival rate × scheduler × design point ×
frequency cap × governor policy × seed — is expanded into one batch.  Axes
factorise into four kinds (see DESIGN.md §9–10):

* **design-affecting** (``design``, ``design.<field>``): each combination
  becomes a padded ``SimTables`` lane, reusing ``repro.dse.batch``'s
  inert-padding scheme (pad every design to the widest PE count, stack
  leaf-wise);
* **policy** (``governor``, ``governor_params``): static governors bake into
  the tables and behave like design axes; *dynamic* (ondemand-family)
  governors become stacked :class:`~repro.core.dvfs.GovernorPolicy` lanes
  vmapped through the closed-loop DTPM kernel — hundreds of policy
  parameterisations per compiled program, peak temperature from the inline
  RC loop;
* **trace-affecting** (``trace``, ``trace.<field>``, aliases ``rate`` /
  ``seed`` / ``jobs``): each combination becomes a stacked workload row;
* **faults** (``failures``, alias ``faults``): each value is one fail-stop
  fault set, stacked into ``(F, P)`` fail-time plans and vmapped (outermost,
  so the design axis stays streamable) through the fail-stop kernel — the
  axis adds ZERO compiles per policy shape, and all-no-op axes reuse the
  fault-free program outright (DESIGN.md §14);
* **static** (``scheduler``): a compile-time branch of the kernel — swept in
  an outer python loop, one compiled program per value.

For one scheduler the whole (designs × policies × traces) cross-product runs
as ONE vmapped/jitted tensor program per *policy shape* (static / dynamic) —
and every lane is bit-for-bit equal to a per-point ``run(..., backend="jax")``
(padding is inert; a vmap lane equals a single call; the RC stepper's
spectral e^{A·dt} keeps the thermal math batch-width independent).
``backend="ref"`` sweeps the same cross-product through the event-heap
oracle lane by lane.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core.dvfs import stack_policies
from ..core.jobgen import JobTrace
from ..dse.batch import pad_node_map, stack_tables, stack_traces
from ..dse.space import DesignPoint
from ..obs import metrics as _metrics
from ..obs import telemetry as _obs_tel
from . import faults as _faults
from . import shardexec
from .shardexec import (_sweep_grid, _sweep_grid_dtpm,
                        _sweep_grid_dtpm_faults, _sweep_grid_faults)
from .config import Scenario, TraceSpec
from .errors import BackendCapabilityError, LaneAxisError, ScenarioError
from .result import SweepResult
from .run import run, tables_for

AXIS_ALIASES = {
    "rate": "trace.rate_jobs_per_ms",
    "seed": "trace.seed",
    "jobs": "trace.num_jobs",
    "faults": "failures",
}

_DESIGN_FIELDS = {f.name for f in dataclasses.fields(DesignPoint)}
_TRACE_FIELDS = {f.name for f in dataclasses.fields(TraceSpec)}

# number of times a fused grid program has been traced (re-compiled); the
# one-program-per-policy-shape sweep contract is asserted against this.
# The registered obs counter IS the module attribute — read it via
# ``compile_count.value`` / the ``obs.metrics`` registry (DESIGN.md §11;
# the deprecated ``compile_count[0]`` list alias is gone).
compile_count = shardexec._compile_count


def _canon(name: str) -> str:
    return AXIS_ALIASES.get(name, name)


def _axis_kind(name: str) -> str:
    name = _canon(name)
    if name == "scheduler":
        return "static"
    if name == "failures":
        return "faults"
    if name in ("governor", "governor_params"):
        return "policy"
    if name == "design":
        return "design"
    if name.startswith("design."):
        field = name.split(".", 1)[1]
        if field not in _DESIGN_FIELDS:
            raise LaneAxisError(f"unknown design axis field {field!r}")
        return "design"
    if name == "trace":
        return "trace"
    if name.startswith("trace."):
        field = name.split(".", 1)[1]
        if field not in _TRACE_FIELDS:
            raise LaneAxisError(f"unknown trace axis field {field!r}")
        return "trace"
    raise LaneAxisError(
        f"unknown sweep axis {name!r}; use 'design', 'design.<field>', "
        f"'governor', 'governor_params', 'scheduler', 'trace', "
        f"'trace.<field>', 'failures' or aliases {sorted(AXIS_ALIASES)}")


def _apply_axes(scn: Scenario, names: Sequence[str],
                values: Sequence) -> Scenario:
    """Apply axis values to a scenario ('trace'-axis JobTraces excluded)."""
    for name, value in zip(names, values):
        name = _canon(name)
        if name == "trace" and isinstance(value, JobTrace):
            continue                       # materialised out-of-band
        scn = scn.replace(**{name: value})
    return scn


def _lane_trace(scn: Scenario, names: Sequence[str],
                values: Sequence) -> JobTrace:
    for name, value in zip(names, values):
        if _canon(name) == "trace" and isinstance(value, JobTrace):
            return value
    return scn.job_trace()


def _design_lanes(base: Scenario, design_axes: List[str],
                  combos: List[Tuple], pad_pes: Optional[int],
                  host: bool = False):
    """Padded+stacked tables and thermal-node map for the design lanes.

    ``host=True`` stacks numpy leaves (the chunked/sharded executor's
    streaming source — the full grid never becomes device-resident)."""
    scns = [_apply_axes(base, design_axes, c) for c in combos]
    dbs = [s.soc() for s in scns]
    P = max(db.num_pes for db in dbs)
    if pad_pes is not None:
        if pad_pes < P:
            raise ValueError(f"pad_pes={pad_pes} < widest design {P}")
        P = pad_pes
    tables = stack_tables([tables_for(s, pad_pes=P, host=host) for s in scns],
                          host=host)
    return tables, pad_node_map(dbs, P)


def sweep(scenario: Scenario, axes: Dict[str, Sequence],
          backend: str = "jax", pad_pes: Optional[int] = None,
          design_batch=None, telemetry: Optional[bool] = None,
          chunk: Optional[int] = None,
          shard: Optional[bool] = None) -> SweepResult:
    """Simulate the cross-product of ``axes`` around ``scenario``.

    ``axes`` maps axis names to value sequences; result arrays are shaped
    ``tuple(len(v) for v in axes.values())`` in dict order.  ``pad_pes``
    fixes the padded PE width (jit-cache stability across design mixes);
    ``design_batch`` (a prebuilt ``repro.dse.DesignBatch``) short-circuits
    table construction when the caller already stacked the design axis —
    it must correspond to a single ``"design"`` axis with matching points.

    ``telemetry`` (default: ``scenario.telemetry``) fills
    ``SweepResult.telemetry`` with one per-window
    :class:`~repro.obs.telemetry.Telemetry` per lane (an object array shaped
    like the axes).  On the jax backend the lanes' timelines are replayed
    from the already-computed grid outputs through the kernels' jitted
    telemetry scans — the simulations are not re-run (DESIGN.md §11).

    ``chunk``/``shard`` scale the design/policy lane axis (jax backend only,
    DESIGN.md §13): ``shard`` splits the lanes across the local devices via
    a ``NamedSharding`` over ``repro.sharding.lane_mesh()`` (default
    ``None`` = auto — shard exactly when more than one device is present;
    ``False`` pins the single-device path); ``chunk=N`` streams the lanes
    through ONE compiled program in fixed-shape N-lane chunks, bounding peak device memory at O(chunk) instead of
    O(grid).  Both are bit-for-bit equal to the unsharded sweep — lanes are
    independent, and uneven lane counts are padded with inert (dropped)
    lanes — and neither adds compiles per policy shape.

    The whole call is the host span ``repro.sweep`` (DESIGN.md §11).
    """
    with _metrics.span("repro.sweep"):
        return _sweep(scenario, axes, backend, pad_pes, design_batch,
                      telemetry, chunk, shard)


def _sweep(scenario: Scenario, axes: Dict[str, Sequence], backend: str,
           pad_pes: Optional[int], design_batch, telemetry: Optional[bool],
           chunk: Optional[int], shard: Optional[bool]) -> SweepResult:
    if not axes:
        raise ValueError("axes must name at least one swept dimension")
    if chunk is not None and (not isinstance(chunk, int) or chunk < 1):
        raise ValueError(f"chunk must be a positive lane count, got {chunk!r}")
    names = list(axes)
    values = {n: tuple(axes[n]) for n in names}
    if any(len(v) == 0 for v in values.values()):
        raise ValueError("every sweep axis needs at least one value")
    canon = [_canon(n) for n in names]
    if len(set(canon)) != len(canon):
        dups = sorted({c for c in canon if canon.count(c) > 1})
        raise ValueError(
            f"duplicate sweep axes after alias resolution: {dups} "
            f"(e.g. 'seed' and 'trace.seed' name the same field)")
    kinds = {n: _axis_kind(n) for n in names}
    static_axes = [n for n in names if kinds[n] == "static"]
    design_axes = [n for n in names if kinds[n] == "design"]
    policy_axes = [n for n in names if kinds[n] == "policy"]
    trace_axes = [n for n in names if kinds[n] == "trace"]
    # a whole-object axis would silently overwrite per-field axes of the
    # same object (duplicated lanes, no error) — reject the combination
    for whole in ("trace", "design"):
        fields = [n for n in names if _canon(n).startswith(whole + ".")]
        if whole in canon and fields:
            raise ValueError(
                f"axis '{whole}' conflicts with per-field axes {fields}: "
                f"a whole-'{whole}' value replaces the fields those axes set")

    want_tel = scenario.telemetry if telemetry is None else bool(telemetry)
    if backend == "ref":
        if chunk is not None or shard:
            raise BackendCapabilityError(
                "jax-backend lane options (chunk/shard)", "ref",
                "backend='jax'",
                detail="the ref backend runs lane by lane already")
        return _sweep_ref(scenario, names, values, want_tel)
    if backend != "jax":
        raise ScenarioError(f"unknown backend {backend!r}; have "
                            f"('ref', 'jax')")
    mesh = shardexec.resolve_mesh(shard)
    lane_exec = chunk is not None or mesh is not None

    # fault lanes: every value of a 'faults'/'failures' axis is one fault
    # set; with no such axis the base scenario's failures apply to all lanes
    fault_axes = [n for n in names if kinds[n] == "faults"]
    fault_sets = ([_faults.normalize_failures(v)
                   for v in values[fault_axes[0]]] if fault_axes
                  else [scenario.failures])
    have_faults = any(not f.is_noop for fs in fault_sets for f in fs)

    # classify the governor lanes by policy shape: static governors bake
    # into the tables (design-kind lanes), the dynamic ondemand family
    # becomes vmapped GovernorPolicy lanes through the DTPM kernel
    policy_combos = list(itertools.product(
        *(values[n] for n in policy_axes))) or [()]
    pol_scns = [_apply_axes(scenario, policy_axes, c) for c in policy_combos]
    policies = [s.make_policy() for s in pol_scns]
    dyn_flags = {p.dynamic for p in policies}
    if len(dyn_flags) > 1:
        raise LaneAxisError(
            "a sweep cannot mix static and dynamic (ondemand-family) "
            "governors in one batch — they compile to different policy "
            "shapes; split the sweep per governor kind (DESIGN.md §10)")
    dynamic = dyn_flags.pop()
    if not dynamic:
        design_axes = design_axes + policy_axes   # baked into table lanes
        policy_axes = []
    if have_faults and dynamic and want_tel:
        raise BackendCapabilityError(
            "telemetry with faults under a dynamic governor", "jax",
            "backend='ref' (it records sampling windows in-loop)",
            detail="fail-stop rollback breaks the window-closure invariant "
                   "the post-hoc replay assumes")

    static_combos = list(itertools.product(
        *(values[n] for n in static_axes))) or [()]
    design_combos = list(itertools.product(
        *(values[n] for n in design_axes))) or [()]
    trace_combos = list(itertools.product(
        *(values[n] for n in trace_axes))) or [()]

    # workloads: one stacked (S, J) pair shared by every design lane
    with _metrics.span("repro.stage"):
        t_scns = [_apply_axes(scenario, trace_axes, c) for c in trace_combos]
        traces = [_lane_trace(s, trace_axes, c)
                  for s, c in zip(t_scns, trace_combos)]
        job_counts = {t.num_jobs for t in traces}
        if len(job_counts) > 1:
            raise LaneAxisError(
                f"the jax backend needs equal job counts per lane to stack "
                f"one (S, J) workload tensor, got {sorted(job_counts)}; "
                f"sweep the 'jobs' axis with backend='ref' instead")
        arrival, app_idx = stack_traces(traces)
        num_jobs = int(arrival.shape[1])

    # design-lane base: dynamic tables carry the OPP ladders, so the (first)
    # dynamic governor must be applied before tables are built; every dynamic
    # parameterisation shares the same tables (run._tables_key collapses them)
    lane_base = pol_scns[0] if dynamic else scenario

    if design_batch is not None:
        if design_axes != ["design"] or tuple(
                values["design"]) != design_batch.points:
            raise ValueError("design_batch requires a single 'design' axis "
                             "matching design_batch.points")
        if dynamic:
            if design_batch.tables.exec_opp is None:
                raise ValueError(
                    "design_batch tables lack the OPP ladders a dynamic "
                    "governor needs; build them with "
                    "build_design_batch(..., governor=<dynamic governor>)")
        elif design_batch.tables.exec_opp is not None:
            # dynamic-built tables bake exec_us at the ondemand initial
            # (fmin) OPP — running the static kernel on them would silently
            # break the per-point run() equivalence contract
            raise ValueError(
                "design_batch was built for a dynamic governor; a static "
                "sweep needs build_design_batch(...) without one")
        elif scenario.governor != "design":
            # build_design_batch bakes each point's frequency-cap governor
            # into the tables; any other governor would silently diverge
            # from the per-point run() equivalence contract
            raise ValueError("design_batch tables pin the design frequency "
                             "caps; the scenario must use governor='design'")
        if int(design_batch.tables.exec_us.shape[1]) \
                != len(scenario.applications()):
            raise ValueError("design_batch was built for a different "
                             "application list than the scenario's")
        tables, node_of_pe = design_batch.tables, design_batch.node_of_pe

    # tables depend on the static (scheduler) axis only through the offline
    # ILP table — hoist the (D, …) stack out of the loop unless a swept
    # combo actually selects the "table" policy
    any_table = any(
        _apply_axes(lane_base, static_axes, sc).scheduler == "table"
        for sc in static_combos)
    if have_faults and any_table:
        raise BackendCapabilityError(
            "fail-stop injection with the 'table' scheduler", "jax",
            "backend='ref'",
            detail="the offline ILP table pins tasks to PEs, so dead-PE "
                   "fallback needs the runtime schedulers (met/etf)")
    rebuild_per_combo = design_batch is None and any_table
    with _metrics.span("repro.stage"):
        if design_batch is None and not rebuild_per_combo:
            tables, node_of_pe = _design_lanes(lane_base, design_axes,
                                               design_combos, pad_pes,
                                               host=lane_exec)

        gov_stack = stack_policies(policies) if dynamic else None

        # stacked (F, P) fault plans: pe_ids validate against the narrowest
        # design lane; plans are emitted at the padded PE width.  All-noop
        # lanes leave plans=None — the sweep then runs the exact fault-free
        # program (zero extra compiles) and tiles its results over the
        # fault axis.
        plans, scan_steps = None, None
        if have_faults:
            min_pes = min(
                _apply_axes(lane_base, design_axes, c).design.num_pes
                for c in design_combos)
            plans, max_f = _faults.stack_fault_plans(
                fault_sets, min_pes, width=int(tables.num_pes))
            scan_steps = _faults.fault_scan_steps(
                num_jobs, int(tables.t_max), max_f)

    per_static = []
    for sc in static_combos:
        s_scn = _apply_axes(lane_base, static_axes, sc)
        if rebuild_per_combo:
            with _metrics.span("repro.stage"):
                tables, node_of_pe = _design_lanes(s_scn, design_axes,
                                                   design_combos, pad_pes,
                                                   host=lane_exec)
        if dynamic:
            if plans is not None:
                if lane_exec:
                    out = shardexec.run_dtpm_grid(
                        tables, gov_stack, arrival, app_idx,
                        policy=s_scn.scheduler, num_jobs=num_jobs,
                        chunk=chunk, mesh=mesh, fplans=plans,
                        scan_steps=scan_steps)
                else:
                    with _metrics.span("repro.launch"):
                        out = _sweep_grid_dtpm_faults(
                            tables, gov_stack, plans, arrival, app_idx,
                            policy=s_scn.scheduler, num_jobs=num_jobs,
                            scan_steps=scan_steps)
            elif lane_exec:
                out = shardexec.run_dtpm_grid(tables, gov_stack, arrival,
                                              app_idx,
                                              policy=s_scn.scheduler,
                                              num_jobs=num_jobs,
                                              chunk=chunk, mesh=mesh)
            else:
                with _metrics.span("repro.launch"):
                    out = _sweep_grid_dtpm(tables, gov_stack, arrival,
                                           app_idx, policy=s_scn.scheduler,
                                           num_jobs=num_jobs)
            temps = out["peak_temp_c"]
        else:
            if plans is not None:
                if lane_exec:
                    out, temps = shardexec.run_static_grid(
                        tables, node_of_pe, arrival, app_idx,
                        policy=s_scn.scheduler, num_jobs=num_jobs,
                        bins=s_scn.thermal.bins,
                        repeats=s_scn.thermal.repeats,
                        chunk=chunk, mesh=mesh, fplans=plans,
                        scan_steps=scan_steps)
                else:
                    with _metrics.span("repro.launch"):
                        out, temps = _sweep_grid_faults(
                            tables, node_of_pe, plans, arrival, app_idx,
                            policy=s_scn.scheduler, num_jobs=num_jobs,
                            bins=s_scn.thermal.bins,
                            repeats=s_scn.thermal.repeats,
                            scan_steps=scan_steps)
            elif lane_exec:
                out, temps = shardexec.run_static_grid(
                    tables, node_of_pe, arrival, app_idx,
                    policy=s_scn.scheduler, num_jobs=num_jobs,
                    bins=s_scn.thermal.bins, repeats=s_scn.thermal.repeats,
                    chunk=chunk, mesh=mesh)
            else:
                with _metrics.span("repro.launch"):
                    out, temps = _sweep_grid(tables, node_of_pe, arrival,
                                             app_idx, policy=s_scn.scheduler,
                                             num_jobs=num_jobs,
                                             bins=s_scn.thermal.bins,
                                             repeats=s_scn.thermal.repeats)
        with _metrics.span("repro.wait"):
            jax.block_until_ready((out, temps))
        with _metrics.span("repro.assemble"):
            if plans is not None and not fault_axes:
                # base-scenario faults, no fault axis: drop the F=1 lane axis
                # so the grid keeps its fault-free shape
                out = {k: v[0] for k, v in out.items()}
                temps = temps[0]
            entry = dict(
                avg_latency_us=np.asarray(out["avg_job_latency_us"],
                                          np.float64),
                makespan_us=np.asarray(out["makespan_us"], np.float64),
                energy_j=np.asarray(out["energy_j"], np.float64),
                peak_temp_c=np.asarray(temps, np.float64),
                busy_per_pe_us=np.asarray(out["busy_per_pe_us"], np.float64))
        if want_tel:
            entry["telemetry"] = _telemetry_grid(
                s_scn, design_axes, design_combos, policies, tables,
                app_idx, out, dynamic,
                num_faults=(len(fault_sets)
                            if fault_axes and plans is not None else 0))
        if fault_axes and plans is None:
            # every fault lane is a no-op: the fault-free program ran once
            # (the §14 no-op contract — zero extra compiles) and its results
            # tile verbatim across the fault axis
            with _metrics.span("repro.assemble"):
                entry = {k: np.repeat(v[None], len(fault_sets), axis=0)
                         for k, v in entry.items()}
        per_static.append(entry)

    # assemble: (static..., faults..., design..., policy..., trace..., extra)
    # then the user's axes-dict order
    d_lens = [len(values[n]) for n in design_axes]
    p_lens = [len(values[n]) for n in policy_axes]
    t_lens = [len(values[n]) for n in trace_axes]
    s_lens = [len(values[n]) for n in static_axes]
    f_lens = [len(values[n]) for n in fault_axes]
    internal = static_axes + fault_axes + design_axes + policy_axes \
        + trace_axes
    perm = [internal.index(n) for n in names]
    # (Σstatic[, F], D[, G], S)
    grid_ndim = (4 if dynamic else 3) + (1 if fault_axes else 0)

    def _assemble(key: str) -> np.ndarray:
        stacked = np.stack([g[key] for g in per_static])
        extra = stacked.shape[grid_ndim:]
        arr = stacked.reshape(*s_lens, *f_lens, *d_lens, *p_lens, *t_lens,
                              *extra)
        k = len(internal)
        return np.transpose(arr, axes=perm + list(range(k, arr.ndim)))

    with _metrics.span("repro.assemble"):
        makespan = _assemble("makespan_us")
        return SweepResult(
            base=scenario, backend="jax", axes=values,
            avg_latency_us=_assemble("avg_latency_us"),
            throughput_jobs_per_ms=num_jobs / np.maximum(makespan, 1e-9)
            * 1e3,
            makespan_us=makespan, energy_j=_assemble("energy_j"),
            peak_temp_c=_assemble("peak_temp_c"),
            busy_per_pe_us=_assemble("busy_per_pe_us"),
            telemetry=_assemble("telemetry") if want_tel else None)


def _telemetry_grid(s_scn: Scenario, design_axes: List[str],
                    design_combos: List[Tuple], policies, tables,
                    app_idx, out, dynamic: bool,
                    num_faults: int = 0) -> np.ndarray:
    """Per-lane :class:`Telemetry` objects for one static combo, as an
    object array shaped like the internal grid ((D, G, S) dynamic,
    (D, S) static).  Each lane slices the stacked tables (leaf-wise) and the
    grid outputs, then replays the kernel's jitted telemetry scan — the
    simulation itself is not re-run.  ``num_faults > 0`` (static governors
    only — faulted dynamic telemetry is rejected upstream) prepends the
    fault-lane axis: the replay runs per fault lane on that lane's final
    schedule, so dead PEs show zero utilisation past their fail time."""
    if num_faults:
        return np.stack([
            _telemetry_grid(s_scn, design_axes, design_combos, policies,
                            tables, app_idx,
                            {k: v[f] for k, v in out.items()}, dynamic)
            for f in range(num_faults)])
    keys = ("scheduled", "start", "finish", "onpe", "makespan_us")
    D = len(design_combos)
    S = int(np.asarray(app_idx).shape[0])
    if dynamic:
        G = len(policies)
        grid = np.empty((D, G, S), object)
        for d in range(D):
            tb = jax.tree_util.tree_map(lambda x, _d=d: x[_d], tables)
            for g in range(G):
                for s in range(S):
                    out_l = {k: out[k][d, g, s] for k in keys + ("onopp",)}
                    grid[d, g, s] = _obs_tel.jax_dtpm_telemetry(
                        tb, policies[g], out_l, app_idx[s])
        return grid
    grid = np.empty((D, S), object)
    for d in range(D):
        tb = jax.tree_util.tree_map(lambda x, _d=d: x[_d], tables)
        lane_scn = _apply_axes(s_scn, design_axes, design_combos[d])
        db, gov = lane_scn.soc(), lane_scn.make_governor()
        for s in range(S):
            out_l = {k: out[k][d, s] for k in keys}
            grid[d, s] = _obs_tel.jax_static_telemetry(db, gov, tb, out_l,
                                                       app_idx[s])
    return grid


def _sweep_ref(scenario: Scenario, names: List[str],
               values: Dict[str, Tuple],
               want_tel: bool = False) -> SweepResult:
    """Cross-product sweep through the reference kernel, lane by lane."""
    shape = tuple(len(values[n]) for n in names)
    lanes = list(itertools.product(*(values[n] for n in names)))
    results = []
    for combo in lanes:
        scn = _apply_axes(scenario, names, combo)
        trace = _lane_trace(scn, names, combo)
        results.append(run(scn, backend="ref", trace_override=trace,
                           telemetry=want_tel))
    P = max(r.utilization.shape[0] for r in results)
    busy = np.zeros((len(lanes), P), np.float64)
    for i, r in enumerate(results):
        busy[i, :r.utilization.shape[0]] = r.utilization * r.makespan_us

    def _arr(field):
        return np.asarray([getattr(r, field) for r in results],
                          np.float64).reshape(shape)

    tel = None
    if want_tel:
        tel = np.empty(len(lanes), object)
        tel[:] = [r.telemetry for r in results]
        tel = tel.reshape(shape)
    return SweepResult(
        base=scenario, backend="ref", axes=values,
        avg_latency_us=_arr("avg_latency_us"),
        throughput_jobs_per_ms=_arr("throughput_jobs_per_ms"),
        makespan_us=_arr("makespan_us"), energy_j=_arr("energy_j"),
        peak_temp_c=_arr("peak_temp_c"),
        busy_per_pe_us=busy.reshape(*shape, P),
        telemetry=tel)
