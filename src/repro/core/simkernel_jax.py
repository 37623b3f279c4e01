"""Vectorised JAX simulation kernel — batched design-space exploration.

The paper's speed story (system-level simulation ~600× faster than cycle
accurate gem5) is re-thought for accelerators: instead of making *one*
event-heap simulation fast, the whole simulator becomes a fixed-shape tensor
program (an epoch-based ``lax.scan`` + masked argmin selects) so that
**thousands of simulations — seeds × injection rates × SoC configs ×
schedulers × DTPM policies — run batched under ``vmap``/``jit``**.

Semantics are identical to ``simkernel_ref`` (same epoch ordering, same
tie-breaking, float32 arithmetic): the two kernels are cross-validated in
``tests/test_sim_equivalence.py`` and ``tests/test_dtpm.py``.

Supported here: MET / ETF / table schedulers with *static* DVFS governors
(performance / powersave / userspace — one OPP baked into the tables) **and
dynamic DTPM policies** (the ondemand family): the epoch scan closes the
DVFS loop inside the compiled program — each sampling window gathers
per-cluster utilisation, applies the shared :func:`~repro.core.dvfs.
ondemand_index` transition, advances the §6 RC thermal network by its exact
update and clamps clusters above the thermal cap — and execution latency is
re-indexed from a precomputed (A, T, P, K) OPP table, so ``exec_us`` stays a
gather, never a re-profile (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .applications import Application
from .dvfs import (Governor, GovernorPolicy, MAX_OPP_LEVELS,
                   PerformanceGovernor, ondemand_index, padded_ladder,
                   throttle_index, validate_policy_params)
from .power import active_power, idle_power
from .resources import INF, NOMINAL_FREQ, ResourceDB
from . import thermal as _thermal
from ..obs.metrics import counter as _obs_counter
from ..obs.metrics import span as _obs_span

BIG = jnp.float32(1e30)

# jit-trace counters (the python bodies below run only on compile): the run
# manifest reports them, tests assert the telemetry path never re-traces the
# simulation programs (DESIGN.md §11)
_COMPILES_STATIC = _obs_counter("kernel.jax.simulate.compile_count")
_COMPILES_DTPM = _obs_counter("kernel.jax.simulate_dtpm.compile_count")
_COMPILES_TELEMETRY = _obs_counter("obs.telemetry.scan.compile_count")
# the DAG the most recently built tables hold: its edges (summed over the
# apps) and its largest in-degree — the corner turn's width (K_in)
_EDGES = _obs_counter("sim.tables.edges")
_MAX_IN_DEGREE = _obs_counter("sim.tables.max_in_degree")
# epoch-scan steps launched, summed over lanes (J·T per lane, or the
# fail-stop bound): the sequential work a call asks of the device
_SCAN_STEPS = _obs_counter("sim.scan.steps")


def count_scan_steps(lanes: int, num_jobs: int, t_max: int,
                     scan_steps: Optional[int] = None) -> None:
    """Add one launch's scan steps (``lanes`` × its static scan length) to
    ``sim.scan.steps``."""
    _SCAN_STEPS.inc(int(lanes) * (num_jobs * t_max if scan_steps is None
                                  else int(scan_steps)))

# Frequency domains: one per SoC cluster; make_soc uses 0=big, 1=LITTLE,
# 2=accelerator fabric.  Padded PE slots map to the last (accel) domain,
# which never moves (one OPP level) and carries zero power — inert.
MIN_DOMAINS = 3


# --------------------------------------------------------------------------
# Static tables (per (db, apps, governor) triple: built on the host, placed
# on the device as constants)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SimTables:
    exec_us: jnp.ndarray        # (A, T, P) f32 — DVFS-scaled latency, BIG=unsupported
    pred_idx: jnp.ndarray       # (A, T, K_in) i32 predecessor ids, -1 = none
    pred_bytes: jnp.ndarray     # (A, T, K_in) f32 bytes on each pred edge
    succ_idx: jnp.ndarray       # (A, T, K_out) i32 successor ids, -1 = none
    valid: jnp.ndarray          # (A, T) bool
    comm_mult: jnp.ndarray      # (P, P) f32 in {0,1,penalty}
    comm_startup: jnp.ndarray   # () f32
    comm_inv_bw: jnp.ndarray    # () f32
    power_active: jnp.ndarray   # (P,) f32  W while busy
    power_idle: jnp.ndarray     # (P,) f32  W while idle
    table_pe: jnp.ndarray       # (A, T) i32 — table-scheduler assignment (or -1)
    node_of_pe: jnp.ndarray     # (P,) i32 thermal node per PE slot
    pe_domain: jnp.ndarray      # (P,) i32 frequency domain (cluster) per slot
    pe_is_cpu: jnp.ndarray      # (P,) f32 1.0 = CPU slot (counts in util)
    # DTPM-only OPP tables (None for static-governor tables):
    exec_opp: Optional[jnp.ndarray] = None          # (A, T, P, K) f32
    power_active_opp: Optional[jnp.ndarray] = None  # (P, K) f32
    opp_freq: Optional[jnp.ndarray] = None          # (C, K) f32 asc, top-padded
    num_opp: Optional[jnp.ndarray] = None           # (C,) i32 real level count
    domain_node: Optional[jnp.ndarray] = None       # (C,) i32 thermal node
    domain_cpu: Optional[jnp.ndarray] = None        # (C,) f32 CPU PEs per domain
    t_max: int = 0
    num_pes: int = 0
    depth: int = 0              # edges on the longest path of any app's DAG


jax.tree_util.register_dataclass(
    SimTables,
    data_fields=["exec_us", "pred_idx", "pred_bytes", "succ_idx", "valid",
                 "comm_mult",
                 "comm_startup", "comm_inv_bw", "power_active", "power_idle",
                 "table_pe", "node_of_pe", "pe_domain", "pe_is_cpu",
                 "exec_opp", "power_active_opp", "opp_freq", "num_opp",
                 "domain_node", "domain_cpu"],
    meta_fields=["t_max", "num_pes", "depth"],
)


def build_tables(db: ResourceDB, apps: Sequence[Application],
                 governor: Optional[Governor] = None,
                 table: Optional[Dict[Tuple[str, int], int]] = None,
                 pad_tasks: Optional[int] = None,
                 pad_pes: Optional[int] = None,
                 freq_caps: Optional[Mapping[str, float]] = None) -> SimTables:
    """Build device-resident simulation tables for one SoC design: the
    :func:`build_tables_host` tables placed with one ``jax.device_put``."""
    return jax.device_put(build_tables_host(
        db, apps, governor=governor, table=table, pad_tasks=pad_tasks,
        pad_pes=pad_pes, freq_caps=freq_caps))


def build_tables_host(db: ResourceDB, apps: Sequence[Application],
                      governor: Optional[Governor] = None,
                      table: Optional[Dict[Tuple[str, int], int]] = None,
                      pad_tasks: Optional[int] = None,
                      pad_pes: Optional[int] = None,
                      freq_caps: Optional[Mapping[str, float]] = None
                      ) -> SimTables:
    """Build one SoC design's simulation tables with numpy leaves: the
    one-design case of :func:`build_tables_batch_host`.

    ``pad_tasks`` / ``pad_pes`` pad the task and PE axes to a fixed size so
    tables from *different* designs stack into one (D, …) batch (see
    ``repro.dse.batch``).  Padding is inert by construction: padded task rows
    are invalid (pre-scheduled), padded PE columns carry BIG latency (never
    win an argmin) and zero active/idle power (no energy contribution).

    A *dynamic* governor (``governor.policy().dynamic``) additionally builds
    the OPP-indexed tables the DTPM kernel gathers from: per-level execution
    latency ``exec_opp``, per-level active power, and the per-domain OPP
    frequency ladders.  ``freq_caps`` (pe_type → max GHz) truncates each
    ladder — the design's hardware envelope; it defaults to the governor's
    own ``freq_caps`` (attached by ``Scenario.make_governor`` from the
    design point), keeping ref and jax on the same capped OPP set.
    ``table`` ((app name, task) → PE slot) fills ``table_pe`` for the
    table scheduler.
    """
    governor = governor or PerformanceGovernor()
    if freq_caps is None:
        freq_caps = getattr(governor, "freq_caps", None)
    batch, _ = build_tables_batch_host([db], apps, [governor], [freq_caps],
                                       pad_tasks=pad_tasks, pad_pes=pad_pes)
    tables = jax.tree_util.tree_map(lambda x: x[0, ...], batch)
    if table is not None:
        for ai, app in enumerate(apps):
            for t in range(app.num_tasks):
                tables.table_pe[ai, t] = table.get((app.name, t), -1)
    return tables


def build_tables_batch_host(
        dbs: Sequence[ResourceDB], apps: Sequence[Application],
        governors: Sequence[Governor],
        freq_caps: Optional[Sequence[Optional[Mapping[str, float]]]] = None,
        pad_tasks: Optional[int] = None, pad_pes: Optional[int] = None
        ) -> Tuple[SimTables, int]:
    """Simulation tables of D designs with numpy leaves of a leading (D, …)
    axis, and the number of distinct rows filled.

    Design ``d`` runs ``governors[d]``; ``freq_caps[d]`` truncates its OPP
    ladders under a dynamic governor (default: the governor's own
    ``freq_caps``).  Every design pads to the widest one (or ``pad_pes``).

    No per-PE value depends on the design as such: a slot's latency and
    power rows depend on its PE type and cluster frequency (and, under a
    dynamic governor, its capped OPP ladder), given the profiles.  So the
    designs' slots are first keyed into (D, P) index arrays; one row is
    filled per distinct key — keyed by profile *content*, as every design
    holds its own copy — and the (D, …) leaves are numpy gathers of those
    rows, plus a trailing inert row for padded slots.  Each leaf equals
    the stack of the designs' one-at-a-time tables bit for bit: a row is
    the same scalar arithmetic, f32(base) · f32(nominal / f), whichever
    slot it lands in.  Dependency lists depend only on ``apps`` and are
    built once.
    """
    if freq_caps is None:
        freq_caps = [getattr(g, "freq_caps", None) for g in governors]
    kinds = {g.policy().dynamic for g in governors}
    if len(kinds) != 1:
        raise ValueError("designs mix static and dynamic governors")
    dynamic = kinds.pop()
    D, A, K = len(dbs), len(apps), MAX_OPP_LEVELS
    T = max(a.num_tasks for a in apps)
    P = max(db.num_pes for db in dbs)
    if pad_tasks is not None:
        if pad_tasks < T:
            raise ValueError(f"pad_tasks={pad_tasks} < max tasks {T}")
        T = pad_tasks
    if pad_pes is not None:
        if pad_pes < P:
            raise ValueError(f"pad_pes={pad_pes} < widest design's {P} PEs")
        P = pad_pes

    # (D, P) slot attributes: row key index (len(rows) = the inert padding
    # row, patched in below), cluster; per design its domain count and each
    # domain's last CPU slot's key (the one the per-slot loop left there)
    profiles: List[Mapping] = []
    keys: Dict[tuple, int] = {}
    rows: List[tuple] = []                  # (profiles, pe, f, ladder)
    slot = np.full((D, P), -1, dtype=np.int64)
    cluster = np.full((D, P), -1, dtype=np.int64)
    domains = np.empty(D, dtype=np.int64)
    dom_rows: List[Dict[int, int]] = []
    dom_cpus: List[Dict[int, int]] = []
    for d, (db, gov, caps) in enumerate(zip(dbs, governors, freq_caps)):
        g = next((i for i, prof in enumerate(profiles)
                  if prof == db.profiles), None)
        if g is None:
            g = len(profiles)
            profiles.append(db.profiles)
        freq: Dict[int, float] = {}
        last: Dict[int, int] = {}
        cpus: Dict[int, int] = {}
        for j, pe in enumerate(db.pes):
            f, ladder = 0.0, None
            if pe.is_cpu:
                f = freq.get(pe.cluster)
                if f is None:
                    f = freq[pe.cluster] = gov.initial_freq(pe.pe_type)
                if dynamic:
                    _, row, n = padded_ladder(pe.pe_type, caps)
                    ladder = (tuple(row), n)
            key = (g, pe.pe_type, f, ladder)
            i = keys.get(key)
            if i is None:
                i = keys[key] = len(rows)
                rows.append((profiles[g], pe, f, ladder))
            slot[d, j] = i
            cluster[d, j] = pe.cluster
            if pe.is_cpu:
                last[pe.cluster] = i
                cpus[pe.cluster] = cpus.get(pe.cluster, 0) + 1
        domains[d] = max(MIN_DOMAINS, max(pe.cluster for pe in db.pes) + 1)
        dom_rows.append(last)
        dom_cpus.append(cpus)
    pad = len(rows)
    real = slot >= 0
    slot[~real] = pad

    # one row per key; row ``pad`` stays inert (BIG latency, zero power)
    exec_rows = np.full((pad + 1, A, T), 1e30, dtype=np.float32)
    p_act_rows = np.zeros(pad + 1, dtype=np.float32)
    p_idle_rows = np.zeros(pad + 1, dtype=np.float32)
    node_rows = np.full(pad + 1, _thermal.NODE_ACCEL, dtype=np.int32)
    cpu_rows = np.zeros(pad + 1, dtype=np.float32)
    if dynamic:
        exec_opp_rows = np.full((pad + 1, A, T, K), 1e30, dtype=np.float32)
        p_opp_rows = np.zeros((pad + 1, K), dtype=np.float32)
        ladder_rows = np.zeros((pad + 1, K), dtype=np.float32)
        num_opp_rows = np.ones(pad + 1, dtype=np.int32)
    for i, (prof, pe, f, ladder) in enumerate(rows):
        scale = NOMINAL_FREQ[pe.pe_type] / f if pe.is_cpu else 1.0
        if dynamic:
            if pe.is_cpu:
                ladder_rows[i] = ladder[0]
                num_opp_rows[i] = ladder[1]
                p_opp_rows[i] = [active_power(pe, fk) for fk in ladder[0]]
                opp_scale = np.array(
                    [np.float32(NOMINAL_FREQ[pe.pe_type] / fk)
                     for fk in ladder[0]], dtype=np.float32)
            else:
                p_opp_rows[i] = active_power(pe, 0.0)
                opp_scale = np.ones(K, dtype=np.float32)
        for ai, app in enumerate(apps):
            base = np.array([prof.get(t, {}).get(pe.pe_type, INF)
                             for t in app.task_names], dtype=np.float32)
            ok = np.isfinite(base)
            n = app.num_tasks
            exec_rows[i, ai, :n][ok] = base[ok] * np.float32(scale)
            if dynamic:
                exec_opp_rows[i, ai, :n][ok] = base[ok, None] * opp_scale
        p_act_rows[i] = active_power(pe, f)
        p_idle_rows[i] = idle_power(pe)
        node_rows[i] = _thermal.pe_node(pe.pe_type)
        cpu_rows[i] = 1.0 if pe.is_cpu else 0.0

    # dependency lists, padded to the widest in- and out-degree (>= 1)
    k_in = max(1, max(a.max_in_degree for a in apps))
    k_out = max(1, max(a.max_out_degree for a in apps))
    pred_idx = np.full((A, T, k_in), -1, dtype=np.int32)
    pred_bytes = np.zeros((A, T, k_in), dtype=np.float32)
    succ_idx = np.full((A, T, k_out), -1, dtype=np.int32)
    valid = np.zeros((A, T), dtype=bool)
    for ai, app in enumerate(apps):
        n = app.num_tasks
        valid[ai, :n] = True
        pred_idx[ai, :n], pred_bytes[ai, :n] = app.pred_lists(k_in)
        succ_idx[ai, :n] = app.succ_lists(k_out)
    _EDGES.set(sum(a.num_edges for a in apps))
    _MAX_IN_DEGREE.set(max(a.max_in_degree for a in apps))

    def per_design(x):
        return np.broadcast_to(x, (D,) + x.shape).copy()

    # real slots talk at 1 within a cluster, at the design's penalty across
    # clusters, and not at all to themselves or to padding
    penalty = np.array([db.comm.cross_cluster_penalty for db in dbs],
                       dtype=np.float32)
    comm_mult = np.where(cluster[:, :, None] != cluster[:, None, :],
                         penalty[:, None, None], np.float32(1.0))
    comm_mult[~(real[:, :, None] & real[:, None, :])] = 0.0
    comm_mult[:, np.arange(P), np.arange(P)] = 0.0

    opp_kw: Dict[str, np.ndarray] = {}
    if dynamic:
        if len(set(domains.tolist())) != 1:
            raise ValueError("designs differ in frequency-domain count")
        C = int(domains[0])
        dom = np.full((D, C), pad, dtype=np.int64)
        domain_cpu = np.zeros((D, C), dtype=np.float32)
        for d, (last, cpus) in enumerate(zip(dom_rows, dom_cpus)):
            for c, i in last.items():
                dom[d, c] = i
                domain_cpu[d, c] = cpus[c]
        opp_kw = dict(
            exec_opp=np.ascontiguousarray(
                exec_opp_rows[slot].transpose(0, 2, 3, 1, 4)),
            power_active_opp=p_opp_rows[slot], opp_freq=ladder_rows[dom],
            num_opp=num_opp_rows[dom], domain_node=node_rows[dom],
            domain_cpu=domain_cpu)

    tables = SimTables(
        exec_us=np.ascontiguousarray(exec_rows[slot].transpose(0, 2, 3, 1)),
        pred_idx=per_design(pred_idx), pred_bytes=per_design(pred_bytes),
        succ_idx=per_design(succ_idx), valid=per_design(valid),
        comm_mult=comm_mult,
        comm_startup=np.array([db.comm.startup_us for db in dbs],
                              dtype=np.float32),
        comm_inv_bw=np.array([1.0 / db.comm.bw_bytes_per_us for db in dbs],
                             dtype=np.float32),
        power_active=p_act_rows[slot], power_idle=p_idle_rows[slot],
        table_pe=np.full((D, A, T), -1, dtype=np.int32),
        node_of_pe=node_rows[slot],
        pe_domain=np.where(real, cluster, domains[:, None] - 1
                           ).astype(np.int32),
        pe_is_cpu=cpu_rows[slot],
        t_max=T, num_pes=P, depth=max(a.depth for a in apps), **opp_kw)
    return tables, pad


# --------------------------------------------------------------------------
# The per-window DTPM transition — one function, two drivers
# --------------------------------------------------------------------------

def fixed_order_sum(x):
    """Sum over the leading axis in one fixed pairwise order: the axis is
    zero-padded to a power of two and halved by elementwise adds.

    Every sum on the result path goes through here, never through a
    contraction or ``jnp.sum``.  XLA groups the terms of a reduce (and of a
    dot, which the TPU also runs through bfloat16 passes at DEFAULT
    precision) differently per backend, compiled lane width and padded
    extent; this order depends on none of them, and trailing zero terms —
    inert PE padding (DESIGN.md §5) — leave the result bit-identical.  So
    a padded, vmapped, sharded or chunked lane equals its single run on
    any device (DESIGN.md §1, §13).
    """
    x = jnp.asarray(x)
    n = x.shape[0]
    width = 1 << max(n - 1, 0).bit_length()
    if width != n:
        x = jnp.concatenate([x, jnp.zeros((width - n,) + x.shape[1:],
                                          x.dtype)])
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


def _slot_sum(values, slot, num_slots: int):
    """(num_slots,) sums of ``values`` grouped by the same-shaped integer
    ``slot``: a masked :func:`fixed_order_sum`, never a one-hot
    contraction."""
    hit = slot[..., None] == jnp.arange(num_slots, dtype=slot.dtype)
    terms = jnp.where(hit, values[..., None], 0.0)
    return fixed_order_sum(terms.reshape(-1, num_slots))


def _window_step(tables: SimTables, valid_j, window, up, cap, Am1_rc, B_rc,
                 st, carry):
    """One sampling window: utilisation → governor step, window power →
    exact RC step, temperature → throttle clamp (ref kernel order).

    ``carry`` is ``(opp_idx, next_w, temps, peak)``.  Returns the advanced
    carry plus the window's observables ``(util, node_power_w)`` — the DTPM
    epoch scan drives this lazily per decision epoch (dropping the aux), the
    telemetry scan stacks carry + aux per window via ``lax.scan`` ys
    (DESIGN.md §11).  Because commits never start before an already-closed
    window (``start ≥ data_ready ≥ epoch ≥ window end``), replaying the
    windows against the *final* schedule state yields exactly the in-loop
    values — tests pin the replayed peak to the kernel's ``peak_temp_c``.
    """
    opp_idx, next_w, temps, peak = carry
    w1, w0 = next_w, next_w - window
    committed = st["scheduled"] & valid_j                      # (J, T)
    ov = jnp.clip(jnp.minimum(st["finish"], w1)
                  - jnp.maximum(st["start"], w0), 0.0, window)
    ov = jnp.where(committed, ov, 0.0)                         # (J, T)
    cpu_w = tables.pe_is_cpu[st["onpe"]]                       # (J, T)
    busy_dom = _slot_sum(ov * cpu_w, tables.pe_domain[st["onpe"]],
                         tables.opp_freq.shape[0])             # (C,)
    util = busy_dom / jnp.maximum(window * tables.domain_cpu, 1e-9)
    proposed = ondemand_index(tables.opp_freq, tables.num_opp, up, util,
                              xp=jnp)
    # realised per-node window power: active at the latched OPP + idle
    P = tables.num_pes
    p_task = tables.power_active_opp[st["onpe"], st["onopp"]]  # (J, T)
    e_act = _slot_sum(ov * p_task, st["onpe"], P)              # (P,) W·us
    busy_pe = _slot_sum(ov, st["onpe"], P)
    idle_frac = 1.0 - jnp.clip(busy_pe / window, 0.0, 1.0)
    p_pe = e_act / window + tables.power_idle * idle_frac      # (P,) W
    node_p = _slot_sum(p_pe, tables.node_of_pe, _thermal.NUM_NODES)  # (3,)
    temps = _thermal.exact_step_jax(temps, node_p, Am1_rc, B_rc)
    peak = jnp.maximum(peak, jnp.max(temps[:3]))
    opp_idx = throttle_index(proposed, temps[tables.domain_node], cap,
                             xp=jnp)
    return (opp_idx, next_w + window, temps, peak), (util, node_p)


# --------------------------------------------------------------------------
# The simulation kernel — one epoch-scan, static DVFS as the degenerate case
# --------------------------------------------------------------------------

def _epoch_scan(tables: SimTables, policy: str, num_jobs: int,
                arrival: jnp.ndarray, app_idx: jnp.ndarray,
                gov: Optional[GovernorPolicy],
                faults: Optional[jnp.ndarray] = None,
                scan_steps: Optional[int] = None):
    """Shared epoch-scan body: ``gov=None`` compiles the static-OPP program
    (tables carry the latency/power at the governor's fixed OPP); a dynamic
    ``GovernorPolicy`` closes the DVFS + thermal loop per sampling window.

    ``faults`` (optional, (P,) f32 fail times, ``+inf`` = never — see
    ``repro.scenario.faults.fault_plan``) compiles the fail-stop program
    (DESIGN.md §14): the carry gains a per-PE ``fired`` mask and a per-task
    re-enqueue ``floor``; when an epoch crosses a fail time the dead PE's
    unfinished tasks and their committed descendants roll back inside the
    scan, and the scheduler's argmin excludes dead PEs (graceful
    degradation: accelerator tasks fall back to surviving CPU PEs).
    ``faults=None`` keeps this program byte-identical to the fault-free
    kernel.  ``scan_steps`` (static) bounds the iteration count and is
    required with faults — rollbacks re-commit tasks, so ``J·T`` no longer
    suffices (``repro.scenario.faults.fault_scan_steps``).
    """
    T, P = tables.t_max, tables.num_pes
    J = num_jobs
    dtpm = gov is not None
    faulted = faults is not None
    if faulted and policy == "table":
        raise ValueError(
            "fail-stop injection needs a PE-masking scheduler; the table "
            "policy pins static assignments — use met/etf (DESIGN.md §14)")
    if faulted and scan_steps is None:
        raise ValueError("the faulted scan needs a static scan_steps bound "
                         "(see repro.scenario.faults.fault_scan_steps)")

    valid_j = tables.valid[app_idx]        # (J, T)
    table_j = tables.table_pe[app_idx]     # (J, T)
    if not dtpm:
        exec_j = tables.exec_us[app_idx]   # (J, T, P)

    # static iteration bound: one commit per real task, plus the rollback
    # re-commits + skip epochs a caller-supplied fault budget adds
    total = J * T if scan_steps is None else scan_steps

    # dependencies as fixed-width lists: the carry keeps each task's count
    # of uncommitted preds and the max finish of its committed ones, both
    # updated at commit through the committed task's successor list
    state = dict(
        scheduled=~valid_j,                              # invalid = pre-done
        finish=jnp.zeros((J, T), jnp.float32),
        start=jnp.zeros((J, T), jnp.float32),
        onpe=jnp.zeros((J, T), jnp.int32),
        pe_free=jnp.zeros((P,), jnp.float32),
        open=jnp.sum(tables.pred_idx[app_idx] >= 0, axis=-1,
                     dtype=jnp.int32),                   # (J, T) preds left
        pred_fin=jnp.full((J, T), -BIG, jnp.float32),    # max pred finish
    )
    if faulted:
        state.update(
            fired=jnp.zeros((P,), bool),                 # PE dead already
            floor=jnp.zeros((J, T), jnp.float32),        # re-enqueue floor
        )
    if dtpm:
        C = tables.opp_freq.shape[0]
        window = jnp.asarray(gov.sample_window_us, jnp.float32)
        up = jnp.asarray(gov.up_threshold, jnp.float32)
        cap = jnp.asarray(gov.thermal_cap_c, jnp.float32)
        # exact per-window RC update (DESIGN.md §6): unconditionally stable
        Am1_rc, B_rc = _thermal.exact_step_matrices_jax(gov.thermal_dt_s)
        amb = jnp.asarray(_thermal.T_AMBIENT_C, jnp.float32)
        state.update(
            onopp=jnp.zeros((J, T), jnp.int32),          # OPP latched at commit
            opp_idx=jnp.zeros((C,), jnp.int32),          # ondemand starts at fmin
            next_w=window,
            temps=jnp.full((4,), _thermal.T_AMBIENT_C, jnp.float32),
            peak_t=amb,
        )

    flat_order = (jnp.arange(J, dtype=jnp.int32)[:, None] * T
                  + jnp.arange(T, dtype=jnp.int32)[None, :])      # (J, T)
    rows = jnp.arange(J, dtype=jnp.int32)[:, None, None]

    def advance_window(st, carry):
        """Advance one sampling window; the telemetry aux is dropped here
        (dead code the compiler eliminates — the program is unchanged)."""
        return _window_step(tables, valid_j, window, up, cap, Am1_rc, B_rc,
                            st, carry)[0]

    def child_of(mask):
        """(J, T): tasks with a predecessor in ``mask``, scattered through
        the successor lists (the -1 padding lands in a dropped column)."""
        succ = tables.succ_idx[app_idx]                            # (J,T,K)
        tgt = jnp.where(mask[..., None] & (succ >= 0), succ, T)
        return jnp.zeros((J, T + 1), bool).at[rows, tgt].set(True)[:, :T]

    def apply_faults(st, fire):
        """Fail-stop rollback (the in-scan twin of the reference kernel's
        ``apply_failure``): invalidate unfinished tasks on the PEs firing
        now plus their committed-descendant closure, reset their records,
        recompute the queue drain times from the surviving schedule and
        the open-pred counts and pred finishes from the surviving preds,
        and floor direct victims at the fail time (descendants and tasks
        whose pred was lost re-ready off their preds' fresh finish times).
        The closure walks the successor lists ``tables.depth`` times: no
        descendant is further than the longest path."""
        committed = st["scheduled"] & valid_j
        onpe, fin = st["onpe"], st["finish"]
        ftime = faults[onpe]                                       # (J, T)
        inv = committed & fire[onpe] & (fin > ftime)
        closure = lambda _, acc: acc | (committed & child_of(acc))
        inv = jax.lax.fori_loop(0, tables.depth, closure, inv)
        any_pred_inv = child_of(inv)                               # (J, T)
        roots = inv & ~any_pred_inv                # all preds still committed
        sched2 = st["scheduled"] & ~inv
        fin2 = jnp.where(inv, 0.0, fin)
        recomputed = jnp.zeros((P,), jnp.float32).at[onpe].max(
            jnp.where(sched2 & valid_j, fin2, 0.0))
        pidx = tables.pred_idx[app_idx]                            # (J,T,K)
        src = jnp.maximum(pidx, 0)
        done = (pidx >= 0) & sched2[rows, src]
        new = dict(
            st,
            scheduled=sched2,
            finish=fin2,
            start=jnp.where(inv, 0.0, st["start"]),
            onpe=jnp.where(inv, 0, onpe),
            pe_free=jnp.where(jnp.any(inv), recomputed, st["pe_free"]),
            open=jnp.sum((pidx >= 0) & ~done, axis=-1, dtype=jnp.int32),
            pred_fin=jnp.max(jnp.where(done, fin2[rows, src], -BIG),
                             axis=-1),
            fired=st["fired"] | fire,
            floor=jnp.where(roots, ftime,
                            jnp.where(any_pred_inv, 0.0, st["floor"])),
        )
        if dtpm:
            new["onopp"] = jnp.where(inv, 0, st["onopp"])
        return new

    def body(st, _):
        scheduled = st["scheduled"]
        # 1. eligibility: job tasks whose preds are all committed
        eligible = (~scheduled) & (st["open"] == 0)                     # (J, T)
        # 2. epoch time (no comm): max(arrival, max pred finish); rolled-back
        # direct fault victims additionally wait out the fail time (floor)
        ready = jnp.maximum(arrival[:, None], st["pred_fin"])            # (J, T)
        if faulted:
            ready = jnp.maximum(ready, st["floor"])
        ready = jnp.where(eligible, ready, BIG)
        # 3. lexicographic argmin (ready, job, task)
        rmin = jnp.min(ready)
        tie = eligible & (ready <= rmin)
        pick = jnp.min(jnp.where(tie, flat_order, jnp.int32(2**30)))
        j, t = pick // T, pick % T
        a = app_idx[j]
        any_left = rmin < BIG * 0.5

        # 3a. fail-stop events this epoch crosses fire before anything else
        # (the reference kernel triggers them at heap pop); the pick then
        # goes stale exactly when the rollback took one of its preds — that
        # epoch is skipped, like the oracle's stale heap entries
        if faulted:
            fire = (~st["fired"]) & (faults <= rmin) & any_left
            st = jax.lax.cond(jnp.any(fire), apply_faults,
                              lambda s, _f: s, st, fire)
            skip = st["open"][j, t] > 0
            do_commit = any_left & ~skip
        else:
            do_commit = any_left

        # 3b. DVFS windows elapsed before this epoch close the loop: the
        # governor transition + thermal feedback run, then latency re-indexes
        if dtpm:
            now = jnp.where(do_commit, rmin, -BIG)
            opp_idx, next_w, temps, peak = jax.lax.while_loop(
                lambda c: c[1] <= now,
                functools.partial(advance_window, st),
                (st["opp_idx"], st["next_w"], st["temps"], st["peak_t"]))
            st = dict(st, opp_idx=opp_idx, next_w=next_w, temps=temps,
                      peak_t=peak)
            opp_of_pe = opp_idx[tables.pe_domain]                     # (P,)
            ex = tables.exec_opp[a, t][jnp.arange(P), opp_of_pe]
        else:
            ex = exec_j[j, t]                                         # (P,)

        # 4. per-PE data-ready with comm from the producer PEs of the
        # task's K_in predecessors, selected from row j by a (K, T) match
        # (no gather; the -1 padding matches no task: finish -BIG, PE 0)
        sel = tables.pred_idx[a, t][:, None] == jnp.arange(T)           # (K, T)
        src_pe = jnp.max(jnp.where(sel, st["onpe"][j], 0), axis=1)      # (K,)
        mult = tables.comm_mult[src_pe]                                 # (K, P)
        base = (tables.comm_startup
                + tables.pred_bytes[a, t] * tables.comm_inv_bw)         # (K,)
        comm = mult * base[:, None]                                     # (K, P)
        pf_row = jnp.max(jnp.where(sel, st["finish"][j], -BIG), axis=1)  # (K,)
        data_ready = jnp.maximum(
            rmin, jnp.max(pf_row[:, None] + comm, axis=0))              # (P,)
        start_c = jnp.maximum(data_ready, st["pe_free"])                # (P,)
        fin_c = start_c + ex                                            # (P,)

        # 5. policy — dead PEs are excluded from the argmin the same way the
        # reference schedulers apply ctx.available (np.inf candidates), NOT
        # via pe_free: the oracle skips its pe_free recompute when a fault
        # invalidates nothing, so the mask is the only exclusion channel
        if policy == "etf":
            cand = jnp.where(st["fired"], jnp.inf, fin_c) if faulted else fin_c
            pe = jnp.argmin(cand).astype(jnp.int32)
        elif policy == "met":
            # canonical MET: min execution time, availability ignored
            # (DVFS-scaled at the current OPP, matching the reference)
            cand = jnp.where(st["fired"], jnp.inf, ex) if faulted else ex
            pe = jnp.argmin(cand).astype(jnp.int32)
        elif policy == "table":
            pe = table_j[j, t]
        else:
            raise ValueError(f"unknown policy {policy!r}")

        # 6. commit (no-op when nothing eligible — padding iterations) as
        # masked elementwise updates, no branch: the picked task's cell and
        # its successors' cells in row j (the successors lose one open pred
        # and see this finish; the -1 padding matches no column)
        s0 = jnp.maximum(data_ready[pe], st["pe_free"][pe])
        f0 = s0 + ex[pe]
        hit = (flat_order == pick) & do_commit                          # (J, T)
        sidx = tables.succ_idx[a, t]                                    # (K,)
        kid = ((jnp.arange(J) == j)[:, None] & do_commit
               & jnp.any(sidx[:, None] == jnp.arange(T), axis=0))       # (J, T)
        new = dict(
            st,
            scheduled=st["scheduled"] | hit,
            finish=jnp.where(hit, f0, st["finish"]),
            start=jnp.where(hit, s0, st["start"]),
            onpe=jnp.where(hit, pe, st["onpe"]),
            pe_free=jnp.where((jnp.arange(P) == pe) & do_commit, f0,
                              st["pe_free"]),
            open=st["open"] - kid.astype(jnp.int32),
            pred_fin=jnp.where(kid, jnp.maximum(st["pred_fin"], f0),
                               st["pred_fin"]),
        )
        if dtpm:
            new["onopp"] = jnp.where(hit, opp_of_pe[pe], st["onopp"])
        return new, None

    st, _ = jax.lax.scan(body, state, None, length=total)

    busy = st["finish"] - st["start"]                                   # (J, T)
    makespan = jnp.max(jnp.where(valid_j, st["finish"], 0.0))
    if dtpm:
        # drain the windows between the last decision epoch and the makespan
        # so peak_temp_c covers the schedule's execution tail, including the
        # final partial window (its start precedes the makespan; no further
        # commits happen, so this cannot perturb ref<->jax schedule parity)
        opp_idx, next_w, temps, peak = jax.lax.while_loop(
            lambda c: c[1] - window < makespan,
            functools.partial(advance_window, st),
            (st["opp_idx"], st["next_w"], st["temps"], st["peak_t"]))
        st = dict(st, opp_idx=opp_idx, next_w=next_w, temps=temps,
                  peak_t=peak)
    job_finish = jnp.max(jnp.where(valid_j, st["finish"], 0.0), axis=1)
    avg_latency = fixed_order_sum(job_finish - arrival) / J
    # energy: active while busy + idle leakage elsewhere  (uJ = W * us);
    # active power is gathered per task
    busy = jnp.where(valid_j, busy, 0.0)
    p_task = (tables.power_active_opp[st["onpe"], st["onopp"]] if dtpm
              else tables.power_active[st["onpe"]])                     # (J, T)
    e_active = fixed_order_sum((busy * p_task).reshape(-1))
    busy_per_pe = _slot_sum(busy, st["onpe"], tables.num_pes)           # (P,)
    e_idle = fixed_order_sum(
        tables.power_idle * jnp.maximum(makespan - busy_per_pe, 0.0))
    energy_j = (e_active + e_idle) * 1e-6                # W·us -> J

    out = dict(
        finish=st["finish"], start=st["start"], onpe=st["onpe"],
        scheduled=st["scheduled"], job_finish=job_finish,
        makespan_us=makespan, avg_job_latency_us=avg_latency,
        energy_j=energy_j, busy_per_pe_us=busy_per_pe,
    )
    if dtpm:
        out.update(onopp=st["onopp"], opp_idx=st["opp_idx"],
                   peak_temp_c=st["peak_t"])
    return out


@functools.partial(jax.jit,
                   static_argnames=("policy", "num_jobs", "scan_steps"))
def _simulate(tables: SimTables, policy: str, num_jobs: int,
              arrival: jnp.ndarray, app_idx: jnp.ndarray,
              faults: Optional[jnp.ndarray] = None,
              scan_steps: Optional[int] = None):
    if tables.exec_opp is not None:
        # dynamic-built tables bake exec_us at the governor's initial (fmin)
        # OPP — the static kernel would return plausible but wrong numbers
        raise ValueError("tables were built for a dynamic governor; run "
                         "them through simulate_jax_dtpm (DESIGN.md §7)")
    _COMPILES_STATIC.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    return _epoch_scan(tables, policy, num_jobs, arrival, app_idx, None,
                       faults, scan_steps)


@functools.partial(jax.jit,
                   static_argnames=("policy", "num_jobs", "scan_steps"))
def _simulate_dtpm(tables: SimTables, policy: str, num_jobs: int,
                   arrival: jnp.ndarray, app_idx: jnp.ndarray,
                   gov: GovernorPolicy,
                   faults: Optional[jnp.ndarray] = None,
                   scan_steps: Optional[int] = None):
    if tables.exec_opp is None:
        raise ValueError("tables lack OPP ladders; build them with the "
                         "dynamic governor (build_tables(governor=...))")
    _COMPILES_DTPM.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    return _epoch_scan(tables, policy, num_jobs, arrival, app_idx, gov,
                       faults, scan_steps)


def _fault_steps(num_jobs: int, t_max: int, faults) -> int:
    """Static scan bound for a concrete (P,) fault plan: every fault may
    roll back all J·T committed tasks and costs one skipped epoch."""
    n = int(np.isfinite(np.asarray(faults)).sum())
    return num_jobs * t_max * (1 + n) + n


def simulate_jax(tables: SimTables, policy: str, arrival: np.ndarray,
                 app_idx: np.ndarray, faults=None):
    """Single simulation.  ``arrival``: (J,) f32; ``app_idx``: (J,) i32.

    ``faults``: optional (P,) fail-time plan (f32, ``+inf`` = never fails;
    see ``repro.scenario.faults.fault_plan``) — compiles the fail-stop
    program (DESIGN.md §14), bit-for-bit equal to the reference kernel's
    rollback semantics on comm-free traces.
    """
    with _obs_span("repro.stage"):
        J = int(arrival.shape[0])
        arrival = jnp.asarray(arrival, jnp.float32)
        app_idx = jnp.asarray(app_idx, jnp.int32)
        steps = None
        if faults is not None:
            steps = _fault_steps(J, tables.t_max, faults)
            faults = jnp.asarray(faults, jnp.float32)
        count_scan_steps(1, J, tables.t_max, steps)
    with _obs_span("repro.launch"):
        if faults is None:
            return _simulate(tables, policy, J, arrival, app_idx)
        return _simulate(tables, policy, J, arrival, app_idx, faults,
                         scan_steps=steps)


def simulate_jax_dtpm(tables: SimTables, policy: str, arrival: np.ndarray,
                      app_idx: np.ndarray, gov: GovernorPolicy,
                      faults=None):
    """Single closed-loop DTPM simulation under a dynamic governor policy.

    The output dict gains ``onopp`` (the OPP index latched per task),
    ``opp_idx`` (final per-domain OPP) and ``peak_temp_c`` — the peak on-chip
    temperature from the inline RC loop the throttle feedback integrates.
    Windows advance lazily at decision epochs (mirroring the reference
    kernel), then drain to the makespan after the last commit so the peak
    covers the schedule's execution tail; throttle decisions during the
    drain are moot (nothing is left to schedule).
    """
    if not gov.dynamic:
        raise ValueError("static governors bake into the tables; use "
                         "simulate_jax (DESIGN.md §7)")
    with _obs_span("repro.stage"):
        validate_policy_params(gov.sample_window_us, gov.up_threshold,
                               gov.thermal_dt_s)
        J = int(arrival.shape[0])
        arrival = jnp.asarray(arrival, jnp.float32)
        app_idx = jnp.asarray(app_idx, jnp.int32)
        steps = None
        if faults is not None:
            steps = _fault_steps(J, tables.t_max, faults)
            faults = jnp.asarray(faults, jnp.float32)
        count_scan_steps(1, J, tables.t_max, steps)
    with _obs_span("repro.launch"):
        if faults is None:
            return _simulate_dtpm(tables, policy, J, arrival, app_idx, gov)
        return _simulate_dtpm(tables, policy, J, arrival, app_idx, gov,
                              faults, scan_steps=steps)


# --------------------------------------------------------------------------
# Telemetry scans — per-window (W, C) timelines from a realised schedule
# --------------------------------------------------------------------------
#
# Both scans replay the kernel's window machinery against the *final* epoch
# scan state.  For the DTPM kernel this is value-identical to the in-loop
# carry (see _window_step's docstring: no commit can overlap a closed
# window), so telemetry costs one extra small program and the simulation
# program itself — the telemetry=False path — stays byte-identical.

@functools.partial(jax.jit, static_argnames=("num_windows",))
def _telemetry_scan_dtpm(tables: SimTables, gov: GovernorPolicy,
                         app_idx, scheduled, start, finish, onpe, onopp,
                         num_windows: int):
    """(W, …) ys of the DTPM window carry: OPP index, utilisation, node
    power and RC temperatures per sampling window."""
    _COMPILES_TELEMETRY.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    valid_j = tables.valid[app_idx]
    C = tables.opp_freq.shape[0]
    window = jnp.asarray(gov.sample_window_us, jnp.float32)
    up = jnp.asarray(gov.up_threshold, jnp.float32)
    cap = jnp.asarray(gov.thermal_cap_c, jnp.float32)
    Am1_rc, B_rc = _thermal.exact_step_matrices_jax(gov.thermal_dt_s)
    st = dict(scheduled=scheduled, start=start, finish=finish,
              onpe=onpe, onopp=onopp)
    step = functools.partial(_window_step, tables, valid_j, window, up, cap,
                             Am1_rc, B_rc, st)
    carry0 = (jnp.zeros((C,), jnp.int32), window,
              jnp.full((4,), _thermal.T_AMBIENT_C, jnp.float32),
              jnp.float32(_thermal.T_AMBIENT_C))

    def body(carry, _):
        new, (util, node_p) = step(carry)
        return new, dict(opp_idx=new[0], util=util, power_w=node_p,
                         temps_c=new[2])

    _, ys = jax.lax.scan(body, carry0, None, length=num_windows)
    return ys


@functools.partial(jax.jit, static_argnames=("num_windows", "num_domains"))
def _telemetry_scan_static(tables: SimTables, app_idx, scheduled, start,
                           finish, onpe, window_us, num_windows: int,
                           num_domains: int):
    """Static-governor telemetry: same window observables at the tables'
    fixed OPP (frequency columns are filled by the caller — they are
    constants of the governor, not of the schedule).  The RC network
    integrates in real time (dt = window)."""
    _COMPILES_TELEMETRY.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    valid_j = tables.valid[app_idx]
    P = tables.num_pes
    C = num_domains
    window = jnp.asarray(window_us, jnp.float32)
    Am1_rc, B_rc = _thermal.exact_step_matrices_jax(window * 1e-6)
    committed = scheduled & valid_j
    dom_of_task = tables.pe_domain[onpe]
    cpu_w = tables.pe_is_cpu[onpe]
    domain_cpu = jnp.zeros((C,), jnp.float32).at[tables.pe_domain].add(
        tables.pe_is_cpu)
    p_task = tables.power_active[onpe]                         # (J, T)

    def body(carry, w):
        temps = carry
        w0 = w.astype(jnp.float32) * window
        w1 = w0 + window
        ov = jnp.clip(jnp.minimum(finish, w1) - jnp.maximum(start, w0),
                      0.0, window)
        ov = jnp.where(committed, ov, 0.0)
        busy_dom = _slot_sum(ov * cpu_w, dom_of_task, C)
        util = busy_dom / jnp.maximum(window * domain_cpu, 1e-9)
        e_act = _slot_sum(ov * p_task, onpe, P)
        busy_pe = _slot_sum(ov, onpe, P)
        idle_frac = 1.0 - jnp.clip(busy_pe / window, 0.0, 1.0)
        p_pe = e_act / window + tables.power_idle * idle_frac
        node_p = _slot_sum(p_pe, tables.node_of_pe, _thermal.NUM_NODES)
        temps = _thermal.exact_step_jax(temps, node_p, Am1_rc, B_rc)
        return temps, dict(util=util, power_w=node_p, temps_c=temps)

    _, ys = jax.lax.scan(body, jnp.full((4,), _thermal.T_AMBIENT_C,
                                        jnp.float32),
                         jnp.arange(num_windows))
    return ys
