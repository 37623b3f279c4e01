"""Stack per-design simulation tables into one (D, …) tensor program.

Designs differ in PE count, so every per-design :class:`SimTables` is built
padded to the fleet-wide maximum (``build_tables(pad_pes=…)``) and the padded
tables are stacked leaf-wise into a single pytree whose data fields carry a
leading design axis.  Padding is inert by construction (BIG latency, zero
power — see DESIGN.md §5), so the batched kernel needs **no masking logic**:
``jax.vmap`` over the design axis × the trace axis runs designs × seeds ×
injection rates in one ``jit``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.applications import Application
from ..core.dvfs import Governor
from ..core.jobgen import JobTrace
from ..core.simkernel_jax import SimTables, _simulate, build_tables
from ..core.thermal import NODE_ACCEL, cluster_nodes
from ..obs import metrics as _metrics
from .space import DesignPoint


@dataclasses.dataclass(frozen=True)
class DesignBatch:
    """D stacked designs ready for batched simulation.

    No per-PE mask is stored: padding is inert inside the kernel (DESIGN.md
    §5), and consumers slice per-design outputs with ``points[d].num_pes``.
    """
    points: Tuple[DesignPoint, ...]
    tables: SimTables                 # data fields carry a leading (D, …) axis
    node_of_pe: jnp.ndarray           # (D, P) i32 thermal node per PE slot

    @property
    def num_designs(self) -> int:
        return len(self.points)

    @property
    def dynamic(self) -> bool:
        """True when the tables carry OPP ladders for dynamic DTPM policies."""
        return self.tables.exec_opp is not None


def stack_tables(tables: Sequence[SimTables], host: bool = False) -> SimTables:
    """Leaf-wise stack of identically-shaped SimTables into (D, …) tensors.

    ``host=True`` stacks into numpy leaves instead of device arrays — the
    form the chunked/sharded executor (``scenario.shardexec``) streams from,
    so a grid larger than device memory is never device-resident at once.
    """
    shapes = {(t.t_max, t.num_pes) for t in tables}
    if len(shapes) != 1:
        raise ValueError(f"tables must be padded to one shape, got {shapes}")
    if host:
        return jax.tree_util.tree_map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *tables)
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *tables)


def pad_node_map(dbs, pad_pes: int) -> jnp.ndarray:
    """(D, P) thermal node per PE slot; padded slots are inert (zero-power)
    and binned to the accel node by convention."""
    nodes = np.full((len(dbs), pad_pes), NODE_ACCEL, dtype=np.int32)
    for i, db in enumerate(dbs):
        nodes[i, :db.num_pes] = cluster_nodes(db)
    return jnp.asarray(nodes)


def build_design_batch(points: Sequence[DesignPoint],
                       apps: Sequence[Application],
                       pad_pes: Optional[int] = None,
                       governor: Optional[Governor] = None) -> DesignBatch:
    """Build + pad + stack the simulation tables for a list of designs.

    By default every design bakes its own frequency-cap (userspace) governor
    — the static-DVFS slice of the space.  Passing a *dynamic* ``governor``
    (the ondemand family) instead builds the OPP-indexed tables the DTPM
    kernel gathers from, with each design's OPP ladder truncated at its
    per-cluster frequency caps — so Pareto search ranks dynamic policies
    under the design's static envelope, not just static caps.

    Host spans (DESIGN.md §11): ``repro.tables.build`` covers ``to_db`` and
    the per-design ``build_tables`` loop, ``repro.tables.stack`` the
    stacking and the node map.
    """
    if not points:
        raise ValueError("empty design list")
    with _metrics.span("repro.tables.build"):
        dbs = [p.to_db() for p in points]
        P = max(db.num_pes for db in dbs)
        if pad_pes is not None:
            if pad_pes < P:
                raise ValueError(f"pad_pes={pad_pes} < widest design {P}")
            P = pad_pes
        if governor is not None:
            if not governor.policy().dynamic:
                # a uniform static governor would silently override the
                # per-design frequency caps the sweep contract assumes
                raise ValueError(
                    "build_design_batch bakes per-design frequency caps; "
                    "pass a dynamic (ondemand-family) governor to add OPP "
                    "ladders, or None for the static design-cap tables")
            per_design = [
                build_tables(db, apps, governor=governor, pad_pes=P,
                             freq_caps=p.freq_caps())
                for p, db in zip(points, dbs)]
        else:
            per_design = [build_tables(db, apps, governor=p.governor(),
                                       pad_pes=P)
                          for p, db in zip(points, dbs)]
    with _metrics.span("repro.tables.stack"):
        return DesignBatch(points=tuple(points),
                           tables=stack_tables(per_design),
                           node_of_pe=pad_node_map(dbs, P))


def stack_traces(traces: Sequence[JobTrace]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(S, J) arrival / app-index tensors from S equal-length job traces."""
    lens = {t.num_jobs for t in traces}
    if len(lens) != 1:
        raise ValueError(f"traces must have equal job counts, got {lens}")
    arr = jnp.asarray(np.stack([t.arrival_us for t in traces]), jnp.float32)
    idx = jnp.asarray(np.stack([t.app_index for t in traces]), jnp.int32)
    return arr, idx


@functools.partial(jax.jit, static_argnames=("policy", "num_jobs"))
def _simulate_grid(tables: SimTables, policy: str, num_jobs: int,
                   arrival: jnp.ndarray, app_idx: jnp.ndarray):
    """(D designs) × (S traces) simulations as one tensor program."""
    per_trace = jax.vmap(
        lambda tb, a, i: _simulate(tb, policy, num_jobs, a, i),
        in_axes=(None, 0, 0))                      # map traces, share design
    per_design = jax.vmap(per_trace, in_axes=(0, None, None))
    return per_design(tables, arrival, app_idx)


@functools.partial(jax.jit,
                   static_argnames=("policy", "num_jobs", "scan_steps"))
def _simulate_grid_faults(tables: SimTables, policy: str, num_jobs: int,
                          arrival: jnp.ndarray, app_idx: jnp.ndarray,
                          fplans: jnp.ndarray, scan_steps: int):
    """(F fault plans) × (D designs) × (S traces) fail-stop simulations.

    ``fplans``: (F, P) f32 per-PE fail times (``+inf`` = never fails, see
    ``repro.scenario.faults``); ``scan_steps`` is the static epoch budget
    covering the widest lane's rollbacks (DESIGN.md §14).  The fault axis is
    outermost so the design axis stays streamable (``scenario.shardexec``).
    """
    per_trace = jax.vmap(
        lambda tb, a, i, fp: _simulate(tb, policy, num_jobs, a, i, fp,
                                       scan_steps=scan_steps),
        in_axes=(None, 0, 0, None))
    per_design = jax.vmap(per_trace, in_axes=(0, None, None, None))
    per_fault = jax.vmap(per_design, in_axes=(None, None, None, 0))
    return per_fault(tables, arrival, app_idx, fplans)


def simulate_design_batch(batch: DesignBatch, policy: str,
                          arrival: jnp.ndarray, app_idx: jnp.ndarray) -> Dict:
    """Run all designs × traces in one jitted call.

    ``arrival``/``app_idx``: (S, J) as from :func:`stack_traces`.  Every entry
    of the returned dict gains leading (D, S) axes over ``simulate_jax``'s
    output — e.g. ``avg_job_latency_us`` is (D, S), ``busy_per_pe_us`` is
    (D, S, P).
    """
    arrival = jnp.asarray(arrival, jnp.float32)
    app_idx = jnp.asarray(app_idx, jnp.int32)
    if arrival.ndim != 2:
        raise ValueError("arrival must be (num_traces, num_jobs)")
    return _simulate_grid(batch.tables, policy, int(arrival.shape[1]),
                          arrival, app_idx)
