"""Build D designs' simulation tables as one (D, …) stack of tensors.

Designs differ in PE count, so every design is padded to the fleet-wide
maximum.  :func:`build_design_batch` fills the batch on the host in one
pass (``build_tables_batch_host``: one row per distinct PE type and
frequency, gathered over the designs) and places it on the device once,
one transfer per leaf whatever the number of designs.  Padding is inert by
construction (BIG latency, zero power — see DESIGN.md §5), so the batched
kernel needs **no masking logic**: the sweep's one grid program
(``scenario.shardexec._sweep_grid``) vmaps the design axis × the trace axis
and runs designs × seeds × injection rates in one ``jit``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.applications import Application
from ..core.dvfs import Governor
from ..core.jobgen import JobTrace
from ..core.simkernel_jax import SimTables, build_tables_batch_host
from ..core.thermal import NODE_ACCEL, cluster_nodes
from ..obs import metrics as _metrics
from .space import DesignPoint

# host->device transfers build_design_batch makes, one per leaf placed: a
# batch's leaf count, whatever its number of designs
_PLACEMENTS = _metrics.counter("dse.tables.placements")
# distinct (PE type, frequency) rows the latest batch filled, summed over
# profile groups: what its designs share, whatever their number
_ROWS_FILLED = _metrics.counter("dse.tables.rows_filled")


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
    form the chunked executor (``scenario.shardexec``) streams from,
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
    return jnp.asarray(_node_map_host(dbs, pad_pes))


def _node_map_host(dbs, pad_pes: int) -> np.ndarray:
    nodes = np.full((len(dbs), pad_pes), NODE_ACCEL, dtype=np.int32)
    for i, db in enumerate(dbs):
        nodes[i, :db.num_pes] = cluster_nodes(db)
    return nodes


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

    The whole batch is filled on the host by ``build_tables_batch_host``
    (one row per distinct PE type and frequency, counted by
    ``dse.tables.rows_filled``, gathered over the designs), then the tables
    and node map are placed with one ``jax.device_put`` (counted by
    ``dse.tables.placements``, one per leaf).  Host spans (DESIGN.md §11):
    ``repro.tables.build`` covers ``to_db`` and the fill,
    ``repro.tables.stack`` the placement.
    """
    if not points:
        raise ValueError("empty design list")
    if governor is None:
        governors, caps = [p.governor() for p in points], None
    elif governor.policy().dynamic:
        governors = [governor] * len(points)
        caps = [p.freq_caps() for p in points]
    else:
        # a uniform static governor would silently override the per-design
        # frequency caps the sweep contract assumes
        raise ValueError(
            "build_design_batch bakes per-design frequency caps; pass a "
            "dynamic (ondemand-family) governor to add OPP ladders, or None "
            "for the static design-cap tables")
    with _metrics.span("repro.tables.build"):
        tables, rows = build_tables_batch_host(
            [p.to_db() for p in points], apps, governors, caps,
            pad_pes=pad_pes)
        _ROWS_FILLED.set(rows)
    with _metrics.span("repro.tables.stack"):
        # uncommitted, unsharded placement, as jnp.stack would give: the
        # batched programs find the jit cache entries they always had
        tables, node_of_pe = jax.device_put((tables, tables.node_of_pe))
        _PLACEMENTS.inc(
            len(jax.tree_util.tree_leaves((tables, node_of_pe))))
        return DesignBatch(points=tuple(points), tables=tables,
                           node_of_pe=node_of_pe)


def stack_traces(traces: Sequence[JobTrace]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(S, J) arrival / app-index tensors from S equal-length job traces."""
    lens = {t.num_jobs for t in traces}
    if len(lens) != 1:
        raise ValueError(f"traces must have equal job counts, got {lens}")
    arr = jnp.asarray(np.stack([t.arrival_us for t in traces]), jnp.float32)
    idx = jnp.asarray(np.stack([t.app_index for t in traces]), jnp.int32)
    return arr, idx
