"""Logical-axis sharding rules (MaxText-style) for the whole framework.

Model code annotates parameters and activations with *logical* axis names
("batch", "embed", "mlp", "heads", "kv", "vocab", "expert", "seq", ...).
The launcher installs a rule set mapping logical names -> mesh axes; on CPU
smoke tests no rules are installed and every annotation is a no-op.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

_STATE = threading.local()


def _rules() -> Optional[Dict[str, MeshAxes]]:
    return getattr(_STATE, "rules", None)


def _mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


# Default rule sets -----------------------------------------------------------

def rules_single_pod() -> Dict[str, MeshAxes]:
    """16×16 (data, model) single-pod mesh."""
    return {
        "batch": "data",
        "kv_batch": "data",      # KV-cache batch dim (can differ from batch:
                                 # weight-stationary decode replicates batch
                                 # activations but keeps the cache sharded)
        "fsdp": "data",          # weight shard axis for gather-on-use FSDP
        "model": "model",        # TP axis: heads / mlp / vocab / experts
        "expert": "model",       # MoE expert parallelism
        "seq": None,             # sequence usually replicated (flag-controlled)
        "kv_seq": "model",       # decode KV-cache sequence dim (flash-decode)
        "q_seq": "model",        # blocked-attention query rows (context par.)
    }


def rules_multi_pod() -> Dict[str, MeshAxes]:
    """2×16×16 (pod, data, model) mesh: DP and FSDP span pod×data.

    FSDP over both axes halves per-chip parameter/optimizer bytes vs the
    single-pod layout; the cross-pod traffic this adds is the weight
    all-gather + gradient reduce-scatter on the DCN-mapped ``pod`` axis
    (compressible — see optim.compression)."""
    r = rules_single_pod()
    r["batch"] = ("pod", "data")
    r["fsdp"] = ("pod", "data")
    return r


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[Dict[str, MeshAxes]] = None):
    """Install mesh + logical rules for model-code annotations."""
    old_rules, old_mesh = _rules(), _mesh()
    _STATE.rules = rules
    _STATE.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _STATE.rules = old_rules
        _STATE.mesh = old_mesh


def logical_to_pspec(axes: Sequence[Optional[str]]) -> P:
    """Map logical axis names to a PartitionSpec under the installed rules."""
    rules = _rules()
    if rules is None:
        return P()
    out = []
    for a in axes:
        m = rules.get(a) if a is not None else None
        out.append(m)
    # drop trailing Nones for cleanliness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Annotate an activation with logical axes (no-op without rules).

    Inside a partial-manual ``shard_map`` region (e.g. the pipeline's 'pod'
    axis) the constraint must be built against the CONTEXT abstract mesh,
    whose axis types carry the Manual marking."""
    rules = _rules()
    mesh = _mesh()
    if rules is None or mesh is None:
        return x
    m = mesh
    am = jax.sharding.get_abstract_mesh()
    if am is not None and am.shape and any(
            t == jax.sharding.AxisType.Manual for t in am.axis_types):
        m = am
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, logical_to_pspec(axes)))


def named_sharding(axes: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    mesh = _mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_pspec(axes))


def current_mesh() -> Optional[Mesh]:
    return _mesh()


# Lane meshes — 1-D device meshes for embarrassingly-parallel lane axes ------

LANE_AXIS = "lanes"


def lane_mesh(devices: Optional[Sequence[jax.Device]] = None,
              ) -> Optional[Mesh]:
    """A 1-D mesh over the local devices, axis name :data:`LANE_AXIS`.

    The simulator's sweep grids are embarrassingly parallel along their
    leading design/policy lane axis (every lane is an independent vmapped
    simulation), so a flat 1-D mesh is the whole story — no model/data
    split.  Returns ``None`` on a single device: callers fall back to the
    unsharded path and nothing is ever resharded on 1-device hosts.
    """
    devices = tuple(devices) if devices is not None else tuple(
        jax.local_devices())
    if len(devices) <= 1:
        return None
    return Mesh(np.asarray(devices), (LANE_AXIS,))


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """``NamedSharding`` splitting an array's *leading* axis across the
    lane mesh (remaining axes replicated)."""
    return NamedSharding(mesh, P(LANE_AXIS))


def lane_count(mesh: Optional[Mesh]) -> int:
    """Devices along the lane axis (1 when unsharded)."""
    return 1 if mesh is None else int(mesh.shape[LANE_AXIS])


def mesh_axis(logical: str):
    """(mesh axis name(s), total size) the logical axis maps to, or (None, 1)."""
    rules, mesh = _rules(), _mesh()
    if rules is None or mesh is None:
        return None, 1
    ax = rules.get(logical)
    if ax is None:
        return None, 1
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return axes, size
