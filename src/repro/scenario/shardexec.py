"""The sweep's grid programs and their sharded + chunked lane execution
(DESIGN.md §13).

The compiled grid programs are embarrassingly parallel along their
leading *lane* axis — the stacked design axis of ``SimTables`` (static
sweeps) or, when the policy grid is the wide one, the stacked
:class:`~repro.core.dvfs.GovernorPolicy` axis (dynamic DTPM sweeps).  This
module scales that axis two ways, composably:

* **lane sharding** — the per-chunk lane tensors are placed with a
  ``NamedSharding`` over the 1-D lane mesh (``repro.sharding.lane_mesh``,
  all local devices) before entering the jitted grid program, so XLA's SPMD
  partitioner splits the vmapped lanes across devices.  Lanes are
  independent, so partitioning never changes per-lane numerics: sharded
  results are bit-for-bit equal to the single-device sweep.
* **chunked streaming** — lanes stream through ONE compiled program in
  fixed-shape chunks (``sweep(..., chunk=N)``): the stacked lane tensors
  stay host-resident (numpy leaves) and only one chunk is device-resident
  at a time, so peak device memory is O(chunk), not O(grid).

Both paths pad the lane count up to the chunk/device quantum by repeating
lane 0.  Unlike ``dse.batch``'s *in-kernel* inert padding (BIG latency,
zero power), pad lanes here are ordinary simulations whose outputs are
sliced off before assembly — inert by construction because lanes never
interact.  Chunk shapes are pinned (every chunk padded to the same width,
``pad_pes``-style), so chunking and uneven lane counts never add compiles:
one trace per (policy shape, chunk width).

Observability: ``scenario.shard.devices`` (lane-mesh width of the most
recent launch), ``scenario.shard.pad_lanes`` (inert lanes added) and
``scenario.sweep.chunks`` (chunks streamed) in the ``obs.metrics`` registry;
each chunk's slice, pad and placement is a ``repro.stage`` span, its
launch a ``repro.launch`` span, and the concatenation a ``repro.wait`` then
``repro.assemble`` span (DESIGN.md §11).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dse.batch import _simulate_grid, _simulate_grid_faults
from ..dse.thermal_jax import peak_temperature_grid
from ..core.simkernel_jax import _simulate_dtpm
from ..obs import metrics as _metrics
from ..sharding import lane_count, lane_mesh, lane_sharding

# lane-mesh width of the most recent sharded launch (1 = unsharded)
shard_devices = _metrics.counter("scenario.shard.devices")
# cumulative inert pad lanes added for chunk/device-count divisibility
shard_pad_lanes = _metrics.counter("scenario.shard.pad_lanes")
# cumulative fixed-shape chunks streamed through the grid programs
sweep_chunks = _metrics.counter("scenario.sweep.chunks")
# the sweep's one-program-per-policy-shape trace counter (re-exported as
# ``sweep.compile_count``; looked up here, not in the jitted bodies, so the
# registry is never touched under trace)
_compile_count = _metrics.counter("scenario.sweep.compile_count")


def host_tree(tree):
    """The pytree with every array leaf as host-resident numpy (the form the
    chunked streamer slices from, keeping device residency O(chunk))."""
    return jax.tree_util.tree_map(np.asarray, tree)


def padded_width(lanes: int, chunk: Optional[int], quantum: int) -> int:
    """The pinned per-chunk lane width: ``chunk`` (or all lanes) rounded up
    to the device-count quantum.  Fixed across chunks and across grids of
    different lane counts (when ``chunk`` is given), so the jit cache sees
    one shape."""
    base = lanes if chunk is None else chunk
    return -(-base // quantum) * quantum


def pad_lane_axis(tree, lanes: int, width: int, axis: int = 0):
    """Pad every leaf's lane ``axis`` from ``lanes`` up to ``width`` by
    repeating lane 0 — pad lanes are real, independent simulations whose
    outputs are dropped, so padding is inert by construction."""
    if lanes == width:
        return tree

    def _pad(x):
        reps = np.take(x, np.zeros(width - lanes, np.intp), axis=axis)
        return np.concatenate([np.asarray(x), reps], axis=axis)

    return jax.tree_util.tree_map(_pad, tree)


def _device_put_lanes(tree, mesh):
    """Place a chunk's host lane tensors: straight onto the lane sharding
    when a mesh is installed (never staged through one device first),
    default single-device placement otherwise."""
    return jax.device_put(tree, None if mesh is None else lane_sharding(mesh))


def _slice_lanes(tree, lo: int, hi: int, axis: int = 0):
    return jax.tree_util.tree_map(
        lambda x: x[(slice(None),) * axis + (slice(lo, hi),)], tree)


# --------------------------------------------------------------------------
# The grid programs — ONE trace per (policy shape, lane width).  The plain
# sweep launches them on whole device-resident grids, the streamer below on
# host-staged (and optionally sharded) chunks.  Nothing is donated: the
# outputs share no shape with the lane inputs, so no buffer could alias.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("policy", "num_jobs", "bins",
                                             "repeats"))
def _sweep_grid(tables, node_of_pe, arrival, app_idx, policy, num_jobs,
                bins, repeats):
    """Schedule simulation + thermal scan for (D, S) lanes, ONE program."""
    _compile_count.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    out = _simulate_grid(tables, policy, num_jobs, arrival, app_idx)
    temps = peak_temperature_grid(out, node_of_pe, tables.power_active,
                                  tables.power_idle, bins=bins,
                                  repeats=repeats)
    return out, temps


@functools.partial(jax.jit, static_argnames=("policy", "num_jobs"))
def _sweep_grid_dtpm(tables, gov, arrival, app_idx, policy, num_jobs):
    """Closed-loop DTPM lanes: (D designs, G policies, S traces) in ONE
    program.  Peak temperature comes from the kernel's inline RC loop (the
    one the throttle feedback integrates), so no post-hoc thermal scan."""
    _compile_count.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    per_trace = jax.vmap(
        lambda tb, g, a, i: _simulate_dtpm(tb, policy, num_jobs, a, i, g),
        in_axes=(None, None, 0, 0))
    per_policy = jax.vmap(per_trace, in_axes=(None, 0, None, None))
    per_design = jax.vmap(per_policy, in_axes=(0, None, None, None))
    return per_design(tables, gov, arrival, app_idx)


@functools.partial(jax.jit, static_argnames=("policy", "num_jobs", "bins",
                                             "repeats", "scan_steps"))
def _sweep_grid_faults(tables, node_of_pe, fplans, arrival, app_idx, policy,
                       num_jobs, bins, repeats, scan_steps):
    """Fail-stop lanes (F fault plans, D designs, S traces), ONE program.

    The fault axis is outermost so the design axis stays streamable by the
    chunked/sharded executor; the thermal scan vmaps per fault lane over the
    same (D, S) grid program the fault-free path uses (DESIGN.md §14)."""
    _compile_count.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    out = _simulate_grid_faults(tables, policy, num_jobs, arrival, app_idx,
                                fplans, scan_steps)
    temps = jax.vmap(lambda o: peak_temperature_grid(
        o, node_of_pe, tables.power_active, tables.power_idle, bins=bins,
        repeats=repeats))(out)
    return out, temps


@functools.partial(jax.jit,
                   static_argnames=("policy", "num_jobs", "scan_steps"))
def _sweep_grid_dtpm_faults(tables, gov, fplans, arrival, app_idx, policy,
                            num_jobs, scan_steps):
    """Fail-stop DTPM lanes: (F fault plans, D designs, G policies,
    S traces) through the closed-loop kernel in ONE program."""
    _compile_count.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace
    per_trace = jax.vmap(
        lambda tb, g, a, i, fp: _simulate_dtpm(tb, policy, num_jobs, a, i, g,
                                               fp, scan_steps=scan_steps),
        in_axes=(None, None, 0, 0, None))
    per_policy = jax.vmap(per_trace, in_axes=(None, 0, None, None, None))
    per_design = jax.vmap(per_policy, in_axes=(0, None, None, None, None))
    per_fault = jax.vmap(per_design, in_axes=(None, None, None, None, 0))
    return per_fault(tables, gov, arrival, app_idx, fplans)


# --------------------------------------------------------------------------
# The streamer
# --------------------------------------------------------------------------

def _stream(lane_tree, lanes: int, chunk: Optional[int], mesh,
            launch) -> list:
    """Stream ``lane_tree`` (host numpy leaves, lane axis leading) through
    ``launch(device_chunk)`` in fixed-width chunks; returns the per-chunk
    results with pad lanes still attached (callers slice after concat)."""
    quantum = lane_count(mesh)
    width = padded_width(lanes, chunk, quantum)
    shard_devices.reset()
    shard_devices.inc(quantum)
    outs = []
    for lo in range(0, lanes, width):
        with _metrics.span("repro.stage"):
            hi = min(lo + width, lanes)
            piece = _slice_lanes(lane_tree, lo, hi)
            if hi - lo < width:
                shard_pad_lanes.inc(width - (hi - lo))
                piece = pad_lane_axis(piece, hi - lo, width)
            sweep_chunks.inc()
            piece = _device_put_lanes(piece, mesh)
        with _metrics.span("repro.launch"):
            outs.append(launch(piece))
    return outs


def _concat_out(chunks: list, lanes: int, axis: int = 0) -> Dict:
    """Concatenate per-chunk output dicts on the lane axis and drop the pad
    lanes (host-side: chunk outputs leave the device as they arrive)."""
    with _metrics.span("repro.wait"):
        jax.block_until_ready(chunks)
    with _metrics.span("repro.assemble"):
        keys = chunks[0].keys()
        out = {k: np.concatenate([np.asarray(c[k]) for c in chunks],
                                 axis=axis)
               for k in keys}
        sl = (slice(None),) * axis + (slice(0, lanes),)
        return {k: v[sl] for k, v in out.items()}


def run_static_grid(tables, node_of_pe, arrival, app_idx, *, policy: str,
                    num_jobs: int, bins: int, repeats: int,
                    chunk: Optional[int] = None, mesh=None,
                    fplans=None,
                    scan_steps: Optional[int] = None
                    ) -> Tuple[Dict, np.ndarray]:
    """The sharded/chunked twin of ``sweep._sweep_grid``: (D, S) lanes with
    the design axis streamed/sharded; returns host-resident outputs with
    exactly D lanes (bit-for-bit equal to the unsharded grid).

    ``fplans``/``scan_steps`` switch to the fail-stop grid: outputs gain a
    leading (F,) fault-lane axis, and the design axis (still the streamed
    one) moves to position 1 — the fault axis is outermost precisely so
    streaming stays a design-axis slice (DESIGN.md §14)."""
    with _metrics.span("repro.stage"):
        lanes = int(np.asarray(tables.exec_us).shape[0])
        lane_tree = (host_tree(tables), host_tree(node_of_pe))
        faulted = fplans is not None
        fdev = jnp.asarray(fplans, jnp.float32) if faulted else None

    def launch(piece):
        tb, nodes = piece
        if faulted:
            out, temps = _sweep_grid_faults(
                tb, nodes, fdev, arrival, app_idx, policy=policy,
                num_jobs=num_jobs, bins=bins, repeats=repeats,
                scan_steps=scan_steps)
        else:
            out, temps = _sweep_grid(tb, nodes, arrival, app_idx,
                                     policy=policy, num_jobs=num_jobs,
                                     bins=bins, repeats=repeats)
        out = dict(out)
        out["_peak_temp_scan_c"] = temps
        return out

    out = _concat_out(_stream(lane_tree, lanes, chunk, mesh, launch), lanes,
                      axis=1 if faulted else 0)
    return out, out.pop("_peak_temp_scan_c")


def run_dtpm_grid(tables, gov, arrival, app_idx, *, policy: str,
                  num_jobs: int, chunk: Optional[int] = None, mesh=None,
                  fplans=None, scan_steps: Optional[int] = None) -> Dict:
    """The sharded/chunked twin of ``sweep._sweep_grid_dtpm``: (D, G, S)
    lanes, streaming/sharding whichever of the design (D) and policy (G)
    axes is wider — the GovernorPolicy leaves are as much a lane stack as
    the SimTables leaves (DESIGN.md §10).  ``fplans``/``scan_steps`` switch
    to the fail-stop grid: outputs gain a leading (F,) fault-lane axis and
    the streamed axis shifts one position right (DESIGN.md §14)."""
    with _metrics.span("repro.stage"):
        D = int(np.asarray(tables.exec_us).shape[0])
        G = int(np.asarray(gov.up_threshold).shape[0])
        tables_h, gov_h = host_tree(tables), host_tree(gov)
        faulted = fplans is not None
        fdev = jnp.asarray(fplans, jnp.float32) if faulted else None
    if D >= G:                               # stream designs, reuse policies
        with _metrics.span("repro.stage"):
            gov_dev = jax.tree_util.tree_map(jnp.asarray, gov_h)

        def launch(tb):
            if faulted:
                return _sweep_grid_dtpm_faults(
                    tb, gov_dev, fdev, arrival, app_idx, policy=policy,
                    num_jobs=num_jobs, scan_steps=scan_steps)
            return _sweep_grid_dtpm(tb, gov_dev, arrival, app_idx,
                                    policy=policy, num_jobs=num_jobs)

        return _concat_out(_stream(tables_h, D, chunk, mesh, launch), D,
                           axis=1 if faulted else 0)
    with _metrics.span("repro.stage"):
        tables_dev = jax.tree_util.tree_map(jnp.asarray, tables_h)

    def launch(g):
        if faulted:
            return _sweep_grid_dtpm_faults(
                tables_dev, g, fdev, arrival, app_idx, policy=policy,
                num_jobs=num_jobs, scan_steps=scan_steps)
        return _sweep_grid_dtpm(tables_dev, g, arrival, app_idx,
                                policy=policy, num_jobs=num_jobs)

    return _concat_out(_stream(gov_h, G, chunk, mesh, launch), G,
                       axis=2 if faulted else 1)


def resolve_mesh(shard: Optional[bool], devices=None):
    """The lane mesh a sweep should use: ``shard=None`` auto-shards when
    more than one local device is present, ``False`` never shards, ``True``
    asks for the mesh explicitly (still ``None`` — unsharded — when only
    one device exists; the chunked path works either way)."""
    if shard is False:
        return None
    return lane_mesh(devices)
