"""The sweep's one grid program and its one lane executor (DESIGN.md §13).

``_sweep_grid`` is the only compiled grid program.  Its lanes are the
cross-product of up to four axes, nested from one ordered list, outermost
first: fault plans (F), designs (the stacked ``SimTables`` axis, D),
dynamic :class:`~repro.core.dvfs.GovernorPolicy` stacks (G) and traces
(S).  An absent axis (``fplans=None``, ``gov=None``) is simply not mapped.
``gov=None`` runs the static kernel plus the binned thermal scan; a policy
stack runs the closed-loop DTPM kernel, whose inline RC loop gives the peak
temperature.

``run_grid`` is the only executor.  With no chunk it launches the program
once; otherwise it streams.  It scales the wider of the design and policy
axes two ways, composably:

* **lane sharding** — with a lane mesh (``repro.sharding.lane_mesh``, all
  local devices) the lane tensors are placed with a ``NamedSharding`` over
  its one axis before entering the jitted grid program, so XLA's SPMD
  partitioner splits the vmapped lanes across devices.  Lanes are
  independent, so partitioning never changes per-lane numerics: sharded
  results are bit-for-bit equal to the single-device sweep.  With no
  chunk the device trees are placed device to device, never through host
  numpy, and a design tree's placement is kept with the tree (so with its
  ``DesignBatch``) per mesh and width: later launches on the same batch
  find their lanes resident and transfer nothing.
* **chunked streaming** — lanes stream through ONE compiled program in
  fixed-shape chunks (``sweep(..., chunk=N)``): the stacked lane tensors
  stay host-resident (numpy leaves, see :func:`stages_on_host`, the one
  rule for host staging: chunks only) and only one chunk is
  device-resident at a time, so peak device memory is O(chunk), not
  O(grid).

Both pad the lane count up to the chunk/device quantum by repeating lane 0.
Unlike ``dse.batch``'s *in-kernel* inert padding (BIG latency, zero power),
pad lanes here are ordinary simulations whose outputs are sliced off before
assembly — inert by construction because lanes never interact.  Chunk
shapes are pinned (every chunk padded to the same width, ``pad_pes``-style),
so chunking and uneven lane counts never add compiles: one trace per
(policy shape, chunk width).

Observability: ``scenario.shard.devices`` (lane-mesh width of the most
recent launch), ``scenario.shard.pad_lanes`` (inert lanes added),
``scenario.shard.resident_hits`` (mesh launches that found their design
lanes already placed) and ``scenario.sweep.chunks`` (chunks streamed) in
the ``obs.metrics`` registry; each placement or chunk's slice, pad and
placement is a ``repro.stage`` span, each launch a ``repro.launch`` span,
and a chunked concatenation a ``repro.wait`` then ``repro.assemble`` span
(DESIGN.md §11).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.simkernel_jax import _simulate, _simulate_dtpm
from ..dse.thermal_jax import peak_temperature_grid
from ..obs import metrics as _metrics
from ..sharding import lane_count, lane_mesh, lane_sharding

# lane-mesh width of the most recent sharded launch (1 = unsharded)
shard_devices = _metrics.counter("scenario.shard.devices")
# cumulative inert pad lanes added for chunk/device-count divisibility
shard_pad_lanes = _metrics.counter("scenario.shard.pad_lanes")
# cumulative unchunked mesh launches whose design lanes were already placed
shard_resident_hits = _metrics.counter("scenario.shard.resident_hits")
# cumulative fixed-shape chunks streamed through the grid program
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
    outputs are dropped, so padding is inert by construction.  Host numpy
    leaves pad on the host, device arrays on their device."""
    if lanes == width:
        return tree

    def _pad(x):
        xp = jnp if isinstance(x, jax.Array) else np
        reps = xp.take(x, xp.zeros(width - lanes, xp.int32), axis=axis)
        return xp.concatenate([x, reps], axis=axis)

    return jax.tree_util.tree_map(_pad, tree)


def _device_put_lanes(tree, mesh):
    """Place lane tensors (a chunk's host numpy, or device arrays): straight
    onto the lane sharding when a mesh is installed (never staged through
    one device first), default single-device placement otherwise."""
    return jax.device_put(tree, None if mesh is None else lane_sharding(mesh))


def _slice_lanes(tree, lo: int, hi: int, axis: int = 0):
    return jax.tree_util.tree_map(
        lambda x: x[(slice(None),) * axis + (slice(lo, hi),)], tree)


# --------------------------------------------------------------------------
# The grid program — ONE trace per (policy shape, lane width).  run_grid
# launches it on whole device-resident grids or on host-staged (and
# optionally sharded) chunks.  Nothing is donated: the outputs share no
# shape with the lane inputs, so no buffer could alias.
# --------------------------------------------------------------------------

# the lane axes, outermost first, each with the positions in
# (tables, gov, fplans, arrival, app_idx) of the arguments that carry it;
# the fault axis is outermost so the design axis stays streamable
_LANE_AXES = ((2,), (0,), (1,), (3, 4))        # fault, design, policy, trace


@functools.partial(jax.jit, static_argnames=("policy", "num_jobs", "bins",
                                             "repeats", "scan_steps"))
def _sweep_grid(tables, node_of_pe, gov, fplans, arrival, app_idx, *,
                policy, num_jobs, bins, repeats, scan_steps):
    """(F faults, D designs, G policies, S traces) lanes in ONE program.

    ``gov=None`` drops the policy axis and runs the static kernel, whose
    peak temperatures come from the binned thermal scan over each lane's
    schedule; a stacked policy runs the DTPM kernel and takes its inline
    RC peak.  ``fplans=None`` drops the fault axis (``scan_steps`` is then
    unused).  Returns ``(out, temps)``, both with the leading lane axes
    present in that order."""
    _compile_count.inc()  # lint: waive JX003 -- deliberate: counts compiles, python body runs per trace

    def lane(tb, g, fp, a, i):
        kernel = _simulate if g is None else _simulate_dtpm
        head = (tb, policy, num_jobs, a, i) + (() if g is None else (g,))
        if fp is None:
            return kernel(*head)
        return kernel(*head, fp, scan_steps=scan_steps)

    args = (tables, gov, fplans, arrival, app_idx)
    grid = lane
    for carriers in reversed([c for c in _LANE_AXES
                              if args[c[0]] is not None]):
        grid = jax.vmap(grid, in_axes=tuple(0 if k in carriers else None
                                            for k in range(len(args))))
    out = grid(*args)
    if gov is not None:
        return out, out.pop("peak_temp_c")

    def thermal(o):
        return peak_temperature_grid(o, node_of_pe, tables.power_active,
                                     tables.power_idle, bins=bins,
                                     repeats=repeats)

    return out, thermal(out) if fplans is None else jax.vmap(thermal)(out)


# --------------------------------------------------------------------------
# The executor
# --------------------------------------------------------------------------

def stages_on_host(chunk: Optional[int]) -> bool:
    """Whether a sweep stacks its lane tensors on the host: only to stream
    them in chunks, so that device memory stays O(chunk).  Without a chunk
    the launch takes the device-resident trees, placing them device to
    device when a lane mesh splits them."""
    return chunk is not None


def _place(tree, lanes: int, width: int, mesh, split: bool):
    """``tree``'s device arrays on ``mesh``, device to device: padded to
    ``width`` lanes and split on the lane sharding, or replicated whole."""
    if not split:
        return jax.device_put(tree, NamedSharding(mesh, P()))
    shard_pad_lanes.inc(width - lanes)
    return _device_put_lanes(pad_lane_axis(tree, lanes, width), mesh)


def _on_mesh(tables, node_of_pe, gov, lanes: int, by_design: bool, mesh):
    """The unchunked launch's trees on ``mesh``: the lane tree (designs, or
    the wider policy stack) split, the other replicated.  Returns
    ``(tables, node_of_pe, gov, width)``.

    The design tree's placement is kept with ``tables``, keyed by the
    mesh's device ids (``lane_mesh()`` builds a new ``Mesh`` a call) and
    the padded width.  It lives in the instance's ``__dict__``, as
    ``functools.cached_property`` keeps its values, so it dies with the
    tables (and with the ``DesignBatch`` that holds them); tables are
    frozen and their leaves immutable, so a kept placement never goes
    stale."""
    width = padded_width(lanes, None, lane_count(mesh))
    shard_devices.set(lane_count(mesh))
    kept = vars(tables).setdefault("_lane_placements", {})
    key = (tuple(int(i) for i in mesh.device_ids.flat),
           width if by_design else None)
    designs = kept.get(key)
    if designs is not None:
        shard_resident_hits.inc()
    else:
        designs = kept[key] = _place((tables, node_of_pe), lanes, width,
                                     mesh, by_design)
    if gov is not None:
        gov = _place(gov, lanes, width, mesh, not by_design)
    return designs + (gov, width)


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


def _concat_out(chunks: list, lanes: int, axis: int):
    """Concatenate per-chunk output trees on the lane axis and drop the pad
    lanes (host-side: chunk outputs leave the device as they arrive)."""
    with _metrics.span("repro.wait"):
        jax.block_until_ready(chunks)
    with _metrics.span("repro.assemble"):
        sl = (slice(None),) * axis + (slice(0, lanes),)
        return jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs],
                                       axis=axis)[sl], *chunks)


def run_grid(tables, node_of_pe, gov, fplans, arrival, app_idx, *,
             policy: str, num_jobs: int, bins: int, repeats: int,
             scan_steps: Optional[int] = None, chunk: Optional[int] = None,
             mesh=None):
    """Run ``_sweep_grid`` over its lanes; returns ``(out, temps)``.

    With no ``chunk`` the program is launched once and returns device
    arrays: on the trees as given when there is no ``mesh``, else on their
    placement over it (:func:`_on_mesh`), with the pad lanes dropped.  With
    a ``chunk`` the wider of the design (D) and policy (G) axes streams
    through :func:`_stream` from host numpy (the other is placed whole),
    and the host-resident outputs hold exactly the real lanes.  Every path
    is bit-for-bit equal to the plain launch (DESIGN.md §10, §13)."""
    kw = dict(policy=policy, num_jobs=num_jobs, bins=bins, repeats=repeats,
              scan_steps=scan_steps)
    D = tables.exec_us.shape[0]
    G = 0 if gov is None else gov.up_threshold.shape[0]
    by_design = D >= G
    lanes = D if by_design else G
    # the lane axis sits after the fault axis, the policy axis after D
    axis = (fplans is not None) + (not by_design)
    if chunk is None:
        width = lanes
        if mesh is not None:
            with _metrics.span("repro.stage"):
                tables, node_of_pe, gov, width = _on_mesh(
                    tables, node_of_pe, gov, lanes, by_design, mesh)
        with _metrics.span("repro.launch"):
            out = _sweep_grid(tables, node_of_pe, gov, fplans, arrival,
                              app_idx, **kw)
        return out if width == lanes else _slice_lanes(out, 0, lanes, axis)
    with _metrics.span("repro.stage"):
        designs = (host_tree(tables), host_tree(node_of_pe))
        fdev = None if fplans is None else jnp.asarray(fplans, jnp.float32)
        lane_tree = designs if by_design else host_tree(gov)
        whole = jax.tree_util.tree_map(
            jnp.asarray, host_tree(gov) if by_design else designs)

    def launch(piece):
        (tb, nodes), g = (piece, whole) if by_design else (whole, piece)
        return _sweep_grid(tb, nodes, g, fdev, arrival, app_idx, **kw)

    return _concat_out(_stream(lane_tree, lanes, chunk, mesh, launch), lanes,
                       axis=axis)


def resolve_mesh(shard: Optional[bool], devices=None):
    """The lane mesh a sweep should use: ``shard=None`` auto-shards when
    more than one local device is present, ``False`` never shards, ``True``
    asks for the mesh explicitly (still ``None`` — unsharded — when only
    one device exists; the chunked path works either way)."""
    if shard is False:
        return None
    return lane_mesh(devices)
