"""Sharded + chunked sweep execution (DESIGN.md §13).

The lane-scaling contract: ``sweep(..., chunk=N)`` and ``sweep(...,
shard=True)`` are *bit-for-bit* equal to the plain single-device sweep —
including uneven lane counts (pad lanes are dropped) and telemetry replay —
because lanes are independent simulations and every launch runs the one
grid program (``shardexec._sweep_grid``).  Multi-device sharding is exercised in a subprocess
(the forced 8-device CPU topology must not leak into other tests).
"""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.dse import DesignPoint
from repro.dse.batch import stack_traces
from repro.obs import metrics
from repro.scenario import FaultSpec, Scenario, TraceSpec, run, sweep
from repro.scenario import shardexec
from repro.scenario.sweep import _apply_axes, _design_lanes, compile_count
from repro.sharding import LANE_AXIS, lane_sharding

SCN = Scenario(apps=("wifi_tx",), scheduler="etf", governor="design",
               trace=TraceSpec(rate_jobs_per_ms=25.0, num_jobs=16, seed=3))
POINTS = [DesignPoint(cross_cluster_penalty=1.0 + 0.5 * i) for i in range(5)]
FIELDS = ("avg_latency_us", "makespan_us", "energy_j", "peak_temp_c",
          "busy_per_pe_us")


def _assert_bitexact(a, b):
    for f in FIELDS:
        av, bv = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert np.array_equal(av, bv), f
    if a.telemetry is not None:
        assert b.telemetry is not None
        for ta, tb in zip(a.telemetry.ravel(), b.telemetry.ravel()):
            assert ta.num_windows == tb.num_windows
            assert np.array_equal(ta.util, tb.util)
            assert np.array_equal(ta.temps_c, tb.temps_c)
            assert np.array_equal(ta.freq_idx, tb.freq_idx)


# ------------------------------------------------ pad/width helpers

def test_padded_width_is_pinned():
    # no chunk: all lanes, rounded to the device quantum
    assert shardexec.padded_width(5, None, 1) == 5
    assert shardexec.padded_width(5, None, 8) == 8
    # chunk given: the width is chunk-derived, NOT lane-derived, so grids
    # of different lane counts share one jit cache entry
    assert shardexec.padded_width(5, 2, 1) == 2
    assert shardexec.padded_width(3, 2, 1) == 2
    assert shardexec.padded_width(5, 3, 2) == 4
    assert shardexec.padded_width(100, 8, 8) == 8


def test_pad_lane_axis_repeats_lane0():
    tree = {"a": np.arange(6.0).reshape(3, 2), "b": np.arange(3)}
    out = shardexec.pad_lane_axis(tree, 3, 5)
    assert out["a"].shape == (5, 2) and out["b"].shape == (5,)
    np.testing.assert_array_equal(out["a"][3], tree["a"][0])
    np.testing.assert_array_equal(out["a"][4], tree["a"][0])
    np.testing.assert_array_equal(out["a"][:3], tree["a"])
    # width == lanes is the identity (same object, no copy)
    assert shardexec.pad_lane_axis(tree, 3, 3) is tree


def test_pad_lane_axis_pads_device_arrays_on_the_device():
    """Device leaves pad with device ops (the unchunked mesh path places
    device trees, never host numpy), equal to the host padding."""
    host = {"a": np.arange(6.0).reshape(3, 2), "b": np.arange(3)}
    dev = jax.tree_util.tree_map(jax.numpy.asarray, host)
    out = shardexec.pad_lane_axis(dev, 3, 5)
    assert all(isinstance(x, jax.Array) for x in out.values())
    want = shardexec.pad_lane_axis(host, 3, 5)
    for k in host:
        np.testing.assert_array_equal(np.asarray(out[k]), want[k])


# ------------------------------------------------ the one grid program

FAULT_SETS = [(), (FaultSpec(0, 400.0),)]
POLICY_PARAMS = [(("up_threshold", 0.5 + 0.08 * i),) for i in range(3)]
# one job count per case below, so that every case is a variant shape no
# other test has compiled yet
CASE_JOBS = iter((23, 29, 31, 37, 41, 43, 47, 53))


def _assert_lanes_equal_run(sr, base, axes):
    """Every lane of ``sr`` is bit-equal to its per-point jax ``run()``."""
    names = list(axes)
    for idx in np.ndindex(*sr.shape):
        scn = _apply_axes(base, names,
                          [axes[n][i] for n, i in zip(names, idx)])
        r = run(scn, backend="jax")
        P = scn.design.num_pes
        assert sr.avg_latency_us[idx] == r.avg_latency_us, idx
        assert sr.makespan_us[idx] == r.makespan_us, idx
        assert sr.energy_j[idx] == r.energy_j, idx
        assert sr.peak_temp_c[idx] == r.peak_temp_c, idx
        assert np.array_equal(sr.busy_per_pe_us[idx][:P],
                              np.asarray(r.raw["busy_per_pe_us"])), idx


@pytest.mark.parametrize("chunk", [None, 2], ids=["direct", "chunk2"])
@pytest.mark.parametrize("faulted", [False, True],
                         ids=["nofaults", "faults"])
@pytest.mark.parametrize("dynamic", [False, True],
                         ids=["static", "dynamic"])
def test_grid_program_lanes_equal_run(dynamic, faulted, chunk):
    """Each variant of the one grid program, launched directly or streamed
    in 2-lane chunks: every lane of ``sweep()`` equals the per-point
    ``run(backend="jax")``, and each new variant shape is one compile.
    Chunked static grids stream 5 uneven design lanes; chunked DTPM grids
    stream whichever lane axis is wider, the policy axis (G > D) and the
    design axis (D > G) in turn."""
    base = SCN.replace(**{"trace.num_jobs": next(CASE_JOBS)})
    fault_axis = {"faults": FAULT_SETS} if faulted else {}
    if dynamic:
        base = base.replace(governor="ondemand")
        grids = [{"governor_params": POLICY_PARAMS, "seed": [0]},
                 {"design": POINTS[:3], "governor_params": POLICY_PARAMS[:2],
                  "seed": [0]}]
    else:
        grids = [{"design": POINTS, "seed": [0, 1]}]
    chunks = metrics.counter("scenario.sweep.chunks")
    pads = metrics.counter("scenario.shard.pad_lanes")
    for grid in grids:
        axes = {**fault_axis, **grid}
        n0, c0, p0 = compile_count.value, chunks.value, pads.value
        sr = sweep(base, axes=axes, chunk=chunk)
        assert compile_count.value - n0 == 1
        _assert_lanes_equal_run(sr, base, axes)
        if chunk is None:
            assert chunks.value == c0
        elif not dynamic:
            assert chunks.value - c0 == 3          # ceil(5 / 2)
            assert pads.value - p0 == 1            # last chunk: 1 real lane
            assert metrics.counter("scenario.shard.devices").value == 1


def _one_device_mesh():
    return Mesh(np.asarray(jax.devices()[:1]), (LANE_AXIS,))


@pytest.mark.parametrize("chunk", [None, 2], ids=["nochunk", "chunk2"])
@pytest.mark.parametrize("with_mesh", [False, True], ids=["nomesh", "mesh"])
def test_stages_on_host_only_for_chunks(chunk, with_mesh, monkeypatch):
    """Host staging is for chunks alone: ``run_grid`` pulls its lanes to
    host numpy exactly when a chunk is set, with or without a lane mesh."""
    mesh = _one_device_mesh() if with_mesh else None
    assert shardexec.stages_on_host(chunk) == (chunk is not None)
    tables, nodes = _design_lanes(SCN, ["design"], [(p,) for p in POINTS[:2]],
                                  None, host=shardexec.stages_on_host(chunk))
    arrival, app_idx = stack_traces([SCN.job_trace()])
    pulled = []
    real = shardexec.host_tree
    monkeypatch.setattr(shardexec, "host_tree",
                        lambda t: pulled.append(t) or real(t))
    out, temps = shardexec.run_grid(
        tables, nodes, None, None, arrival, app_idx, policy="etf",
        num_jobs=SCN.trace.num_jobs, bins=SCN.thermal.bins,
        repeats=SCN.thermal.repeats, chunk=chunk, mesh=mesh)
    assert bool(pulled) == (chunk is not None)
    assert np.asarray(temps).shape == (2, 1)


def test_run_grid_mesh_launch_places_device_trees_once(monkeypatch):
    """No chunk on a lane mesh: the device trees are placed device to
    device (no host pull, no numpy leaf placed), the placement is kept with
    the tables, and a second launch on the same tables finds it resident
    and equals the plain launch bit for bit."""
    tables, nodes = _design_lanes(SCN, ["design"], [(p,) for p in POINTS[:3]],
                                  None)
    arrival, app_idx = stack_traces([SCN.job_trace()])
    kw = dict(policy="etf", num_jobs=SCN.trace.num_jobs,
              bins=SCN.thermal.bins, repeats=SCN.thermal.repeats)
    plain = shardexec.run_grid(tables, nodes, None, None, arrival, app_idx,
                               **kw)

    def no_host(_):
        raise AssertionError("the mesh launch pulled lanes to the host")

    placed = []
    real_put = shardexec._device_put_lanes

    def device_only(tree, mesh):
        leaves = jax.tree_util.tree_leaves(tree)
        assert all(isinstance(x, jax.Array) for x in leaves)
        placed.append(len(leaves))
        return real_put(tree, mesh)

    monkeypatch.setattr(shardexec, "host_tree", no_host)
    monkeypatch.setattr(shardexec, "_device_put_lanes", device_only)
    hits = metrics.counter("scenario.shard.resident_hits")
    for n in range(2):
        h0 = hits.value
        out = shardexec.run_grid(tables, nodes, None, None, arrival, app_idx,
                                 mesh=_one_device_mesh(), **kw)
        assert hits.value - h0 == n
        assert len(placed) == 1
        for a, b in zip(jax.tree_util.tree_leaves(plain),
                        jax.tree_util.tree_leaves(out)):
            assert isinstance(b, jax.Array)
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert metrics.counter("scenario.shard.devices").value == 1


def test_run_grid_direct_launch_takes_the_trees_as_given(monkeypatch):
    """No chunk and no mesh: one launch on the device trees, with no host
    pull, no slice and no re-placement, returning device arrays."""
    sweep(SCN, axes={"design": POINTS[:2], "seed": [0]})  # warm the shape
    tables, nodes = _design_lanes(SCN, ["design"], [(p,) for p in POINTS[:2]],
                                  None)
    arrival, app_idx = stack_traces([SCN.job_trace()])

    def no_host(_):
        raise AssertionError("the direct launch pulled lanes to the host")

    monkeypatch.setattr(shardexec, "host_tree", no_host)
    monkeypatch.setattr(shardexec, "_device_put_lanes", no_host)
    c0, n0 = metrics.counter("scenario.sweep.chunks").value, \
        compile_count.value
    out, temps = shardexec.run_grid(
        tables, nodes, None, None, arrival, app_idx, policy="etf",
        num_jobs=SCN.trace.num_jobs, bins=SCN.thermal.bins,
        repeats=SCN.thermal.repeats)
    assert isinstance(temps, jax.Array) and temps.shape == (2, 1)
    assert all(isinstance(v, jax.Array) for v in out.values())
    assert metrics.counter("scenario.sweep.chunks").value == c0
    assert compile_count.value == n0


def test_chunked_telemetry_replay_bitexact():
    axes = {"design": POINTS[:3], "seed": [0]}
    _assert_bitexact(sweep(SCN, axes=axes, telemetry=True),
                     sweep(SCN, axes=axes, telemetry=True, chunk=2))
    scn = SCN.replace(governor="ondemand")
    axes = {"governor_params": [(("up_threshold", 0.6),),
                                (("up_threshold", 0.8),),
                                (("up_threshold", 0.9),)], "seed": [0]}
    _assert_bitexact(sweep(scn, axes=axes, telemetry=True),
                     sweep(scn, axes=axes, telemetry=True, chunk=2))


def test_chunk_shape_is_jit_stable():
    """Streaming more lanes through the same chunk width adds no compiles."""
    axes = {"design": POINTS, "seed": [0]}
    sweep(SCN, axes=axes, chunk=2)                         # traces once
    before = metrics.counter("scenario.sweep.compile_count").value
    sweep(SCN, axes={"design": POINTS[:3], "seed": [0]}, chunk=2)
    sweep(SCN, axes=axes, chunk=2)
    assert metrics.counter("scenario.sweep.compile_count").value == before


# ------------------------------------------------ argument validation

def test_chunk_validation():
    axes = {"design": POINTS[:2], "seed": [0]}
    with pytest.raises(ValueError, match="positive lane count"):
        sweep(SCN, axes=axes, chunk=0)
    with pytest.raises(ValueError, match="positive lane count"):
        sweep(SCN, axes=axes, chunk=2.5)
    with pytest.raises(ValueError, match="jax-backend lane options"):
        sweep(SCN, axes={"seed": [0]}, backend="ref", chunk=2)
    with pytest.raises(ValueError, match="jax-backend lane options"):
        sweep(SCN, axes={"seed": [0]}, backend="ref", shard=True)


def test_device_put_lanes_places_host_numpy_on_the_lane_sharding(
        monkeypatch):
    """A host chunk goes straight onto the lane sharding in one placement,
    not first onto one device and then resharded."""
    mesh = _one_device_mesh()
    calls = []
    real_put = jax.device_put

    def spy(x, device=None, **kw):
        calls.append((x, device))
        return real_put(x, device, **kw)

    monkeypatch.setattr(jax, "device_put", spy)
    tree = {"a": np.arange(8.0).reshape(4, 2), "b": np.arange(4)}
    out = shardexec._device_put_lanes(tree, mesh)
    (placed, where), = calls
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(placed))
    assert where == lane_sharding(mesh)
    assert out["a"].sharding == lane_sharding(mesh)
    np.testing.assert_array_equal(np.asarray(out["a"]), tree["a"])


def test_streaming_hides_no_warnings():
    """The streamer passes every launch warning through (a donation the
    backend cannot use is reported, not silenced), and the grid program
    donates nothing, so a chunked sweep lowers without that warning."""
    def launch(piece):
        warnings.warn("Some donated buffers were not usable: float32[4].")
        return {"x": np.asarray(piece["a"])}

    with pytest.warns(UserWarning, match="donated buffers"):
        shardexec._stream({"a": np.arange(3.0)}, 3, None, None, launch)
    scn = SCN.replace(**{"trace.num_jobs": 9})        # a fresh program shape
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*donated buffers")
        sweep(scn, axes={"design": POINTS[:3], "seed": [0]}, chunk=2)


def test_resolve_mesh_single_device():
    # one local device: no mesh — the chunked path runs unsharded
    assert shardexec.resolve_mesh(None) is None
    assert shardexec.resolve_mesh(True) is None
    assert shardexec.resolve_mesh(False) is None


# ------------------------------------------------ multi-device (subprocess)

PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.dse import DesignPoint
    from repro.obs import metrics
    from repro.scenario import Scenario, TraceSpec, sweep

    assert jax.device_count() == 8
    SCN = Scenario(apps=("wifi_tx",), scheduler="etf", governor="design",
                   trace=TraceSpec(rate_jobs_per_ms=25.0, num_jobs=16,
                                   seed=3))
    points = [DesignPoint(cross_cluster_penalty=1.0 + 0.5 * i)
              for i in range(5)]
    FIELDS = ("avg_latency_us", "makespan_us", "energy_j", "peak_temp_c",
              "busy_per_pe_us")

    def check(a, b):
        for f in FIELDS:
            assert np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f))), f
        if a.telemetry is not None:
            for ta, tb in zip(a.telemetry.ravel(), b.telemetry.ravel()):
                assert np.array_equal(ta.util, tb.util)
                assert np.array_equal(ta.temps_c, tb.temps_c)
""")

SCRIPT = PRELUDE + textwrap.dedent("""
    axes = {"design": points, "seed": [0, 1]}
    plain = sweep(SCN, axes=axes, shard=False)
    pads = metrics.counter("scenario.shard.pad_lanes")

    # 5 uneven lanes sharded over 8 devices: padded to 8, bit-for-bit
    check(plain, sweep(SCN, axes=axes))            # shard=None auto-shards
    assert metrics.counter("scenario.shard.devices").value == 8
    assert pads.value == 3                         # 5 lanes -> width 8

    # sharding composes with chunking (chunk=2 -> width 8 per chunk)
    check(plain, sweep(SCN, axes=axes, shard=True, chunk=2))

    # telemetry replays from sharded grid outputs unchanged
    check(sweep(SCN, axes=axes, shard=False, telemetry=True),
          sweep(SCN, axes=axes, shard=True, telemetry=True))

    # the DTPM policy-lane axis shards too
    scn = SCN.replace(governor="ondemand")
    paxes = {"governor_params": [(("up_threshold", 0.5 + 0.08 * i),)
                                 for i in range(5)], "seed": [0]}
    check(sweep(scn, axes=paxes, shard=False), sweep(scn, axes=paxes))
    print("SHARD_OK")
""")

RESIDENT_SCRIPT = PRELUDE + textwrap.dedent("""
    import gc
    import weakref
    from repro.core.applications import get_application
    from repro.core.dvfs import get_governor
    from repro.dse import build_design_batch, evaluate
    from repro.scenario import shardexec

    apps = [get_application("wifi_tx")]
    traces = [SCN.replace(**{"trace.seed": s}).job_trace() for s in (0, 1)]
    batches = {"design": build_design_batch(points, apps),
               "ondemand": build_design_batch(
                   points, apps, governor=get_governor("ondemand"))}

    def ev(gov, batch, shard):
        return evaluate(points, apps, traces, batch=batch, governor=gov,
                        shard=shard)

    def same(a, b):
        for f in ("latency_per_trace_us", "energy_per_trace_j",
                  "temp_per_trace_c"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f

    plain = {g: ev(g, b, False) for g, b in batches.items()}

    # the unchunked mesh path never stages on the host: no host pull, and
    # every placement takes device arrays
    def no_host(_):
        raise AssertionError("the unchunked mesh path pulled lanes to host")

    real_put = shardexec._device_put_lanes

    def device_only(tree, mesh):
        assert all(isinstance(x, jax.Array)
                   for x in jax.tree_util.tree_leaves(tree))
        return real_put(tree, mesh)

    shardexec.host_tree = no_host
    shardexec._device_put_lanes = device_only
    hits = metrics.counter("scenario.shard.resident_hits")
    pads = metrics.counter("scenario.shard.pad_lanes")

    # 5 uneven designs over 8 devices (3 pad lanes dropped), static and
    # dynamic-governor batches: bit for bit; the second call on a batch
    # finds its lanes resident and pads nothing more
    for g, batch in batches.items():
        h0, p0 = hits.value, pads.value
        same(plain[g], ev(g, batch, True))
        assert (hits.value, pads.value) == (h0, p0 + 3)
        assert metrics.counter("scenario.shard.devices").value == 8
        same(plain[g], ev(g, batch, True))
        assert (hits.value, pads.value) == (h0 + 1, p0 + 3)

    # a fresh batch of the same designs misses, and its kept placement is
    # released with it
    fresh = build_design_batch(points, apps)
    h0 = hits.value
    same(plain["design"], ev("design", fresh, True))
    assert hits.value == h0
    placed, = vars(fresh.tables)["_lane_placements"].values()
    refs = [weakref.ref(fresh)] + [weakref.ref(x) for x in
                                   jax.tree_util.tree_leaves(placed)]
    del fresh, placed
    gc.collect()
    assert all(r() is None for r in refs)
    print("RESIDENT_OK")
""")


def _run_on_8_devices(script: str) -> str:
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_sweep_bitexact_8_virtual_devices():
    assert "SHARD_OK" in _run_on_8_devices(SCRIPT)


def test_resident_lanes_8_virtual_devices():
    """``evaluate(batch=…, shard=True)`` with no chunk: no host staging,
    bit-for-bit equal to ``shard=False`` (uneven lanes, static and dynamic
    governors), the placement kept with the batch and released with it."""
    assert "RESIDENT_OK" in _run_on_8_devices(RESIDENT_SCRIPT)
