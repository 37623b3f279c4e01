"""Sharded + chunked sweep execution (DESIGN.md §13).

The lane-scaling contract: ``sweep(..., chunk=N)`` and ``sweep(...,
shard=True)`` are *bit-for-bit* equal to the plain single-device sweep —
including uneven lane counts (pad lanes are dropped) and telemetry replay —
because lanes are independent simulations and the chunk programs run the
same fused grid bodies.  Multi-device sharding is exercised in a subprocess
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
from repro.obs import metrics
from repro.scenario import Scenario, TraceSpec, sweep
from repro.scenario import shardexec
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


# ------------------------------------------------ chunked == plain (1 device)

def test_chunked_static_sweep_bitexact():
    """chunk=2 over 5 uneven design lanes: equal to the plain sweep, with
    the streaming counters accounting for every chunk and pad lane."""
    axes = {"design": POINTS, "seed": [0, 1]}
    plain = sweep(SCN, axes=axes)
    chunks = metrics.counter("scenario.sweep.chunks")
    pads = metrics.counter("scenario.shard.pad_lanes")
    c0, p0 = chunks.value, pads.value
    chunked = sweep(SCN, axes=axes, chunk=2)
    _assert_bitexact(plain, chunked)
    assert chunks.value - c0 == 3          # ceil(5 / 2)
    assert pads.value - p0 == 1            # last chunk holds 1 real lane
    assert metrics.counter("scenario.shard.devices").value == 1


def test_chunked_dtpm_sweep_bitexact_both_lane_axes():
    """The DTPM grid streams whichever lane axis is wider: the design axis
    (D >= G) and the stacked GovernorPolicy axis (G > D) both chunk clean."""
    scn = SCN.replace(governor="ondemand")
    params = [(("up_threshold", 0.5 + 0.08 * i),) for i in range(5)]
    # G=5 > D=1: policy lanes stream
    axes = {"governor_params": params, "seed": [0, 1]}
    _assert_bitexact(sweep(scn, axes=axes), sweep(scn, axes=axes, chunk=2))
    # D=3 > G=2: design lanes stream
    axes = {"design": POINTS[:3], "governor_params": params[:2],
            "seed": [0]}
    _assert_bitexact(sweep(scn, axes=axes), sweep(scn, axes=axes, chunk=2))


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
    mesh = Mesh(np.asarray(jax.devices()[:1]), (LANE_AXIS,))
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
    backend cannot use is reported, not silenced), and the grid programs
    donate nothing, so a chunked sweep lowers without that warning."""
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

SCRIPT = textwrap.dedent("""
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
    axes = {"design": points, "seed": [0, 1]}
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


def test_sharded_sweep_bitexact_8_virtual_devices():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD_OK" in out.stdout, out.stdout
