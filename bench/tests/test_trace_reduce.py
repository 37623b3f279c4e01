"""The reduction from a profiler trace to the per-layer metrics, checked
on a hand-built trace and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench.harness import trace
from bench.harness.runner import Window
from bench.harness.spec import load_metric
from bench.tests.cells import ROOT

DATA = Path(__file__).parent / "data"
PER_LAYER = {m["name"]: m for m in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["per_layer"]}


def _reader(name):
    return load_metric(ROOT, PER_LAYER[name]).reader


def _view():
    # window 0..100 ns; two calls; device busy 10-30 (grid), 25-40 (small
    # program), 60-70 (grid) on dev0 and 10-20 on dev1
    return trace.TraceView(
        window=(0.0, 100.0),
        programs={
            "/device:TPU:0": [("jit__sweep_grid", 10.0, 30.0),
                              ("jit_broadcast_in_dim", 25.0, 40.0),
                              ("jit__sweep_grid", 60.0, 70.0)],
            "/device:TPU:1": [("jit__sweep_grid", 10.0, 20.0)]},
        spans=[("bench.window", 0.0, 100.0),
               ("bench.inputs", 0.0, 5.0),
               ("bench.call", 5.0, 50.0),
               ("bench.table_build", 5.0, 9.0),
               ("bench.record", 50.0, 55.0),
               ("bench.inputs", 55.0, 58.0),
               ("bench.call", 58.0, 90.0)])


def test_interval_algebra():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert trace.gaps([(1, 4), (5, 7)], 0, 10) == [(0, 1), (4, 5), (7, 10)]
    assert trace.covered([(1, 4), (5, 7)], 2, 6) == 3
    assert trace.innermost([("a", 0, 10), ("b", 2, 4)], 3) == "b"
    assert trace.innermost([("a", 0, 10)], 11) is None


def test_busy_union_and_metrics():
    v = _view()
    assert v.busy("/device:TPU:0") == [(10.0, 40.0), (60.0, 70.0)]
    w = Window(setup_s=1.0, window_s=1e-7, call_s=[4.5e-8, 3.2e-8],
               tasks=[10, 10], view=v)
    # idle: dev0 60 of 100, dev1 90 of 100 -> mean 75 %
    assert _reader("device_idle_share").read(w) == pytest.approx(75.0)
    # grid programs: dev0 30 ns, dev1 10 ns -> 20 ns per device, 2 calls
    assert _reader("scan_device_ms_per_call").read(w) == pytest.approx(1e-5)
    # call 1: 45 ns wall, busy dev0 30 dev1 10 -> host 25; call 2: 32 wall,
    # busy dev0 10 dev1 0 -> host 27; mean 26 ns
    assert _reader("host_ms_per_call").read(w) == pytest.approx(26e-6)
    assert _reader("table_build_ms_per_call").read(w) == pytest.approx(2e-6)


def test_nothing_to_read_gives_none():
    v = _view()
    v.spans = [s for s in v.spans if s[0] != "bench.table_build"]
    v.programs = {d: [p for p in ps if p[0] == "jit_broadcast_in_dim"]
                  for d, ps in v.programs.items()}
    w = Window(1.0, 1e-7, [1e-8], [1], v)
    assert _reader("table_build_ms_per_call").read(w) is None
    assert _reader("scan_device_ms_per_call").read(w) is None


def test_only_the_cells_devices_count():
    """A plane of a device outside the cell is dropped; a device of the
    cell that ran nothing stays, idle."""
    v = _view()
    extra = dict(v.programs, **{"/device:TPU:7": []})
    one = trace.select_devices(extra, [0])
    assert list(one) == ["/device:TPU:0"]
    v1 = trace.TraceView(v.window, one, v.spans)
    w = Window(1.0, 1e-7, [4.5e-8, 3.2e-8], [10, 10], v1)
    # dev0 alone: idle 60 of 100; grid programs 30 ns over 2 calls
    assert _reader("device_idle_share").read(w) == pytest.approx(60.0)
    assert _reader("scan_device_ms_per_call").read(w) == pytest.approx(1.5e-5)
    two = trace.select_devices(extra, [0, 2])
    assert two["/device:TPU:2"] == []
    w2 = Window(1.0, 1e-7, [4.5e-8, 3.2e-8], [10, 10],
                trace.TraceView(v.window, two, v.spans))
    assert _reader("device_idle_share").read(w2) == pytest.approx(80.0)


def test_breakdown_labels_idle_by_host_span():
    b = trace.breakdown(_view())
    progs = dict(b["device_ops"])
    assert progs["jit__sweep_grid"] == pytest.approx((30 + 10) * 1e-9 / 2)
    idle = dict(b["idle_gaps"])
    # dev0 gaps: 0-10 (inputs 0-5, call 5-10), 40-60, 70-100
    assert idle["bench.inputs"] == pytest.approx((5 + 3 + 5 + 3) * 1e-9 / 2)
    assert sum(idle.values()) == pytest.approx((60 + 90) * 1e-9 / 2)


@pytest.mark.parametrize("path", sorted(DATA.glob("*_trace.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    """A trace recorded on the chip, reduced again: the same numbers as the
    run that recorded it printed."""
    rec = json.loads(path.read_text())
    w = Window(rec["setup_s"], rec["window_s"], rec["call_s"], rec["tasks"],
               trace.TraceView.from_json(rec["view"]))
    for name, want in rec["metrics"].items():
        assert _reader(name).read(w) == pytest.approx(want, rel=1e-12)
    assert trace.breakdown(w.view) == json.loads(json.dumps(rec["breakdown"]))
