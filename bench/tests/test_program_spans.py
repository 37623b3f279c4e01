"""The accepted per-layer metrics and the breakdown on a hand-built view
that also holds the program's own host spans (``repro.*``,
``repro.obs.metrics.span``).  ``read_xplane`` keeps only ``bench.*``
spans, so such a view is what the reduction gives once its name filter
admits ``repro.*`` too; these cases show that the accepted metrics would
read the same there."""
import pytest

from bench.harness import trace
from bench.harness.runner import Window
from bench.tests.test_trace_reduce import _reader, _view

# A table build split in two inside ``bench.table_build``, then per call an
# outer ``repro.run`` holding stage, launch, wait and assemble, and one
# stage span that crosses the window's end.
PROGRAM_SPANS = [
    ("repro.tables.build", 5.0, 8.0), ("repro.tables.stack", 8.0, 9.0),
    ("repro.run", 9.0, 49.0), ("repro.stage", 9.0, 10.0),
    ("repro.launch", 10.0, 11.0), ("repro.wait", 11.0, 40.0),
    ("repro.assemble", 40.0, 45.0),
    ("repro.run", 58.0, 89.0), ("repro.stage", 58.0, 59.5),
    ("repro.launch", 59.5, 60.0), ("repro.wait", 60.0, 70.0),
    ("repro.assemble", 70.0, 80.0),
    ("repro.stage", 95.0, 105.0)]

ACCEPTED = ("device_idle_share", "scan_device_ms_per_call",
            "host_ms_per_call", "table_build_ms_per_call")


def _window(view):
    return Window(setup_s=1.0, window_s=1e-7, call_s=[4.5e-8, 3.2e-8],
                  tasks=[10, 10], view=view)


def _view_with_program_spans():
    v = _view()
    v.spans = sorted(v.spans + PROGRAM_SPANS, key=lambda s: s[1])
    return v


@pytest.mark.parametrize("name", ACCEPTED)
def test_accepted_metrics_ignore_program_spans(name):
    r = _reader(name)
    assert r.read(_window(_view_with_program_spans())) == r.read(
        _window(_view()))


def test_breakdown_labels_idle_by_program_span():
    b0 = trace.breakdown(_view(), top=50)
    b = trace.breakdown(_view_with_program_spans(), top=50)
    assert b["device_ops"] == b0["device_ops"]
    idle = dict(b["idle_gaps"])
    # dev0 waits in none of its gaps; dev1 waits 20-40 and 60-70
    assert idle["repro.wait"] == pytest.approx((0 + 30) * 1e-9 / 2)
    assert idle["repro.assemble"] == pytest.approx((15 + 15) * 1e-9 / 2)
    assert idle["repro.tables.build"] == pytest.approx((3 + 3) * 1e-9 / 2)
    # 9-10 and 58-59.5 on both devices, and 95-100 of the span that
    # crosses the window's end
    assert idle["repro.stage"] == pytest.approx((7.5 + 7.5) * 1e-9 / 2)
    # the program's spans name all of the table build's idle time
    assert "bench.table_build" not in idle
    assert sum(idle.values()) == pytest.approx((60 + 90) * 1e-9 / 2)
