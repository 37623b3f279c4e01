"""Device idle share of the traced window (layer: device): one minus the
union of program executions over the window's length, mean over the
cell's devices, in percent."""
from bench.harness import trace

UNIT = "%"
SOURCE = "device_trace"
BETTER = "lower"
LAYER = "device"
MOVES = "sim_tasks_per_s"


def read(w):
    v = w.view
    if not v.devices:
        return None
    lo, hi = v.window
    idle = [1.0 - trace.total(v.busy(d)) / (hi - lo) for d in v.devices]
    return 100.0 * sum(idle) / len(idle)
