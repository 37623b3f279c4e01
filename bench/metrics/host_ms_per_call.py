"""Host time per call (layer: entry (host)): each call's wall time minus
the device-busy time inside it (the union of program executions, mean over
the cell's devices), averaged over the traced window's calls.  Covers
scenario expansion, trace stacking, table lookup or build, lane placement
dispatch and result assembly."""
from bench.harness import trace

UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
LAYER = "entry (host)"
MOVES = "sim_tasks_per_s"


def read(w):
    v = w.view
    calls = v.calls()
    if not calls or not v.devices:
        return None
    busy = {d: v.busy(d) for d in v.devices}
    host = []
    for s, e in calls:
        dev = sum(trace.covered(b, s, e) for b in busy.values()) / len(busy)
        host.append((e - s) - dev)
    return sum(host) / len(host) * 1e-6
