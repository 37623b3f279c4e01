"""Device time of the simulation programs per simulated task (layer: grid
programs and epoch scan (device)): the module time of the programs
``scan_device_ms_per_call`` matches, summed over the cell's devices, over
the DAG tasks of the traced window's calls.  Comparable across DAG shapes
(a 6-task chain, a 449-task CPI) and chip counts: a sharded call's
programs on every chip count."""
from bench.metrics.scan_device_ms_per_call import PROGRAMS

UNIT = "us"
SOURCE = "device_trace"
BETTER = "lower"
LAYER = "grid programs and epoch scan (device)"
MOVES = "sim_tasks_per_s"


def read(w):
    v = w.view
    tasks = sum(w.tasks)
    if not v.calls() or not v.devices or not tasks:
        return None
    lo, hi = v.window
    t = 0.0
    for dev in v.devices:
        t += sum(min(e, hi) - max(s, lo) for n, s, e in v.programs[dev]
                 if n in PROGRAMS and e > lo and s < hi)
    if t == 0.0:
        return None
    return t * 1e-3 / tasks
