"""Device time of the simulation programs per call, per device (layer:
grid programs and epoch scan (device)).  Programs are matched by their XLA
module name against ``PROGRAMS``; a rename of the grid programs is fixed
here alone."""
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
LAYER = "grid programs and epoch scan (device)"
MOVES = "sim_tasks_per_s"

PROGRAMS = frozenset({
    "jit__sweep_grid", "jit__sweep_grid_dtpm", "jit__sweep_grid_faults",
    "jit__sweep_grid_dtpm_faults", "jit__simulate", "jit__simulate_dtpm",
    "jit__simulate_grid", "jit__simulate_grid_faults",
    "jit__peak_temp_single",
})


def read(w):
    v = w.view
    calls = v.calls()
    if not calls or not v.devices:
        return None
    lo, hi = v.window
    t = 0.0
    for dev in v.devices:
        t += sum(min(e, hi) - max(s, lo) for n, s, e in v.programs[dev]
                 if n in PROGRAMS and e > lo and s < hi)
    if t == 0.0:
        return None
    return t / len(v.devices) / len(calls) * 1e-6
