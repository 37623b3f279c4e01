"""Table build time per call (layer: dse table build): the benchmark's
``bench.table_build`` span around ``build_design_batch``, summed over the
traced window and divided by its calls.  Nothing to read in cells whose
calls build no tables."""
UNIT = "ms"
SOURCE = "host_clock"
BETTER = "lower"
LAYER = "dse table build"
MOVES = "sim_tasks_per_s"


def read(w):
    v = w.view
    calls = v.calls()
    builds = v.span_intervals("bench.table_build")
    lo, hi = v.window
    builds = [(s, e) for s, e in builds if s >= lo and e <= hi]
    if not calls or not builds:
        return None
    return sum(e - s for s, e in builds) / len(calls) * 1e-6
