"""One run of one cell: set-up, warm-up, the measured window, the check
against the reference, and the result line.

The window is a closed loop: one caller makes a call, waits for its
results on the host, then makes the next, for ``--seconds``; the call that
crosses the deadline completes and the window ends with it, so the window
holds whole calls only.  Calls, their inputs and their records are
bracketed by the benchmark's own spans (``bench.window``, ``bench.inputs``,
``bench.call``, ``bench.record``, and inside a call ``bench.sweep``,
``bench.run``, ``bench.table_build``, ``bench.evaluate``), which a traced
run reads on the profiler's clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from . import check, entries, trace, traffic
from .spec import Cell

# A traced window is cut to this length: the profiler records every XLA op
# on the device (about 1.5 million a second in the DTPM loop) and writes
# them out at the end at about 25 us each.
TRACE_SECONDS = 2.0


@dataclasses.dataclass
class Window:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    setup_s: float
    window_s: float
    call_s: List[float]
    tasks: List[int]
    view: Optional[trace.TraceView] = None


@contextlib.contextmanager
def compile_events():
    """Counts JAX's backend compilations while open (a persistent-cache hit
    counts too: it is a program loaded).  Yields a one-element list."""
    import jax.monitoring
    count = [0]

    def on(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def measure(entry, call, seconds: float, span, capture=None):
    """The window: calls back to back until ``seconds`` have passed.
    Returns the calls, their per-lane statistics, each call's wall time,
    the window's length and, under ``capture``, the reduced trace."""
    calls: List[entries.Call] = []
    stats: List[Dict] = []
    call_s: List[float] = []
    with capture if capture is not None else contextlib.nullcontext():
        with span("bench.window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                with span("bench.inputs"):
                    c = entry.inputs(traffic.WINDOW, len(calls))
                a = time.perf_counter()
                with span("bench.call"):
                    out = call(c)
                b = time.perf_counter()
                with span("bench.record"):
                    stats.append(entry.stats(out, c))
                calls.append(c)
                call_s.append(b - a)
                if b >= deadline:
                    break
            window_s = time.perf_counter() - t0
    return calls, stats, call_s, window_s, (
        capture.view if capture is not None else None)


def compare(cell: Cell, calls, stats, seed: int,
            control_dtype=None) -> Dict[str, float]:
    """The numbers compared for ``correct``: the program's statistics
    against the reference on the lanes the seed picks.  With
    ``control_dtype`` the reference computed at that precision stands in
    for the program (the control)."""
    picks = check.sample_lanes(calls, cell.traffic["check_lanes"], seed)
    ref = functools.partial(cell.entry.reference, cell.config)
    nonfinite = check.nonfinite(stats)
    if control_dtype is not None:
        stats = check.control_stats(ref, calls, picks, control_dtype,
                                    list(stats[0]))
    numbers = check.gaps(check.references(ref, calls, picks), stats)
    numbers["nonfinite_lanes"] = nonfinite
    return numbers


def run_cell(root: Path, cell: Cell, seed: int, seconds: float,
             traced: bool, t_start: float,
             wrap: Optional[Callable] = None) -> Dict:
    """The result line's object.  ``wrap(call)`` replaces the entry's call
    (used only by the tests that break the timed path)."""
    import jax
    from repro.jax_cache import enable_compile_cache
    from repro.obs import metrics as repro_metrics

    enable_compile_cache(root / ".jax_cache")
    span = jax.profiler.TraceAnnotation
    entry = cell.entry(cell.config, cell.traffic, seed, span)
    call = entry.call if wrap is None else wrap(entry.call)

    entry.setup()
    warm = entry.inputs(traffic.WARM, 0)
    entry.stats(call(warm), warm)
    setup_s = time.perf_counter() - t_start

    traces_before = repro_metrics.jit_compile_count()
    devices = jax.devices()[:cell.chips]
    capture = (trace.Capture(root / ".bench_trace" / cell.name,
                             [d.id for d in devices]) if traced else None)
    with compile_events() as compiles:
        calls, stats, call_s, window_s, view = measure(
            entry, call, min(seconds, TRACE_SECONDS) if traced else seconds,
            span, capture)
    print(f"compiles in the window: {compiles[0]} backend compilations or "
          f"cache loads, {repro_metrics.jit_compile_count() - traces_before}"
          f" repro program traces (compile_count counters); setup_s "
          f"{setup_s!r}", flush=True)

    # the CPU backend (used only by the tests) keeps no memory statistics
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    w = Window(setup_s, window_s, call_s, [c.tasks for c in calls], view)

    t_check = time.perf_counter()
    numbers = compare(cell, calls, stats, seed)
    correct, table = check.judge(numbers, check.load_limits(root, cell.name))
    print(f"reference check: {cell.traffic['check_lanes']} lanes in "
          f"{time.perf_counter() - t_check!r} s", flush=True)

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.reader.read(w)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(calls),
              "failed": sum(1 for s in stats if check.nonfinite([s])),
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = sum(trace.total(view.busy(d))
                               for d in view.devices) * 1e-9 / max(
                                   len(view.devices), 1)
        device["window_s"] = (view.window[1] - view.window[0]) * 1e-9
        result["breakdown"] = trace.breakdown(view)
    result["checks"] = table
    return result


def main(argv: List[str], t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell once and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    root = Path(__file__).resolve().parents[2]
    sys.path.insert(0, str(root / "src"))
    from .spec import load_cell
    cell = load_cell(root, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); this "
              f"benchmark measures the chip only", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3

    result = run_cell(root, cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    for name, t in result["checks"].items():
        print(f"check {name}: {t['value']!r} (limit {t['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
