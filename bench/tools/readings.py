#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, for one cell,
in one process (set-up is paid once):

    python3 bench/tools/readings.py --workload <cell> --seeds 11 12 ... \\
        --seconds 3 [--control-seeds 11 12 13] [--out FILE]

For each seed it runs a window of ``--seconds`` at the cell's own load and
prints the numbers the check compares (the program's); for each control
seed it prints the same numbers with the reference computed in bfloat16 put
in the program's place (the control: one precision step below the float32
the configuration states).  The limits are then set between the largest
program reading and the smallest control reading (``bench/limits``).
Benchmark runs never run this.  Needs a TPU, as a run does.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    import jax
    import ml_dtypes
    from bench.harness import runner, traffic
    from bench.harness.spec import load_cell
    from repro.jax_cache import enable_compile_cache

    cell = load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"readings: needs {cell.chips} chips", file=sys.stderr)
        return 3
    enable_compile_cache(ROOT / ".jax_cache")
    span = jax.profiler.TraceAnnotation
    rows = []
    for seed in args.seeds:
        entry = cell.entry(cell.config, cell.traffic, seed, span)
        entry.setup()
        warm = entry.inputs(traffic.WARM, 0)
        entry.stats(entry.call(warm), warm)
        calls, stats, call_s, window_s, _ = runner.measure(
            entry, entry.call, args.seconds, span)
        t0 = time.perf_counter()
        row = {"seed": seed, "calls": len(calls),
               "program": runner.compare(cell, calls, stats, seed)}
        row["check_s"] = time.perf_counter() - t0
        if seed in args.control_seeds:
            row["control"] = runner.compare(
                cell, calls, stats, seed, control_dtype=ml_dtypes.bfloat16)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload,
               "device": devices[0].device_kind, "rows": rows}
    for side in ("program", "control"):
        vals = [r[side] for r in rows if side in r]
        if vals:
            agg = max if side == "program" else min
            summary[side + "_" + agg.__name__] = {
                k: agg(v[k] for v in vals) for k in vals[0]}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
