#!/usr/bin/env python3
"""Call time against trace length, for choosing a cell's ``num_jobs``:

    python3 bench/tools/scale.py --workload <cell> --jobs 150 300 600 \\
        [--stop-s 4.5] [--out FILE]

For each trace length it builds the cell's entry at that length (the rest
of the traffic as committed), times the warm-up call (compilation
included) and three calls, and one reference lane; it stops at the first
length whose median call exceeds ``--stop-s``.  One JSON line per length.
Benchmark runs never run this.  Needs a TPU, as a run does.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--jobs", type=int, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    ap.add_argument("--stop-s", type=float, default=4.5)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    import jax
    from bench.harness import traffic
    from bench.harness.spec import load_cell
    from repro.jax_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("scale: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache(ROOT / ".jax_cache")
    span = jax.profiler.TraceAnnotation
    for jobs in args.jobs:
        cell = load_cell(ROOT, args.workload)
        cell.traffic["num_jobs"] = jobs
        cell.config["trace_jobs"] = [jobs]
        entry = cell.entry(cell.config, cell.traffic, args.seed, span)
        entry.setup()
        t0 = time.perf_counter()
        warm = entry.inputs(traffic.WARM, 0)
        entry.stats(entry.call(warm), warm)
        warm_s = time.perf_counter() - t0
        call_s = []
        for i in range(3):
            c = entry.inputs(traffic.WINDOW, i)
            t0 = time.perf_counter()
            entry.stats(entry.call(c), c)
            call_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        cell.entry.reference(cell.config, c.lanes[-1])
        row = {"workload": args.workload, "jobs": jobs, "lanes": len(c.lanes),
               "tasks": c.tasks, "warm_s": warm_s, "call_s": call_s,
               "ref_lane_s": time.perf_counter() - t0,
               "peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
                   "peak_bytes_in_use")}
        print(json.dumps(row), flush=True)
        if args.out:
            with args.out.open("a") as f:
                f.write(json.dumps(row) + "\n")
        if statistics.median(call_s) > args.stop_s:
            break
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
