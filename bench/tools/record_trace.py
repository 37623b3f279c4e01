#!/usr/bin/env python3
"""Record a small traced window of one cell as a test fixture for the
trace reduction (``bench/tests/data/<cell>_trace.json``):

    python3 bench/tools/record_trace.py --workload <cell> --seed <n> \\
        --seconds 0.5 --out bench/tests/data/<cell>_trace.json

The file holds the reduced trace (``TraceView``), the window's call times
and task counts, and the per-layer metrics and breakdown computed from it,
so that ``bench/tests/test_trace_reduce.py`` can reduce it again and
compare.  Needs a TPU.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench.harness import runner, trace, traffic
    from bench.harness.spec import load_cell
    from repro.jax_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache(ROOT / ".jax_cache")
    cell = load_cell(ROOT, args.workload)
    span = jax.profiler.TraceAnnotation
    entry = cell.entry(cell.config, cell.traffic, args.seed, span)
    entry.setup()
    warm = entry.inputs(traffic.WARM, 0)
    entry.stats(entry.call(warm), warm)
    setup_s = time.perf_counter() - T_START
    capture = trace.Capture(ROOT / ".bench_trace" / cell.name,
                            [d.id for d in jax.devices()[:cell.chips]])
    calls, _, call_s, window_s, view = runner.measure(
        entry, entry.call, args.seconds, span, capture)
    w = runner.Window(setup_s, window_s, call_s, [c.tasks for c in calls],
                      view)
    metrics = {m.name: m.reader.read(w) for m in cell.per_layer}
    rec = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "setup_s": setup_s, "window_s": window_s, "call_s": call_s,
           "tasks": w.tasks, "view": view.to_json(),
           "metrics": {k: v for k, v in metrics.items() if v is not None},
           "breakdown": trace.breakdown(view)}
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(rec))
    print(json.dumps({k: rec[k] for k in ("workload", "metrics",
                                          "breakdown")}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
