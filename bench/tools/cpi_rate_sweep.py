#!/usr/bin/env python3
"""The highest CPI rate the Table-2 SoC sustains on pulse-Doppler CPIs,
by a sweep of offered rates through the program's ``sweep()``:

    python3 bench/tools/cpi_rate_sweep.py [--cpis 64] [--seeds 3] \\
        [--rates 0.5 0.6 ...] [--scheduler etf] [--out FILE]

Each lane is a trace of ``--cpis`` periodic CPI arrivals (±5 % jitter, as
the ``pd_cpi_grid`` traffic draws them) at one offered rate.  The SoC
sustains a rate when the backlog at the end, the makespan less the last
arrival, stays within twice the latency of a CPI alone (offered at a
tenth of the lowest rate): past the highest sustained rate the backlog
grows with every CPI.  Prints one JSON line per rate and a summary line.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpis", type=int, default=64)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--rates", type=float, nargs="+",
                    default=[0.5, 0.6, 0.7, 0.75, 0.8, 0.85, 0.9, 0.95, 1.0,
                             1.1, 1.2])
    ap.add_argument("--scheduler", default="etf")
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    import jax
    from bench.entries.cpi_grid import periodic_trace
    from bench.harness.entries import Entry
    from bench.harness.spec import load_cell

    cell = load_cell(ROOT, "pd_cpi_grid")
    entry = cell.entry(cell.config, cell.traffic, 0,
                       jax.profiler.TraceAnnotation)
    base = entry.scenario(cell.config["design"], args.scheduler,
                          "performance", ())
    from repro.scenario import sweep

    def lanes(rates, cpis):
        traces = [periodic_trace(r, cell.traffic["jitter"], cpis,
                                 entry.ref_app.name, 1000 * k + s)
                  for k, r in enumerate(rates) for s in range(args.seeds)]
        out = sweep(base, axes={"trace": [Entry.job_trace(t)
                                          for t in traces]})
        last = np.asarray([t.arrival_us[-1] for t in traces], np.float64)
        return (np.asarray(out.avg_latency_us).reshape(len(rates), -1),
                (np.asarray(out.makespan_us) - last).reshape(len(rates), -1))

    alone, _ = lanes([min(args.rates) / 10], 4)
    alone_us = float(alone.mean())
    lat, backlog = lanes(args.rates, args.cpis)
    rows = []
    for r, l, b in zip(args.rates, lat, backlog):
        row = {"rate_cpi_per_ms": r, "mean_latency_us": l.tolist(),
               "backlog_us": b.tolist(),
               "sustained": bool((b <= 2 * alone_us).all())}
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate_cpi_per_ms"] for r in rows if r["sustained"]]
    summary = {"scheduler": args.scheduler, "cpis": args.cpis,
               "alone_latency_us": alone_us,
               "device": jax.devices()[0].device_kind,
               "highest_sustained": max(ok) if ok else None}
    print(json.dumps(summary), flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps({"summary": summary, "rows": rows},
                                       indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
