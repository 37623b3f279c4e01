#!/usr/bin/env python3
"""Chip smoke test: the DS3X simulator's main path on a TPU, checked
against the reference kernel.

    python chip_smoke.py              # phases a-d on one chip
    python chip_smoke.py --chips 4    # lane-sharded DSE sweep vs unsharded

Everything runs in this one process (a chip belongs to one process at a
time) through the public entry points, at the benchmarks' sizes:

a. ``run(backend="jax")`` on the Table-2 SoC, wifi_tx, 150 jobs at
   20 jobs/ms, under the performance, ondemand and throttle (27 C cap,
   50 ms thermal step) governors, against ``run(backend="ref")``; the same
   scenarios on a free interconnect must give the identical schedule.
b. The Fig-3 grid (10 rates x 3 seeds, 120 jobs) through
   ``sweep(backend="jax")`` for met, etf and the ILP table, lane by lane
   against the same sweep on ``backend="ref"``, and the paper's ordering
   etf < ilp < met at the highest rate.
c. The 64-design (latin hypercube) x 4-trace DSE sweep through
   ``dse.evaluate``; 8 designs bit for bit against per-design
   ``run(backend="jax")`` (the padded-batch contract, DESIGN.md §5).
d. A ``faults`` lane axis with one and two fail-stop PEs on a free
   interconnect, against ``backend="ref"`` (DESIGN.md §14).

``--chips 4`` runs only the DSE sweep of phase c with ``shard=True``
across the four chips and with ``shard=False``, and requires the lane mesh
to be four wide and the two results to be bit for bit equal (DESIGN.md
§13).

Tolerances, as DESIGN.md §1 states them: on a free interconnect schedules
(finish times, PEs, latched OPPs) and makespans are exact and the rest is
held to the float32 bounds below; with interconnect cost the kernels'
float32 vs float64 arithmetic may break ties differently, so the metrics
are held to the looser bounds of ``tests/test_dtpm.py``.

Each phase prints its cold (compile included) and warm wall time; warm
calls end on host arrays.  These are smoke wall times, not benchmark
numbers.  The last line of stdout is one JSON object naming the device.
With no TPU, outside the repository, or when any check fails, the script
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# free interconnect: exact agreement (float32 arithmetic on both kernels)
RTOL_LATENCY_EXACT = 1e-6     # mean of exact job latencies, f32 vs f64 sum
RTOL_ENERGY_EXACT = 1e-5      # as tests/test_faults_jax.py
# with interconnect cost (tie-breaks may differ; as tests/test_dtpm.py)
RTOL_LATENCY = 1e-4
RTOL_ENERGY = 1e-3
# peak temperature of the RC network, float32 (device) vs float64 (host):
# a few float32 ulps at 25-30 C, accumulated over the thermal scan
ATOL_PEAK_C = 1e-4


class Checks:
    """Collects every comparison of a phase; prints each one's worst error
    so a failing chip run shows all disagreements, not only the first."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failures = []

    def close(self, name, got, want, rtol=0.0, atol=0.0):
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        err = np.abs(got - want)
        bound = atol + rtol * np.abs(want)
        worst = float(np.max(err / np.maximum(np.abs(want), 1e-30)))
        bad = int(np.sum(err > bound))
        print(f"  {name}: max rel err {worst!r} (rtol {rtol}, atol {atol}),"
              f" {bad}/{err.size} outside")
        if bad or got.shape != want.shape:
            self.failures.append(f"{name}: {bad}/{err.size} outside bound")

    def equal(self, name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        bad = (int(np.sum(got != want)) if got.shape == want.shape
               else max(got.size, want.size))
        print(f"  {name}: {bad}/{want.size} differ")
        if bad:
            self.failures.append(f"{name}: {bad}/{want.size} differ")

    def true(self, name, cond, detail=""):
        print(f"  {name}: {'ok' if cond else 'FAILED'} {detail}")
        if not cond:
            self.failures.append(f"{name} {detail}")

    def done(self):
        if self.failures:
            raise AssertionError(f"phase {self.phase}: "
                                 + "; ".join(self.failures))


def cold_warm(label, fn):
    """Call ``fn`` twice (compile, then cached); print both wall times."""
    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    warm = time.perf_counter() - t0
    print(f"  {label}: cold {cold!r} s, warm {warm!r} s (smoke wall time)")
    return out


def _free_interconnect_soc():
    """The Table-2 SoC with zero-cost communication — the comm-free traces
    on which the two kernels agree bit for bit.  A ``DesignPoint`` subclass,
    so it rides through ``Scenario``/``run``/``sweep`` unchanged."""
    from repro.core.resources import CommModel
    from repro.dse import DesignPoint

    @dataclasses.dataclass(frozen=True, order=True)
    class FreeInterconnect(DesignPoint):
        def to_db(self):
            db = super().to_db()
            db.comm = CommModel(startup_us=0.0, bw_bytes_per_us=1e30)
            return db

    return FreeInterconnect()


def _host_binned_peak(records, num_pes, nodes, p_act, p_idle, makespan_us,
                      bins, repeats):
    """Float64 host twin of the static-governor peak temperature
    (``dse.thermal_jax.binned_power_trace`` + ``peak_temperature``) over a
    reference-kernel schedule; the reference backend itself reports a
    steady-state read-out, a different quantity (``scenario.result``)."""
    from repro.core import thermal
    dt_us = max(makespan_us, 1e-6) / bins
    edges = np.arange(bins) * dt_us
    busy = np.zeros((bins, num_pes))
    for r in records:
        busy[:, r.pe_id] += np.clip(np.minimum(r.finish_us, edges + dt_us)
                                    - np.maximum(r.start_us, edges), 0, dt_us)
    util = np.clip(busy / dt_us, 0.0, 1.0)
    power_pe = p_act * util + p_idle * (1.0 - util)
    node_p = np.stack([power_pe[:, nodes == n].sum(axis=1)
                       for n in range(thermal.NUM_NODES)], axis=1)
    A, B = thermal.exact_step_matrices(dt_us * 1e-6)
    temps = thermal.steady_state(node_p.mean(axis=0))
    peak = temps[:3].max()
    for k in range(bins * repeats):
        temps = thermal.exact_step(temps, node_p[k % bins], A, B)
        peak = max(peak, temps[:3].max())
    return float(peak)


def _check_schedule(chk, name, scn, jx, ref):
    """The jax schedule equals the reference kernel's task for task."""
    from repro.scenario import tables_for
    recs = ref.raw.records
    jid = np.asarray([r.job_id for r in recs])
    tid = np.asarray([r.task_id for r in recs])
    out = jx.raw
    chk.equal(f"{name} tasks committed",
              int(np.asarray(out["scheduled"]).sum()), len(recs))
    chk.equal(f"{name} finish", np.asarray(out["finish"])[jid, tid],
              np.asarray([r.finish_us for r in recs], np.float32))
    chk.equal(f"{name} start", np.asarray(out["start"])[jid, tid],
              np.asarray([r.start_us for r in recs], np.float32))
    pe = np.asarray([r.pe_id for r in recs])
    chk.equal(f"{name} pe", np.asarray(out["onpe"])[jid, tid], pe)
    if "onopp" in out:
        tb = tables_for(scn)
        cpu = np.asarray([p.is_cpu for p in scn.soc().pes])[pe]
        freq = np.asarray(tb.opp_freq)[np.asarray(tb.pe_domain)[pe],
                                       np.asarray(out["onopp"])[jid, tid]]
        chk.equal(f"{name} latched OPP", freq[cpu],
                  np.asarray([r.freq_ghz for r in recs], np.float32)[cpu])
    chk.equal(f"{name} makespan", np.float32(jx.makespan_us),
              np.float32(ref.makespan_us))


def _check_peak(chk, name, scn, jx, ref, atol):
    from repro.core import thermal
    from repro.scenario import tables_for
    if scn.make_policy().dynamic:
        # the reference kernel integrates the same RC loop in float64
        want = max(thermal.T_AMBIENT_C, ref.telemetry.peak_temp_c)
    else:
        tb = tables_for(scn)
        db = scn.soc()
        want = _host_binned_peak(
            ref.raw.records, db.num_pes, thermal.cluster_nodes(db),
            np.asarray(tb.power_active, np.float64)[:db.num_pes],
            np.asarray(tb.power_idle, np.float64)[:db.num_pes],
            ref.makespan_us, scn.thermal.bins, scn.thermal.repeats)
    chk.close(f"{name} peak temp", jx.peak_temp_c, want, atol=atol)


def phase_a():
    """Single-scenario run() at bench_dtpm's size, three governors."""
    from repro.scenario import Scenario, TraceSpec, run
    chk = Checks("a")
    base = Scenario(apps=("wifi_tx",),
                    trace=TraceSpec(rate_jobs_per_ms=20.0, num_jobs=150,
                                    seed=0))
    free = base.replace(design=_free_interconnect_soc())
    for gov, params in (("performance", ()), ("ondemand", ()),
                        ("throttle", (("thermal_cap_c", 27.0),
                                      ("thermal_dt_s", 0.05)))):
        for label, scn, exact in (("", base, False),
                                  (" free-interconnect", free, True)):
            scn = scn.replace(governor=gov, governor_params=params)
            name = f"{gov}{label}"
            jx = cold_warm(f"{name} run(jax)", lambda: run(scn, backend="jax"))
            ref = run(scn, backend="ref",
                      telemetry=exact and scn.make_policy().dynamic)
            chk.close(f"{name} latency", jx.avg_latency_us,
                      ref.avg_latency_us,
                      rtol=RTOL_LATENCY_EXACT if exact else RTOL_LATENCY)
            chk.close(f"{name} energy", jx.energy_j, ref.energy_j,
                      rtol=RTOL_ENERGY_EXACT if exact else RTOL_ENERGY)
            if exact:
                _check_schedule(chk, name, scn, jx, ref)
                _check_peak(chk, name, scn, jx, ref, ATOL_PEAK_C)
    chk.done()


def phase_b():
    """The Fig-3 rate x seed grid per scheduler, jax lanes vs ref lanes."""
    from repro.scenario import Scenario, TraceSpec, sweep
    chk = Checks("b")
    rates = [1, 5, 10, 20, 30, 40, 50, 60, 70, 80]
    axes = {"rate": rates, "seed": (0, 1, 2)}
    base = Scenario(apps=("wifi_tx",), trace=TraceSpec(num_jobs=120))
    curves = {}
    for name, policy in (("met", "met"), ("etf", "etf"), ("ilp", "table")):
        scn = base.replace(scheduler=policy)
        jx = cold_warm(f"{name} sweep(jax) 30 lanes",
                       lambda: sweep(scn, axes=axes, backend="jax"))
        t0 = time.perf_counter()
        ref = sweep(scn, axes=axes, backend="ref")
        print(f"  {name} sweep(ref): {time.perf_counter() - t0!r} s")
        chk.close(f"{name} latency", jx.avg_latency_us, ref.avg_latency_us,
                  rtol=RTOL_LATENCY)
        chk.close(f"{name} makespan", jx.makespan_us, ref.makespan_us,
                  rtol=RTOL_LATENCY)
        chk.close(f"{name} energy", jx.energy_j, ref.energy_j,
                  rtol=RTOL_ENERGY)
        curves[name] = jx.avg_latency_us.mean(axis=1)
    hi = {n: float(c[-1]) for n, c in curves.items()}
    chk.true("etf < ilp < met at 80 jobs/ms",
             hi["etf"] < hi["ilp"] < hi["met"], repr(hi))
    chk.done()


def _dse_inputs():
    from repro.dse import DesignSpace
    from repro.scenario import Scenario, TraceSpec
    base = Scenario(apps=("wifi_tx", "wifi_rx"), scheduler="etf",
                    governor="design",
                    trace=TraceSpec(rate_jobs_per_ms=20.0, num_jobs=32))
    points = DesignSpace().sample_lhs(64, seed=0)
    seeds = range(4)
    traces = [base.with_seed(s).job_trace() for s in seeds]
    return base, points, seeds, traces


def phase_c():
    """64 designs x 4 traces in one evaluate(); 8 designs vs run()."""
    from repro.dse import evaluate
    from repro.scenario import run
    chk = Checks("c")
    base, points, seeds, traces = _dse_inputs()
    apps = base.applications()
    ev = cold_warm("evaluate 64 designs x 4 traces",
                   lambda: evaluate(points, apps, traces))
    runs = [[run(base.replace(design=p).with_seed(s), backend="jax")
             for s in seeds] for p in points[:8]]
    for lane_field, run_field in (("latency_per_trace_us", "avg_latency_us"),
                                  ("energy_per_trace_j", "energy_j"),
                                  ("temp_per_trace_c", "peak_temp_c")):
        chk.equal(f"8 designs x 4 traces {run_field}: lanes == run()",
                  getattr(ev, lane_field)[:8],
                  [[getattr(r, run_field) for r in row] for row in runs])
    chk.done()


def phase_d():
    """Fail-stop fault lanes on a free interconnect vs the ref kernel."""
    from repro.scenario import FaultSpec, Scenario, TraceSpec, run, sweep
    chk = Checks("d")
    scn = Scenario(design=_free_interconnect_soc(), apps=("wifi_tx",),
                   scheduler="etf",
                   trace=TraceSpec(rate_jobs_per_ms=20.0, num_jobs=64))
    lanes = [(), (FaultSpec(0, 400.0),),
             (FaultSpec(0, 400.0), FaultSpec(10, 800.0))]
    axes = {"faults": lanes, "seed": (0, 1, 2, 3)}
    jx = cold_warm("sweep(jax) 3 fault lanes x 4 traces",
                   lambda: sweep(scn, axes=axes))
    ref = sweep(scn, axes=axes, backend="ref")
    chk.equal("makespan", jx.makespan_us.astype(np.float32),
              ref.makespan_us.astype(np.float32))
    chk.close("latency", jx.avg_latency_us, ref.avg_latency_us,
              rtol=RTOL_LATENCY_EXACT)
    chk.close("energy", jx.energy_j, ref.energy_j, rtol=RTOL_ENERGY_EXACT)
    chk.true("a lost PE lengthens the makespan",
             bool(np.all(jx.makespan_us[1:] >= jx.makespan_us[0])))
    # per-lane schedules, and each lane equals its own run()
    for f, lane in enumerate(lanes):
        one = scn.replace(failures=lane)
        r_jx, r_ref = run(one, backend="jax"), run(one, backend="ref")
        _check_schedule(chk, f"{len(lane)}-fault lane", one, r_jx, r_ref)
        chk.equal(f"{len(lane)}-fault lane == run()",
                  jx.avg_latency_us[f, 0], r_jx.avg_latency_us)
    chk.done()


def phase_shard(num_chips: int):
    """The DSE sweep lane-sharded over the chips vs unsharded."""
    from repro.dse import evaluate
    from repro.obs import metrics
    chk = Checks("shard")
    base, points, seeds, traces = _dse_inputs()
    apps = base.applications()
    sharded = cold_warm(f"evaluate shard=True over {num_chips} chips",
                        lambda: evaluate(points, apps, traces, shard=True))
    width = metrics.counter("scenario.shard.devices").value
    plain = cold_warm("evaluate shard=False",
                      lambda: evaluate(points, apps, traces, shard=False))
    chk.equal("lane mesh width (scenario.shard.devices)", width, num_chips)
    for field in ("latency_per_trace_us", "energy_per_trace_j",
                  "temp_per_trace_c"):
        chk.equal(f"{field} sharded == unsharded",
                  getattr(sharded, field), getattr(plain, field))
    chk.done()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded DSE sweep across "
                         "four chips against the unsharded one")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.jax_cache import enable_compile_cache
    cache = enable_compile_cache(ROOT / ".jax_cache")
    kind = devices[0].device_kind
    print(f"device {kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache}")

    phases = ([("shard", phase_shard, (args.chips,))] if args.chips > 1
              else [("a", phase_a, ()), ("b", phase_b, ()),
                    ("c", phase_c, ()), ("d", phase_d, ())])
    failed = []
    for name, fn, fn_args in phases:
        print(f"phase {name}: {fn.__doc__}")
        t0 = time.perf_counter()
        try:
            fn(*fn_args)
        except Exception:                                  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAILED' if name in failed else 'passed'} "
              f"in {time.perf_counter() - t0!r} s")
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
