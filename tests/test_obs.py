"""repro.obs: telemetry timelines, Chrome traces, metrics (DESIGN.md §11).

Three contracts pinned here:

* **telemetry equality** — on comm-free integer-latency traces the ref
  kernel's in-loop window recording and the jax kernel's post-hoc telemetry
  scan produce the same (W, C) timelines (same tolerance discipline as
  tests/test_dtpm.py), for both the closed DTPM loop and static governors;
* **zero overhead** — ``telemetry=False`` runs are byte-identical to the
  pre-observability kernel: same output arrays, no extra compiles of the
  simulation program;
* **artifact schemas** — Chrome trace-event JSON validates (and the
  validator catches corruption), bench payloads and run manifests carry
  their schema tags, the report CLI renders/validates them.
"""
import json

import numpy as np
import pytest

from repro.core.applications import wifi_tx
from repro.core.dvfs import OndemandGovernor, get_governor
from repro.core.jobgen import deterministic_trace
from repro.core.resources import CommModel, make_soc_table2
from repro.core.schedulers import get_scheduler
from repro.core.simkernel_jax import (_COMPILES_DTPM, build_tables,
                                      simulate_jax, simulate_jax_dtpm)
from repro.core.simkernel_ref import simulate
from repro.obs import (Telemetry, TelemetryRecorder, bench_cli, chrome_trace,
                       metrics, validate_chrome_trace, write_chrome_trace)
from repro.obs.bench import BENCH_SCHEMA, rows_payload
from repro.obs.metrics import MANIFEST_SCHEMA
from repro.obs.telemetry import (TELEMETRY_SCHEMA, _bucket_pow2, domain_count,
                                 jax_dtpm_telemetry, jax_static_telemetry,
                                 num_windows_for, ref_static_telemetry)
from repro.scenario import Scenario, TraceSpec, run, sweep
from repro.scenario.sweep import compile_count

SCN = Scenario(apps=("wifi_tx",),
               trace=TraceSpec(rate_jobs_per_ms=25.0, num_jobs=24, seed=3))


def _comm_free_db():
    db = make_soc_table2()
    db.comm = CommModel(startup_us=0.0, bw_bytes_per_us=1e30)
    return db


# ------------------------------------------------ metrics registry

def test_counter_and_timer_registry():
    c = metrics.counter("test_obs.count")
    assert metrics.counter("test_obs.count") is c      # registry identity
    c.reset()
    assert c.inc() == 1 and c.inc(3) == 4
    assert c.value == 4 and int(c) == 4
    t = metrics.timer("test_obs.timer")
    with t:
        pass
    assert t.count >= 1 and t.last_s >= 0.0
    assert t.last_us == t.last_s * 1e6
    snap = metrics.snapshot()
    assert snap["counters"]["test_obs.count"] == 4
    assert "test_obs.timer" in snap["timers"]


def test_sweep_compile_count_is_obs_counter():
    """The legacy module attribute IS the registered counter; the deprecated
    one-element-list alias (``compile_count[0]``), kept for one release
    after the registry landed, is now gone."""
    assert compile_count is metrics.counter("scenario.sweep.compile_count")
    with pytest.raises(TypeError):
        compile_count[0]                            # noqa: B018 — alias removed
    with pytest.raises(TypeError):
        compile_count[0] = 7
    assert not hasattr(metrics.Counter, "__getitem__")
    assert not hasattr(metrics.Counter, "__setitem__")


def test_window_sizing_helpers():
    assert num_windows_for(100.0, 50.0) == 2           # exact multiple
    assert num_windows_for(101.0, 50.0) == 3
    assert num_windows_for(0.0, 50.0) == 0
    assert [_bucket_pow2(n) for n in (1, 2, 3, 5, 33)] == [1, 2, 4, 8, 64]


# ------------------------------------------------ telemetry equality

def test_dtpm_telemetry_ref_jax_agree():
    """Comm-free ondemand trace: the ref kernel's in-loop window recording
    equals the jax kernel's post-hoc telemetry scan — same OPP decision in
    every window, utilisation/power/temperature to float32 tolerance."""
    db = _comm_free_db()
    app = wifi_tx()
    trace = deterministic_trace(25.0, 64, ["wifi_tx"])
    gov = OndemandGovernor(sample_window_us=50.0)
    rec = TelemetryRecorder(gov.sample_window_us)
    ref = simulate(db, [app], trace, get_scheduler("etf"), gov,
                   telemetry=rec)
    tel_ref = rec.build(domain_count(db))
    tables = build_tables(db, [app], governor=gov)
    out = simulate_jax_dtpm(tables, "etf", trace.arrival_us, trace.app_index,
                            gov.policy())
    tel_jax = jax_dtpm_telemetry(tables, gov.policy(), out, trace.app_index)
    W = num_windows_for(ref.makespan_us, gov.sample_window_us)
    assert W > 0
    assert tel_ref.num_windows == tel_jax.num_windows == W
    assert tel_ref.num_domains == tel_jax.num_domains == domain_count(db)
    # the governor made the same OPP decision in every window
    np.testing.assert_array_equal(tel_ref.freq_idx, tel_jax.freq_idx)
    np.testing.assert_allclose(tel_ref.freq_ghz, tel_jax.freq_ghz, rtol=1e-6)
    np.testing.assert_allclose(tel_ref.util, tel_jax.util, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(tel_ref.power_w, tel_jax.power_w, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(tel_ref.temps_c, tel_jax.temps_c, rtol=1e-4)
    # the replayed timeline reproduces the kernel's inline RC peak
    assert tel_jax.peak_temp_c == pytest.approx(float(out["peak_temp_c"]),
                                                rel=1e-6)
    np.testing.assert_allclose(tel_ref.peak_temp_c, tel_jax.peak_temp_c,
                               rtol=1e-4)


def test_static_telemetry_ref_jax_agree():
    """Static governor: both backends replay the same window observables;
    the frequency columns are governor constants — exactly equal."""
    db = _comm_free_db()
    app = wifi_tx()
    trace = deterministic_trace(25.0, 64, ["wifi_tx"])
    gov = get_governor("performance")
    ref = simulate(db, [app], trace, get_scheduler("etf"), gov)
    tel_ref = ref_static_telemetry(db, ref, gov)
    tables = build_tables(db, [app], governor=gov)
    out = simulate_jax(tables, "etf", trace.arrival_us, trace.app_index)
    tel_jax = jax_static_telemetry(db, gov, tables, out, trace.app_index)
    assert tel_ref.num_windows == tel_jax.num_windows \
        == num_windows_for(ref.makespan_us, tel_ref.window_us)
    np.testing.assert_array_equal(tel_ref.freq_idx, tel_jax.freq_idx)
    np.testing.assert_array_equal(tel_ref.freq_ghz, tel_jax.freq_ghz)
    np.testing.assert_allclose(tel_ref.util, tel_jax.util, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(tel_ref.power_w, tel_jax.power_w, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(tel_ref.temps_c, tel_jax.temps_c, rtol=1e-4)


def test_telemetry_is_zero_overhead():
    """``telemetry=True`` must not touch the simulation: identical output
    arrays, and the DTPM program is NOT re-traced (the timeline is a
    separate scan over the already-computed schedule)."""
    scn = SCN.replace(governor="ondemand")
    r0 = run(scn, backend="jax")                       # telemetry off
    assert r0.telemetry is None
    n_dtpm = _COMPILES_DTPM.value
    r1 = run(scn, backend="jax", telemetry=True)
    assert _COMPILES_DTPM.value == n_dtpm              # no sim re-compile
    assert r1.telemetry is not None
    for key in ("scheduled", "start", "finish", "onpe", "onopp"):
        np.testing.assert_array_equal(np.asarray(r0.raw[key]),
                                      np.asarray(r1.raw[key]))
    assert r0.avg_latency_us == r1.avg_latency_us
    assert r0.energy_j == r1.energy_j
    assert r0.peak_temp_c == r1.peak_temp_c
    # the Scenario field spells the same request declaratively
    r2 = run(scn.replace(telemetry=True), backend="jax")
    assert r2.telemetry is not None
    assert r2.telemetry.num_windows == r1.telemetry.num_windows


def test_result_manifest_attached():
    for backend in ("ref", "jax"):
        man = run(SCN, backend=backend).manifest
        assert man["schema"] == MANIFEST_SCHEMA
        assert man["backend"] == backend
        assert man["scenario"] == SCN.label()
        assert len(man["scenario_hash"]) == 12
        assert man["jit_compile_count"] >= 0
        assert "counters" in man["metrics"] and "timers" in man["metrics"]
        assert "timestamp" in man and "device_platform" in man


def test_manifest_propagates_device_errors(monkeypatch):
    """A manifest names the device it ran on or fails — it never records a
    placeholder platform when JAX cannot report one."""
    import jax

    def no_backend(*args, **kwargs):
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="no backend"):
        metrics.run_manifest()


def test_sweep_telemetry_lanes_match_run():
    """Sweep timelines replay the grid outputs — every lane equals its
    single-scenario ``run(..., telemetry=True)``, without re-simulating."""
    scn = SCN.replace(governor="ondemand")
    params = [(("up_threshold", 0.6),), (("up_threshold", 0.9),)]
    sr = sweep(scn, axes={"governor_params": params}, telemetry=True)
    assert sr.telemetry.shape == (2,)
    for k in (0, 1):
        single = run(scn.replace(governor_params=params[k]), backend="jax",
                     telemetry=True)
        lane = sr.telemetry[k]
        assert isinstance(lane, Telemetry)
        assert lane.num_windows == single.telemetry.num_windows
        np.testing.assert_array_equal(lane.freq_idx,
                                      single.telemetry.freq_idx)
        np.testing.assert_allclose(lane.temps_c, single.telemetry.temps_c,
                                   rtol=1e-6)
        np.testing.assert_allclose(lane.util, single.telemetry.util,
                                   rtol=1e-6)
    # telemetry off -> field stays None (no silent cost)
    assert sweep(scn, axes={"governor_params": params[:1]}).telemetry is None


def test_sweep_telemetry_static_and_ref_lanes():
    sr = sweep(SCN, axes={"seed": [0, 1]}, telemetry=True)
    assert sr.telemetry.shape == (2,)
    single = run(SCN.with_seed(1), backend="jax", telemetry=True)
    np.testing.assert_array_equal(sr.telemetry[1].freq_ghz,
                                  single.telemetry.freq_ghz)
    np.testing.assert_allclose(sr.telemetry[1].temps_c,
                               single.telemetry.temps_c, rtol=1e-6)
    sr_ref = sweep(SCN, axes={"seed": [0]}, backend="ref", telemetry=True)
    assert isinstance(sr_ref.telemetry[0], Telemetry)
    assert sr_ref.telemetry[0].num_windows > 0


def test_telemetry_json_roundtrip_and_props():
    res = run(SCN.replace(governor="ondemand"), backend="ref",
              telemetry=True)
    tel = res.telemetry
    d = tel.to_dict()
    assert d["schema"] == TELEMETRY_SCHEMA
    back = Telemetry.from_dict(json.loads(json.dumps(d)))
    np.testing.assert_array_equal(back.freq_idx, tel.freq_idx)
    np.testing.assert_allclose(back.temps_c, tel.temps_c, rtol=1e-6)
    with pytest.raises(ValueError, match="schema"):
        Telemetry.from_dict({"schema": "bogus"})
    assert np.all(np.diff(tel.time_us) > 0)            # window-end timestamps
    assert tel.time_us[-1] == pytest.approx(tel.num_windows * tel.window_us)
    assert tel.peak_temp_c == float(np.max(tel.temps_c[:, :3]))
    assert tel.avg_power_w > 0.0


# ------------------------------------------------ Chrome trace (Perfetto)

def test_chrome_trace_schema_valid(tmp_path):
    scn = SCN.replace(governor="ondemand")
    res = run(scn, backend="ref", telemetry=True)
    db = scn.soc()
    tr = chrome_trace(db, res.raw, apps=scn.applications(),
                      trace=scn.job_trace(), telemetry=res.telemetry)
    assert validate_chrome_trace(tr) == []
    events = tr["traceEvents"]
    # one thread-name track per PE, matched B/E pair per committed task
    names = [e for e in events if e["ph"] == "M" and e["name"] == "thread_name"]
    assert len(names) == db.num_pes
    n_b = sum(e["ph"] == "B" for e in events)
    n_e = sum(e["ph"] == "E" for e in events)
    assert n_b == n_e == len(res.raw.records)
    # counter tracks carry the telemetry timelines
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert counters == {"freq_ghz", "util", "temp_c"}
    # task names resolve through the app graph
    assert any(e["name"].startswith("wifi_tx.") for e in events
               if e["ph"] == "B")
    path = tmp_path / "trace.json"
    write_chrome_trace(path, tr)
    assert validate_chrome_trace(json.loads(path.read_text())) == []


def test_chrome_trace_validator_catches_corruption():
    ok = {"traceEvents": [
        {"name": "t", "ph": "B", "pid": 0, "tid": 0, "ts": 1.0},
        {"name": "t", "ph": "E", "pid": 0, "tid": 0, "ts": 2.0}]}
    assert validate_chrome_trace(ok) == []
    assert validate_chrome_trace({}) == ["missing or non-list 'traceEvents'"]
    unmatched = {"traceEvents": ok["traceEvents"][:1]}
    assert any("unmatched 'B'" in e for e in validate_chrome_trace(unmatched))
    backwards = {"traceEvents": [
        {"name": "t", "ph": "B", "pid": 0, "tid": 0, "ts": 2.0},
        {"name": "t", "ph": "E", "pid": 0, "tid": 0, "ts": 1.0}]}
    errs = validate_chrome_trace(backwards)
    assert any("non-monotonic" in e for e in errs)
    assert any("precedes its 'B'" in e for e in errs)
    orphan_end = {"traceEvents": [
        {"name": "t", "ph": "E", "pid": 0, "tid": 0, "ts": 1.0}]}
    assert any("no open 'B'" in e for e in validate_chrome_trace(orphan_end))
    missing = {"traceEvents": [{"ph": "B", "pid": 0, "tid": 0, "ts": 0.0}]}
    assert any("missing key 'name'" in e
               for e in validate_chrome_trace(missing))


# ------------------------------------------------ bench harness + report CLI

def test_bench_cli_json_payload(tmp_path, capsys):
    path = tmp_path / "BENCH_unit.json"
    rc = bench_cli(lambda: [("unit/x", 1.5, "note")], "unit", "doc",
                   ["--json", str(path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "name,value,derived" in out and "unit/x,1.5000,note" in out
    payload = json.loads(path.read_text())
    assert payload["schema"] == BENCH_SCHEMA
    assert payload["manifest"]["schema"] == MANIFEST_SCHEMA
    assert payload["manifest"]["bench"] == "unit"
    assert payload["manifest"]["wall_s"] >= 0.0
    assert payload["rows"] == [
        {"name": "unit/x", "value": 1.5, "derived": "note"}]
    # rows_payload is the same serialisation benchmarks/run.py could reuse
    again = rows_payload([("unit/x", 1.5, "note")], "unit", 0.0)
    assert again["rows"] == payload["rows"]


def test_report_cli_trace_validate_render(tmp_path, capsys):
    from repro.obs import report
    trace_p = tmp_path / "TRACE.json"
    tel_p = tmp_path / "TELEMETRY.json"
    rc = report.main(["--jobs", "12", "--governor", "ondemand",
                      "--trace", str(trace_p), "--telemetry", str(tel_p)])
    assert rc == 0
    assert validate_chrome_trace(json.loads(trace_p.read_text())) == []
    assert json.loads(tel_p.read_text())["schema"] == TELEMETRY_SCHEMA
    assert report.main(["--validate", str(trace_p)]) == 0
    out = capsys.readouterr().out
    assert "valid Chrome trace" in out and "perfetto" in out
    # rendering: bench payload + telemetry dump summaries
    bench_p = tmp_path / "BENCH_unit.json"
    bench_p.write_text(json.dumps(rows_payload([("a/b", 2.0, "d")],
                                               "unit", 0.1)))
    assert report.main([str(bench_p), str(tel_p)]) == 0
    out = capsys.readouterr().out
    assert "manifest:" in out and "rows (1):" in out and "windows" in out
    # corruption makes --validate exit non-zero
    bad = tmp_path / "BAD.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "t", "ph": "B", "pid": 0, "tid": 0, "ts": 0.0}]}))
    assert report.main(["--validate", str(bad)]) == 1


# ------------------------------------------------ host spans (profiler)

OUTER = ("repro.run", "repro.sweep", "repro.evaluate")


def _profiled(fn, tmp_path):
    """``fn()`` under the JAX profiler: its result and the host plane's
    ``repro.*`` spans as ``(name, start_ns, end_ns)``, by start."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = [(e.name, e.start_ns, e.end_ns)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _leaves(spans):
    """The leaf spans in time order, after checking that no two of them
    overlap (leaves never nest inside each other)."""
    leaves = [s for s in spans if s[0] not in OUTER]
    for a, b in zip(leaves, leaves[1:]):
        assert a[2] <= b[1], (a, b)
    return leaves


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _assert_equal_outputs(a, b, fields):
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


RUN_FIELDS = ("avg_latency_us", "throughput_jobs_per_ms", "makespan_us",
              "energy_j", "avg_power_w", "peak_temp_c", "utilization")


def test_span_is_a_trace_annotation_and_metrics_stays_stdlib():
    """``span`` is JAX's profiler annotation, and ``repro.obs.metrics``
    imports JAX only when the first span is made."""
    import os
    import subprocess
    import sys
    from jax.profiler import TraceAnnotation
    assert isinstance(metrics.span("repro.stage"), TraceAnnotation)
    code = ("import sys, repro.obs.metrics as m; "
            "assert 'jax' not in sys.modules; m.span('repro.x'); "
            "assert 'jax' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("governor,telemetry,leaves", [
    ("ondemand", False,
     ["stage", "stage", "launch", "wait", "assemble", "assemble"]),
    ("performance", False,
     ["stage", "stage", "launch", "launch", "wait", "assemble",
      "assemble"]),
    # the telemetry replay scan is a launch of its own, between the run's
    # wait and its assembly
    ("ondemand", True,
     ["stage", "stage", "launch", "wait", "launch", "wait", "assemble",
      "assemble", "assemble"]),
])
def test_run_spans(tmp_path, governor, telemetry, leaves):
    """``run(backend="jax")``: one ``repro.run`` span holds every leaf, in
    the order stage → launch → wait → assemble; the outputs are bit for bit
    those of the same run with the profiler off."""
    scn = SCN.replace(governor=governor)
    off = run(scn, backend="jax", telemetry=telemetry)
    on, spans = _profiled(
        lambda: run(scn, backend="jax", telemetry=telemetry), tmp_path)
    outer = [s for s in spans if s[0] in OUTER]
    assert [s[0] for s in outer] == ["repro.run"]
    got = _leaves(spans)
    assert [n for n, _, _ in got] == [f"repro.{n}" for n in leaves]
    assert all(_inside(s, outer[0]) for s in got)
    _assert_equal_outputs(off, on, RUN_FIELDS)
    for key in ("scheduled", "start", "finish", "onpe"):
        np.testing.assert_array_equal(np.asarray(off.raw[key]),
                                      np.asarray(on.raw[key]))
    if telemetry:
        np.testing.assert_array_equal(off.telemetry.temps_c,
                                      on.telemetry.temps_c)


SWEEP_FIELDS = ("avg_latency_us", "throughput_jobs_per_ms", "makespan_us",
                "energy_j", "peak_temp_c", "busy_per_pe_us")


@pytest.mark.parametrize("chunk,leaves", [
    # traces staged, lanes staged, then per scheduler launch, wait and
    # assembly, then the grid's assembly
    (None, ["stage", "stage"] + ["launch", "wait", "assemble"] * 2
     + ["assemble"]),
    # streamed in 1-lane chunks: the lanes go to the host once, then each
    # chunk is staged and launched, and the chunks are waited for and
    # concatenated before the sweep's own (no-op) wait and assembly
    (1, ["stage", "stage"]
     + (["stage"] + ["stage", "launch"] * 2 + ["wait", "assemble", "wait",
                                              "assemble"]) * 2
     + ["assemble"]),
])
def test_sweep_spans(tmp_path, chunk, leaves):
    """A met/etf × 2-design sweep: every leaf inside ``repro.sweep``, in
    the order stage → launch → wait → assemble; outputs bit for bit equal
    with the profiler on and off."""
    axes = {"scheduler": ["met", "etf"], "design.num_fft": [2, 4]}
    off = sweep(SCN, axes=axes, chunk=chunk, shard=False)
    on, spans = _profiled(
        lambda: sweep(SCN, axes=axes, chunk=chunk, shard=False), tmp_path)
    outer = [s for s in spans if s[0] in OUTER]
    assert [s[0] for s in outer] == ["repro.sweep"]
    got = _leaves(spans)
    assert [n for n, _, _ in got] == [f"repro.{n}" for n in leaves]
    assert all(_inside(s, outer[0]) for s in got)
    _assert_equal_outputs(off, on, SWEEP_FIELDS)


def test_design_batch_and_evaluate_spans(tmp_path):
    """``build_design_batch`` is ``repro.tables.build`` then
    ``repro.tables.stack``; ``evaluate(batch=)`` is ``repro.evaluate``
    holding ``repro.sweep`` and its leaves; outputs bit for bit equal with
    the profiler on and off."""
    import dataclasses
    from repro.core.applications import get_application
    from repro.dse import DesignSpace, build_design_batch, evaluate

    points = DesignSpace().sample_lhs(3, seed=1)
    apps = [get_application("wifi_tx")]
    traces = [SCN.replace(trace=dataclasses.replace(SCN.trace, seed=s))
              .job_trace() for s in (1, 2)]

    def call():
        batch = build_design_batch(points, apps, pad_pes=16)
        return evaluate(points, apps, traces, batch=batch)

    off = call()
    on, spans = _profiled(call, tmp_path)
    names = [n for n, _, _ in spans]
    assert names.count("repro.evaluate") == names.count("repro.sweep") == 1
    ev = spans[names.index("repro.evaluate")]
    sw = spans[names.index("repro.sweep")]
    assert _inside(sw, ev)
    got = _leaves(spans)
    assert [n for n, _, _ in got] == [
        "repro.tables.build", "repro.tables.stack", "repro.stage",
        "repro.stage", "repro.launch", "repro.wait", "repro.assemble",
        "repro.assemble"]
    assert not any(_inside(s, ev) for s in got[:2])
    assert all(_inside(s, sw) for s in got[2:])
    _assert_equal_outputs(off, on, ("avg_latency_us", "energy_j",
                                    "peak_temp_c", "latency_per_trace_us"))
