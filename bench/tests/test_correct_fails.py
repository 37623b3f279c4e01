"""``correct`` comes out false when the timed path is broken, and when the
control stands in for the program; it comes out true on the sound path.

Each run skips the look for a chip and drives the rest of a run (set-up,
warm-up, window, check) at a size the CPU holds, with the entry's call
broken underneath:

* ``stale``: the call returns its first result again (state unchanged);
* ``half``: half of the lanes (``run``: half of the trace's jobs) are
  simulated and the rest take the mean over them;
* ``altered``: every lane's mean latency is altered by 1 % where the
  entry produces it.

The cells run on one chip, so there is no exchange between chips to leave
out.  The control is the reference computed in bfloat16 in the program's
place (``runner.compare(control_dtype=…)``).
"""
import dataclasses
import time
import types

import ml_dtypes
import numpy as np
import pytest

from bench.harness import check, entries, runner, traffic
from bench.tests.cells import ROOT, tiny_cell

CELLS = ["fig3_grid", "dtpm_run", "dse_lhs"]
SEED = 2**31 + 99


def _stale(cell):
    def wrap(call):
        first = []

        def f(c):
            if not first:
                first.append(call(c))
            return first[0]
        return f
    return wrap


def _fill_mean(a, keep):
    a = np.array(a, np.float64)
    a[..., keep:] = a[..., :keep].mean(axis=-1, keepdims=True)
    return a


def _half(cell):
    kind = cell.traffic["entry"]

    def wrap(call):
        def f(c):
            if kind == "run":
                t = c.args["trace"]
                keep = len(t.arrival_us) // 2
                half = dataclasses.replace(t, arrival_us=t.arrival_us[:keep],
                                           app_index=t.app_index[:keep])
                return call(dataclasses.replace(
                    c, args=dict(c.args, trace=half)))
            if kind == "sweep":
                traces = c.args["axes"]["trace"]
                args = dict(c.args, axes=dict(c.args["axes"],
                                              trace=traces[:len(traces) // 2]))
            else:
                traces = c.args["traces"]
                args = dict(c.args, traces=traces[:len(traces) // 2])
            n = len(traces)
            keep = n // 2
            out = call(dataclasses.replace(c, args=args))
            pad = lambda a: _fill_mean(  # noqa: E731
                np.concatenate([a, a[..., :n - keep]], axis=-1), keep)
            if kind == "sweep":
                return types.SimpleNamespace(
                    **{k: pad(getattr(out, k)) for k in entries.STATS})
            return types.SimpleNamespace(
                latency_per_trace_us=pad(out.latency_per_trace_us),
                energy_per_trace_j=pad(out.energy_per_trace_j),
                temp_per_trace_c=pad(out.temp_per_trace_c))
        return f
    return wrap


def _altered(cell):
    kind = cell.traffic["entry"]

    def wrap(call):
        def f(c):
            out = call(c)
            if kind == "run":
                return dataclasses.replace(
                    out, avg_latency_us=out.avg_latency_us * 1.01)
            if kind == "sweep":
                return dataclasses.replace(
                    out, avg_latency_us=out.avg_latency_us * 1.01)
            return dataclasses.replace(
                out, latency_per_trace_us=out.latency_per_trace_us * 1.01)
        return f
    return wrap


FAULTS = {"stale": _stale, "half": _half, "altered": _altered}


def _run(name, wrap=None):
    cell = tiny_cell(name)
    return runner.run_cell(ROOT, cell, SEED, 0.5, False, time.perf_counter(),
                           wrap=None if wrap is None else wrap(cell))


@pytest.mark.parametrize("name", CELLS)
def test_sound_path_is_correct(name):
    res = _run(name)
    bad = {k: t for k, t in res["checks"].items()
           if t["limit"] is None or t["value"] > t["limit"]}
    assert res["correct"], bad
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(name, fault):
    res = _run(name, FAULTS[fault])
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference in bfloat16, in the program's place, fails the
    cell's limits."""
    import jax
    cell = tiny_cell(name)
    span = jax.profiler.TraceAnnotation
    entry = cell.entry(cell.config, cell.traffic, SEED, span)
    calls = [entry.inputs(traffic.WINDOW, i) for i in range(2)]
    stats = [entry.stats(entry.call(c), c) for c in calls]
    limits = check.load_limits(ROOT, name)
    sound = runner.compare(cell, calls, stats, SEED)
    control = runner.compare(cell, calls, stats, SEED,
                             control_dtype=ml_dtypes.bfloat16)
    assert check.judge(sound, limits)[0], sound
    assert not check.judge(control, limits)[0], control
