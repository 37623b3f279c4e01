"""``cpi_grid``: the ``sweep`` entry on pulse-Doppler CPIs.

One ``repro.scenario.sweep()`` per call, over the traffic's ``axes`` × the
call's fresh CPI traces, as ``sweep`` does, with three differences:

* arrivals are periodic, one CPI per period at each of the traffic's
  ``rates_jobs_per_ms`` (CPIs per ms), each gap jittered uniformly by
  ``±jitter`` of the period, drawn from ``--seed`` and the call index;
* the application is the configuration's ``dag`` (``pulses`` ×
  ``doppler_bins``), given to the program as its ``pulse_doppler_cpi``
  DAG and to the plain reference as ``bench/reference/pulse_doppler.py``;
* a lane's tasks are its CPIs times the DAG's task count.
"""
from __future__ import annotations

import numpy as np

from bench.entries.sweep import SweepEntry
from bench.harness import traffic as tr
from bench.harness.entries import Lane
from bench.reference import ds3, pulse_doppler


def periodic_trace(rate_per_ms: float, jitter: float, num_jobs: int,
                   app_name: str, seed: int) -> tr.Trace:
    """One CPI per period (1000 / rate µs), each gap scaled by a uniform
    draw in [1 − jitter, 1 + jitter]; arrivals are the gaps' float32
    running sum."""
    rng = np.random.default_rng(seed)
    period = 1000.0 / float(rate_per_ms)
    gaps = (period * rng.uniform(1.0 - jitter, 1.0 + jitter, size=num_jobs)
            ).astype(np.float32)
    return tr.Trace(np.cumsum(gaps, dtype=np.float32),
                    np.zeros(num_jobs, np.int32), (app_name,),
                    float(rate_per_ms), int(seed))


class CpiGridEntry(SweepEntry):

    def __init__(self, cfg, trf, seed, span):
        super().__init__(cfg, trf, seed, span)
        self.ref_app = pulse_doppler.app(**cfg["dag"])

    def traces(self, stream, i):
        t = self.trf
        return [periodic_trace(rate, t["jitter"], t["num_jobs"],
                               self.ref_app.name,
                               tr.stream_seed(self.seed, stream, i, k, s))
                for k, rate in enumerate(t["rates_jobs_per_ms"])
                for s in range(t["traces_per_rate"])]

    def tasks(self, trace):
        return len(trace.arrival_us) * self.ref_app.num_tasks

    def scenario(self, design, scheduler, governor, params):
        from repro.core.applications import pulse_doppler_cpi
        from repro.dse import DesignPoint
        from repro.scenario import Scenario, ThermalSpec
        return Scenario(design=DesignPoint(**design),
                        apps=(pulse_doppler_cpi(**self.cfg["dag"]),),
                        scheduler=scheduler, governor=governor,
                        governor_params=params,
                        thermal=ThermalSpec(**self.cfg["thermal"]))

    @staticmethod
    def reference(cfg, lane: Lane, time_dtype=np.float32,
                  value_dtype=np.float64):
        d = lane.design
        soc = ds3.make_soc(d["num_big"], d["num_little"], d["num_scr"],
                           d["num_fft"], d["num_vit"],
                           d["cross_cluster_penalty"])
        caps = {ds3.CPU_BIG: d["big_freq_ghz"],
                ds3.CPU_LITTLE: d["little_freq_ghz"]}
        gov = ds3.make_governor(lane.governor, dict(lane.params), caps)
        r = ds3.simulate(soc, [pulse_doppler.app(**cfg["dag"])],
                         lane.trace.arrival_us, lane.trace.app_index,
                         lane.scheduler, gov, bins=cfg["thermal"]["bins"],
                         repeats=cfg["thermal"]["repeats"],
                         time_dtype=time_dtype, value_dtype=value_dtype)
        return {"avg_latency_us": r.avg_latency_us,
                "makespan_us": r.makespan_us, "energy_j": r.energy_j,
                "peak_temp_c": r.peak_temp_c}


ENTRY = CpiGridEntry
