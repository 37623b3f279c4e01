"""What every entry shares.  An entry is the program call a cell's window
drives; the traffic file's ``entry`` names it, and its class is ``ENTRY``
in ``bench/entries/<entry>.py`` (found by that name, like a metric).

Each entry builds one call's inputs from the seed and the call index
(fresh traces every call, fresh designs where the traffic samples them),
makes the call, and returns the call's per-lane statistics in lane order
together with the lane descriptions the reference is run on.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

from . import traffic as tr
from ..reference import ds3

STATS = ("avg_latency_us", "makespan_us", "energy_j", "peak_temp_c")


@dataclasses.dataclass(frozen=True)
class Lane:
    """What the reference needs to simulate one lane."""
    design: Dict
    scheduler: str
    governor: str
    params: Tuple[Tuple[str, float], ...]
    trace: tr.Trace


@dataclasses.dataclass
class Call:
    index: int
    lanes: List[Lane]
    tasks: int                      # DAG tasks the call simulates
    args: Dict                      # the program-side inputs


def params_of(params: Mapping[str, float]) -> Tuple[Tuple[str, float], ...]:
    """Governor parameters as the sorted ``(key, value)`` pairs a
    ``Scenario`` holds."""
    return tuple(sorted(dict(params).items()))


class Entry:
    """Shared plumbing: the configuration's scenario pieces as program
    objects, and the traces of a call."""

    def __init__(self, cfg: Dict, trf: Dict, seed: int, span):
        self.cfg, self.trf, self.seed, self.span = cfg, trf, int(seed), span
        self.apps = tuple(cfg["apps"])
        self.mix = cfg.get("app_mix")
        if trf["num_jobs"] not in cfg["trace_jobs"]:
            raise ValueError(f"traffic num_jobs {trf['num_jobs']} is not one "
                             f"of the configuration's {cfg['trace_jobs']}")

    # ------------------------------------------------------------- inputs
    def traces(self, stream: int, i: int) -> List[tr.Trace]:
        t = self.trf
        return tr.call_traces(self.seed, stream, i, t["rates_jobs_per_ms"],
                              t["traces_per_rate"], t["num_jobs"], self.apps,
                              self.mix)

    def tasks(self, trace: tr.Trace) -> int:
        """DAG tasks in one trace."""
        return ds3.tasks_of([ds3.app(a) for a in self.apps], trace.app_index)

    def governor_params(self, governor: str) -> Tuple[Tuple[str, float], ...]:
        return params_of(self.cfg["governors"][governor])

    @staticmethod
    def job_trace(trace: tr.Trace):
        from repro.core.jobgen import JobTrace
        return JobTrace(trace.arrival_us, trace.app_index, trace.app_names)

    def scenario(self, design: Dict, scheduler: str, governor: str,
                 params: Tuple[Tuple[str, float], ...]):
        from repro.dse import DesignPoint
        from repro.scenario import Scenario, ThermalSpec
        return Scenario(design=DesignPoint(**design), apps=self.apps,
                        scheduler=scheduler, governor=governor,
                        governor_params=params,
                        thermal=ThermalSpec(**self.cfg["thermal"]))

    @staticmethod
    def reference(cfg: Dict, lane: Lane, **dtypes) -> Dict[str, float]:
        """The plain reference of one lane; an entry whose lanes carry more
        than a design, scheduler, governor and trace brings its own."""
        return reference_lane(cfg, lane, **dtypes)

    def setup(self) -> None:
        """Work a cell does once per process (before warm-up)."""

    def inputs(self, stream: int, i: int) -> Call:
        raise NotImplementedError

    def call(self, c: Call):
        raise NotImplementedError

    def stats(self, out, c: Call) -> Dict[str, np.ndarray]:
        raise NotImplementedError


def reference_lane(cfg: Dict, lane: Lane, time_dtype=np.float32,
                   value_dtype=np.float64) -> Dict[str, float]:
    """The plain reference's statistics for one lane."""
    d = lane.design
    soc = ds3.make_soc(d["num_big"], d["num_little"], d["num_scr"],
                       d["num_fft"], d["num_vit"],
                       d["cross_cluster_penalty"])
    caps = {ds3.CPU_BIG: d["big_freq_ghz"], ds3.CPU_LITTLE: d["little_freq_ghz"]}
    gov = ds3.make_governor(lane.governor, dict(lane.params), caps)
    r = ds3.simulate(soc, [ds3.app(a) for a in cfg["apps"]],
                     lane.trace.arrival_us, lane.trace.app_index,
                     lane.scheduler, gov, bins=cfg["thermal"]["bins"],
                     repeats=cfg["thermal"]["repeats"],
                     time_dtype=time_dtype, value_dtype=value_dtype)
    return {"avg_latency_us": r.avg_latency_us, "makespan_us": r.makespan_us,
            "energy_j": r.energy_j, "peak_temp_c": r.peak_temp_c}


def stats_of(out, keys: Sequence[str]) -> Dict[str, np.ndarray]:
    """The named fields of a result, flattened in lane order."""
    return {k: np.asarray(getattr(out, k), np.float64).reshape(-1)
            for k in keys}
