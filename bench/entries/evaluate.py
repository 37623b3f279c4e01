"""``evaluate``: one DSE round per call, ``build_design_batch(points, apps,
pad_pes=…)`` then ``evaluate(points, apps, traces, batch=…)``.

``designs: "lhs"`` draws a fresh latin-hypercube sample of
``num_designs`` from the configuration's ``design_space`` every call;
``designs: "grid"`` takes the whole space, built once at set-up.  Other
traffic keys: ``pad_pes``, ``scheduler``, ``governor`` (with the
configuration's parameters for it), ``shard`` and optionally ``chunk``.
Lanes are design-major, then the call's traces.
"""
from __future__ import annotations

import numpy as np

from bench.harness import traffic as tr
from bench.harness.entries import Call, Entry, Lane


class EvaluateEntry(Entry):

    def __init__(self, cfg, trf, seed, span):
        super().__init__(cfg, trf, seed, span)
        from repro.core.applications import get_application
        self.app_objs = tuple(get_application(a) for a in self.apps)
        self.params = self.governor_params(trf["governor"])
        self.fixed = None

    @staticmethod
    def _points(designs):
        from repro.dse import DesignPoint
        return [DesignPoint(**d) for d in designs]

    def _batch(self, points):
        from repro.dse import build_design_batch
        with self.span("bench.table_build"):
            return build_design_batch(points, self.app_objs,
                                      pad_pes=self.trf["pad_pes"])

    def setup(self):
        if self.trf["designs"] == "grid":
            designs = tr.grid_designs(self.cfg["design_space"])
            points = self._points(designs)
            self.fixed = (designs, points, self._batch(points))

    def inputs(self, stream, i):
        traces = self.traces(stream, i)
        if self.fixed is not None:
            designs, points = self.fixed[0], self.fixed[1]
        else:
            designs = tr.lhs_designs(
                self.cfg["design_space"], self.trf["num_designs"],
                tr.stream_seed(self.seed, stream, i, 1 << 20))
            points = self._points(designs)
        lanes = [Lane(d, self.trf["scheduler"], self.trf["governor"],
                      self.params, t) for d in designs for t in traces]
        return Call(i, lanes,
                    sum(self.tasks(t) for t in traces) * len(designs),
                    dict(points=points,
                         traces=[self.job_trace(t) for t in traces]))

    def call(self, c):
        from repro.dse import evaluate
        batch = (self.fixed[2] if self.fixed is not None
                 else self._batch(c.args["points"]))
        kw = {"chunk": self.trf["chunk"]} if "chunk" in self.trf else {}
        with self.span("bench.evaluate"):
            return evaluate(c.args["points"], self.app_objs, c.args["traces"],
                            policy=self.trf["scheduler"],
                            thermal_bins=self.cfg["thermal"]["bins"],
                            thermal_repeats=self.cfg["thermal"]["repeats"],
                            batch=batch, governor=self.trf["governor"],
                            governor_params=self.params,
                            shard=self.trf["shard"], **kw)

    def stats(self, out, c):
        return {"avg_latency_us": np.asarray(out.latency_per_trace_us,
                                             np.float64).reshape(-1),
                "energy_j": np.asarray(out.energy_per_trace_j,
                                       np.float64).reshape(-1),
                "peak_temp_c": np.asarray(out.temp_per_trace_c,
                                          np.float64).reshape(-1)}


ENTRY = EvaluateEntry
