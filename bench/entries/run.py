"""``run``: one ``repro.scenario.run(backend="jax")`` per call on one fresh
trace, the governor taken in turn from the traffic's ``governor_cycle``
(with the configuration's parameters for it), under the traffic's
``scheduler``."""
from __future__ import annotations

import numpy as np

from bench.harness.entries import Call, Entry, Lane, STATS


class RunEntry(Entry):

    def inputs(self, stream, i):
        (t,) = self.traces(stream, i)
        cycle = self.trf["governor_cycle"]
        gov = cycle[i % len(cycle)]
        lane = Lane(dict(self.cfg["design"]), self.trf["scheduler"], gov,
                    self.governor_params(gov), t)
        return Call(i, [lane], self.tasks(t), dict(
            scn=self.scenario(lane.design, lane.scheduler, gov, lane.params),
            trace=self.job_trace(t)))

    def call(self, c):
        from repro.scenario import run
        with self.span("bench.run"):
            return run(c.args["scn"], backend="jax",
                       trace_override=c.args["trace"])

    def stats(self, out, c):
        return {k: np.asarray([getattr(out, k)], np.float64) for k in STATS}


ENTRY = RunEntry
