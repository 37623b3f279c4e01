"""``sweep``: one ``repro.scenario.sweep()`` call over the traffic's
``axes`` × the call's fresh traces (passed as ``JobTrace``s on the
``trace`` axis, the last one).

Traffic keys: ``governor`` (a governor of the configuration), ``axes``
(an ordered map of sweep axes: ``scheduler``, ``governor_params`` — each
value a full parameter map — or ``design.<field>``), ``shard``, and
optionally ``telemetry`` and ``chunk``, passed to ``sweep()`` as they are.
Lanes are the product of the axes in their order, then the traces.
"""
from __future__ import annotations

import itertools

from bench.harness.entries import Call, Entry, Lane, STATS, params_of, stats_of

AXES = ("scheduler", "governor_params")


class SweepEntry(Entry):

    def __init__(self, cfg, trf, seed, span):
        super().__init__(cfg, trf, seed, span)
        for name in trf["axes"]:
            if name not in AXES and not name.startswith("design."):
                raise ValueError(f"sweep axis {name!r} has no reference "
                                 f"semantics here; use {AXES} or design.*")

    def _lane(self, combo, trace) -> Lane:
        design = dict(self.cfg["design"])
        scheduler = self.cfg["schedulers"][0]
        params = self.governor_params(self.trf["governor"])
        for name, value in zip(self.trf["axes"], combo):
            if name == "scheduler":
                scheduler = value
            elif name == "governor_params":
                params = params_of(value)
            else:
                design[name.split(".", 1)[1]] = value
        return Lane(design, scheduler, self.trf["governor"], params, trace)

    def inputs(self, stream, i):
        traces = self.traces(stream, i)
        combos = list(itertools.product(*self.trf["axes"].values()))
        lanes = [self._lane(combo, t) for combo in combos for t in traces]
        axes = {n: ([params_of(v) for v in vals] if n == "governor_params"
                    else list(vals))
                for n, vals in self.trf["axes"].items()}
        axes["trace"] = [self.job_trace(t) for t in traces]
        first = self._lane(combos[0], traces[0])
        return Call(i, lanes, sum(self.tasks(t) for t in traces) * len(combos),
                    dict(base=self.scenario(first.design, first.scheduler,
                                            first.governor, first.params),
                         axes=axes))

    def call(self, c):
        from repro.scenario import sweep
        kw = {k: self.trf[k] for k in ("telemetry", "chunk") if k in self.trf}
        with self.span("bench.sweep"):
            return sweep(c.args["base"], axes=c.args["axes"], backend="jax",
                         shard=self.trf["shard"], **kw)

    def stats(self, out, c):
        return stats_of(out, STATS)


ENTRY = SweepEntry
